"""Client-side request latency, 90th percentile over the window's replies.
Touches the tail: it read 434.9-441.4 ms in most runs and 480.4 ms in one in
which the slow share and a few five-period replies together passed 10%."""

from benchmark.harness import percentile


def read(run):
    value = percentile(run.records.get("latency_s", []), 0.90)
    return None if value is None else 1e3 * value
