"""Client-side request latency, 95th percentile over the window's replies.
Sits on the step between the four-period mode and the replies that waited a
second for a dropped connection attempt: it swings between runs."""

from benchmark.harness import percentile


def read(run):
    value = percentile(run.records.get("latency_s", []), 0.95)
    return None if value is None else 1e3 * value
