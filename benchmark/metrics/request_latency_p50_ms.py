"""Client-side request latency, median over the window's replies."""

from benchmark.harness import percentile


def read(run):
    value = percentile(run.records.get("latency_s", []), 0.50)
    return None if value is None else 1e3 * value
