"""Time a request waits in the batcher's queue (`queue_wait_s` of the
server's `serve_request` events inside the window), median."""

from benchmark.harness import percentile


def read(run):
    vals = sorted(e["queue_wait_s"] for e in run.records.get("serve_events", [])
                  if e.get("kind") == "serve_request")
    value = percentile(vals, 0.5)
    return None if value is None else 1e3 * value
