"""Engine time of one batch (`infer_s` of the server's `serve_batch` events
inside the window), median."""

from benchmark.harness import percentile


def read(run):
    vals = sorted(e["infer_s"] for e in run.records.get("serve_events", [])
                  if e.get("kind") == "serve_batch")
    value = percentile(vals, 0.5)
    return None if value is None else 1e3 * value
