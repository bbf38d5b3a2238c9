"""Server-side time of a request outside the queue and the engine:
`latency_s - queue_wait_s - infer_s` of `serve_request` events, median. HTTP
parsing, JPEG decode and resize, the reply."""

from benchmark.harness import percentile


def read(run):
    vals = sorted(e["latency_s"] - e["queue_wait_s"] - e["infer_s"]
                  for e in run.records.get("serve_events", [])
                  if e.get("kind") == "serve_request")
    value = percentile(vals, 0.5)
    return None if value is None else 1e3 * value
