"""Real requests over padded bucket rows, summed over the window's batches
(`serve_batch` events)."""


def read(run):
    batches = [e for e in run.records.get("serve_events", [])
               if e.get("kind") == "serve_batch"]
    rows = sum(e["bucket"] for e in batches)
    if rows <= 0:
        return None
    return 100.0 * sum(e["batch_size"] for e in batches) / rows
