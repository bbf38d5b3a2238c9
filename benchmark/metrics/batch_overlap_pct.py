"""Share of the window's batches that went to the device while the batch
before them was still in flight (`overlapped` of `serve_batch` events): how
often the batcher had the next batch queued behind the running one, so that
the device did not wait for the worker thread between the two. Nothing to
read from a program whose events lack the field."""


def read(run):
    batches = [e for e in run.records.get("serve_events", [])
               if e.get("kind") == "serve_batch" and "overlapped" in e]
    if not batches:
        return None
    return 100.0 * sum(e["overlapped"] for e in batches) / len(batches)
