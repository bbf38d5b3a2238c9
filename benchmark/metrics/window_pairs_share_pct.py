"""How far the window binds in what the step saw: the (query, key) pairs a
sliding layer's mask leaves over the pairs a full layer's leaves on the same
documents (the step's own `window_pairs` / `causal_pairs`, counted on the
device from the segment ids). 100 says that no document of the batch is
longer than the window: the traffic no longer works it, and the sliding
layers' kernels do a full layer's work."""


def read(run):
    counts = run.records.get("packed_counts") or {}
    if not counts.get("causal_pairs") or "window_pairs" not in counts:
        return None
    return 100.0 * counts["window_pairs"] / counts["causal_pairs"]
