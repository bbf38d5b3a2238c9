"""Model FLOP/s utilisation of the traced run of a packed cell: useful
forward+backward FLOPs of what a step's batch held (benchmark/flops_packed.py:
matmuls of the valid tokens, exact attention of the sum of n_i^2, the head
per image; padding and recomputation not counted) x steps a second over
chips x the bf16 peak."""

from benchmark import flops_packed


def read(run):
    counts = run.records.get("packed_counts")
    if run.peaks is None or counts is None or "steps" not in run.records:
        return None
    per_step = flops_packed.model_flops_per_step(
        run.config, counts["tokens"], counts["token_pairs"], counts["images"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
