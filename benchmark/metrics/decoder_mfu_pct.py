"""Model FLOP/s utilisation of the traced run of a decoder cell: useful
forward+backward FLOPs of what a step's batch held (benchmark/flops_laguna.py:
matmuls of the valid tokens, attention of the pairs the masks leave, the
routed experts by the slots held here, the head by the targets; padding and
recomputation not counted) x steps a second over chips x the bf16 peak."""

from benchmark import flops_laguna


def read(run):
    counts = run.records.get("packed_counts")
    if run.peaks is None or counts is None or "steps" not in run.records:
        return None
    per_step = flops_laguna.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["window_pairs"],
        counts["expert_slots_here"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
