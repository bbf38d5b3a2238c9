"""Model FLOP/s utilisation of the traced run of the Ling-3.0-flash cell:
useful forward+backward FLOPs of what a step's batch held
(benchmark/flops_ling.py: the projections and feed-forwards of the valid
tokens, the delta rule by its pairs and tokens, the latent layer's attention
by the pairs the mask leaves, the routed products by the slots routed here,
the head by the targets; padding and recomputation not counted) x steps a
second over chips x the bf16 peak."""

from benchmark import flops_ling


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.peaks is None or "kda_pairs" not in counts
            or "steps" not in run.records):
        return None
    per_step = flops_ling.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["kda_pairs"],
        counts["expert_slots_here"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
