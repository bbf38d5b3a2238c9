"""Model FLOP/s utilisation of the traced run of the Olmo-Hybrid cell: useful
forward+backward FLOPs of what a step's batch held (benchmark/flops_olmo.py:
the projections and the SwiGLU of the valid tokens, the delta rule by its
pairs and tokens at 96 / 192, the attention layer by the pairs the mask
leaves, the head by the targets; padding and recomputation not counted) x
steps a second over chips x the bf16 peak: the share of the whole step."""

from benchmark import flops_olmo


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.peaks is None or "kda_pairs" not in counts
            or "linear_key_head_dim" not in run.config
            or "steps" not in run.records):
        return None
    per_step = flops_olmo.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["kda_pairs"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
