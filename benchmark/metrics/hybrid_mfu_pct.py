"""Model FLOP/s utilisation of the traced run of the hybrid decoder's cell:
useful forward+backward FLOPs of what a step's batch held
(benchmark/flops_granite.py: the projections and MLPs of the valid tokens,
the scan by its pairs and tokens, attention by the pairs the mask leaves,
the tied head by the targets; padding and recomputation not counted) x steps
a second over chips x the bf16 peak."""

from benchmark import flops_granite


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.peaks is None or "ssd_pairs" not in counts
            or "steps" not in run.records):
        return None
    per_step = flops_granite.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["ssd_pairs"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
