"""Model FLOP/s utilisation of the traced run of the LFM2-MoE cell: useful
forward+backward FLOPs of what a step's batch held (benchmark/flops_lfm2.py:
the projections of the valid tokens, the attention layer by the pairs the
mask leaves, the routed experts by the slots routed to an expert held here,
the tied head by the targets; padding, sorted rows no held expert owns and
recomputation not counted) x steps a second over chips x the bf16 peak: the
share of the whole step's peak. `expert_slots_here` is the step's own
counter at the last warm-up step (the router trains and its bias moves, so
the window's own differs a little)."""

from benchmark import flops_lfm2


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.peaks is None or "conv_L_cache" not in run.config
            or "expert_slots_here" not in counts
            or "steps" not in run.records):
        return None
    per_step = flops_lfm2.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["expert_slots_here"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
