"""Model FLOP/s utilisation of a run of the SmallThinker cell: useful
forward+backward FLOPs of what a step's batch held
(benchmark/flops_smallthinker.py: the projections and the router of the valid
tokens, the full layer's attention by the step's own `causal_pairs` and the
three sliding layers' by its `window_pairs`, the ReLU-gated experts by the
slots routed to an expert held here, the untied head by the targets;
padding, sorted rows no held expert owns and recomputation not counted) x
steps a second over chips x the bf16 peak: the share of the whole step's
peak. `expert_slots_here` is the step's own counter at the last warm-up step
(the router trains, so the window's own differs a little)."""

from benchmark import flops_smallthinker


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.peaks is None or "moe_ffn_hidden_size" not in run.config
            or not {"expert_slots_here", "window_pairs"} <= set(counts)
            or "steps" not in run.records):
        return None
    per_step = flops_smallthinker.model_flops_per_step(
        run.config, counts["tokens"], counts["targets"],
        counts["causal_pairs"], counts["window_pairs"],
        counts["expert_slots_here"])
    rate = run.records["steps"] / run.records["window_s"]
    return 100.0 * per_step * rate / (run.chips * run.peaks["bf16_flops"])
