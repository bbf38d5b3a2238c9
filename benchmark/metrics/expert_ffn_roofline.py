"""Roofline share of the held experts' grouped matrix products (the
`ragged-dot*` kernels the TPU compiler makes of `jax.lax.ragged_dot`, forward
and backward, of chip 0): 3 products of 2 x slots x hidden x expert width,
x 3 with the backward, and the bytes of the held experts' weights and the
sorted activations (benchmark/roofline_laguna.py), over their summed device
time. `slots` is what the step counted (`expert_slots_here`, at the last
warm-up step: the router trains, so the window's own differs a little)."""

from benchmark import roofline, roofline_laguna


def read(run):
    counts = run.records.get("packed_counts")
    if run.trace is None or counts is None or "steps" not in run.records:
        return None
    seconds = run.trace.seconds_matching("ragged-dot")
    if seconds <= 0:
        return None
    c = run.config
    need = roofline_laguna.expert_ffn_need(
        counts["expert_slots_here"] / run.chips * run.records["steps"],
        c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
        c["mlp_layer_types"].count("sparse") * run.records["steps"])
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["expert_ffn_bound"] = bound
    run.records["expert_ffn_kernel_s"] = seconds
    return share
