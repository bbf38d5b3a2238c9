"""Roofline share of the Olmo-Hybrid decoder's attention kernels
(`flash_causal_*` events of chip 0, at the heads held, as many key/value
heads as query heads, each hidden_size / the published heads wide, no
position encoding): the FLOPs and bytes causal attention within each document
needs (benchmark/roofline_laguna.py: attention_need, from the step's own
`causal_pairs`) over their summed device time."""

from benchmark import flops_olmo, roofline, roofline_laguna


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or "kda_pairs" not in counts
            or "linear_key_head_dim" not in run.config
            or "steps" not in run.records):
        return None
    seconds = run.trace.seconds_matching("flash_causal_")
    if seconds <= 0:
        return None
    c, steps = run.config, run.records["steps"]
    need = roofline_laguna.attention_need(
        counts["causal_pairs"] / run.chips * steps,
        counts["tokens"] / run.chips * steps, c["num_attention_heads"],
        c["num_key_value_heads"], flops_olmo.head_dim(c),
        sum(kind != flops_olmo.LINEAR for kind in c["layer_types"]))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["flash_causal_bound"] = bound
    run.records["flash_causal_kernel_s"] = seconds
    return share
