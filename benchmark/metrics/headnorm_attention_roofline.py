"""Roofline share of the LFM2-MoE decoder's attention kernels
(`flash_causal_*` events of chip 0, at `num_attention_heads` query heads over
`num_key_value_heads` key/value heads of hidden_size / heads, q and k normed
a head and rotated before them): the FLOPs and bytes causal attention within
each document needs (benchmark/roofline_laguna.py: attention_need, from the
step's own `causal_pairs`) over their summed device time."""

from benchmark import flops_lfm2, roofline, roofline_laguna


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or "conv_L_cache" not in run.config
            or "causal_pairs" not in counts or "steps" not in run.records):
        return None
    seconds = run.trace.seconds_matching("flash_causal_")
    if seconds <= 0:
        return None
    c, steps = run.config, run.records["steps"]
    need = roofline_laguna.attention_need(
        counts["causal_pairs"] / run.chips * steps,
        counts["tokens"] / run.chips * steps, c["num_attention_heads"],
        c["num_key_value_heads"], flops_lfm2.head_dim(c),
        c["layer_types"].count(flops_lfm2.ATTENTION))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["flash_causal_bound"] = bound
    run.records["flash_causal_kernel_s"] = seconds
    return share
