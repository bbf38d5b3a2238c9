"""Roofline share of the latent layer's attention kernels (`flash_latent_*`
events of chip 0: the heads held, each with a key of its own, q and k of
qk_nope_head_dim + qk_rope_head_dim and v of v_head_dim): the FLOPs and
bytes causal attention within each document needs at the two widths
(benchmark/roofline_ling.py: latent_attention_need, from the step's own
`causal_pairs`) over their summed device time."""

from benchmark import flops_ling, roofline, roofline_ling


def read(run):
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or "kda_pairs" not in counts
            or "steps" not in run.records):
        return None
    seconds = run.trace.seconds_matching("flash_latent_")
    if seconds <= 0:
        return None
    steps = run.records["steps"]
    need = roofline_ling.latent_attention_need(
        run.config, counts["causal_pairs"] / run.chips * steps,
        counts["tokens"] / run.chips * steps,
        flops_ling.kinds(run.config).count("latent"))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["flash_latent_bound"] = bound
    run.records["flash_latent_kernel_s"] = seconds
    return share
