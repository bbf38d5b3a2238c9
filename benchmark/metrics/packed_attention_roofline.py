"""Roofline share of the packed attention kernels (`flash_packed_*` events
of chip 0): the FLOPs and bytes that attention within each image needs
(benchmark/roofline_packed.py: the sum of n_i^2, not T^2) over their summed
device time. Reads what the step itself counted. The forward that remat
runs again is in the time and not in the need."""

from benchmark import roofline, roofline_packed


def read(run):
    counts = run.records.get("packed_counts")
    if run.trace is None or counts is None or "steps" not in run.records:
        return None
    seconds = run.trace.seconds_matching("flash_packed_")
    if seconds <= 0:
        return None
    c = run.config
    steps = run.records["steps"]
    need = roofline_packed.packed_attention_need(
        counts["token_pairs"] / run.chips * steps,
        counts["tokens"] / run.chips * steps, c["num_heads"],
        c["embed_dim"] // c["num_heads"], c["num_blocks"])
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["packed_attention_bound"] = bound
    run.records["packed_attention_kernel_s"] = seconds
    return share
