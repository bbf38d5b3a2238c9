"""Roofline share of the attention kernels (`flash_*` events of chip 0):
forward and backward FLOPs and bytes from shapes (benchmark/roofline.py) over
their summed device time. At 256 tokens the bound is memory. Under a remat
policy that runs the forward kernel again in the backward pass, that second
run is in the time and not in the need."""

from benchmark import flops, roofline


def read(run):
    if run.trace is None or "steps" not in run.records:
        return None
    seconds = run.trace.seconds_matching("flash_")
    if seconds <= 0:
        return None
    c = run.config
    per_chip = run.records["global_batch"] // run.chips
    need = roofline.attention_need(
        per_chip * run.records["steps"], c["num_heads"], flops.num_patches(c),
        c["embed_dim"] // c["num_heads"], c["num_blocks"])
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["attention_bound"] = bound
    run.records["attention_kernel_s"] = seconds
    return share
