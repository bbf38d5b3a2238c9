"""Roofline share of the fused clip+AdamW kernels (`fused_adamw*` events of
chip 0): 28 bytes a parameter held by the chip and step over 819 GB/s,
against their summed device time. Memory-bound by construction."""

from benchmark import roofline


def read(run):
    if run.trace is None or "steps" not in run.records:
        return None
    seconds = run.trace.seconds_matching("fused_adamw")
    if seconds <= 0:
        return None
    need = roofline.fused_optimizer_need(
        run.program["params"] / run.chips * run.records["steps"])
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["fused_optimizer_bound"] = bound
    run.records["fused_optimizer_kernel_s"] = seconds
    return share
