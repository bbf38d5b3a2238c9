"""Roofline share of the gated short convolution of the conv layers: the
FLOPs and bytes it needs from shapes and the step's own `tokens`
(benchmark/roofline_lfm2.py: gated_conv_need, the same whatever implements
it: the projection's three streams read and one written a token forward,
seven more with the cotangents backward) over chip 0's device time under the
program's `gconv_in`, `gconv` and `gconv_out` scopes, joined to the trace
through the compiled step's `op_name` metadata (benchmark/scopes.py). A
kernel that a later PR puts inside those scopes is found by the same join.
Where the compiler fuses the gates into the neighbouring projection's
product, that product's time is under the scope and in the share too."""

from benchmark import flops_lfm2, roofline, roofline_lfm2, scopes

SCOPES = ("gconv_in", "gconv", "gconv_out")


def read(run):
    op_scopes = run.program.get("op_scopes")
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or not op_scopes
            or "conv_L_cache" not in run.config
            or not set(SCOPES) & set(op_scopes.values())
            or "steps" not in run.records):
        return None
    seconds = scopes.seconds(run.trace, op_scopes, *SCOPES)
    if seconds <= 0:
        return None
    c = run.config
    need = roofline_lfm2.gated_conv_need(
        counts["tokens"] / run.chips * run.records["steps"],
        c["hidden_size"], c["conv_L_cache"],
        c["layer_types"].count(flops_lfm2.CONV))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["gconv_bound"] = bound
    run.records["gconv_s"] = seconds
    return share
