"""Roofline share of the delta rule of the kda layers: the FLOPs and bytes
it needs from shapes and the step's own counters (benchmark/roofline_ling.py:
the same whatever implements it) over chip 0's device time under the
program's `kda_chunk` and `kda_state` scopes, joined to the trace through the
compiled step's `op_name` metadata (benchmark/scopes.py). A kernel that a
later PR puts inside those scopes is found by the same join."""

from benchmark import flops_ling, roofline, roofline_ling, scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or not op_scopes or "kda_pairs" not in counts
            or "steps" not in run.records):
        return None
    seconds = scopes.seconds(run.trace, op_scopes, "kda_chunk", "kda_state")
    if seconds <= 0:
        return None
    steps = run.records["steps"]
    need = roofline_ling.kda_need(
        run.config, counts["tokens"] / run.chips * steps,
        counts["kda_pairs"] / run.chips * steps,
        counts["kda_live_chunks"] / run.chips * steps,
        flops_ling.kinds(run.config).count("kda"))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["kda_bound"] = bound
    run.records["kda_delta_rule_s"] = seconds
    return share
