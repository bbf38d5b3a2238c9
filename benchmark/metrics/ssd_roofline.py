"""Roofline share of the state-space scan of the mamba layers: the FLOPs and
bytes the scan needs from shapes and the step's own counters
(benchmark/roofline_granite.py: the same whatever implements it) over chip
0's device time under the program's `ssd_chunk` and `ssd_state` scopes,
joined to the trace through the compiled step's `op_name` metadata
(benchmark/scopes.py). A kernel that a later PR puts inside those scopes is
found by the same join."""

from benchmark import roofline, roofline_granite, scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or not op_scopes or "ssd_pairs" not in counts
            or "steps" not in run.records):
        return None
    seconds = scopes.seconds(run.trace, op_scopes, "ssd_chunk", "ssd_state")
    if seconds <= 0:
        return None
    steps = run.records["steps"]
    need = roofline_granite.ssd_need(
        run.config, counts["tokens"] / run.chips * steps,
        counts["ssd_pairs"] / run.chips * steps,
        counts["ssd_live_chunks"] / run.chips * steps,
        run.config["layer_types"].count("mamba"))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["ssd_bound"] = bound
    run.records["ssd_scan_s"] = seconds
    return share
