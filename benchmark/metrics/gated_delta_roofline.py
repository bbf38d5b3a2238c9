"""Roofline share of the delta rule of the linear_attention (Gated DeltaNet)
layers: the FLOPs and bytes it needs from shapes and the step's own counters
(benchmark/roofline_olmo.py: gated_delta_need, the same whatever implements
it, on the yardstick of Ling's `kda_roofline`) over chip 0's device time
under the program's `kda_chunk` and `kda_state` scopes, joined to the trace
through the compiled step's `op_name` metadata (benchmark/scopes.py). A
kernel that a later PR puts inside those scopes is found by the same join."""

from benchmark import flops_olmo, roofline, roofline_olmo, scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    counts = run.records.get("packed_counts") or {}
    if (run.trace is None or not op_scopes or "kda_pairs" not in counts
            or "linear_key_head_dim" not in run.config
            or "steps" not in run.records):
        return None
    seconds = scopes.seconds(run.trace, op_scopes, "kda_chunk", "kda_state")
    if seconds <= 0:
        return None
    steps = run.records["steps"]
    need = roofline_olmo.gated_delta_need(
        run.config, counts["tokens"] / run.chips * steps,
        counts["kda_pairs"] / run.chips * steps,
        counts["kda_live_chunks"] / run.chips * steps,
        run.config["layer_types"].count(flops_olmo.LINEAR))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records["gated_delta_bound"] = bound
    run.records["gated_delta_rule_s"] = seconds
    return share
