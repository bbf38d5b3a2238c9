"""Share of chip 0's busy time under the whole sparse feed-forward's scopes:
`moe_route`, `moe_dispatch`, `expert_ffn`, `moe_combine` and `shared_expert`
(router and group selection, the sort and the gathers in both directions,
the grouped products over the tokens x K buffer, the shared expert; forward
and backward), joined to the trace through the compiled step's `op_name`
metadata (benchmark/scopes.py). Read in the cell whose generator indexes
these scopes AND counts the delta rule (the Ling cell)."""

from benchmark import scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    if run.trace is None or not op_scopes or "kda_pairs" not in (
            run.records.get("packed_counts") or {}):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, "moe_route",
                                  "moe_dispatch", "expert_ffn", "moe_combine",
                                  "shared_expert") / busy
