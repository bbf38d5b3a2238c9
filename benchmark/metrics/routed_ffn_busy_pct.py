"""Share of chip 0's busy time under the sparse feed-forward's scopes in a
model WITHOUT a shared expert: `moe_route`, `moe_dispatch`, `expert_ffn` and
`moe_combine` (router and choice, the sort and the gathers in both
directions, the grouped products over the tokens x K buffer; forward and
backward), joined to the trace through the compiled step's `op_name`
metadata (benchmark/scopes.py). `sparse_ffn_busy_pct` is the same with the
shared expert's scope, in the cell that counts the delta rule."""

from benchmark import scopes

SCOPES = ("moe_route", "moe_dispatch", "expert_ffn", "moe_combine")


def read(run):
    op_scopes = run.program.get("op_scopes")
    if (run.trace is None or not op_scopes
            or "conv_L_cache" not in run.config
            or not set(SCOPES) & set(op_scopes.values())):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, *SCOPES) / busy
