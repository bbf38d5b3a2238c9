"""Share of chip 0's busy time under the expert layer's `moe_route`,
`moe_dispatch` and `moe_combine` scopes (router, top-k, the sort and the
gathers in both directions, forward and backward), joined to the trace
through the compiled step's `op_name` metadata (benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    if run.trace is None or not op_scopes:
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, "moe_route",
                                  "moe_dispatch", "moe_combine") / busy
