"""Share of chip 0's busy time under the gated short convolution's scopes
`gconv_in`, `gconv` and `gconv_out` (the two gates, the taps and the
padding's select, forward and backward; the mixer's two projections are
plain matmuls outside them), joined to the trace through the compiled
step's `op_name` metadata (benchmark/scopes.py)."""

from benchmark import scopes
from benchmark.metrics.gconv_roofline import SCOPES


def read(run):
    op_scopes = run.program.get("op_scopes")
    if (run.trace is None or not op_scopes
            or not set(SCOPES) & set(op_scopes.values())):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, *SCOPES) / busy
