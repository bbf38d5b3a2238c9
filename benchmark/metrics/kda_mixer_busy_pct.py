"""Share of chip 0's busy time under the delta-rule mixer's scopes
`kda_conv`, `kda_gate`, `kda_chunk`, `kda_state` and `kda_out_norm` (the
convolutions and L2 norms, the gate, the delta rule and the gated output
norm, forward and backward; the mixer's projections are plain matmuls
outside them), joined to the trace through the compiled step's `op_name`
metadata (benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    if run.trace is None or not op_scopes or "kda_pairs" not in (
            run.records.get("packed_counts") or {}):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, "kda_conv",
                                  "kda_gate", "kda_chunk", "kda_state",
                                  "kda_out_norm") / busy
