"""Share of chip 0's busy time under the state-space mixer's scopes
`ssm_conv`, `ssd_chunk`, `ssd_state` and `ssm_gate_norm` (the convolution,
the scan and the gated norm, forward and backward; the mixer's two
projections are plain matmuls outside them), joined to the trace through the
compiled step's `op_name` metadata (benchmark/scopes.py)."""

from benchmark import scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    if run.trace is None or not op_scopes or "ssd_pairs" not in (
            run.records.get("packed_counts") or {}):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, "ssm_conv",
                                  "ssd_chunk", "ssd_state",
                                  "ssm_gate_norm") / busy
