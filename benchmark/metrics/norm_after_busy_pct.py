"""Share of chip 0's busy time under the scopes `post_norm` (the RMSNorm on
what each half of a norm-after block adds) and `qk_norm` (the RMSNorm of q
and of k over the whole projected width), forward and backward, joined to
the trace through the compiled step's `op_name` metadata
(benchmark/scopes.py). A program without those scopes reports nothing."""

from benchmark import scopes


def read(run):
    op_scopes = run.program.get("op_scopes")
    if run.trace is None or not op_scopes or not (
            {"post_norm", "qk_norm"} & set(op_scopes.values())):
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * scopes.seconds(run.trace, op_scopes, "post_norm",
                                  "qk_norm") / busy
