"""Share of chip 0's busy time outside matmul/convolution fusions, named
kernels (custom calls) and collectives, by `hlo_category`: layer norms, GELU,
casts, copies, the scan's stacking."""


def read(run):
    if run.trace is None or "steps" not in run.records:
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    rest = run.trace.self_seconds(
        lambda o: not (o.is_matmul or o.is_kernel or o.is_collective))
    return 100.0 * rest / busy
