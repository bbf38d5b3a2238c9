"""Share of chip 0's busy time inside the decoder's attention kernels
(`flash_causal_*` and `flash_window_*`)."""


def read(run):
    if run.trace is None or "packed_counts" not in run.records:
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * run.trace.seconds_matching(
        "flash_causal_", "flash_window_") / busy
