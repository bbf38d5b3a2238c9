"""Share of chip 0's busy time inside the packed attention kernels
(`flash_packed_*`): the number that says the mechanism does the work."""


def read(run):
    if run.trace is None or "packed_counts" not in run.records:
        return None
    busy = run.trace.self_seconds(lambda o: True)
    if busy <= 0:
        return None
    return 100.0 * run.trace.seconds_matching("flash_packed_") / busy
