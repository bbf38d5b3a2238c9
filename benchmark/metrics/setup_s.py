"""Process start to the opening of the measured window: imports, weights made
on the device from the seed, compile or cache load, warm-up, the reference
check. Host clock."""


def read(run):
    opened = run.records.get("window_open_t")
    return None if opened is None else opened - run.process_start
