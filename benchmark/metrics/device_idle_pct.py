"""Share of the traced window in which no operation ran on the device: 1 -
union of device-op intervals / window, averaged over the chips."""


def read(run):
    if run.trace is None or "steps" not in run.records:
        return None
    return run.trace.idle_pct()
