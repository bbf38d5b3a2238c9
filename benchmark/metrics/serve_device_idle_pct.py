"""As `device_idle_pct`, in a cell that serves requests."""


def read(run):
    if run.trace is None or "answered" not in run.records:
        return None
    return run.trace.idle_pct()
