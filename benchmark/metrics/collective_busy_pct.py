"""Share of the window in which a collective runs on chip 0."""


def read(run):
    if run.trace is None or run.chips < 2 or run.trace.window_s <= 0:
        return None
    running, _ = run.trace.collective_seconds()
    return 100.0 * running / run.trace.window_s
