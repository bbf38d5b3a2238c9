"""Share of the window in which a collective runs on chip 0 while no compute
operation runs there: what overlap work can still win."""


def read(run):
    if run.trace is None or run.chips < 2 or run.trace.window_s <= 0:
        return None
    _, exposed = run.trace.collective_seconds()
    return 100.0 * exposed / run.trace.window_s
