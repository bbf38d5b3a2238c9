"""Share of the window in which chip 0 was idle and no phase of the loop's
`loop_marks` covers the time: the guard on the timeline's coverage (it has
no holes, so this is 0 but for the ends of the window, where the device's
clock and the host's differ by a millisecond or so)."""

from benchmark import loop_spans


def read(run):
    return loop_spans.idle_pct(run, loop_spans.UNNAMED)
