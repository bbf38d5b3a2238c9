"""Share of the window in which chip 0 was idle while the loop thread was
blocked on the loss of a log step (`fence` of benchmark/loop_spans.py). The
device has work queued then, so this is 0 but for the clocks' edge. The six
`loop_idle_*` shares add up to `device_idle_pct`."""

from benchmark import loop_spans


def read(run):
    return loop_spans.idle_pct(run, "fence")
