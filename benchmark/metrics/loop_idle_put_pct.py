"""Share of the window in which chip 0 was idle while the loop thread handed
the host batch to the device (`put` of benchmark/loop_spans.py). The six
`loop_idle_*` shares add up to `device_idle_pct`."""

from benchmark import loop_spans


def read(run):
    return loop_spans.idle_pct(run, "put")
