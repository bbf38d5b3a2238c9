"""Share of the window in which chip 0 was idle while the loop thread was
blocked on the loader's prefetch queue (`wait` of benchmark/loop_spans.py):
the starvation signal, which `data_wait_pct` alone is not (the loop's
run-ahead hides queue time from the device). The six `loop_idle_*` shares add
up to `device_idle_pct`."""

from benchmark import loop_spans


def read(run):
    return loop_spans.idle_pct(run, "wait")
