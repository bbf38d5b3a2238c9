"""Share of the window's wall time the loop thread spent blocked on the
loss of a log step (`fence`: `t_dispatch` to `t_fence`): what is left of the
loop's run-ahead once the queue waits (`data_wait_pct`), hand-offs and
dispatches of the interval are paid. With `data_wait_pct` it is near 100 when
the device sets the pace; near 0, the host or the loader does."""

from benchmark import loop_spans


def read(run):
    return loop_spans.wall_pct(run, "fence")
