"""Share of the window's wall time the loop thread spent inside the
`train_step` call (`dispatch`: `t_batch` to `t_dispatch`): the enqueue of
the compiled step, and whatever the runtime makes a caller wait for there."""

from benchmark import loop_spans


def read(run):
    return loop_spans.wall_pct(run, "dispatch")
