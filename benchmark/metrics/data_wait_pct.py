"""Share of the window's wall time the loop thread spent blocked on the
loader's prefetch queue (`wait`: `t_next` to `t_got` of the step records'
`loop_marks`). Queue time, not starvation: the loop dispatches up to a log
interval ahead of the device, so a loader faster than the device and slower
than the dispatch fills this share with time the run-ahead hides (27.8 in a
device-bound run, PERF.md, PR 37), and `loop_fence_pct` falls by as much. The
run is input-bound where this share grows and `loop_fence_pct` goes to 0; the
share of the window the chip stood idle for want of a batch is
`loop_idle_wait_pct`, and that is the one to hold low."""

from benchmark import loop_spans


def read(run):
    return loop_spans.wall_pct(run, "wait")
