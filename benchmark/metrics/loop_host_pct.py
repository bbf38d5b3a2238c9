"""Share of the window's wall time the loop thread spent in its own and
telemetry's Python (`host`: `t_fence` to the next iteration's `t_next`: fault
hook, watchdog, logging, the step record's fetches and write, control
poll)."""

from benchmark import loop_spans


def read(run):
    return loop_spans.wall_pct(run, "host")
