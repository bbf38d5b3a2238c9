"""Share of the window in which chip 0 was idle while the batcher worker
waited for its next batch to fill (`collect`: `t_collect` to `t_stack` of
the server's `serve_batch` events; the telemetry write of the batch before
falls in here)."""

from benchmark import spans


def read(run):
    return spans.idle_pct(run, "collect")
