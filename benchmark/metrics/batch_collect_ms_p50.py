"""Time the worker waits for a batch to fill (`t_stack - t_collect` of
`serve_batch` events: from the end of the batch before, its telemetry write
included, to the pop of this one), median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_batch",
                           lambda e: e["t_stack"] - e["t_collect"])
