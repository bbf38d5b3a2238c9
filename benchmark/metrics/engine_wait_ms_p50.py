"""Time the worker is blocked on a batch's outputs (`t_deliver - t_wait` of
`serve_batch` events: device execution and the fetch of the top-k),
median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_batch",
                           lambda e: e["t_deliver"] - e["t_wait"])
