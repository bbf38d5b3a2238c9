"""From a batch's results reaching the host to the request's handler thread
running again (`wake_s` of `serve_request` events), median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_request", lambda e: e["wake_s"])
