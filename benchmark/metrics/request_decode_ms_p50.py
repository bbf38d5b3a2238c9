"""JPEG decode and resize of one request in its handler thread (`decode_s`
of the server's `serve_request` events), median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_request", lambda e: e["decode_s"])
