"""Serialising and writing the 200 reply (`reply_s` of `serve_request`
events; after `latency_s` is stamped, so in no other server-side number),
median."""

from benchmark import spans


def read(run):
    return spans.median_ms(run, "serve_request", lambda e: e["reply_s"])
