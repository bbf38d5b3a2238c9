"""Requests answered 200 with a well-formed, right answer, per second and
chip, on the client's clock: the MEDIAN, over every run of `rate_span`
consecutive replies that arrived inside the window, of `rate_span` / the time
from the first of them to the last.

Why not replies / seconds: whole replies arrive a batch at a time, so their
count over a fixed window moves in steps of a batch (1.1% at 10 s); and the
host's cores are shared, so in one run in six a stall of a few hundred
milliseconds took 2% off the whole-window rate (PERF.md, PR 22). A span of 64
replies is 8 batches of 8, under a second: its ends fall on the same place
in a batch, and a stall touches a tenth of the spans, not their median."""

import statistics


def read(run):
    arrivals = run.records.get("arrivals")
    span = int(run.traffic.get("rate_span", 0))
    if not arrivals or span < 1 or len(arrivals) <= span:
        return None
    if run.records["failed"]:
        return None
    rates = [span / (b - a) for a, b in zip(arrivals, arrivals[span:]) if b > a]
    return statistics.median(rates) / run.chips
