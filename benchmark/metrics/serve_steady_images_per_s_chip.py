"""The rate the server holds while nothing stands still: the MEDIAN, over
every run of `rate_span` consecutive good replies that arrived inside the
window, of `rate_span` / the time from the first of them to the last, per
chip, on the client's clock.

A span of 64 replies is 8 batches of 8, under a second: its ends fall on the
same place in a batch, and a stall touches a tenth of the spans, not their
median. So this reads the server's pace and cannot see a stall or a tail:
the judged `serve_images_per_s_chip` counts every reply over the whole
window, and what it reads below this one is time in which no reply came."""

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
