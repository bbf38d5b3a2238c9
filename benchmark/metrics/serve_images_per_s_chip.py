"""Requests answered 200 with a well-formed, right answer, per second and
chip, on the client's clock, over ALL the work and ALL the time of the
window: every good reply from the first that arrived inside the window up to
(not with) the first that arrived after its nominal close, divided by the
time between those two arrivals.

The window runs from a reply to a reply, as a train cell's runs from a fence
to a fence: whole replies arrive a batch at a time, so their count over a
fixed ten seconds moves in steps of a batch (0.85%), while the time from the
first reply of one batch to the first of a later one holds whole batches
only. A stall, a slow batch or a tail anywhere in the window, across its
close too, takes its full share off this rate; the pace between stalls is
the per-layer `serve_steady_images_per_s_chip`."""


def read(run):
    arrivals = run.records.get("arrivals")
    closes = run.records.get("arrival_after_close")
    if not arrivals or closes is None or run.records["failed"]:
        return None
    return len(arrivals) / (closes - arrivals[0]) / run.chips
