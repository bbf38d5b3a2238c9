"""Metric readers on records made by hand."""

import types

import pytest

from benchmark.metrics import (serve_images_per_s_chip, serve_latency_p75_ms,
                               serve_steady_images_per_s_chip)


def run_with(records, traffic=None, chips=1):
    return types.SimpleNamespace(records=records, traffic=traffic or {},
                                 chips=chips)


def stalled_arrivals():
    """Batches of 8 replies every 0.1 s (80 a second), each reply 1 ms after
    the one before; one stall of 0.3 s after the 40th batch. The window
    closes nominally at 10.25 s: batch 100 is the first to arrive after."""
    arrivals = []
    for batch in range(101):
        start = 0.1 * batch + (0.3 if batch >= 40 else 0.0)
        arrivals += [start + 0.001 * i for i in range(8)]
    return {"arrivals": arrivals[:800], "arrival_after_close": arrivals[800],
            "failed": 0}


def test_serve_rate_counts_every_reply_over_the_whole_window():
    records = stalled_arrivals()
    # 100 whole batches from the first reply of batch 0 to the first of
    # batch 100: 10.3 s with the stall in it, whatever the nominal edges cut
    assert serve_images_per_s_chip.read(run_with(records)) \
        == pytest.approx(800 / 10.3, rel=1e-12)
    assert serve_images_per_s_chip.read(run_with(records, chips=4)) \
        == pytest.approx(200 / 10.3, rel=1e-12)
    # a stall of 2.4 s more, every request in flight, costs its whole share
    late = dict(records, arrival_after_close=records["arrival_after_close"] + 2.4)
    assert serve_images_per_s_chip.read(run_with(late)) \
        == pytest.approx(800 / 12.7, rel=1e-12)
    # nothing to read: no reply after the close, none inside, a failed one
    for broken in ({"arrival_after_close": None}, {"arrivals": []},
                   {"failed": 1}):
        assert serve_images_per_s_chip.read(
            run_with({**records, **broken})) is None


def test_steady_rate_is_the_median_over_spans_of_replies():
    records = stalled_arrivals()
    arrivals = records["arrivals"]
    run = run_with(records, {"rate_span": 64})
    # a span of 64 replies is 8 whole batches: 0.8 s, whatever its offset in
    # a batch; the 64 of 736 spans that cross the stall read 58.2 and leave
    # the median alone: it cannot see the stall, which is why it judges
    # nothing
    assert serve_steady_images_per_s_chip.read(run) == pytest.approx(80.0, rel=1e-9)
    assert serve_images_per_s_chip.read(run) < 78.0
    assert serve_steady_images_per_s_chip.read(
        run_with(records, {"rate_span": 64}, 4)) == pytest.approx(20.0, rel=1e-9)
    # nothing to read: too few replies, or a failed one
    assert serve_steady_images_per_s_chip.read(run_with(
        {"arrivals": arrivals[:64], "failed": 0}, {"rate_span": 64})) is None
    assert serve_steady_images_per_s_chip.read(run_with(
        {"arrivals": arrivals, "failed": 1}, {"rate_span": 64})) is None


def test_latency_percentile():
    latencies = sorted(0.001 * i for i in range(1, 101))     # 1..100 ms
    assert serve_latency_p75_ms.read(run_with({"latency_s": latencies})) \
        == pytest.approx(75.25)
    assert serve_latency_p75_ms.read(run_with({})) is None
