"""Metric readers on records made by hand."""

import types

import pytest

from benchmark.metrics import serve_images_per_s_chip, serve_latency_p75_ms


def run_with(records, traffic=None, chips=1):
    return types.SimpleNamespace(records=records, traffic=traffic or {},
                                 chips=chips)


def test_serve_rate_is_the_median_over_spans_of_replies():
    # batches of 8 replies every 0.1 s (80 a second), each reply 1 ms after
    # the one before; one stall of 0.3 s after the 40th batch
    arrivals = []
    for batch in range(100):
        start = 0.1 * batch + (0.3 if batch >= 40 else 0.0)
        arrivals += [start + 0.001 * i for i in range(8)]
    records = {"arrivals": arrivals, "failed": 0}
    run = run_with(records, {"rate_span": 64})
    # a span of 64 replies is 8 whole batches: 0.8 s, whatever its offset in
    # a batch; the 64 of 736 spans that cross the stall read 58.2 and leave
    # the median alone. Replies over the whole window would read 78.4.
    assert serve_images_per_s_chip.read(run) == pytest.approx(80.0, rel=1e-9)
    assert len(arrivals) / (arrivals[-1] - arrivals[0]) < 78.5
    assert serve_images_per_s_chip.read(run_with(records, {"rate_span": 64}, 4)) \
        == pytest.approx(20.0, rel=1e-9)
    # nothing to read: too few replies, or a failed one
    assert serve_images_per_s_chip.read(
        run_with({"arrivals": arrivals[:64], "failed": 0}, {"rate_span": 64})) is None
    assert serve_images_per_s_chip.read(
        run_with({"arrivals": arrivals, "failed": 1}, {"rate_span": 64})) is None


def test_latency_percentile():
    latencies = sorted(0.001 * i for i in range(1, 101))     # 1..100 ms
    assert serve_latency_p75_ms.read(run_with({"latency_s": latencies})) \
        == pytest.approx(75.25)
    assert serve_latency_p75_ms.read(run_with({})) is None
