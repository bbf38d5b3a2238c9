"""The server's batch phases against a device timeline, on events and a
`ReducedTrace` made by hand (every expected value worked out in the
comments): the split of idle time by phase, and the readers built on it."""

import types

import pytest

from benchmark import spans
from benchmark import trace_reduce as tr
from benchmark.metrics import (
    batch_collect_ms_p50, batch_handoff_ms_p50, engine_put_ms_p50,
    engine_wait_ms_p50, request_decode_ms_p50, request_reply_ms_p50,
    request_wake_ms_p50, serve_idle_collect_pct, serve_idle_handoff_pct,
    serve_idle_put_pct, serve_idle_unnamed_pct, serve_idle_wait_pct)

IDLE_READERS = (serve_idle_collect_pct, serve_idle_put_pct,
                serve_idle_wait_pct, serve_idle_handoff_pct,
                serve_idle_unnamed_pct)
OPEN_T = 1_000.0      # the window opens at this `time.time()` ...
TRACE_LO = 5e6        # ... which is 5 ms into the trace


def batch(batch_id, *marks_ms):
    """A `serve_batch` event whose seven marks lie `marks_ms` milliseconds
    after the window opens."""
    e = {"kind": "serve_batch", "batch_id": batch_id, "batch_size": 8,
         "bucket": 8, "infer_s": 1e-3 * (marks_ms[5] - marks_ms[2])}
    e.update({m: OPEN_T + 1e-3 * t for m, t in zip(spans.MARKS, marks_ms)})
    return e


def run_of(events, busy_ms, window_ms=100.0, devices=1):
    """A run whose window is `window_ms` long and whose chip 0 ran one op in
    each (start, end) of `busy_ms`, milliseconds after the window opens."""
    ops = [tr.Op(start=TRACE_LO + 1e6 * a, end=TRACE_LO + 1e6 * b,
                 name=f"fusion.{i}", category="fusion kOutput", text="fusion")
           for i, (a, b) in enumerate(busy_ms)]
    raw = [(f"/device:TPU:{d}", ops) for d in range(devices)]
    trace = tr.reduce_events(raw, [],
                             (TRACE_LO, TRACE_LO + 1e6 * window_ms))
    return types.SimpleNamespace(
        trace=trace, records={"serve_events": events,
                              "window_open_t": OPEN_T,
                              "window_close_t": OPEN_T + 1e-3 * window_ms,
                              "answered": 16})


def ms(split):
    return {k: pytest.approx(v / 1e6, abs=1e-3) for k, v in split.items()}


def two_batches():
    # marks: collect stack put dispatch wait deliver end
    #   batch 0:   0     10   12   16      17    48     50
    #   batch 1:  50     52   53   58      60    90     95
    # device busy 20..47 and 62..88; idle 0..20, 47..62, 88..100 = 47 ms
    events = [batch(0, 0, 10, 12, 16, 17, 48, 50),
              batch(1, 50, 52, 53, 58, 60, 90, 95),
              {"kind": "serve_request", "decode_s": 0.004, "wake_s": 0.001,
               "reply_s": 0.0005},
              {"kind": "serve_request", "decode_s": 0.006, "wake_s": 0.003,
               "reply_s": 0.0015}]
    return run_of(events, [(20, 47), (62, 88)])


def test_a_gap_that_straddles_phases_is_split_at_the_marks():
    run = two_batches()
    split = spans.idle_by_phase(run)
    # gap 0..20:  collect 0..10, stack 10..12, put 12..16, dispatch 16..17,
    #             wait 17..20
    # gap 47..62: wait 47..48, deliver 48..50, collect 50..52, stack 52..53,
    #             put 53..58, dispatch 58..60, wait 60..62
    # gap 88..100: wait 88..90, deliver 90..95, nothing 95..100
    assert ms(split) == ms({k: 1e6 * v for k, v in {
        "collect": 10 + 2, "stack": 2 + 1, "put": 4 + 5, "dispatch": 1 + 2,
        "wait": 3 + 1 + 2 + 2, "deliver": 2 + 5, "unnamed": 5}.items()})
    assert sum(split.values()) == pytest.approx(47e6)
    # "largest overlap wins" would have given the first gap whole to collect
    assert split["collect"] < 20e6


def test_the_five_shares_sum_to_the_idle_share():
    run = two_batches()
    shares = [reader.read(run) for reader in IDLE_READERS]
    # of a 100 ms window: collect 12, put 9, wait 8,
    # handoff = stack 3 + dispatch 3 + deliver 7, unnamed 5
    assert shares == [pytest.approx(v) for v in (12.0, 9.0, 8.0, 13.0, 5.0)]
    assert sum(shares) == pytest.approx(run.trace.idle_pct())
    assert run.trace.idle_pct() == pytest.approx(47.0)


def test_no_events_leaves_everything_unnamed():
    # none at all, and those of a program that does not mark its phases
    old = {"kind": "serve_batch", "batch_size": 8, "bucket": 8,
           "infer_s": 0.03, "queue_wait_s_max": 0.3}
    for events in ([], [old]):
        run = run_of(events, [(20, 47), (62, 88)])
        split = spans.idle_by_phase(run)
        assert split.pop("unnamed") == pytest.approx(47e6)
        assert set(split) == set(spans.PHASES) and not any(split.values())
        assert [r.read(run) for r in IDLE_READERS] == [
            0.0, 0.0, 0.0, 0.0, pytest.approx(47.0)]
        # the duration readers find nothing to read, and say so
        for reader in (engine_put_ms_p50, engine_wait_ms_p50,
                       batch_collect_ms_p50, batch_handoff_ms_p50,
                       request_decode_ms_p50, request_wake_ms_p50,
                       request_reply_ms_p50):
            assert reader.read(run) is None


def test_a_batch_that_straddles_the_windows_edge_is_clipped():
    # its collect began 30 ms before the window opened, and its deliver ends
    # 10 ms after the window closed; the device ran 5..95
    events = [batch(0, -30, 2, 3, 4, 5, 96, 110)]
    run = run_of(events, [(5, 95)])
    phases = spans.batch_phases(run)
    lo, hi = run.trace.window
    assert phases["collect"] == [(lo, pytest.approx(lo + 2e6))]
    assert phases["deliver"] == [(pytest.approx(lo + 96e6), hi)]
    # idle 0..5: collect 2, stack 1, put 1, dispatch 1; 95..100: wait 1,
    # deliver 4
    assert ms(spans.idle_by_phase(run)) == ms({k: 1e6 * v for k, v in {
        "collect": 2, "stack": 1, "put": 1, "dispatch": 1, "wait": 1,
        "deliver": 4, "unnamed": 0}.items()})
    # a batch wholly outside the window adds nothing
    run = run_of(events + [batch(1, 110, 120, 121, 122, 123, 150, 151),
                           batch(2, -90, -80, -79, -78, -77, -40, -30)],
                 [(5, 95)])
    assert sum(spans.idle_by_phase(run).values()) == pytest.approx(10e6)
    assert spans.idle_by_phase(run)["unnamed"] == pytest.approx(0.0)


def test_duration_readers_take_the_median_of_their_marks():
    run = two_batches()
    assert engine_put_ms_p50.read(run) == pytest.approx(4.5)       # 4, 5
    assert engine_wait_ms_p50.read(run) == pytest.approx(30.5)     # 31, 30
    assert batch_collect_ms_p50.read(run) == pytest.approx(6.0)    # 10, 2
    # neither batch was dispatched behind one in flight: the call's end is
    # not marked (benchmark/tests/test_overlap.py has the overlapped case)
    assert batch_handoff_ms_p50.read(run) is None
    assert request_decode_ms_p50.read(run) == pytest.approx(5.0)
    assert request_wake_ms_p50.read(run) == pytest.approx(2.0)
    assert request_reply_ms_p50.read(run) == pytest.approx(1.0)


def test_no_device_or_no_trace_reads_nothing():
    run = two_batches()
    run.trace = tr.reduce_events([], [], (TRACE_LO, TRACE_LO + 1e8))
    assert spans.idle_by_phase(run) is None
    assert [r.read(run) for r in IDLE_READERS] == [None] * 5
    run.trace = None
    assert [r.read(run) for r in IDLE_READERS] == [None] * 5
    # the idle share is chip 0's: a second chip changes nothing
    run = two_batches()
    both = run_of(run.records["serve_events"], [(20, 47), (62, 88)], devices=2)
    assert spans.idle_by_phase(both) == spans.idle_by_phase(run)
