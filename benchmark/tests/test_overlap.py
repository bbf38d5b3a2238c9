"""Batches whose timelines overlap (a program that dispatches batch n+1
before it fetches batch n): `batch_overlap_pct` on events made by hand, and
the split of chip 0's idle time over the worker's phases on such timelines,
every expected value worked out in the comments."""

import pytest

from benchmark.metrics import (
    batch_handoff_ms_p50, batch_overlap_pct, engine_infer_ms_p50,
    engine_wait_ms_p50, serve_device_idle_pct)
from benchmark.tests.test_spans import IDLE_READERS, batch, run_of


def with_overlapped(events, flags):
    for e, flag in zip(events, flags):
        if flag is not None:
            e["overlapped"] = flag
    return events


def three_batches():
    # The worker, ms after the window opens: it dispatches batch 0 (its
    # compiled call returns at 8), finds a full bucket pending and
    # dispatches batch 1 (returns at 14) before it turns to batch 0's
    # answers (14..40) and futures (..42); dispatches batch 2 (42..47)
    # before batch 1's answers (47..70, ..72); then nothing is pending, it
    # waits for batch 2 to be done and fetches it (95.5..96, ..98).
    # marks: collect stack put dispatch wait deliver end
    #   batch 0:   0     4    5    7     14    40     42
    #   batch 1:   8    10   11   13     47    70     72   overlapped
    #   batch 2:  42    43   44   46   95.5    96     98   overlapped
    return with_overlapped([batch(0, 0, 4, 5, 7, 14, 40, 42),
                            batch(1, 8, 10, 11, 13, 47, 70, 72),
                            batch(2, 42, 43, 44, 46, 95.5, 96, 98)],
                           [0, 1, 1])


@pytest.mark.parametrize("flags, want", [
    ([0, 0, 0], 0.0), ([1, 1, 1], 100.0), ([0, 1, 1], pytest.approx(200 / 3)),
    ([None, None, None], None),     # the parent's events lack the field
])
def test_batch_overlap_pct(flags, want):
    events = with_overlapped([batch(i, *(10 * i + j for j in range(7)))
                              for i in range(3)], flags)
    events.append({"kind": "serve_request", "queue_wait_s": 0.1})
    assert batch_overlap_pct.read(run_of(events, [(0, 100)])) == want


def test_no_batch_reads_nothing():
    assert batch_overlap_pct.read(run_of([], [(0, 100)])) is None


def test_idle_shares_add_up_where_the_device_never_waits_between_batches():
    # the device goes from batch to batch without a gap: busy 8..95, idle
    # 0..8 and 95..100. Only batch 0's timeline covers 0..8 and only batch
    # 2's 95..100, so every idle moment lies in one phase:
    #   0..8:    collect 4, stack 1, put 2, dispatch 1
    #   95..100: dispatch 0.5, wait 0.5, deliver 2, unnamed 2
    run = run_of(three_batches(), [(8, 95)])
    shares = [reader.read(run) for reader in IDLE_READERS]
    # collect, put, wait, handoff = stack + dispatch + deliver, unnamed
    assert shares == [pytest.approx(v) for v in (4.0, 2.0, 0.5, 4.5, 2.0)]
    assert sum(shares) == pytest.approx(serve_device_idle_pct.read(run))
    assert serve_device_idle_pct.read(run) == pytest.approx(13.0)


def test_idle_under_two_timelines_is_counted_in_both_phases():
    # the same batches on a device that starts late and pauses between two
    # queued batches: busy 9..39, 39.2..69, 69.1..95. Idle 14.3 ms:
    #   0..9:     collect0 4, stack0 1, put0 2, dispatch0 7..9 = 2, and
    #             batch 1's collect 8..9 = 1 on top
    #   39..39.2: wait0, and batch 1's stretched dispatch (13..47) on top
    #   69..69.1: wait1, and batch 2's stretched dispatch (46..95.5) on top
    #   95..100:  dispatch2 0.5, wait2 0.5, deliver2 2, unnamed 2
    # The five shares exceed `serve_device_idle_pct` by exactly the idle
    # time that two timelines cover, 1 + 0.2 + 0.1: the split is exact only
    # while the device does not idle with a batch queued behind another.
    run = run_of(three_batches(), [(9, 39), (39.2, 69), (69.1, 95)])
    shares = [reader.read(run) for reader in IDLE_READERS]
    assert shares == [pytest.approx(v) for v in (
        4.0 + 1.0, 2.0, 0.2 + 0.1 + 0.5,
        1.0 + (2.0 + 0.2 + 0.1 + 0.5) + 2.0, 2.0)]
    assert serve_device_idle_pct.read(run) == pytest.approx(14.3)
    assert sum(shares) == pytest.approx(14.3 + 1.3)


def test_duration_readers_on_overlapped_batches():
    run = run_of(three_batches(), [(8, 95)])
    # the device has a batch from its call, or from the delivery of the one
    # ahead if that is later, to its own delivery: batch 1 70 - max(13, 40),
    # batch 2 96 - max(46, 70); batch 0 has none ahead in the window
    # (t_put -> t_deliver would read 35, 59, 52: the wait behind the batch
    # ahead inside it)
    assert engine_infer_ms_p50.read(run) == pytest.approx(28.0)   # 30, 26
    # t_wait -> t_deliver is the batch's own fetch: 26, 23, 0.5
    assert engine_wait_ms_p50.read(run) == pytest.approx(23.0)
    # the worker's own Python: stack, the call (it ends where the worker
    # turns to the batch AHEAD, that batch's t_wait), deliver:
    # batch 1: 1 + (14 - 13) + 2, batch 2: 1 + (47 - 46) + 2; batch 0 was
    # not dispatched behind another. (`dispatch` as a phase would read
    # 1 + 34 + 2 and 1 + 49.5 + 2: a period, not the worker's time)
    assert batch_handoff_ms_p50.read(run) == pytest.approx(4.0)
    # a device that idles between two batches: batch 2's call comes after
    # batch 1 has left it, and counts from the call
    late = three_batches()
    late[2].update(batch(2, 72, 73, 74, 76, 95.5, 96, 98), overlapped=1)
    assert engine_infer_ms_p50.read(run_of(late, [(8, 95)])) \
        == pytest.approx(25.0)                                    # 30, 20


def test_the_two_readers_leave_out_what_the_marks_do_not_close():
    # no batch ahead inside the window: nothing to read for either
    alone = with_overlapped([batch(5, 0, 4, 5, 7, 14, 40, 42)], [1])
    run = run_of(alone, [(8, 40)])
    assert engine_infer_ms_p50.read(run) is None
    assert batch_handoff_ms_p50.read(run) is None
    # batches not dispatched behind one in flight (or from a program whose
    # events lack the flag): the worker collects the next batch before it
    # turns to any answer, so no mark ends the call; the device time is
    # still read
    for flag in (0, None):
        events = three_batches()
        for e in events:
            e.pop("overlapped")
        run = run_of(with_overlapped(events, [flag] * 3), [(8, 95)])
        assert batch_handoff_ms_p50.read(run) is None
        assert engine_infer_ms_p50.read(run) == pytest.approx(28.0)
