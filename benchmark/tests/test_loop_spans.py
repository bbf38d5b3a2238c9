"""The train loop's phases against a device timeline, on marks and a
`ReducedTrace` made by hand (every expected value worked out in the
comments): the split of the window's wall time and of chip 0's idle time by
phase, and the eleven readers built on it."""

import types

import pytest

from benchmark import loop_spans
from benchmark import trace_reduce as tr
from benchmark.metrics import (
    data_wait_pct, loop_dispatch_pct, loop_fence_pct, loop_host_pct,
    loop_idle_dispatch_pct, loop_idle_fence_pct, loop_idle_host_pct,
    loop_idle_put_pct, loop_idle_unnamed_pct, loop_idle_wait_pct,
    loop_put_pct)

WALL_READERS = (data_wait_pct, loop_put_pct, loop_dispatch_pct,
                loop_fence_pct, loop_host_pct)
IDLE_READERS = (loop_idle_wait_pct, loop_idle_put_pct, loop_idle_dispatch_pct,
                loop_idle_fence_pct, loop_idle_host_pct,
                loop_idle_unnamed_pct)
OPEN_T = 1_000.0      # the window opens at this `time.time()` ...
TRACE_LO = 5e6        # ... which is 5 ms into the trace


def row(step, *marks_ms):
    """A row of `loop_marks` whose five marks lie `marks_ms` milliseconds
    after the window opens."""
    return [step] + [OPEN_T + 1e-3 * t for t in marks_ms]


def run_of(rows, busy_ms, window_ms=100.0, trace=True):
    """A run whose window is `window_ms` long and whose chip 0 ran one op in
    each (start, end) of `busy_ms`, milliseconds after the window opens."""
    ops = [tr.Op(start=TRACE_LO + 1e6 * a, end=TRACE_LO + 1e6 * b,
                 name=f"fusion.{i}", category="fusion kOutput", text="fusion")
           for i, (a, b) in enumerate(busy_ms)]
    reduced = tr.reduce_events([("/device:TPU:0", ops)], [],
                               (TRACE_LO, TRACE_LO + 1e6 * window_ms))
    records = {"window_open_t": OPEN_T,
               "window_close_t": OPEN_T + 1e-3 * window_ms,
               "window_s": 1e-3 * window_ms, "steps": 2}
    if rows is not None:
        records["loop_marks"] = rows
    return types.SimpleNamespace(trace=reduced if trace else None,
                                 records=records)


def three_steps():
    # marks:       next  got batch dispatch fence   (host to the next `next`)
    #   step 20:   -30  -28   -25      -24     0    the window opens at its fence
    #   step 21:     6    7    10       12    12    no fence
    #   step 22:    13   13    15       16   100    the window closes at its fence
    #   step 23:   104  105   108      109   109
    # wall time of the window: host 0..6 + 12..13 = 7, wait 6..7 = 1,
    #   put 7..10 + 13..15 = 5, dispatch 10..12 + 15..16 = 3, fence 16..100 = 84
    # device busy 11.5..99: idle 0..11.5 and 99..100 = 12.5 ms
    return run_of([row(20, -30, -28, -25, -24, 0), row(21, 6, 7, 10, 12, 12),
                   row(22, 13, 13, 15, 16, 100),
                   row(23, 104, 105, 108, 109, 109)], [(11.5, 99)])


def test_the_five_wall_shares_add_up_to_the_window():
    run = three_steps()
    shares = [reader.read(run) for reader in WALL_READERS]
    assert shares == [pytest.approx(v) for v in (1.0, 5.0, 3.0, 84.0, 7.0)]
    assert sum(shares) == pytest.approx(100.0)


def test_the_six_idle_shares_add_up_to_the_idle_share():
    run = three_steps()
    split = loop_spans.idle_by_phase(run)
    # gap 0..11.5: host 0..6, wait 6..7, put 7..10, dispatch 10..11.5
    # gap 99..100: fence
    assert {k: pytest.approx(v / 1e6, abs=1e-6) for k, v in split.items()} \
        == {"host": 6, "wait": 1, "put": 3, "dispatch": 1.5, "fence": 1,
            "unnamed": 0}
    shares = [reader.read(run) for reader in IDLE_READERS]
    assert shares == [pytest.approx(v, abs=1e-9)
                      for v in (1.0, 3.0, 1.5, 1.0, 6.0, 0.0)]
    assert sum(shares) == pytest.approx(run.trace.idle_pct())
    assert run.trace.idle_pct() == pytest.approx(12.5)


def test_idle_time_no_row_covers_is_unnamed():
    # the rows stop at step 21: from its fence on (12 ms) nothing is named
    run = run_of([row(20, -30, -28, -25, -24, 0), row(21, 6, 7, 10, 12, 12)],
                 [(11.5, 99)])
    split = loop_spans.idle_by_phase(run)
    assert split["unnamed"] == pytest.approx(1e6)       # 99..100
    assert sum(split.values()) == pytest.approx(12.5e6)
    assert sum(r.read(run) for r in WALL_READERS) == pytest.approx(12.0)


@pytest.mark.parametrize("rows", [None, []])
def test_a_program_without_marks_reads_nothing(rows):
    # the parent of the PR that brought the marks: records without them
    run = run_of(rows, [(11.5, 99)])
    assert loop_spans.phases(run) is None
    assert [r.read(run) for r in WALL_READERS + IDLE_READERS] == [None] * 11


def test_no_trace_leaves_the_wall_shares_and_no_idle_share():
    run = three_steps()
    run.trace = None
    assert sum(r.read(run) for r in WALL_READERS) == pytest.approx(100.0)
    assert [r.read(run) for r in IDLE_READERS] == [None] * 6
    no_device = three_steps()
    no_device.trace.devices.clear()
    assert [r.read(no_device) for r in IDLE_READERS] == [None] * 6
