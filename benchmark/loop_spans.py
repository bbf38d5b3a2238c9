"""The train loop's own timeline, laid beside the device's.

`vitax.train.loop` stamps every iteration with five `time.time()` marks and
its step records carry them as `loop_marks`, one row
`[step, t_next, t_got, t_batch, t_dispatch, t_fence]` an iteration; the
generator hands the run's rows to the readers as `run.records["loop_marks"]`.
A phase lasts from its mark to the next one, `host` from `t_fence` to the
next row's `t_next`, so the phases tile the loop thread's time; the program
writes that rule once (`vitax.telemetry.record.phase_intervals`) and this
module only clips its intervals to the window:

  wait      blocked on the loader's prefetch queue. The loop dispatches up
            to a log interval of steps ahead of the device, so this is time
            its run-ahead hides: input-bound time only where `fence` has
            gone to 0. What of it the chip stood idle through is
            `loop_idle_wait_pct`, the starvation signal
  put       the host-to-device hand-off of the batch
  dispatch  the `train_step` call
  fence     blocked on the loss (log steps): what the run-ahead has left
  host      the rest: logging, the record's fetches and write, the hooks

The device trace counts nanoseconds from a start on that same clock
(`trace_reduce.reduce_xplane`), so one offset,
`window_open_t * 1e9 - run.trace.window[0]`, puts both on one axis, as
`spans.py` does for the server's marks. One thread runs the loop, so its
phases never overlap: the window's wall time and the device's idle time are
each split over them exactly, by intersection, and idle time that no phase
covers is `unnamed`.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import trace_reduce as tr

try:        # a program from before PR 37 stamps no marks and has no rule
    from vitax.telemetry.record import LOOP_PHASES as PHASES, phase_intervals
except ImportError:
    PHASES, phase_intervals = (), None
UNNAMED = "unnamed"


def phases(run) -> Optional[Dict[str, List[tr.Interval]]]:
    """{phase: intervals} of the run's rows, in seconds on the host clock,
    clipped to the window. None where the program stamped no marks or the
    run has no window."""
    rows = run.records.get("loop_marks")
    if (not rows or phase_intervals is None
            or "window_open_t" not in run.records):
        return None
    lo, hi = run.records["window_open_t"], run.records["window_close_t"]
    out: Dict[str, List[tr.Interval]] = {p: [] for p in PHASES}
    for _, phase, a, b in phase_intervals(rows):
        if min(b, hi) > max(a, lo):
            out[phase].append((max(a, lo), min(b, hi)))
    return out


def wall_pct(run, phase: str) -> Optional[float]:
    """Share of the window's wall time the loop thread spent in `phase`;
    the five add up to 100 where the rows cover the window."""
    split = phases(run)
    if split is None or run.records.get("window_s", 0) <= 0:
        return None
    return 100.0 * tr.total(split[phase]) / run.records["window_s"]


def idle_by_phase(run) -> Optional[Dict[str, float]]:
    """{phase or `unnamed`: nanoseconds} of chip 0's idle time inside the
    window; the values add up to the whole of it. None where there are no
    marks, no trace or no device in it."""
    split = phases(run)
    if split is None or run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window
    open_t = run.records["window_open_t"]
    busy = run.trace.devices[0].busy
    out, named = {}, []
    for phase, cover in split.items():
        cover = tr.union([((a - open_t) * 1e9 + lo, (b - open_t) * 1e9 + lo)
                          for a, b in cover])
        named += cover
        # a phase lies inside the window, so what of it is not busy is idle
        out[phase] = tr.total(tr.subtract(cover, busy))
    out[UNNAMED] = tr.total(tr.subtract(tr.gaps(busy, lo, hi),
                                        tr.union(named)))
    return out


def idle_pct(run, phase: str) -> Optional[float]:
    """Idle time of chip 0 under `phase`, as a share of the window."""
    split = idle_by_phase(run)
    if split is None or run.trace.window_s <= 0:
        return None
    return split[phase] / (1e7 * run.trace.window_s)
