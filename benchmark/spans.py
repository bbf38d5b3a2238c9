"""The server's own spans, laid beside the device's timeline.

The server stamps every phase of a batch with `time.time()` into its
`serve_batch` events (vitax/serve/batcher.py, engine.py); a traced run hands
the window's events to the readers as `run.records["serve_events"]`. The
device trace counts nanoseconds from a start that lies on that same clock
(`trace_reduce.reduce_xplane`), so one offset,
`window_open_t * 1e9 - run.trace.window[0]`, puts both on one axis.

One worker thread runs the batches, so its phases never overlap: the idle
time of the device is split over them exactly, by intersection, and what no
phase covers is `unnamed`.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import trace_reduce as tr
from benchmark.harness import percentile

# a phase lasts from its mark to the next one
MARKS = ("t_collect", "t_stack", "t_put", "t_dispatch", "t_wait",
         "t_deliver", "t_end")
PHASES = tuple(m[2:] for m in MARKS[:-1])
UNNAMED = "unnamed"


def events(run, kind: str) -> List[dict]:
    return [e for e in run.records.get("serve_events", [])
            if e.get("kind") == kind]


def batch_phases(run) -> Dict[str, List[tr.Interval]]:
    """{phase: merged intervals} of the window's `serve_batch` events, in
    the trace's nanoseconds, clipped to the window. Events from a program
    without the marks give nothing."""
    lo, hi = run.trace.window
    open_t = run.records["window_open_t"]
    out: Dict[str, List[tr.Interval]] = {p: [] for p in PHASES}
    for e in events(run, "serve_batch"):
        if not all(m in e for m in MARKS):
            continue
        at = [(e[m] - open_t) * 1e9 + lo for m in MARKS]
        for phase, a, b in zip(PHASES, at, at[1:]):
            out[phase].append((max(a, lo), min(b, hi)))
    return {p: tr.union(v) for p, v in out.items()}


def idle_by_phase(run) -> Optional[Dict[str, float]]:
    """{phase or `unnamed`: nanoseconds} of chip 0's idle time inside the
    window; the values add up to the whole of it. None where the trace has
    no device or the run no window."""
    if (run.trace is None or not run.trace.devices
            or "window_open_t" not in run.records):
        return None
    busy = run.trace.devices[0].busy
    phases = batch_phases(run)
    # a phase lies inside the window, so what of it is not busy is idle
    out = {p: tr.total(tr.subtract(cover, busy))
           for p, cover in phases.items()}
    named = tr.union([i for cover in phases.values() for i in cover])
    out[UNNAMED] = tr.total(tr.subtract(
        tr.gaps(busy, *run.trace.window), named))
    return out


def idle_pct(run, *phases: str) -> Optional[float]:
    """Idle time of chip 0 under `phases`, as a share of the window."""
    split = idle_by_phase(run)
    if split is None or run.trace.window_s <= 0:
        return None
    return sum(split[p] for p in phases) / (1e7 * run.trace.window_s)


def batches_with_the_one_ahead(run) -> List[tuple]:
    """[(batch, the batch dispatched before it)] over the window's
    `serve_batch` events that carry the seven marks, paired by `batch_id`;
    a batch whose predecessor lies outside the window is left out. The one
    worker thread runs `dispatch(n)` and then `deliver(n - 1)`, so some of
    what a batch's own marks leave open is closed by its predecessor's."""
    marked = {e["batch_id"]: e for e in events(run, "serve_batch")
              if "batch_id" in e and all(m in e for m in MARKS)}
    return [(e, marked[i - 1]) for i, e in sorted(marked.items())
            if i - 1 in marked]


def median_ms_of(values) -> Optional[float]:
    value = percentile(sorted(values), 0.5)
    return None if value is None else 1e3 * value


def median_ms(run, kind: str, seconds_of) -> Optional[float]:
    """Median over the window's `kind` events of `seconds_of(event)`, in ms;
    events that lack a field it reads (an older program's) are left out."""
    vals = []
    for e in events(run, kind):
        try:
            vals.append(seconds_of(e))
        except KeyError:
            pass
    return median_ms_of(vals)
