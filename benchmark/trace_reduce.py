"""From a profiler trace (`.xplane.pb`) to the numbers the metric readers use.

Reads the trace with `jax.profiler.ProfileData` (nothing but JAX). Every PR
computes the same number the same way: device busy time is the UNION of the
intervals in which an operation runs on the device (not the sum of their
durations), an operation's own time is its duration less the operations
nested inside it (a `while` encloses its body on the op line), and a
collective's exposed time is the part of it during which no other operation
runs on the same device.

The window is the one the generator recorded on the host clock, which the
trace shares; else the harness's own `bench/window` span; else the extent of
the device events. Device events are clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"              # what the core executes, nested by `while`
ASYNC_LINE = "Async XLA Ops"      # start..done of copies, slices, collectives
COLLECTIVE_MARKS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast",
                    "async-collective")
ASYNC_START, ASYNC_DONE = "async-collective-start", "async-collective-done"
KERNEL_TARGET = "tpu_custom_call"         # a Pallas kernel
LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """(instruction name, category, result shape) from the HLO text the TPU
    profiler gives an op event as its name, e.g.

      %add_add_fusion.2 = bf16[8,256,1024]{2,1,0:T(8,128)} fusion(...),
          kind=kOutput, calls=%fused_computation.96

    `ProfileData` does not expose the event metadata that holds xprof's
    `hlo_category`, so the category is read off the text: the opcode, for a
    fusion with its kind (on the TPU `kOutput` is a convolution or dot with
    what was fused onto its output), for a custom call its target."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), "", ""
    depth, opcode_at = 0, -1
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode_at = i + 1
            break
    if opcode_at < 0:
        return head.lstrip("%"), "", ""
    shape = LAYOUT.sub("", rest[:opcode_at - 1])
    opcode = rest[opcode_at:].split("(", 1)[0]
    category = opcode
    if opcode == "fusion":
        kind = rest.rsplit("kind=", 1)[-1].split(",", 1)[0] if "kind=" in rest else ""
        category = f"fusion {kind}".strip()
    elif opcode == "custom-call":
        target = rest.rsplit('custom_call_target="', 1)[-1].split('"', 1)[0] \
            if "custom_call_target=" in rest else ""
        category = f"custom-call {target}".strip()
    return head.lstrip("%"), category, shape


# ---- interval arithmetic (nanoseconds in, nanoseconds out) ----------------

def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of `intervals`."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(hi - lo for lo, hi in intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of merged cover `a` that merged cover `b` does not touch."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(cover: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], cover)


# ---- events ---------------------------------------------------------------

@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str
    category: str
    text: str           # the event's whole name, lower case: kernel names
    shape: str = ""
    self_ns: float = 0.0
    nested: Optional[List[Interval]] = None

    @property
    def is_collective(self) -> bool:
        """A collective by its opcode or name (`all-gather`, the
        `async-collective-start/done` pair the TPU compiler makes of an
        overlapped one), or a fusion that calls one (`kind=kCustom,
        calls=%all-reduce-scatter`: the fused reduce-scatter)."""
        hay = f"{self.category} {self.name}"
        if any(m in hay for m in COLLECTIVE_MARKS):
            return True
        _, sep, called = self.text.rpartition("calls=%")
        return bool(sep) and any(m in called for m in COLLECTIVE_MARKS)

    @property
    def is_matmul(self) -> bool:
        return (self.category in ("fusion kOutput", "convolution", "dot")
                or "convolution" in self.name)

    @property
    def is_kernel(self) -> bool:
        return KERNEL_TARGET in self.category


def assign_self_times(ops: List[Op]) -> None:
    """Own time of each op: its duration less the union of the ops nested
    directly inside it. An op that only overlaps another (two engines at
    work at once) is nested in neither; both keep their time, and it comes
    off the nearest op that encloses it whole."""
    ops.sort(key=lambda o: (o.start, -(o.end - o.start)))
    stack: List[Tuple[Op, List[Interval]]] = []
    for op in ops:
        while stack and stack[-1][0].end <= op.start:
            stack.pop()
        for parent, children in reversed(stack):
            if parent.end >= op.end:
                children.append((op.start, op.end))
                break
        mine: List[Interval] = []
        stack.append((op, mine))
        op.nested = mine
    for op in ops:
        op.self_ns = max((op.end - op.start) - total(union(op.nested)), 0.0)
        op.nested = None


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Op]                # the op line, clipped to the window
    busy: List[Interval]
    async_ops: List[Op] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ReducedTrace:
    window: Interval
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]   # harness spans, host clock

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device: Optional[int] = None) -> float:
        """Seconds in which an op ran; averaged over devices by default."""
        devs = self.devices if device is None else [self.devices[device]]
        if not devs:
            return 0.0
        return sum(total(d.busy) for d in devs) / len(devs) / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def self_seconds(self, pred: Callable[[Op], bool], device: int = 0) -> float:
        if not self.devices:
            return 0.0
        return sum(o.self_ns for o in self.devices[device].ops if pred(o)) / 1e9

    def seconds_matching(self, *marks: str, device: int = 0) -> float:
        """Own time of ops whose name or string stats hold one of `marks`."""
        marks = tuple(m.lower() for m in marks)
        return self.self_seconds(lambda o: any(m in o.text for m in marks),
                                 device)

    def count_matching(self, *marks: str, device: int = 0) -> int:
        marks = tuple(m.lower() for m in marks)
        if not self.devices:
            return 0
        return sum(any(m in o.text for m in marks)
                   for o in self.devices[device].ops)

    def category_seconds(self, device: int = 0) -> Dict[str, float]:
        out: Dict[str, float] = {}
        if self.devices:
            for o in self.devices[device].ops:
                out[o.category] = out.get(o.category, 0.0) + o.self_ns / 1e9
        return out

    def top_ops(self, n: int = 10, device: int = 0) -> List[List]:
        """[name, seconds] of the ops with most own time; kernels go by the
        name their `pallas_call` carries, the rest by HLO name without its
        numeric suffix, with the category where that says more."""
        sums: Dict[str, float] = {}
        if self.devices:
            for o in self.devices[device].ops:
                key = display_name(o)
                sums[key] = sums.get(key, 0.0) + o.self_ns / 1e9
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in ranked]

    def collective_seconds(self, device: int = 0) -> Tuple[float, float]:
        """(seconds a collective is in flight, seconds of that with no
        compute op running) on one device. In flight: the op itself where it
        is synchronous; from start to done on the async line; from an
        `async-collective-start` to its `-done` on the op line. Compute: a
        leaf of the op line that belongs to no collective (the `-done` op
        that waits for one is not compute). The op line is serial, so the
        exposed part is about the own time of the collective ops on it."""
        if not self.devices:
            return 0.0, 0.0
        dev = self.devices[device]
        flights = [(o.start, o.end) for o in dev.ops + dev.async_ops
                   if o.is_collective]
        started: Dict[str, float] = {}
        for o in dev.ops:               # sorted by start
            if ASYNC_START in o.name:
                started[o.name.replace(ASYNC_START, ASYNC_DONE)] = o.start
            elif ASYNC_DONE in o.name and o.name in started:
                flights.append((started.pop(o.name), o.end))
        coll = union(flights)
        compute = union([(o.start, o.end) for o in dev.ops
                         if not o.is_collective
                         and o.self_ns >= 0.999 * (o.end - o.start)])
        exposed = subtract(coll, compute)
        return total(coll) / 1e9, total(exposed) / 1e9

    def idle_gaps(self, n: int = 10, device: int = 0) -> List[List]:
        """[span name, seconds]: idle time of one device by what the host
        was doing (the harness span that overlaps each gap most)."""
        if not self.devices:
            return []
        spans = [s for s in self.spans if s[0] != WINDOW_SPAN]
        sums: Dict[str, float] = {}
        for lo, hi in gaps(self.devices[device].busy, *self.window):
            best, best_ov = "no_harness_span", 0.0
            for name, a, b in spans:
                ov = min(hi, b) - max(lo, a)
                if ov > best_ov:
                    best, best_ov = name, ov
            sums[best] = sums.get(best, 0.0) + (hi - lo) / 1e9
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in ranked]


def display_name(op: Op) -> str:
    """Instruction name without its number, category and result shape:
    equal ops of every layer and step fall under one name."""
    base = op.name.rsplit(".", 1)[0] if op.name.rsplit(".", 1)[-1].isdigit() \
        else op.name
    if op.is_kernel:
        return base
    label = f"{base} [{op.category}]" if op.category else base
    return f"{label} {op.shape[:60]}".strip()


# ---- reading --------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper() \
        and "host" not in name.lower()


def _op_from_event(ev) -> Op:
    text = str(ev.name)
    name, category, shape = parse_hlo(text)
    return Op(start=float(ev.start_ns),
              end=float(ev.start_ns) + float(ev.duration_ns),
              name=name, category=category, text=text.lower(), shape=shape)


def reduce_xplane(path: str,
                  window_unix: Optional[Tuple[float, float]] = None
                  ) -> ReducedTrace:
    """`window_unix`: the window on the host clock (`time.time()` seconds).
    Event times count nanoseconds from the trace's `profile_start_time`,
    which is on that same clock (checked on the chip: 3 microseconds apart),
    so the window needs no host span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    started_ns = None
    raw: List[Tuple[str, List[Op], List[Op]]] = []
    for plane in data.planes:
        if _is_device_plane(plane.name):
            lines = {line.name: [_op_from_event(e) for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE)}
            if OPS_LINE in lines:
                raw.append((plane.name, lines[OPS_LINE],
                            lines.get(ASYNC_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if str(ev.name).startswith(SPAN_PREFIX):
                        spans.append((str(ev.name), float(ev.start_ns),
                                      float(ev.start_ns)
                                      + float(ev.duration_ns)))
        elif plane.name == "Task Environment":
            started_ns = dict(plane.stats).get("profile_start_time")
    window = None
    if window_unix is not None and started_ns is not None:
        window = (window_unix[0] * 1e9 - started_ns,
                  window_unix[1] * 1e9 - started_ns)
    return reduce_events(raw, spans, window)


def _clipped(ops: Sequence[Op], window: Interval) -> List[Op]:
    kept = []
    for o in ops:
        lo, hi = max(o.start, window[0]), min(o.end, window[1])
        if hi > lo:
            kept.append(dataclasses.replace(o, start=lo, end=hi))
    return kept


def reduce_events(raw: Sequence[Tuple], spans: Sequence[Tuple[str, float, float]],
                  window: Optional[Interval] = None) -> ReducedTrace:
    """The reduction itself, on plain lists (the tests feed it by hand):
    `raw` holds (device name, ops of the op line[, ops of the async line]).
    The window is the one given, else the `bench/window` span, else the
    extent of the ops."""
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    every = [o for entry in raw for o in entry[1]]
    if window is not None:
        pass
    elif windows:
        window = max(windows, key=lambda w: w[1] - w[0])
    elif every:
        window = (min(o.start for o in every), max(o.end for o in every))
    else:
        window = (0.0, 0.0)
    devices = []
    for entry in sorted(raw, key=lambda r: r[0]):
        kept = _clipped(entry[1], window)
        assign_self_times(kept)
        devices.append(DeviceTrace(
            name=entry[0], ops=kept,
            busy=union([(o.start, o.end) for o in kept]),
            async_ops=_clipped(entry[2] if len(entry) > 2 else [], window)))
    return ReducedTrace(window=window, devices=devices, spans=list(spans))
