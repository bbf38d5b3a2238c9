"""What every generator and metric reader shares: the run's context, the
device block, the compile counter, program facts, spans and the profiler.

Nothing here names a cell, a configuration or a metric.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from benchmark import manifest as mf

# everything a run writes goes here: inside the checkout, gitignored, fixed
WORK_DIR = os.path.join(mf.BENCH_DIR, ".work")

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


@dataclasses.dataclass
class Run:
    """One run of one cell: what generators fill and metric readers read."""
    cell: dict
    config: dict
    config_kwargs: dict          # the `Config` fields the configuration sets
    traffic: dict
    seed: int
    seconds: float
    trace_on: bool
    process_start: float
    work_dir: str
    records: Dict[str, Any] = dataclasses.field(default_factory=dict)
    program: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Any] = dataclasses.field(default_factory=dict)
    failures: List[str] = dataclasses.field(default_factory=list)
    trace: Any = None            # trace_reduce.ReducedTrace on a traced run
    peaks: Optional[dict] = None
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
        return bool(ok)


def device_block() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def live_peak_bytes() -> Optional[int]:
    """`peak_bytes_in_use` on the fullest chip: on this runtime it counts
    live buffers and leaves out a running program's temporaries."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts traces and backend compiles through `jax.monitoring`, so that
    a compile inside the measured window shows whatever caused it."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kwargs):
        if name in COMPILE_EVENTS:
            self.count += 1


def program_facts(compiled) -> dict:
    """What the compiler put in a compiled program, read off its text (a
    copy of `chip_smoke.py:program_facts`): kernels by the `name=` their
    `pallas_call` carries, collectives, and the compiler's own memory
    accounting for one device."""
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    mem = compiled.memory_analysis()
    alias = getattr(mem, "alias_size_in_bytes", 0)
    return {
        "tpu_custom_call": len(calls),
        "attention_kernels": sum("/flash_" in ln for ln in calls),
        "fused_optimizer_kernels": sum("fused_adamw_kernel" in ln
                                       for ln in calls),
        "all_gather": text.count(" all-gather(")
        + text.count(" all-gather-start("),
        "reduce_scatter": text.count(" reduce-scatter(")
        + text.count("calls=%all-reduce-scatter"),
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": alias,
        # arguments + temporaries + the outputs that alias no argument
        "step_bytes": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                       + max(mem.output_size_in_bytes - alias, 0)),
    }


def span(name: str):
    """A harness span on the profiler's host timeline (no-op cost when no
    trace is running)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench/{name}")


@contextlib.contextmanager
def profiler(run: Run):
    """Trace the enclosed code when the run is a traced one. The traffic
    mix may turn the host tracer off (`trace_host_level` 0) where the
    runtime's own host events would slow the traced run; the window then
    comes from the run's clock alone."""
    if not run.trace_on:
        yield
        return
    import jax
    trace_dir = os.path.join(run.work_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = int(run.traffic.get("trace_host_level", 1))
    t0 = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    run.records["trace_start_s"] = time.time() - t0
    try:
        yield
    finally:
        t0 = time.time()
        jax.profiler.stop_trace()
        run.records["trace_stop_s"] = time.time() - t0


def reduce_trace(run: Run) -> None:
    """After the window: `run.trace` from the xplane the profiler wrote,
    clipped to the window the generator recorded on the host clock."""
    from benchmark import trace_reduce
    path = trace_reduce.find_xplane(os.path.join(run.work_dir, "trace"))
    if not run.trace_on or path is None:
        return
    run.records["xplane_path"] = path
    run.records["xplane_bytes"] = os.path.getsize(path)
    window = None
    if "window_open_t" in run.records and "window_close_t" in run.records:
        window = (run.records["window_open_t"], run.records["window_close_t"])
    t0 = time.time()
    run.trace = trace_reduce.reduce_xplane(path, window_unix=window)
    run.records["trace_reduce_s"] = time.time() - t0


def percentile(sorted_vals, q: float) -> Optional[float]:
    """Linear-interpolated percentile of an ascending list (a copy of
    `tools/serve_bench.py:percentile`)."""
    if not sorted_vals:
        return None
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (len(sorted_vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)
