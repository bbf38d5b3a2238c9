"""Recorder: structured per-step run records, fanned out to pluggable sinks.

One `record_step` per log step turns the loop's host-side measurements into a
versioned, machine-readable record (schema 1):

    schema, time, step, epoch, step_in_epoch, loss, lr, grad_norm,
    sec_per_iter, images_per_sec, tokens_per_sec, data_wait_s, ckpt_stall_s,
    compiles, mfu, mem_used_bytes, mem_peak_bytes[, mem_limit_bytes]
    [, loop_marks]     (the loop's timeline: one row of LOOP_MARKS a step)
    [, padding_frac]   (packed batches: from the step's own counters)

MFU comes from the analytic FLOPs model (telemetry/flops.py) over the
measured sec/iter — no device work, no tracing. `event()` appends
non-step records (watchdog hang dumps, run metadata) to the same JSONL
stream, tagged with `kind`.

Everything here is host-side by construction: building a Recorder, or not,
cannot change the compiled step program or add device->host syncs
(tests/test_telemetry.py pins that with a lowered-program equality check).
"""

from __future__ import annotations

import os
import sys
import time
from typing import Optional

from vitax.telemetry.flops import (
    decoder_flops_per_step, detect_peak_tflops, mfu, model_flops_per_step,
    packed_flops_per_step)

SCHEMA_VERSION = 1

# one row of a step record's `loop_marks`: the global step and the five
# `time.time()` marks its iteration stamped (vitax/train/loop.py, module
# docstring: each opens a phase that lasts to the next mark)
LOOP_MARKS = ("step", "t_next", "t_got", "t_batch", "t_dispatch", "t_fence")
# the phase each of the five marks opens, in the marks' order
LOOP_PHASES = ("wait", "put", "dispatch", "fence", "host")
# what `compiles` counts: every jaxpr trace and every backend compile of the
# process, cached or not, through `jax.monitoring`
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")

def phase_intervals(rows) -> list:
    """[(step, phase, start, end)] of consecutive rows of LOOP_MARKS: a phase
    lasts from its mark to the row's next one, `host` to the next row's
    `t_next`, so the last row has no `host`. The one place the rule is
    written for the program's readers (tools/metrics_report.py, which
    imports nothing, keeps a copy)."""
    out = []
    for row, after in zip(rows, list(rows[1:]) + [None]):
        ends = list(row[2:]) + ([after[1]] if after is not None else [])
        out += [(row[0], phase, a, b)
                for phase, a, b in zip(LOOP_PHASES, row[1:], ends)]
    return out


# acceptance contract of a step record: tools/metrics_report.py and the
# tier-1 round-trip test key off this exact set
REQUIRED_STEP_KEYS = (
    "schema", "step", "loss", "sec_per_iter", "data_wait_s", "mfu",
    "mem_used_bytes",
)

# (record key, counter of pairs the mask lets through, counter of pairs the
# attention kernels compute for them): how much of the kernels' work some
# query sees, 1.0 at best (vitax/ops/flash_blocked.py: computed_pairs); the
# last, the same of the expert layers: sorted rows their loops worked on over
# the slots whose expert the chip holds (vitax/models/experts.py: block_rows)
COMPUTED_OVER_NEEDED = (
    ("attn_computed_over_needed", "token_pairs", "computed_pairs"),
    ("causal_computed_over_needed", "causal_pairs", "causal_computed_pairs"),
    ("window_computed_over_needed", "window_pairs", "window_computed_pairs"),
    ("expert_rows_over_slots", "expert_slots_here", "expert_rows_computed"),
)


class Recorder:
    """Fan structured records out to sinks; owns the run's MFU constants.
    `peak_tflops` is None on the host CPU, and every step record then
    carries `mfu: null` (vitax/telemetry/flops.py)."""

    def __init__(self, cfg, sinks, n_devices: int, device_kind: str,
                 rank: int = 0):
        self.cfg = cfg
        self.sinks = list(sinks)
        self.n_devices = n_devices
        self.device_kind = device_kind
        self.rank = rank
        self.peak_tflops = detect_peak_tflops(
            device_kind, getattr(cfg, "peak_tflops", 0.0))
        self.flops_per_step = model_flops_per_step(cfg)
        self.tokens_per_step = cfg.batch_size * cfg.num_patches
        # cumulative, from the recorder's birth: the record after a
        # recompile says so (a week-long run has no other witness)
        self.compiles = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name, duration, **kwargs):
        if name in COMPILE_EVENTS:
            self.compiles += 1

    def _write(self, record: dict) -> None:
        for sink in self.sinks:
            try:
                sink.write(record)
            except Exception as e:  # noqa: BLE001 — telemetry must not kill training
                print(f"vitax.telemetry: sink {type(sink).__name__} failed "
                      f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)

    def record_step(self, *, step: int, epoch: int, step_in_epoch: int,
                    loss: float, lr: float, sec_per_iter: float,
                    data_wait_s: float, grad_norm: Optional[float] = None,
                    ckpt_stall_s: float = 0.0,
                    loop_marks: Optional[list] = None,
                    packed_counts: Optional[dict] = None,
                    expert_load: Optional[list] = None) -> dict:
        """One record per log step. `sec_per_iter` / `data_wait_s` /
        `ckpt_stall_s` are the per-step averages since the previous record;
        `step` is the global optimizer-step count (monotonically increasing
        across epochs). `ckpt_stall_s` is the zero-stall snapshot pipeline's
        staging time charged to the loop thread (vitax/checkpoint/
        snapshot.py) — the acceptance pin keeps it ~0 on non-final saves.
        `loop_marks`: the loop's rows of LOOP_MARKS since the previous
        record, written as they are; `data_wait_s` is their mean `wait`
        (t_got - t_next). `compiles` is the recorder's own cumulative count.
        `packed_counts`: a packed step's own counters (`tokens`,
        `padding_tokens`, `images`, `token_pairs`; vitax/train/step.py) —
        throughput, MFU and `padding_frac` then come from what the batch
        held, not from `batch_size x num_patches`. Where the step also
        counted the score pairs its attention kernels compute
        (`computed_pairs`, or a decoder's `causal_` / `window_computed_pairs`),
        the record holds computed / needed: `attn_computed_over_needed`, or
        `causal_` / `window_computed_over_needed`. A decoder step's
        (`targets`, `causal_pairs`, `window_pairs`, `expert_slots_here` in
        place of `token_pairs`, and beside it `expert_rows_computed`, the
        sorted rows the expert layers' loops worked on for those slots, with
        `expert_rows_over_slots`; `images` are documents; a scan's
        `ssd_pairs` and `ssd_live_chunks`, a delta rule's `kda_pairs` and
        `kda_live_chunks`, a grouped router's `tokens_choosing_held_group`,
        a balanced router's `route_load_max_over_mean`, ReGLU experts'
        `expert_hidden_live`) are written into the record as they are, with
        `expert_load`, its per-layer per-expert load; beside
        `expert_hidden_live`, the (sorted row, hidden unit) pairs a ReLU gate
        left above 0, stands `expert_hidden_live_share`, that count over the
        slots held x `expert_dim`."""
        images, tokens = self.cfg.batch_size, self.tokens_per_step
        flops_per_step = self.flops_per_step
        if packed_counts is not None:
            images, tokens = packed_counts["images"], packed_counts["tokens"]
            if self.cfg.decoder:
                flops_per_step = decoder_flops_per_step(
                    self.cfg, tokens, packed_counts["targets"],
                    packed_counts["causal_pairs"],
                    packed_counts["window_pairs"],
                    packed_counts["expert_slots_here"],
                    packed_counts.get("ssd_pairs", 0.0),
                    packed_counts.get("kda_pairs", 0.0))
            else:
                flops_per_step = packed_flops_per_step(
                    self.cfg, tokens, packed_counts["token_pairs"], images)
        record = {
            "schema": SCHEMA_VERSION,
            "time": time.time(),
            "step": int(step),
            "epoch": int(epoch),
            "step_in_epoch": int(step_in_epoch),
            "loss": float(loss),
            "lr": float(lr),
            "sec_per_iter": float(sec_per_iter),
            "images_per_sec": (images / sec_per_iter
                               if sec_per_iter > 0 else 0.0),
            "tokens_per_sec": (tokens / sec_per_iter
                               if sec_per_iter > 0 else 0.0),
            "data_wait_s": float(data_wait_s),
            "ckpt_stall_s": float(ckpt_stall_s),
            "compiles": self.compiles,
            "mfu": mfu(self.cfg, sec_per_iter, self.n_devices,
                       self.peak_tflops, flops_per_step),
        }
        if packed_counts is not None:
            slots = tokens + packed_counts["padding_tokens"]
            record["padding_frac"] = (packed_counts["padding_tokens"] / slots
                                      if slots else 0.0)
        for key, needed, computed in COMPUTED_OVER_NEEDED:
            if packed_counts and packed_counts.get(needed) and (
                    computed in packed_counts):
                record[key] = packed_counts[computed] / packed_counts[needed]
        if packed_counts is not None and self.cfg.decoder:
            record.update({k: packed_counts[k] for k in (
                "targets", "causal_pairs", "window_pairs",
                "expert_slots_here", "expert_rows_computed", "ssd_pairs",
                "ssd_live_chunks",
                "kda_pairs", "kda_live_chunks", "tokens_choosing_held_group",
                "route_load_max_over_mean", "expert_hidden_live")
                if k in packed_counts}, expert_load=expert_load)
            units = packed_counts["expert_slots_here"] * self.cfg.expert_dim
            if "expert_hidden_live" in packed_counts and units:
                record["expert_hidden_live_share"] = (
                    packed_counts["expert_hidden_live"] / units)
        if grad_norm is not None:
            record["grad_norm"] = float(grad_norm)
        if loop_marks is not None:
            record["loop_marks"] = loop_marks
        record.update(memory_stats_bytes())
        self._write(record)
        return record

    def event(self, kind: str, **payload) -> dict:
        """Non-step record (watchdog dump, run metadata), JSONL-tagged with
        `kind`; the TensorBoard sink ignores these by design."""
        record = {"schema": SCHEMA_VERSION, "time": time.time(),
                  "kind": kind, "rank": self.rank, **payload}
        self._write(record)
        return record

    def close(self) -> None:
        from jax import monitoring
        try:
            monitoring.unregister_event_duration_listener(self._on_duration)
        except (AssertionError, ValueError):   # closed twice, or cleared
            pass
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:  # noqa: BLE001 # vtx: ignore[VTX106] a failing sink must not break the others' close
                pass


def memory_stats_bytes() -> dict:
    """Schema-keyed HBM stats (vitax/utils/logging.py memory_stats_dict,
    renamed to the record's mem_*_bytes fields). mem_used_bytes is always
    present — 0 when the backend exposes no stats (CPU) — because the record
    contract promises the key; peak/limit appear only when reported."""
    from vitax.utils.logging import memory_stats_dict
    stats = memory_stats_dict()
    out = {"mem_used_bytes": int(stats.get("bytes_in_use", 0))}
    if stats.get("peak_bytes_in_use"):
        out["mem_peak_bytes"] = int(stats["peak_bytes_in_use"])
    if stats.get("bytes_limit"):
        out["mem_limit_bytes"] = int(stats["bytes_limit"])
    return out


def build_recorder(cfg, n_devices: int, device_kind: str,
                   rank: int = 0) -> Optional[Recorder]:
    """Recorder for this run, or None when telemetry is off.

    None when --metrics_dir is unset, on non-zero ranks (process 0 owns the
    global step records; the watchdog stays per-rank via stderr), or — fail
    soft, never crash a run over its observability — when metrics_dir cannot
    be created or written."""
    metrics_dir = getattr(cfg, "metrics_dir", "") or ""
    if not metrics_dir or rank != 0:
        return None
    from vitax.telemetry.sinks import JsonlSink
    try:
        os.makedirs(metrics_dir, exist_ok=True)
        sinks = [JsonlSink(os.path.join(metrics_dir, "metrics.jsonl"))]
    except OSError as e:
        print(f"vitax.telemetry: --metrics_dir {metrics_dir!r} is not "
              f"writable ({e}); telemetry disabled for this run",
              file=sys.stderr, flush=True)
        return None
    if getattr(cfg, "tensorboard", False):
        from vitax.telemetry.sinks import make_tensorboard_sink
        tb = make_tensorboard_sink(os.path.join(metrics_dir, "tb"))
        if tb is not None:
            sinks.append(tb)
    return Recorder(cfg, sinks, n_devices, device_kind, rank=rank)
