#!/usr/bin/env python3
"""Summarize a vitax telemetry JSONL run (vitax/telemetry/, schema 1).

Human mode prints the run at a glance — step range, p50/p95 sec/iter, MFU,
data-wait fraction, the loop thread's phases (p50/p95 and share of the run,
from `loop_marks`), the steps at which something compiled,
checkpoint-stall percentiles, peer-replication volume
and restore path, throughput, a loss sparkline, memory peak, watchdog
events; `--json` emits the same summary as one JSON object for CI.

    python tools/metrics_report.py /runs/exp7/metrics.jsonl
    python tools/metrics_report.py /runs/exp7/metrics.jsonl --json

Accelerator-free: reads only the JSONL file. Corrupt lines (a run killed
mid-write can truncate at most the last one) are counted, never fatal.
Exit status: 0 with >= 1 step record, 2 otherwise.
"""

from __future__ import annotations

import argparse
import json
import sys

SPARK_CHARS = "▁▂▃▄▅▆▇█"


def percentile(sorted_vals, q: float) -> float:
    """Linear-interpolated percentile of an ascending list (numpy-free: the
    report must run on bare CI hosts)."""
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (len(sorted_vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)


def sparkline(vals, width: int = 40) -> str:
    """Downsampled unicode sparkline (empty string for < 2 points)."""
    if len(vals) < 2:
        return ""
    if len(vals) > width:  # mean-pool into `width` buckets
        step = len(vals) / width
        vals = [sum(vals[int(i * step):max(int((i + 1) * step), int(i * step) + 1)])
                / max(int((i + 1) * step) - int(i * step), 1)
                for i in range(width)]
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(
        SPARK_CHARS[min(int((v - lo) / span * (len(SPARK_CHARS) - 1)),
                        len(SPARK_CHARS) - 1)]
        for v in vals)


# a phase of the loop thread lasts from its mark to the next one, `host` to
# the next iteration's t_next (vitax/train/loop.py, module docstring; a row
# of `loop_marks` is [step, t_next, t_got, t_batch, t_dispatch, t_fence])
LOOP_PHASES = ("wait", "put", "dispatch", "fence", "host")


def loop_phase_seconds(steps) -> dict:
    """{phase: [seconds, one an iteration]} over the records' `loop_marks`.
    `host` closes on the row of the next step, across records; the run's
    last row, and one followed by another run's rows, leaves it open."""
    rows = [row for r in steps for row in r.get("loop_marks") or []]
    out = {phase: [] for phase in LOOP_PHASES}
    for row, after in zip(rows, rows[1:] + [None]):
        ends = list(row[2:])
        if after is not None and after[0] == row[0] + 1 and after[1] >= row[5]:
            ends.append(after[1])
        for phase, a, b in zip(LOOP_PHASES, row[1:], ends):
            out[phase].append(b - a)
    return out


def run_ahead_spent(record) -> bool:
    """Whether a record's queue wait can be starvation. The loop dispatches
    up to a log interval of steps ahead of the device, so while the rows'
    `fence` (the device's backlog at the log step) outlasts their `wait`,
    the device never saw the wait. A record without marks cannot say, and
    counts by its wait alone."""
    rows = record.get("loop_marks")
    if not rows:
        return True
    return (sum(row[5] - row[4] for row in rows)
            < sum(row[2] - row[1] for row in rows))


def load_records(path: str):
    """(step_records, event_records, corrupt_line_count). Step records are
    sorted by step; anything with a `kind` tag is an event."""
    steps, events, corrupt = [], [], 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                corrupt += 1
                continue
            if not isinstance(rec, dict):
                corrupt += 1
            elif rec.get("kind"):
                events.append(rec)
            elif "step" in rec and "loss" in rec:
                steps.append(rec)
            else:
                corrupt += 1
    steps.sort(key=lambda r: r["step"])
    return steps, events, corrupt


def summarize(path: str) -> dict:
    steps, events, corrupt = load_records(path)
    summary = {
        "path": path,
        "schema": steps[0].get("schema") if steps else None,
        "records": len(steps),
        "events": len(events),
        "corrupt_lines": corrupt,
        "hang_events": sum(1 for e in events if e.get("kind") == "hang"),
        "fault_events": sum(1 for e in events if e.get("kind") == "fault"),
        "hang_escalations": sum(1 for e in events
                                if e.get("kind") == "hang_escalation"),
        # vitax/telemetry/threads.py excepthook: uncaught background-thread
        # exceptions (healthy runs hold this at 0)
        "thread_crashes": sum(1 for e in events
                              if e.get("kind") == "thread_crash"),
        # fleet serving (vitax/serve/fleet/ writes these into serve.jsonl —
        # point this report at it for the overload/rotation story)
        "admission_shed_count": sum(1 for e in events
                                    if e.get("kind") == "admission"),
        "replica_restarts": sum(1 for e in events
                                if e.get("kind") == "replica_restart"),
        # serve-path chaos layer (vitax/faults.py serve sites + the
        # router's containment: breaker/budget/hedge/brownout events)
        "serve_fault_events": sum(1 for e in events
                                  if e.get("kind") == "serve_fault"),
        "breaker_open_count": sum(
            1 for e in events if e.get("kind") == "breaker"
            and e.get("event") in ("open", "reopen")),
        "retry_budget_exhausted": sum(
            1 for e in events if e.get("kind") == "retry_budget"
            and e.get("event") == "exhausted"),
        "hedge_count": sum(1 for e in events if e.get("kind") == "hedge"
                           and e.get("event") == "fired"),
        "hedge_wins": sum(1 for e in events if e.get("kind") == "hedge"
                          and e.get("event") == "win"),
        # completed brownout episodes only (exit events carry the length;
        # a run killed while degraded under-counts by the live episode)
        "brownout_seconds": round(sum(
            float(e.get("degraded_s", 0.0)) for e in events
            if e.get("kind") == "brownout" and e.get("event") == "exit"), 3),
    }
    # fleet growth (vitax/serve/fleet/autoscale.py): scaling actions by
    # outcome, mirroring the control_events bucket style
    autoscale = [e for e in events if e.get("kind") == "autoscale"]
    summary["autoscale_events"] = {
        "scale_out": sum(1 for e in autoscale
                         if e.get("event") == "scale_out"),
        "scale_in": sum(1 for e in autoscale
                        if e.get("event") == "scale_in"),
        "retires": sum(1 for e in autoscale if e.get("event") == "retire"),
        "scale_out_failures": sum(1 for e in autoscale
                                  if e.get("event") == "scale_out_failed"),
        "forced_drains": sum(1 for e in autoscale
                             if e.get("event") == "scale_in"
                             and e.get("forced")),
        # a maxed-out (or agent-full) fleet asking the chip arbiter for a
        # whole host instead of failing the scale-out
        "escalations": sum(1 for e in autoscale
                           if e.get("event") == "scale_out"
                           and e.get("outcome") == "escalated"),
    }
    # chip arbitration (vitax/arbiter/): borrow/return/deny traffic, with
    # denies bucketed by the policy's reason so hysteresis is visible
    arbiter = [e for e in events if e.get("kind") == "arbiter"]
    deny_reasons: dict = {}
    for e in arbiter:
        if e.get("event") == "deny":
            reason = str(e.get("reason", "unknown"))
            deny_reasons[reason] = deny_reasons.get(reason, 0) + 1
    summary["arbiter_events"] = {
        "requests": sum(1 for e in arbiter if e.get("event") == "request"),
        "borrows": sum(1 for e in arbiter if e.get("event") == "borrow"),
        "returns": sum(1 for e in arbiter if e.get("event") == "return"),
        "borrow_failures": sum(1 for e in arbiter
                               if e.get("event") == "borrow_failed"),
        "return_failures": sum(1 for e in arbiter
                               if e.get("event") == "return_failed"),
        "denies": deny_reasons,
    }
    # prediction cache (vitax/serve/fleet/cache.py): hit events carry
    # running totals, so the LAST one yields the rate (misses are counted
    # router-side but deliberately not emitted per-event)
    cache_events = [e for e in events if e.get("kind") == "cache"]
    if cache_events:
        last_cache = cache_events[-1]
        hits = int(last_cache.get("hits_total", len(cache_events)))
        misses = int(last_cache.get("misses_total", 0))
        summary["cache_hits"] = hits
        summary["cache_hit_rate"] = round(hits / max(hits + misses, 1), 4)
    # batch fill (serve_request events from replicas): how full the padded
    # bucket each request ran in actually was — the continuous-batching
    # acceptance metric (composed dispatch raises the p50)
    fills = sorted(e["batch_size"] / max(e.get("bucket", 1), 1)
                   for e in events
                   if e.get("kind") == "serve_request" and "batch_size" in e)
    if fills:
        summary["batch_fill_p50"] = round(percentile(fills, 0.50), 4)
        summary["batch_fill_p95"] = round(percentile(fills, 0.95), 4)
    # control plane (vitax/train/control.py + the supervisor's elastic
    # restarts): kind:"control" records, bucketed by their `event` field
    control = [e for e in events if e.get("kind") == "control"]
    summary["control_events"] = {
        "agreed_preemptions": sum(1 for e in control
                                  if e.get("event") == "agreed_preempt"),
        "agreed_escalations": sum(1 for e in control
                                  if e.get("event") == "agreed_escalation"),
        "peer_loss_detections": sum(1 for e in control
                                    if e.get("event") == "peer_loss"),
        "topology_changes": sum(1 for e in control
                                if e.get("event") == "topology_change"),
        "elastic_resumes": sum(1 for e in control
                               if e.get("event") == "elastic_resume"),
    }
    # the training pod's process-count history: every topology flip the
    # control plane saw (supervisor/arbiter `topology_change` observations
    # and the loop's own `elastic_resume` actions), in record order — an
    # arbiter borrow/return drill reads N -> N-1 -> N here
    summary["train_topology_timeline"] = [
        {"event": e.get("event"),
         "from_processes": e.get("from_processes"),
         "to_processes": e.get("to_processes")}
        for e in control
        if e.get("event") in ("topology_change", "elastic_resume")]
    summary["hang_hard_exits"] = sum(1 for e in events
                                     if e.get("kind") == "hang_hard_exit")
    # zero-stall checkpointing + peer replication (vitax/checkpoint/
    # snapshot.py + peer.py): replication volume, restore path taken, and
    # whether any peer restore had to fall back to Orbax
    repl = [e for e in events if e.get("kind") == "peer_replication"]
    summary["peer_replication_windows"] = len(repl)
    summary["peer_replication_bytes"] = sum(
        int(e.get("bytes", 0)) for e in repl)
    restores = [e for e in events if e.get("kind") == "restore"]
    summary["peer_restores"] = sum(1 for e in restores
                                   if e.get("path") == "peer")
    summary["restore_path"] = (restores[-1].get("path")
                               if restores else None)
    summary["control_events"]["peer_restore_failures"] = sum(
        1 for e in control if e.get("event") == "peer_restore_failed")
    # supervisor restarts (vitax/supervise.py appends these between child
    # runs, so they interleave with the child's own records)
    restarts = [e for e in events if e.get("kind") == "restart"]
    summary["restart_count"] = len(restarts)
    summary["last_exit_code"] = (restarts[-1].get("exit_code")
                                 if restarts else None)
    evals = [e for e in events if e.get("kind") == "eval"]
    if evals:
        last = max(evals, key=lambda e: (e.get("epoch", 0), e.get("time", 0)))
        summary["eval_last"] = {k: last.get(k)
                                for k in ("epoch", "top1", "top5", "n")}
    # scenario registry (vitax/programs/): finetune warm-start provenance
    # and the distill loss decomposition at the latest log step
    fts = [e for e in events if e.get("kind") == "finetune"]
    if fts:
        last = max(fts, key=lambda e: e.get("time", 0))
        summary["finetune_last"] = {
            k: last.get(k)
            for k in ("init_npz", "loaded", "reinit", "frozen_frac")}
    distills = [e for e in events if e.get("kind") == "distill"]
    if distills:
        last = max(distills, key=lambda e: (e.get("step", 0),
                                            e.get("time", 0)))
        summary["distill_last"] = {
            k: last.get(k)
            for k in ("step", "epoch", "kl", "ce", "teacher_top1",
                      "student_top1", "alpha", "temp")}
    # quantized-serving accuracy gate (vitax/serve/quant.py run_quant_gate):
    # latest quantized-vs-f32 comparison; deltas are in points
    gates = [e for e in events if e.get("kind") == "quant_gate"]
    if gates:
        last = max(gates, key=lambda e: e.get("time", 0))
        summary["quant_gate_last"] = {
            k: last.get(k)
            for k in ("weights_dtype", "baseline_dtype",
                      "act_quant", "fused_dequant",
                      "top1_f32", "top1_quant", "top5_f32", "top5_quant",
                      "delta_top1", "delta_top5", "n")}
    if not steps:
        return summary

    sec = sorted(r["sec_per_iter"] for r in steps if "sec_per_iter" in r)
    losses = [r["loss"] for r in steps]
    mfus = [r["mfu"] for r in steps if r.get("mfu") is not None]  # null: CPU run
    waits = [r.get("data_wait_s", 0.0) for r in steps]
    stalls = sorted(r["ckpt_stall_s"] for r in steps if "ckpt_stall_s" in r)
    phases = loop_phase_seconds(steps)
    in_phases = sum(sum(v) for v in phases.values())
    compiles_before = 0
    compile_steps = []   # records whose cumulative `compiles` rose
    for r in steps:
        if r.get("compiles", 0) > compiles_before:
            compile_steps.append(r["step"])
        compiles_before = r.get("compiles", compiles_before)
    # fraction of each recorded step spent waiting on host data (both sides
    # are per-step averages over the same record interval)
    timed = [r for r in steps
             if r.get("sec_per_iter") and "data_wait_s" in r]
    wait_fracs = [r["data_wait_s"] / r["sec_per_iter"] for r in timed]
    summary.update({
        "first_step": steps[0]["step"],
        "last_step": steps[-1]["step"],
        "sec_per_iter_p50": round(percentile(sec, 0.50), 6),
        "sec_per_iter_p95": round(percentile(sec, 0.95), 6),
        "mfu_last": round(mfus[-1], 6) if mfus else None,
        "mfu_max": round(max(mfus), 6) if mfus else None,
        "data_wait_s_mean": round(sum(waits) / len(waits), 6),
        # zero-stall checkpointing acceptance metric: staging time charged
        # to the loop thread per step; ~0 unless a save was synchronous
        "ckpt_stall_s_p50": (round(percentile(stalls, 0.50), 6)
                             if stalls else None),
        "ckpt_stall_s_p95": (round(percentile(stalls, 0.95), 6)
                             if stalls else None),
        # where the loop thread's time went: per-iteration seconds of each
        # phase and its share of all the marked time (records without
        # `loop_marks` give none)
        "loop_phases": ({
            phase: {"p50": round(percentile(sorted(v), 0.50), 6),
                    "p95": round(percentile(sorted(v), 0.95), 6),
                    "share": round(sum(v) / in_phases, 6)}
            for phase, v in phases.items() if v} if in_phases > 0 else None),
        "compile_steps": compile_steps,
        "data_wait_fraction": (round(sum(wait_fracs) / len(wait_fracs), 6)
                               if wait_fracs else None),
        # the streaming data plane's acceptance metric (ROADMAP item 3):
        # fraction of recorded steps that were input-bound — data wait over
        # 10% of the step, and longer than the fence that followed it where
        # the record has marks. A healthy pipeline holds this at ~0.
        "input_bound": (round(sum(1 for r, w in zip(timed, wait_fracs)
                                  if w > 0.1 and run_ahead_spent(r))
                              / len(wait_fracs), 6)
                        if wait_fracs else None),
        "loss_first": round(losses[0], 6),
        "loss_last": round(losses[-1], 6),
        "loss_min": round(min(losses), 6),
        "images_per_sec_last": round(steps[-1].get("images_per_sec", 0.0), 2),
        "tokens_per_sec_last": round(steps[-1].get("tokens_per_sec", 0.0), 2),
        "mem_peak_bytes": max((r.get("mem_peak_bytes",
                                     r.get("mem_used_bytes", 0))
                               for r in steps), default=0),
        "loss_curve": [round(v, 4) for v in losses],
    })
    return summary


def print_human(summary: dict) -> None:
    print(f"run: {summary['path']}")
    print(f"  records: {summary['records']} step + {summary['events']} event"
          f" ({summary['corrupt_lines']} corrupt lines skipped), "
          f"schema {summary['schema']}")
    if summary.get("hang_events"):
        print(f"  !! watchdog hang events: {summary['hang_events']}")
    if summary.get("hang_escalations"):
        print(f"  !! watchdog escalations (checkpoint+exit): "
              f"{summary['hang_escalations']}")
    if summary.get("fault_events"):
        print(f"  injected faults fired: {summary['fault_events']}")
    if summary.get("thread_crashes"):
        print(f"  !! background thread crashes: {summary['thread_crashes']}")
    ce = summary.get("control_events") or {}
    if any(ce.values()):
        print(f"  !! control plane: {ce['agreed_preemptions']} agreed "
              f"preemption(s), {ce['agreed_escalations']} agreed "
              f"escalation(s), {ce['peer_loss_detections']} peer loss(es), "
              f"{ce['topology_changes']} topology change(s), "
              f"{ce['elastic_resumes']} elastic resume(s)")
    if ce.get("peer_restore_failures"):
        print(f"  !! peer restores that fell back to Orbax: "
              f"{ce['peer_restore_failures']}")
    if summary.get("peer_replication_windows"):
        print(f"  peer replication: {summary['peer_replication_windows']} "
              f"window(s), "
              f"{summary['peer_replication_bytes'] / 1024 ** 2:.2f} MiB "
              f"mirrored to buddies")
    if summary.get("restore_path"):
        print(f"  restore path: {summary['restore_path']} "
              f"({summary['peer_restores']} peer restore(s))")
    if summary.get("hang_hard_exits"):
        print(f"  !! watchdog hard-deadline exits: "
              f"{summary['hang_hard_exits']}")
    if summary.get("restart_count"):
        print(f"  !! supervisor restarts: {summary['restart_count']} "
              f"(last child exit code {summary['last_exit_code']})")
    if summary.get("admission_shed_count"):
        print(f"  admission sheds (429): {summary['admission_shed_count']}")
    if summary.get("replica_restarts"):
        print(f"  !! fleet replica restarts: {summary['replica_restarts']}")
    if summary.get("serve_fault_events"):
        print(f"  injected serve faults fired: "
              f"{summary['serve_fault_events']}")
    if summary.get("breaker_open_count"):
        print(f"  !! circuit breaker opens: {summary['breaker_open_count']}")
    if summary.get("retry_budget_exhausted"):
        print(f"  !! retry budget exhaustions (fast 503): "
              f"{summary['retry_budget_exhausted']}")
    if summary.get("hedge_count"):
        print(f"  hedged requests: {summary['hedge_count']} "
              f"({summary['hedge_wins']} won)")
    if summary.get("brownout_seconds"):
        print(f"  !! brownout (degraded mode): "
              f"{summary['brownout_seconds']:.1f}s across completed episodes")
    auto = summary.get("autoscale_events") or {}
    if any(auto.values()):
        print(f"  autoscale: {auto['scale_out']} out, {auto['scale_in']} in "
              f"({auto['retires']} retires, {auto['forced_drains']} forced "
              f"drains, {auto['scale_out_failures']} failed provisions, "
              f"{auto.get('escalations', 0)} arbiter escalation(s))")
    arb = summary.get("arbiter_events") or {}
    if any(arb.values()):
        denies = arb.get("denies") or {}
        deny_desc = ", ".join(f"{k}:{v}" for k, v in sorted(denies.items()))
        print(f"  chip arbiter: {arb['borrows']} borrow(s), "
              f"{arb['returns']} return(s), {arb['requests']} capacity "
              f"request(s), {arb['borrow_failures']} failed borrow(s), "
              f"{arb['return_failures']} failed return(s)"
              + (f"; denies {deny_desc}" if denies else ""))
    timeline = summary.get("train_topology_timeline") or []
    if timeline:
        path = " -> ".join(
            [str(timeline[0]["from_processes"])]
            + [str(t["to_processes"]) for t in timeline])
        print(f"  train topology: {path} process(es) across "
              f"{len(timeline)} transition(s)")
    if summary.get("cache_hits") is not None:
        print(f"  prediction cache: {summary['cache_hits']} hits "
              f"(rate {summary['cache_hit_rate']:.2f})")
    if summary.get("batch_fill_p50") is not None:
        print(f"  batch fill: p50 {summary['batch_fill_p50']:.2f}  "
              f"p95 {summary['batch_fill_p95']:.2f} of bucket")
    ev = summary.get("eval_last")
    if ev:
        print(f"  eval (epoch {ev['epoch']}): top1 {ev['top1']:.4f}  "
              f"top5 {ev['top5']:.4f}  (n={ev['n']})")
    ft = summary.get("finetune_last")
    if ft:
        reinit = ft.get("reinit") or []
        print(f"  finetune: {ft['loaded']} leaves from {ft['init_npz']}"
              + (f", head re-initialized ({len(reinit)} leaves)"
                 if reinit else "")
              + (f", frozen frac {ft['frozen_frac']:.3f}"
                 if ft.get("frozen_frac") else ""))
    dl = summary.get("distill_last")
    if dl:
        print(f"  distill (step {dl['step']}): kl {dl['kl']:.4f}  "
              f"ce {dl['ce']:.4f}  teacher top1 {dl['teacher_top1']:.4f}  "
              f"student top1 {dl['student_top1']:.4f}  "
              f"(alpha {dl['alpha']}, T {dl['temp']})")
    qg = summary.get("quant_gate_last")
    if qg:
        print(f"  quant gate ({qg['weights_dtype']} vs "
              f"{qg['baseline_dtype']}, "
              f"act_quant {qg.get('act_quant') or 'off'}, "
              f"fused_dequant {bool(qg.get('fused_dequant'))}): "
              f"top1 {qg['top1_quant']:.4f} "
              f"(delta {qg['delta_top1']:+.2f} pts)  "
              f"top5 {qg['top5_quant']:.4f} "
              f"(delta {qg['delta_top5']:+.2f} pts)  (n={qg['n']})")
    if not summary["records"]:
        print("  no step records — nothing to summarize")
        return
    print(f"  steps {summary['first_step']}..{summary['last_step']}")
    print(f"  sec/iter: p50 {summary['sec_per_iter_p50']:.4f}  "
          f"p95 {summary['sec_per_iter_p95']:.4f}")
    mfu_last = summary["mfu_last"]
    if mfu_last is not None:
        print(f"  MFU: last {mfu_last:.4f}  max {summary['mfu_max']:.4f}")
    if summary["data_wait_fraction"] is not None:
        # queue time, which the loop's run-ahead hides while the device has
        # a backlog: the count of input-bound steps below says how much of
        # it the device saw
        print(f"  data wait: {summary['data_wait_s_mean']:.4f}s/step, "
              f"{100 * summary['data_wait_fraction']:.1f}% of step time")
    if summary.get("ckpt_stall_s_p50") is not None:
        print(f"  ckpt stall: p50 {summary['ckpt_stall_s_p50']:.4f}s  "
              f"p95 {summary['ckpt_stall_s_p95']:.4f}s per step")
    for phase, v in (summary.get("loop_phases") or {}).items():
        print(f"  loop {phase}: p50 {v['p50']:.4f}s  p95 {v['p95']:.4f}s "
              f"per step, {100 * v['share']:.1f}% of the loop thread")
    if summary.get("compile_steps"):
        print(f"  compiled before the records of steps: "
              f"{summary['compile_steps']}")
    if summary.get("input_bound") is not None:
        flag = " (!!)" if summary["input_bound"] > 0 else ""
        print(f"  input-bound steps (wait > 10% of step and > fence): "
              f"{100 * summary['input_bound']:.1f}%{flag}")
    print(f"  throughput: {summary['images_per_sec_last']:.1f} images/s, "
          f"{summary['tokens_per_sec_last']:.0f} tokens/s (last record)")
    if summary["mem_peak_bytes"]:
        print(f"  HBM peak: {summary['mem_peak_bytes'] / 1024 ** 3:.2f} GiB")
    curve = sparkline(summary["loss_curve"])
    print(f"  loss: {summary['loss_first']:.4f} -> {summary['loss_last']:.4f}"
          f" (min {summary['loss_min']:.4f})"
          + (f"  {curve}" if curve else ""))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="summarize a vitax telemetry JSONL run")
    p.add_argument("path", help="metrics.jsonl written by --metrics_dir")
    p.add_argument("--json", action="store_true",
                   help="emit the summary as one JSON object (CI mode; the "
                        "loss_curve field carries the full curve)")
    args = p.parse_args(argv)

    try:
        summary = summarize(args.path)
    except OSError as e:
        print(f"metrics_report: cannot read {args.path}: {e}",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(summary, sort_keys=True))
    else:
        print_human(summary)
    return 0 if summary["records"] else 2


if __name__ == "__main__":
    sys.exit(main())
