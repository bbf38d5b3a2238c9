#!/usr/bin/env python3
"""Perf-regression CI gate over the measured trajectory.

Folds the round driver's BENCH_r*.json files and the autotuner's trial JSONL
(kind:"autotune_trial") into per-(model, topology) throughput series, then
fails (exit 1) when the LATEST measured number for a series regresses more
than --threshold_pct below the BEST number ever recorded for that same
series. Outage rounds (value 0.0 + "error", e.g. BENCH_r05.json)
are evidence of a dead chip, not a slow program — they are skipped, never
gated on; the gate compares measurements only.

Modes (composable; all requested modes must pass):
  (default)        trajectory regression gate
  --validate       schema-check every BENCH_r*.json + trial JSONL
                   (vitax/telemetry/schema.py)
  --check_ranking  compile-only cost-model sanity: the analytic model must
                   order the known-ordered knob pairs correctly (e.g.
                   gather_overlap off must not out-rank auto on ZeRO-3) —
                   this is the CI arm that needs no hardware at all

--json prints one machine-readable summary object (the CI contract);
exit code is the verdict either way. main(argv) returns the exit code so
tests drive it in-process.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# "images/sec/chip (ViT-l14, train step, TPU v5 lite, mfu=0.62, ...)"
_METRIC_RE = re.compile(r"ViT-(\w+)")
_DEVICE_RE = re.compile(r"(TPU[^,)]*|GPU[^,)]*|cpu)")


def _series_key_from_metric(metric: str):
    m = _METRIC_RE.search(metric or "")
    if not m:
        return None
    dev = _DEVICE_RE.search(metric or "")
    return (m.group(1), dev.group(1).strip() if dev else "unknown")


def load_bench_points(bench_files) -> list:
    """Measured (non-outage) points from BENCH_r*.json, seq-ordered."""
    points = []
    for path in sorted(bench_files):
        try:
            with open(path, encoding="utf-8") as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = obj.get("parsed") if isinstance(obj, dict) else None
        if not isinstance(parsed, dict):
            continue
        value = parsed.get("value")
        if parsed.get("error") or not isinstance(value, (int, float)) \
                or value <= 0:
            continue  # outage / unparsable round: never gate on it
        key = _series_key_from_metric(parsed.get("metric", ""))
        if key is None:
            continue
        points.append({"key": key, "seq": (0, int(obj.get("n", 0))),
                       "value": float(value),
                       "knobs": parsed.get("knobs"),
                       "source": os.path.basename(path)})
    return points


def load_trial_points(trial_files) -> list:
    """Measured, unpruned autotune windows as trajectory points."""
    points = []
    for path in trial_files:
        try:
            f = open(path, encoding="utf-8")
        except OSError:
            continue
        with f:
            for line in f:
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (rec.get("kind") != "autotune_trial"
                        or rec.get("phase") != "measure"
                        or rec.get("pruned_by") is not None
                        or not isinstance(
                            rec.get("images_per_sec_chip"), (int, float))):
                    continue
                points.append({
                    "key": (rec.get("model_preset", "?"),
                            rec.get("topology", "?")),
                    "seq": (1, int(rec.get("trial_id", 0))),
                    "value": float(rec["images_per_sec_chip"]),
                    "knobs": rec.get("knobs"),
                    "source": f"{os.path.basename(path)}"
                              f"#{rec.get('trial_id')}"})
    return points


def gate_trajectory(points, threshold_pct: float) -> list:
    """Per-series verdicts: latest vs best, ok iff within threshold."""
    series = {}
    for p in sorted(points, key=lambda p: p["seq"]):
        series.setdefault(p["key"], []).append(p)
    out = []
    for key, pts in sorted(series.items()):
        best = max(pts, key=lambda p: p["value"])
        latest = pts[-1]
        floor = best["value"] * (1.0 - threshold_pct / 100.0)
        out.append({
            "model": key[0], "topology": key[1], "n_points": len(pts),
            "best": best["value"], "best_source": best["source"],
            "latest": latest["value"], "latest_source": latest["source"],
            "latest_knobs": latest.get("knobs"),
            "regression_pct": round(
                (1.0 - latest["value"] / best["value"]) * 100.0, 3),
            "ok": latest["value"] >= floor,
        })
    return out


def main(argv=None) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--root", default=root,
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--bench_glob", default="BENCH_r*.json")
    ap.add_argument("--trials", nargs="*", default=None,
                    help="autotune trial JSONL files (default: "
                         "AUTOTUNE_TRIALS.jsonl under --root if present)")
    ap.add_argument("--threshold_pct", type=float, default=5.0,
                    help="max tolerated regression of latest vs best")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check the inputs too")
    ap.add_argument("--check_ranking", action="store_true",
                    help="assert cost-model ordering of known knob pairs")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    bench_files = glob.glob(os.path.join(args.root, args.bench_glob))
    if args.trials is None:
        default_trials = os.path.join(args.root, "AUTOTUNE_TRIALS.jsonl")
        args.trials = [default_trials] if os.path.exists(default_trials) \
            else []

    failures = []
    summary = {"kind": "perf_gate", "threshold_pct": args.threshold_pct,
               "bench_files": sorted(os.path.basename(p)
                                     for p in bench_files),
               "trial_files": list(args.trials)}

    points = load_bench_points(bench_files) + load_trial_points(args.trials)
    series = gate_trajectory(points, args.threshold_pct)
    summary["series"] = series
    for s in series:
        if not s["ok"]:
            failures.append(
                f"{s['model']}@{s['topology']}: latest "
                f"{s['latest']:.2f} ({s['latest_source']}) is "
                f"{s['regression_pct']:.1f}% below best "
                f"{s['best']:.2f} ({s['best_source']}), "
                f"threshold {args.threshold_pct}%")

    if args.validate:
        from vitax.telemetry.schema import (validate_bench_file,
                                            validate_trials_file)
        errors = []
        for path in sorted(bench_files):
            errors.extend(validate_bench_file(path))
        for path in args.trials:
            if os.path.exists(path):
                errors.extend(validate_trials_file(path))
        summary["validate_errors"] = errors
        failures.extend(f"schema: {e}" for e in errors)

    if args.check_ranking:
        from vitax.tune.cost import check_ranking
        ranking = check_ranking()
        summary["ranking"] = ranking
        for r in ranking:
            if not r["ok"]:
                failures.append(f"cost-model ranking violated: {r['name']} "
                                f"({r['why']})")

    summary["failures"] = failures
    summary["ok"] = not failures
    if args.json:
        print(json.dumps(summary, sort_keys=True))
    else:
        for s in series:
            mark = "ok " if s["ok"] else "REGRESSED"
            print(f"[perf_gate] {mark} {s['model']}@{s['topology']}: "
                  f"latest {s['latest']:.2f} vs best {s['best']:.2f} "
                  f"img/s/chip ({s['n_points']} points)")
        if args.check_ranking:
            bad = [r for r in summary["ranking"] if not r["ok"]]
            print(f"[perf_gate] cost-model ranking: "
                  f"{len(summary['ranking']) - len(bad)}/"
                  f"{len(summary['ranking'])} pairs ordered correctly")
        if args.validate:
            print(f"[perf_gate] schema: "
                  f"{len(summary['validate_errors'])} errors")
        for fmsg in failures:
            print(f"[perf_gate] FAIL: {fmsg}", file=sys.stderr)
        print(f"[perf_gate] {'PASS' if not failures else 'FAIL'}")
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
