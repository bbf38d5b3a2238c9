#!/usr/bin/env python3
"""Pick per-preset scan/remat knob defaults from measured ladder results.

Reads a ladder JSONL (one line per A/B run,
{"args": "--preset l14 --scan_unroll 2", "result": {bench JSON}}) plus the
default-config rows in BASELINE_MEASURED.json, and flips a preset's default
knobs in TUNED.json ONLY when a ladder winner beats a MEASURED run of the
current default by --min_gain. bench.py's default_scan_blocks /
default_scan_unroll / default_remat_window / default_remat_policy consult
TUNED.json first, so measured winners become the defaults WITHOUT a code
edit.

Safety rules (reviewed in round 4):
- never flip away from a default that has no measurement in the candidate
  set (an unmeasured-but-possibly-faster code default must not be replaced
  by a slower measured row);
- rows whose result carries an "error" field are ignored (a watchdog-killed
  partial run must not become the default);
- a row's knob set comes from the bench's OWN "knobs" field in the result
  JSON (ground truth); CLI-flag reconstruction is the legacy fallback.

Usage: python tools/apply_ladder.py --ladder LADDER.jsonl
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KNOB_KEYS = ("scan_blocks", "scan_unroll", "remat_window", "remat_policy",
             "batch_per_chip")  # per-chip batch rides along: img/s/chip
#   from different per-chip batches is not comparable (and per-chip is
#   device-count independent, so multi-chip watcher hosts still match)


def preset_batch_per_chip(preset):
    """The preset's default PER-CHIP batch (train_presets at n_dev=1)."""
    from bench import train_presets
    return train_presets(1).get(preset, {}).get("batch_size")


def parse_preset(args_str: str):
    """Just the --preset value, tolerant of any other flags (rows carrying
    the bench's own "knobs" record stay eligible even when their CLI line
    has non-knob flags like --steps)."""
    toks = args_str.split()
    for i, t in enumerate(toks):
        if t == "--preset" and i + 1 < len(toks):
            return toks[i + 1]
    return None


def parse_knobs(args_str: str) -> dict:
    """Knob dict from a ladder entry's CLI-args string (only knobs that are
    legal bench A/B levers; unknown flags — or a truncated line, e.g. the
    watcher killed mid-append — make the entry ineligible)."""
    toks = args_str.split()
    knobs = {"preset": None, "scan_blocks": None, "scan_unroll": 0,
             "remat_window": 0, "remat_policy": None}
    valued = {"--preset": "preset", "--scan_unroll": "scan_unroll",
              "--remat_window": "remat_window", "--remat_policy": "remat_policy"}
    i = 0
    while i < len(toks):
        t = toks[i]
        if t == "--no_scan_blocks":
            knobs["scan_blocks"] = False; i += 1
        elif t in valued:
            if i + 1 >= len(toks):
                return {}  # truncated line: skip, never crash the tune loop
            val = toks[i + 1]
            knobs[valued[t]] = (int(val) if valued[t] in
                                ("scan_unroll", "remat_window") else val)
            i += 2
        else:
            return {}  # not a pure knob A/B (e.g. --batch_size): skip
    return knobs


def legacy_entry_knobs(knobs: dict) -> dict:
    """Best-effort knob reconstruction for ladder rows WITHOUT the bench's
    "knobs" field (pre-round-4 format). Uses the PRE-TUNED fallbacks
    (allow_tuned=False): these rows predate the knobs field and therefore
    predate any TUNED flip, so the defaults in effect at measurement time
    were the fallbacks — filling with tuned-now defaults would misattribute
    them to post-flip knob sets."""
    from bench import (default_remat_policy, default_scan_blocks,
                       default_scan_unroll)
    sb, su, rw = knobs["scan_blocks"], knobs["scan_unroll"], knobs["remat_window"]
    if rw > 1:
        sb, su = True, 1
    if sb is None:
        sb = (True if su
              else default_scan_blocks(knobs["preset"], allow_tuned=False))
    if not su:
        su = default_scan_unroll(knobs["preset"], allow_tuned=False)
    policy = knobs["remat_policy"] or default_remat_policy(
        knobs["preset"], allow_tuned=False)
    return {"scan_blocks": sb, "scan_unroll": su, "remat_window": rw,
            "remat_policy": policy,
            "batch_per_chip": preset_batch_per_chip(knobs["preset"])}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ladder", required=True)
    p.add_argument("--out", default=os.path.join(REPO, "TUNED.json"))
    p.add_argument("--min_gain", type=float, default=1.02,
                   help="a ladder winner must beat the measured current "
                        "default by this factor to flip it")
    args = p.parse_args()

    sys.path.insert(0, REPO)  # bench.py: shared knob-default semantics
    import bench
    from bench import (default_remat_policy, default_remat_window,
                       default_scan_blocks, default_scan_unroll)
    # the "current default" must consult the SAME file this run writes —
    # a custom --out must not compare against a stale repo TUNED.json
    bench.TUNED_FILE = args.out

    baseline_path = os.path.join(REPO, "BASELINE_MEASURED.json")
    baselines = {}
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baselines = json.load(f)

    candidates = {}  # preset -> list of (img/s, knobs)
    for preset, entry in baselines.items():
        ips = entry.get("images_per_sec_chip") if isinstance(entry, dict) else None
        if ips:
            candidates.setdefault(preset, []).append((ips, {
                "scan_blocks": entry.get("scan_blocks", True),
                "scan_unroll": entry.get("scan_unroll", 1),
                "remat_window": entry.get("remat_window", 0),
                "remat_policy": entry.get("remat_policy",
                                          default_remat_policy(preset)),
                # stored rows record the GLOBAL batch + device count
                "batch_per_chip": (entry["batch_size"] // entry["n_devices"]
                                   if entry.get("batch_size")
                                   and entry.get("n_devices")
                                   else preset_batch_per_chip(preset))}))

    if os.path.exists(args.ladder):
        with open(args.ladder) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                    preset = parse_preset(row["args"])
                    result = row["result"]
                    value = float(result["value"])
                    errored = "error" in result
                except (json.JSONDecodeError, KeyError, TypeError,
                        ValueError, AttributeError):
                    continue  # one malformed line must never kill the loop
                if not preset or value <= 0 or errored:
                    # an "error" row with a positive partial value (e.g. a
                    # watchdog kill mid-run) must never become the default
                    continue
                rec = result.get("knobs")
                try:
                    if isinstance(rec, dict) and all(k in rec for k in KNOB_KEYS):
                        knobs = {k: rec[k] for k in KNOB_KEYS}  # ground truth
                    else:
                        cli = parse_knobs(row["args"])  # legacy pure-knob rows
                        if not cli.get("preset"):
                            continue
                        knobs = legacy_entry_knobs(cli)
                except (KeyError, TypeError, ValueError):
                    continue  # malformed knob values: skip, never crash
                candidates.setdefault(preset, []).append((value, knobs))

    tuned = {}
    if os.path.exists(args.out):  # preserve prior decisions for other presets
        try:
            with open(args.out) as f:
                tuned = json.load(f)
        except (OSError, json.JSONDecodeError):
            tuned = {}

    changed = False
    for preset, rows in sorted(candidates.items()):
        current = {"scan_blocks": default_scan_blocks(preset),
                   "scan_unroll": default_scan_unroll(preset),
                   "remat_window": default_remat_window(preset),
                   "remat_policy": default_remat_policy(preset),
                   "batch_per_chip": preset_batch_per_chip(preset)}
        # challengers at a different per-chip batch are not comparable to
        # the default's img/s/chip — drop them BEFORE the argmax
        rows = [r for r in rows
                if r[1].get("batch_per_chip") == current["batch_per_chip"]]
        if not rows:
            continue
        cur_meas = max((v for v, k in rows if k == current), default=None)
        if cur_meas is None:
            print(f"{preset}: current default {current} has no measurement "
                  f"— keeping it (never flip away from unmeasured)")
            continue
        best_ips, best_knobs = max(rows, key=lambda r: r[0])
        if best_knobs == current or best_ips < args.min_gain * cur_meas:
            print(f"{preset}: default {current} stands at {cur_meas} "
                  f"img/s/chip (best alternative {best_ips})")
            continue
        tuned[preset] = dict(best_knobs, images_per_sec_chip=best_ips,
                             source="ladder")
        changed = True
        print(f"{preset}: FLIP to {best_knobs} @ {best_ips} img/s/chip "
              f"(measured default was {cur_meas})")

    if not changed:
        print("no default flips; TUNED.json unchanged")
        return 0
    tmp = args.out + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(tuned, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
