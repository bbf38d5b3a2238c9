#!/usr/bin/env python3
"""Self-driving knob search: rank the knob space with the compile-only cost
model, probe the shortlist with AOT compiles, measure on a real chip when one
is up, and commit the winner as presets/<model>_<topology>.json.

Usage (off-TPU, the CI / degraded path — fully deterministic):

    JAX_PLATFORMS=cpu python tools/autotune.py --preset tiny \
        --topologies cpu:1 cpu:8 --compile_only

    # compile-prune against a REAL pod topology, no hardware:
    JAX_PLATFORMS=cpu python tools/autotune.py --preset 10b \
        --topologies v5p:4x4x8 --compile_only --compile_top 2

On a live TPU (`--topologies local`, the default when a chip is up) the
shortlist graduates to short fenced measured windows under successive
halving (vitax/tune/driver.py). Every trial — analytic, compile, measured,
pruned — is one kind:"autotune_trial" JSONL record in --trials, so
tools/perf_gate.py and tools/metrics_report.py can fold the search into the
perf trajectory. libtpu allows ONE process at a time — don't run this
concurrently with bench.py or tools/aot_topology.py.

Off-TPU degradation contract (tests/test_autotune.py): no TPU means
--compile_only is forced (with a printed note), the ranked shortlist and the
emitted preset's knobs are bit-identical run to run, and the exit code is 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cpu:N topologies need N host devices; must be set before jax (which
# vitax.platform imports) first loads — keep this above any vitax import
# that touches jax.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

# HBM bytes per chip by topology-name prefix (abstract topologies have no
# live memory stats; the bound gates compile_probe's fits_hbm verdict)
HBM_BY_PREFIX = {"v5p": 95e9, "v5e": 16e9, "v6e": 32e9, "v4": 32e9,
                 "v3": 16e9}


def resolve_topology(name: str) -> dict:
    """One topology spec -> devices + accounting constants.

    "local"  : whatever backend is up (the only one that can measure)
    "cpu:N"  : first N forced-host CPU devices (compile-only)
    "v5e:2x4" / "v5p:4x4x8" / ... : jax.experimental.topologies AOT target
    """
    import jax

    from vitax.platform import backend_platform
    from vitax.telemetry.flops import detect_peak_tflops

    if name == "local":
        platform = backend_platform()
        devices = jax.devices(platform)
        kind = devices[0].device_kind
        return {"topology": f"local-{len(devices)}x{kind}".replace(" ", ""),
                "devices": list(devices), "n_dev": len(devices),
                "device_kind": kind,
                # a CPU has no peak; the analytic ranking only needs a
                # constant (same as the cpu:N branch below)
                "peak_tflops": detect_peak_tflops(kind) or 1.0,
                "hbm_bound_bytes": 0.0,
                "can_measure": platform == "tpu"}
    if name.startswith("cpu:"):
        n = int(name.split(":", 1)[1])
        cpus = jax.devices("cpu")
        assert len(cpus) >= n, (
            f"{name}: only {len(cpus)} host devices (XLA_FLAGS forces 8; "
            f"ask for <= that)")
        return {"topology": name, "devices": cpus[:n], "n_dev": n,
                "device_kind": "cpu", "peak_tflops": 1.0,
                "hbm_bound_bytes": 0.0, "can_measure": False}
    from jax.experimental import topologies
    td = topologies.get_topology_desc(name, "tpu")
    devices = list(td.devices)
    kind = devices[0].device_kind
    prefix = name.split(":", 1)[0]
    return {"topology": name, "devices": devices, "n_dev": len(devices),
            "device_kind": kind,
            "peak_tflops": detect_peak_tflops(kind),
            "hbm_bound_bytes": HBM_BY_PREFIX.get(prefix, 0.0),
            "can_measure": False}


def main(argv=None) -> int:
    import bench
    from vitax.tune.driver import TrialLog, run_search
    from vitax.tune.preset import preset_path, save_preset

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--preset", default="l14",
                    choices=list(bench.train_presets(1)))
    ap.add_argument("--topologies", nargs="+", default=["local"],
                    help='"local", "cpu:N", or an AOT TPU topology like '
                         '"v5e:2x4" / "v5p:4x4x8"')
    ap.add_argument("--compile_only", action="store_true",
                    help="never run measured windows (forced off-TPU)")
    ap.add_argument("--compile_top", type=int, default=0,
                    help="AOT-compile-probe the top K shortlist candidates "
                         "(0 = analytic ranking only; compiles are minutes "
                         "each at pod scale)")
    ap.add_argument("--shortlist", type=int, default=8,
                    help="survivors past the analytic-rank stage")
    ap.add_argument("--max_candidates", type=int, default=0,
                    help="cap the enumerated space (0 = full grid)")
    ap.add_argument("--budget_steps", type=int, default=240,
                    help="total measured steps across all halving rounds")
    ap.add_argument("--min_steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--trials", default=os.path.join(
        root, "AUTOTUNE_TRIALS.jsonl"))
    ap.add_argument("--presets_dir", default=os.path.join(root, "presets"))
    ap.add_argument("--no_emit", action="store_true",
                    help="rank only; do not write preset files")
    ap.add_argument("--json", action="store_true",
                    help="print one summary JSON line per topology")
    args = ap.parse_args(argv)

    from vitax.platform import backend_platform
    on_tpu = backend_platform() == "tpu"
    if not on_tpu and not args.compile_only:
        print("[autotune] no TPU backend — degrading to --compile_only "
              "(deterministic ranked shortlist; measured windows need a "
              "live chip)", flush=True)
        args.compile_only = True
    if not args.compile_only:
        # measured windows run on the local chip; compile-only probes stay
        # off the cache (a described-topology entry cannot be read back)
        from vitax.platform import setup_compile_cache
        setup_compile_cache()

    preset_kw = bench.train_presets(1)[args.preset]
    log = TrialLog(args.trials)
    rc = 0
    try:
        for topo_name in args.topologies:
            topo = resolve_topology(topo_name)
            measure = (not args.compile_only) and topo["can_measure"]
            kw = dict(preset_kw)
            kw.pop("batch_size", None)  # the search owns the batch ladder
            result = run_search(
                args.preset, topo["topology"], kw, topo["n_dev"], log,
                peak_tflops=topo["peak_tflops"], devices=topo["devices"],
                hbm_bound_bytes=topo["hbm_bound_bytes"],
                max_candidates=args.max_candidates,
                shortlist=args.shortlist, compile_top=args.compile_top,
                measure=measure, budget_steps=args.budget_steps,
                min_steps=args.min_steps, warmup=args.warmup)
            out_path = None
            if result["winner"] and not args.no_emit:
                out_path = preset_path(args.presets_dir, args.preset,
                                       topo["topology"])
                save_preset(out_path, result["winner"])
            summary = {
                "kind": "autotune_summary", "model_preset": args.preset,
                "topology": topo["topology"], "n_dev": topo["n_dev"],
                "measured": measure,
                "n_candidates": result["n_candidates"],
                "n_invalid": result["n_invalid"],
                "shortlist": [r["knobs"] for r in result["ranked"]],
                "winner_knobs": (result["winner"] or {}).get("knobs"),
                "preset_file": out_path,
                "trials": args.trials,
            }
            if args.json:
                print(json.dumps(summary, sort_keys=True), flush=True)
            else:
                print(f"[autotune] {args.preset}@{topo['topology']}: "
                      f"{len(result['ranked'])} ranked survivors"
                      + (f", preset -> {out_path}" if out_path else ""),
                      flush=True)
                if result["ranked"]:
                    best = result["ranked"][0]
                    print(f"[autotune]   best knobs: "
                          f"{json.dumps(best['knobs'], sort_keys=True)}",
                          flush=True)
            if not result["ranked"]:
                print(f"[autotune] {args.preset}@{topo['topology']}: no "
                      f"survivors (all pruned)", file=sys.stderr, flush=True)
                rc = 1
    finally:
        log.close()
    return rc


if __name__ == "__main__":
    sys.exit(main())
