#!/usr/bin/env python3
"""Capture a jax.profiler trace of the bench train step and print a step-time
breakdown (VERDICT round-2 item 3: account for where the non-MFU time goes).

Usage: python tools/profile_step.py --preset l14 [--steps 8] [--out /tmp/prof]

Parses the xplane via xprof's framework_op_stats converter into a table of
self-time by op category (fusion kinds, custom-call kernels, copies, infeed),
printed as JSON + a human table. This is the measurement side of PERF.md's
"Where the time goes" section.
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="l14",
                   choices=["tiny", "b16", "b16_moe", "l14", "10b", "10b_slice"])
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--warmup", type=int, default=3)
    # the shared knob-flag group (vitax/tune/knobs.py): identical surface to
    # bench.py so a trace explains exactly the config the bench measured,
    # --preset_file included (profile a committed autotune winner)
    from vitax.tune.knobs import add_knob_args, knob_payload, knobs_from_args
    add_knob_args(p)
    p.add_argument("--out", default="/tmp/vitax_profile")
    args = p.parse_args()

    from vitax.platform import setup_compile_cache
    setup_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from vitax.telemetry.flops import (detect_peak_tflops,
                                       model_flops_per_image)
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh, batch_pspec
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step

    n_dev = jax.device_count()
    device_kind = jax.devices()[0].device_kind  # vtx: ignore[VTX104] CLI entry point: labels the backend being profiled
    peak = detect_peak_tflops(device_kind)
    if peak is None:
        raise SystemExit(f"profile_step traces a chip; JAX reports "
                         f"{device_kind!r} (a CPU trace says nothing about "
                         f"the device)")
    # presets and remat defaults come FROM bench.py so traces explain exactly
    # the configs the bench measures
    from bench import apply_preset_file, resolve_bench_knobs, train_presets
    apply_preset_file(args, n_dev)
    kn = knobs_from_args(args)
    kw = kn.apply_to_preset_kw(train_presets(n_dev)[args.preset])
    (args.scan_blocks, args.scan_unroll, args.remat_window,
     args.remat_policy) = resolve_bench_knobs(
        args.scan_blocks, args.scan_unroll, args.remat_window,
        args.remat_policy, args.preset,
        other_explicit=kn.other_explicit())
    cfg = Config(num_classes=1000, warmup_steps=0,
                 remat_policy=args.remat_policy, grad_ckpt=args.grad_ckpt,
                 scan_blocks=args.scan_blocks, scan_unroll=args.scan_unroll,
                 remat_window=args.remat_window,
                 use_flash_attention=args.use_flash_attention, **kw).validate()
    print("knobs:", json.dumps(knob_payload(cfg, n_dev), sort_keys=True))

    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh))
    tx, schedule = build_optimizer(cfg, max_iteration=10_000)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    step_fn = make_train_step(cfg, model, tx, mesh, sspecs, schedule=schedule)

    sh = NamedSharding(mesh, batch_pspec())
    rng = np.random.default_rng(0)
    batch = {
        "image": jax.device_put(jnp.asarray(
            rng.normal(size=(cfg.batch_size, cfg.image_size, cfg.image_size, 3)),
            jnp.float32), sh),
        "label": jax.device_put(jnp.asarray(
            rng.integers(0, cfg.num_classes, size=(cfg.batch_size,)),
            jnp.int32), sh),
    }
    rng_key = jax.random.key(1)

    for _ in range(args.warmup):
        state, metrics = step_fn(state, batch, rng_key)
    float(jax.device_get(metrics["loss"]))

    import time
    jax.profiler.start_trace(args.out)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step_fn(state, batch, rng_key)
    float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    step_ms = dt / args.steps * 1e3
    flops = model_flops_per_image(cfg) * cfg.batch_size
    mfu = flops / (dt / args.steps) / (peak * 1e12 * n_dev)
    print(f"\n== {args.preset} remat={args.remat_policy} "
          f"batch={cfg.batch_size}: "
          f"{step_ms:.1f} ms/step, MFU {mfu:.3f} ({device_kind}) ==")

    xplanes = sorted(glob.glob(
        os.path.join(args.out, "**", "*.xplane.pb"), recursive=True))
    if not xplanes:
        print("no xplane captured (device tracing unavailable on this "
              "transport); trace dir:", args.out)
        return
    analyze_xplane(xplanes[-1], args.steps, step_ms, peak)


def analyze_xplane(xplane_path: str, n_steps: int, wall_step_ms: float,
                   peak_tflops: float) -> None:
    """Direct xplane parse: device time by HLO category + top ops, with
    per-category achieved FLOP/s and HBM bytes (roofline attribution)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2 as xpb

    space = xpb.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    tpu_planes = [p for p in space.planes if "/device:TPU" in p.name]
    if not tpu_planes:
        print("no TPU device plane in trace; planes:",
              [p.name for p in space.planes])
        return
    plane = tpu_planes[0]
    print(f"xplane: {xplane_path} (plane {plane.name})")

    def md_stat(md, name):
        for s in md.stats:
            if plane.stat_metadata[s.metadata_id].name == name:
                return (s.str_value or s.int64_value or s.uint64_value
                        or s.double_value)
        return None

    ops_lines = [l for l in plane.lines if l.name == "XLA Ops"]
    steps_lines = [l for l in plane.lines if l.name == "Steps"]
    if not ops_lines:
        print("no 'XLA Ops' line; lines:", [l.name for l in plane.lines])
        return

    device_step_ms = None
    if steps_lines and steps_lines[0].events:
        evs = steps_lines[0].events
        device_step_ms = sum(e.duration_ps for e in evs) / len(evs) / 1e9

    by_cat = {}  # cat -> [time_ps, flops, bytes]
    by_op = {}
    for ev in ops_lines[0].events:
        md = plane.event_metadata[ev.metadata_id]
        cat = str(md_stat(md, "hlo_category") or "?")
        flops = float(md_stat(md, "flops") or 0)
        nbytes = float(md_stat(md, "bytes_accessed") or 0)
        slot = by_cat.setdefault(cat, [0.0, 0.0, 0.0])
        slot[0] += ev.duration_ps
        slot[1] += flops
        slot[2] += nbytes
        oslot = by_op.setdefault(md.display_name or md.name,
                                 [0.0, 0.0, 0.0, cat])
        oslot[0] += ev.duration_ps
        oslot[1] += flops
        oslot[2] += nbytes

    total_ps = sum(v[0] for v in by_cat.values())
    busy_ms = total_ps / 1e9 / n_steps
    print(f"\nwall step: {wall_step_ms:.1f} ms | device busy: "
          f"{busy_ms:.1f} ms/step"
          + (f" | device step span: {device_step_ms:.1f} ms" if device_step_ms
             else "")
          + f" | gap (host/dispatch): {wall_step_ms - busy_ms:.1f} ms")
    print(f"\n-- device time by HLO category ({n_steps} steps) --")
    print(f"{'%time':>7} {'ms/step':>9} {'TFLOP/s':>9} {'GB/s':>8}  category")
    for cat, (ps, fl, by) in sorted(by_cat.items(), key=lambda kv: -kv[1][0]):
        sec = ps / 1e12
        print(f"{ps/total_ps*100:6.2f}% {ps/1e9/n_steps:9.2f} "
              f"{fl/sec/1e12 if sec else 0:9.1f} {by/sec/1e9 if sec else 0:8.0f}"
              f"  {cat}")
    print(f"\n-- top 15 ops by device time (peak {peak_tflops:.0f} TF/s) --")
    for name, (ps, fl, by, cat) in sorted(
            by_op.items(), key=lambda kv: -kv[1][0])[:15]:
        sec = ps / 1e12
        print(f"{ps/total_ps*100:6.2f}% {ps/1e9/n_steps:8.2f}ms "
              f"{fl/sec/1e12 if sec else 0:7.1f}TF/s "
              f"{by/sec/1e9 if sec else 0:6.0f}GB/s [{cat[:12]:12}] {name[:70]}")


if __name__ == "__main__":
    main()
