#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that vitax still starts on the chip.

Drives the system's main path once, through the entry points a user calls,
at the full width of the models the repo supports (depth cut only where one
chip forces it; weights random, from --seed), and checks what comes out by
the repo's own means. Run with no arguments on a machine with one TPU chip:

    python3 chip_smoke.py

Phases (one JSON object per phase on stdout; the trainer's and server's own
logs go to stderr):

  native-decode     the C++ decode library builds from decode.cc and the
                    ImageFolder loader decodes one JPEG through it
  train-l14         the whole ViT-L/14 through `parse_config` + `train`
                    (what run_vit_training.py / python -m vitax.train run):
                    batch 32, bf16, fake data, trainer defaults for every
                    performance knob, 8 steps, epoch-end eval + Orbax save
  train-10b-width   the source paper's widths (5120 / 32 heads / MLP 20480)
                    at depth 2, batch 8, same path and checks
  serve-l14         `build_engine` loads the checkpoint train-l14 wrote,
                    `vitax.serve.server` binds, the AOT buckets warm, and
                    bursts of POST /predict over localhost are answered and
                    compared with an eval-mode forward of the same params

  --chips 4 runs ONLY the path that exists across chips, and what it is
  compared with: the 10B-width model at depth 2 under fsdp=4 ZeRO-3 (global
  batch 32) through the trainer, then the same config, seed and batch on one
  of the four devices in the same process.

The last line of stdout is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as `jax.devices()` reports it. Any failed phase, a device
count other than --chips, or no TPU (JAX falls back to the CPU silently; this
script does not) exits non-zero and prints no such line.

One process per chip: this parent never imports JAX; each phase runs in a
child, one after another, so each gets the chip, reports its own peak device
memory, and shares compiled programs with the others through the persistent
compile cache (vitax/platform.py: JAX_COMPILATION_CACHE_DIR, else the fixed
<checkout>/.jax_cache).

--rehearse is the CPU rehearsal of the `on-chip-measurement` guide, section
2: the same code at a tiny size, children pinned to JAX_PLATFORMS=cpu (four
virtual devices with --chips 4). Its last line carries no "ok" key — a
rehearsal is not a result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TIME_LIMIT_S = 1150  # the contract allows 1200, compilation included

# Model shapes at their published widths. l14: ViT-L/14 (BASELINE.json
# config 3). 10b_width: the source paper's ViT-10B block (config 4) with depth
# cut from 32 to 2 — what one 16 GB chip holds with f32 params + Adam moments.
MODELS = {
    "l14": dict(image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
                num_blocks=24, num_classes=1000),
    "10b_width": dict(image_size=224, patch_size=14, embed_dim=5120,
                      num_heads=32, num_blocks=2, num_classes=1000),
    # --rehearse: control flow only
    "tiny": dict(image_size=32, patch_size=8, embed_dim=64, num_heads=2,
                 num_blocks=2, num_classes=10),
}
TRAIN_STEPS = 8          # > the trainer's 5-step log window, so the last log
LOG_WINDOW = 5           # line's sec/iter is free of the compile step
# bf16 programs under different layouts: the tolerance of the repo's own
# multi-chip dry runs (__graft_entry__.dryrun_multichip, production pair)
LOSS_RTOL = 5e-3


def train_argv(model: str, batch: int, ckpt_dir: str, seed: int) -> list:
    """The trainer command line of one phase. Every performance knob stays at
    its default. The optimisation hyper-parameters are set for 8 steps of
    fake data (every image zero, every label 0): warm-up shortened from
    10,000 steps so the loss moves at all, and a learning rate at which it
    comes down a little each step instead of hitting 0.0000 on the third (as
    it does at the default 1e-3), so that each step's loss says something."""
    argv = ["--fake_data", "--batch_size", str(batch), "--seed", str(seed),
            "--num_epochs", "1", "--steps_per_epoch", str(TRAIN_STEPS),
            "--log_step_interval", "1", "--warmup_steps", "2", "--lr", "1e-6",
            "--ckpt_dir", ckpt_dir, "--ckpt_epoch_interval", "1",
            "--test_epoch_interval", "1", "--eval_max_batches", "1"]
    for key, val in MODELS[model].items():
        argv += [f"--{key}", str(val)]
    return argv


# --------------------------------------------------------------------------
# children: everything below this line runs in a process that owns the chip
# --------------------------------------------------------------------------

class _Tee(io.TextIOBase):
    """Collects what the trainer prints while passing it on to stderr."""

    def __init__(self):
        self.lines = []
        self._buf = ""

    def write(self, s):
        sys.stderr.write(s)
        self._buf += s
        *done, self._buf = self._buf.split("\n")
        self.lines.extend(done)
        return len(s)

    def flush(self):
        sys.stderr.flush()


def device_block() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def unsmooth(avgs: list, window: int) -> list:
    """The raw series behind the trainer's log, which prints the mean of the
    last `window` values (reference parity). With one line per step it is
    recovered up to print precision: the window's sum, less the previous
    window's sum without the value that left it."""
    raw = []
    for k, avg in enumerate(avgs, start=1):
        total = avg * min(k, window)
        before = avgs[k - 2] * min(k - 1, window) if k > 1 else 0.0
        left = raw[k - window - 1] if k > window else 0.0
        raw.append(total - (before - left))
    return raw


_LOG_LINE = re.compile(
    r"epoch (\d+) step (\d+), lr: (\S+), loss: (\S+), sec/iter: ([^\s,]+)")


def parse_train_log(lines: list) -> dict:
    steps = [m for m in map(_LOG_LINE.search, lines) if m]
    loss_avg = [float(m.group(4)) for m in steps]
    time_avg = [float(m.group(5)) for m in steps]
    core = [ln.split("attention core: ", 1)[1] for ln in lines
            if "attention core: " in ln]
    accuracy = [ln for ln in lines if ln.startswith("accuracy on val")]
    return {
        "steps_logged": len(steps),
        "loss": [round(x, 4) for x in unsmooth(loss_avg, LOG_WINDOW)],
        "loss_logged": loss_avg,
        "sec_per_iter": [round(x, 4) for x in unsmooth(time_avg, LOG_WINDOW)],
        "sec_per_iter_first": time_avg[0] if time_avg else None,
        "sec_per_iter_steady": time_avg[-1] if time_avg else None,
        "attention_core": core[0] if core else None,
        "eval": accuracy[-1] if accuracy else None,
        "mem_na_lines": sum("mem: n/a" in ln for ln in lines),
        "completed": any("training completed" in ln for ln in lines),
    }


def memory_blocks() -> list:
    import jax
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({"device": d.id,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")})
    return out


def program_facts(compiled) -> dict:
    """What the compiler put in a compiled program, read off its text.
    Kernels are told apart by the `name=` their pallas_call carries into the
    custom call's op_name. (tools/aot_topology.py reads the same facts off a
    described-topology compile.)"""
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    mem = compiled.memory_analysis()
    return {
        "tpu_custom_call": len(calls),
        "tpu_custom_call_attention": sum("/flash_" in ln for ln in calls),
        "tpu_custom_call_fused_optimizer": sum(
            "fused_adamw_kernel" in ln for ln in calls),
        "all_gather": text.count(" all-gather(")
        + text.count(" all-gather-start("),
        # the TPU compiler fuses it: a kCustom fusion calling a computation
        # named all-reduce-scatter
        "reduce_scatter": text.count(" reduce-scatter(")
        + text.count("calls=%all-reduce-scatter"),
        "compiled_argument_bytes": mem.argument_size_in_bytes,
        "compiled_temp_bytes": mem.temp_size_in_bytes,
    }


def compiled_step_facts(cfg) -> dict:
    """The step program the trainer just ran, built again through the
    builder (vitax/programs) and compiled — a persistent-cache hit where the
    cache is warm."""
    from vitax.programs.builder import lower_step
    lowered, _ = lower_step(cfg, max_iteration=TRAIN_STEPS * cfg.num_epochs)
    t0 = time.time()
    compiled = lowered.compile()
    return {**program_facts(compiled),
            "recompile_s": round(time.time() - t0, 1)}


def check(failures: list, ok: bool, what: str) -> None:
    if not ok:
        failures.append(what)


def run_trainer(args, model: str, batch: int):
    """`parse_config(argv)` + `train(cfg)`, which is all run_vit_training.py
    and `python -m vitax.train` do. Returns what the phases read off it."""
    from vitax.config import parse_config
    from vitax.train.loop import train

    ckpt_dir = os.path.join(args.workdir, f"ckpt_{args.phase}")
    argv = train_argv(model, batch, ckpt_dir, args.seed)
    cfg = parse_config(argv)
    tee = _Tee()
    t0 = time.time()
    with contextlib.redirect_stdout(tee):
        state = train(cfg)
    report = {"argv": " ".join(argv), "model": MODELS[model], "batch": batch,
              "wall_s": round(time.time() - t0, 1),
              **parse_train_log(tee.lines)}
    return cfg, state, tee.lines, report, memory_blocks()


def check_train_result(failures, log, facts, mem, on_tpu) -> None:
    import math
    check(failures, log["completed"], "trainer did not reach 'training completed'")
    check(failures, log["steps_logged"] == TRAIN_STEPS,
          f"{log['steps_logged']} step lines logged, expected {TRAIN_STEPS}")
    check(failures, all(math.isfinite(x) for x in log["loss_logged"]),
          f"non-finite loss {log['loss_logged']}")
    check(failures, len(log["loss_logged"]) > 1
          and max(log["loss_logged"]) - min(log["loss_logged"]) > 1e-3,
          f"loss is constant {log['loss_logged']}")
    if on_tpu:
        check(failures, log["mem_na_lines"] == 0,
              "'mem: n/a' in the step log on a TPU")
        check(failures, all(m["peak_bytes_in_use"] for m in mem),
              f"memory_stats() gave no peak_bytes_in_use: {mem}")
        check(failures, facts["tpu_custom_call_attention"] > 0,
              "no attention kernel (tpu_custom_call) in the compiled step")
        check(failures, facts["tpu_custom_call_fused_optimizer"] > 0,
              "no fused optimizer kernel (tpu_custom_call) in the compiled step")


def phase_native_decode(args) -> dict:
    """The smoke trains on fake data, so the decode path is called once here:
    the .so is gitignored and must build from decode.cc on a fresh copy."""
    import numpy as np
    from PIL import Image

    from vitax import _native
    from vitax.data.imagefolder import ImageFolderDataset
    from vitax.data.transforms import train_transform

    so_present = os.path.exists(_native._SO)
    t0 = time.time()
    lib = _native.load()
    failures = []
    check(failures, lib is not None,
          "native decode library failed to build/load (g++ or libjpeg)")
    root = os.path.join(args.workdir, "jpeg", "class0")
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(args.seed)
    Image.fromarray(rng.integers(0, 256, (300, 280, 3), dtype=np.uint8)).save(
        os.path.join(root, "img.jpg"), quality=90)
    size = MODELS[args.models[0]]["image_size"]
    ds = ImageFolderDataset(os.path.dirname(root),
                            train_transform(size, args.seed, normalize=False))
    images, labels = ds.load_batch([0])
    check(failures, ds.use_native, "ImageFolder loader took the PIL path")
    check(failures, images.shape == (1, size, size, 3),
          f"decoded batch has shape {images.shape}")
    return {"decode_path": "native" if ds.use_native else "pil",
            "built_here": not so_present, "load_s": round(time.time() - t0, 2),
            "batch_shape": list(images.shape), "dtype": str(images.dtype),
            "failures": failures}


def phase_train(args, model: str, batch: int, keep_ckpt: bool) -> dict:
    from vitax.checkpoint.orbax_io import latest_epoch

    cfg, state, _, report, mem = run_trainer(args, model, batch)
    del state  # free the device before the program is compiled again
    facts = compiled_step_facts(cfg)
    failures = []
    check_train_result(failures, report, facts, mem, args.on_tpu)
    check(failures, latest_epoch(cfg.ckpt_dir) == 1,
          f"no committed epoch_1 checkpoint under {cfg.ckpt_dir}")
    if not keep_ckpt:  # gigabytes nobody reads again
        shutil.rmtree(cfg.ckpt_dir, ignore_errors=True)
    return {**report, "memory": mem, **facts,
            "checkpoint": "epoch_1 committed", "failures": failures}


def _post(url: str, body: bytes, timeout: float = 120.0):
    import urllib.request
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": "image/png"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.load(resp)


def phase_serve(args, model: str) -> dict:
    import jax
    import numpy as np
    from PIL import Image

    from vitax.config import Config, build_parser, config_fields_from_namespace
    from vitax.programs.builder import build_engine
    from vitax.serve.server import decode_image_bytes, drain, start_server
    from vitax.train.step import prepare_images

    metrics_dir = os.path.join(args.workdir, "serve_metrics")
    argv = ["--ckpt_dir", os.path.join(args.workdir, "ckpt_train-l14"),
            "--serve_port", "0", "--metrics_dir", metrics_dir,
            # bursts must coalesce into one batch: deployment setting, like
            # the port
            "--max_batch_wait_ms", "100"]
    for key, val in MODELS[model].items():
        argv += [f"--{key}", str(val)]
    # the same three steps as `python -m vitax.serve`: parse, build_engine,
    # bind, warm — then traffic instead of waiting for SIGTERM
    ns = build_parser().parse_args(argv)
    cfg = Config(**config_fields_from_namespace(ns)).validate()
    t0 = time.time()
    engine = build_engine(cfg)
    load_s = time.time() - t0
    httpd, ctx = start_server(cfg, engine)
    failures = []
    try:
        warm = engine.warmup()
        url = f"http://127.0.0.1:{httpd.server_address[1]}/predict"
        rng = np.random.default_rng(args.seed)
        size = cfg.image_size
        bursts = [1, 3, cfg.serve_max_batch]
        bodies = []
        for _ in range(sum(bursts)):
            buf = io.BytesIO()
            Image.fromarray(rng.integers(0, 256, (size, size, 3),
                                         dtype=np.uint8)).save(buf, "PNG")
            bodies.append(buf.getvalue())
        answers = [None] * len(bodies)

        def one(i):
            answers[i] = _post(url, bodies[i])

        start = 0
        for n in bursts:  # n concurrent requests -> one batch of n
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(start, start + n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            start += n
        check(failures, all(a is not None and a[0] == 200 for a in answers),
              f"not every /predict answered 200: {answers}")

        # the reference: an eval-mode forward of the same parameters on the
        # arrays the server decoded, outside the engine's bucketed programs
        images = np.stack([decode_image_bytes(b, ctx.transform)
                           for b in bodies])
        logits = jax.jit(lambda p, x: engine.model.apply(
            p, prepare_images(x), True))(engine.params, images)
        probs = np.asarray(jax.nn.softmax(logits.astype(np.float32), axis=-1))
        ref_top1 = probs.argmax(axis=-1)
        served_top1, worst_prob_gap = [], 0.0  # gap relative to the reference
        for i, a in enumerate(answers):
            if a is None or a[0] != 200:
                continue
            classes, p = a[1]["classes"], a[1]["probs"]
            check(failures, len(classes) == len(p) == engine.topk,
                  f"request {i}: top-k shape {len(classes)}/{len(p)}")
            served_top1.append(int(classes[0]))
            want = float(probs[i, classes[0]])
            worst_prob_gap = max(worst_prob_gap,
                                 abs(p[0] - want) / max(want, 1e-6))
        check(failures, served_top1 == [int(c) for c in ref_top1],
              f"served argmax {served_top1} != eval-mode forward "
              f"{ref_top1.tolist()}")
        check(failures, worst_prob_gap < 5e-2,  # bf16, different batch shapes
              f"served top-1 probability off the reference by "
              f"{worst_prob_gap:.3f} of its value")
        check(failures, engine.compile_count == len(engine.buckets),
              f"compile_count {engine.compile_count} != "
              f"{len(engine.buckets)} buckets after traffic")
    finally:
        drained = drain(httpd, ctx)
    check(failures, drained, "server did not drain")
    with open(os.path.join(metrics_dir, "serve.jsonl"), encoding="utf-8") as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    batches = sorted({(e["batch_size"], e["bucket"]) for e in events
                      if e.get("kind") == "serve_request"})
    check(failures, len({b for _, b in batches}) >= 2,
          f"traffic exercised fewer than two buckets: {batches}")
    return {"argv": " ".join(argv), "model": MODELS[model],
            "engine_load_s": round(load_s, 1),
            "buckets": list(engine.buckets),
            "warmup_s": {str(b): round(t, 2) for b, t in warm.items()},
            "compile_count": engine.compile_count,
            "requests": len(answers),
            "answered_200": sum(a is not None and a[0] == 200
                                for a in answers),
            "batches_seen": [list(b) for b in batches],
            "served_top1": served_top1, "reference_top1": ref_top1.tolist(),
            "top1_prob_rel_gap_max": round(worst_prob_gap, 6),
            "latency_ms": [a[1]["latency_ms"] for a in answers
                           if a is not None and a[0] == 200],
            "memory": memory_blocks(), "failures": failures}


def phase_fsdp4(args, model: str, batch: int) -> dict:
    """fsdp=4 ZeRO-3 through the trainer's normal mesh, then the same config,
    seed and global batch on one of the four devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vitax.programs.builder import Geometry, build_program

    n_dev = jax.device_count()
    # trainer defaults: fsdp_size -1 puts all devices on the ZeRO-3 axis
    cfg, state, lines, report, mem = run_trainer(args, model, batch)
    failures = []

    # where the parameters live: every leaf on n_dev distinct devices, a
    # quarter of the bytes each (small leaves may stay replicated — listed)
    total = per_device = 0
    replicated = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        shards = leaf.addressable_shards
        devices = {s.device.id for s in shards}
        check(failures, len(devices) == n_dev,
              f"{jax.tree_util.keystr(path)} sits on devices {sorted(devices)}")
        total += leaf.nbytes
        per_device += shards[0].data.nbytes
        if shards[0].data.nbytes == leaf.nbytes:
            replicated.append(jax.tree_util.keystr(path))
    share = per_device / total
    check(failures, share < 1.05 / n_dev,
          f"one device holds {share:.3f} of the parameter bytes, "
          f"expected about 1/{n_dev}")
    mesh_shape = [ln for ln in lines if ln.startswith("mesh: ")]
    del state
    shutil.rmtree(cfg.ckpt_dir, ignore_errors=True)  # nobody reads it again

    facts = compiled_step_facts(cfg)
    check_train_result(failures, report, facts, mem, args.on_tpu)
    check(failures, facts["all_gather"] > 0, "no all-gather in the compiled step")
    # XLA's CPU pipeline spells the gradient reduction all-reduce + slice
    check(failures, facts["reduce_scatter"] > 0 or not args.on_tpu,
          "no reduce-scatter in the compiled step")

    # the comparison arm: one device, the builder's train program fed the
    # fake dataset's batch (zero images, label 0), same seed and schedule
    cfg1 = dataclasses.replace(cfg, fsdp_size=1)
    geom1 = Geometry.assemble(cfg1, TRAIN_STEPS, devices=jax.devices()[:1],
                              materialize=True)
    state1 = geom1.state
    step1 = build_program("train", geom1)
    device0 = jax.devices()[0]
    fake = {"image": jax.device_put(jnp.zeros(
                (batch, cfg1.image_size, cfg1.image_size, 3), jnp.float32),
                device0),
            "label": jax.device_put(jnp.zeros((batch,), jnp.int32), device0)}
    rng = jax.random.key(cfg1.seed + 1)
    one_device = []
    for _ in range(TRAIN_STEPS):
        state1, metrics = step1(state1, fake, rng)
        one_device.append(float(jax.device_get(metrics["loss"])))
    del state1
    fsdp = report["loss"]
    check(failures, len(fsdp) == len(one_device)
          and np.allclose(fsdp, one_device, rtol=LOSS_RTOL, atol=1e-3),
          f"fsdp={n_dev} losses {fsdp} differ from one device {one_device}")
    return {**report, "mesh": mesh_shape[0] if mesh_shape else None,
            "loss_one_device": [round(x, 4) for x in one_device],
            "loss_rtol": LOSS_RTOL,
            "param_bytes_total": total, "param_bytes_one_device": per_device,
            "param_share_one_device": round(share, 4),
            "replicated_leaves": replicated,
            "memory_after_fsdp_arm": mem,
            "memory_after_one_device_arm": memory_blocks(),
            **facts, "failures": failures}


def run_child(args) -> int:
    """One phase group in a process of its own. stdout carries JSON lines
    only; everything vitax prints goes to stderr."""
    out = sys.stdout
    sys.stdout = sys.stderr

    def emit(obj):
        out.write(json.dumps(obj) + "\n")
        out.flush()

    from vitax.platform import setup_compile_cache
    cache_dir = setup_compile_cache()
    device = device_block()
    args.on_tpu = device["platform"] == "tpu"
    if not args.on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU: JAX reports platform "
              f"{device['platform']!r} ({device['kind']}, {device['count']} "
              f"device(s)). Not falling back.", file=sys.stderr)
        return 3
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{device['count']} device(s)", file=sys.stderr)
        return 3
    big, wide = args.models
    phases = {
        "train-l14": [("native-decode", lambda: phase_native_decode(args)),
                      ("train-l14", lambda: phase_train(args, big, 32, True))],
        "train-10b-width": [("train-10b-width",
                             lambda: phase_train(args, wide, 8, False))],
        "serve-l14": [("serve-l14", lambda: phase_serve(args, big))],
        "fsdp4-10b-width": [("fsdp4-10b-width",
                             lambda: phase_fsdp4(args, wide, 32))],
    }[args.phase]
    for name, fn in phases:
        t0 = time.time()
        result = fn()
        failures = result.pop("failures")
        emit({"phase": name, "ok": not failures, "device": device,
              "rehearsal": args.rehearse, "compile_cache": cache_dir,
              "phase_s": round(time.time() - t0, 1), **result,
              **({"failures": failures} if failures else {})})
        if failures:
            return 1
    return 0


# --------------------------------------------------------------------------
# parent: never imports JAX
# --------------------------------------------------------------------------

def run_phase(name: str, args, deadline: float) -> list:
    """Run one phase group as a child; relay its JSON lines; return them."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--chips", str(args.chips), "--seed", str(args.seed),
           "--workdir", args.workdir]
    env = dict(os.environ)
    if args.rehearse:
        cmd.append("--rehearse")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={args.chips}").strip()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env,
                            cwd=REPO)
    killer = threading.Timer(max(deadline - time.time(), 1.0), proc.kill)
    killer.start()
    results = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "phase" in obj:
                results.append(obj)
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise SystemExit(f"chip_smoke: phase {name} failed (exit code {rc})")
    if not results or not all(r.get("ok") is True for r in results):
        raise SystemExit(f"chip_smoke: phase {name} reported no passing result")
    return results


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the fsdp=4 path and its one-device "
                         "comparison (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size (no chip, no result)")
    ap.add_argument("--phase", default="", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.models = ("tiny", "tiny") if args.rehearse else ("l14", "10b_width")
    if args.phase:
        return run_child(args)

    # fixed, inside the checkout, gitignored; holds a multi-GB checkpoint
    # between the train and serve phases and is removed at the end
    args.workdir = os.path.join(REPO, ".scratch", "chip_smoke")
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    deadline = time.time() + TIME_LIMIT_S
    names = (["fsdp4-10b-width"] if args.chips == 4
             else ["train-l14", "train-10b-width", "serve-l14"])
    results = []
    try:
        for name in names:
            results += run_phase(name, args, deadline)
    finally:
        shutil.rmtree(args.workdir, ignore_errors=True)
    devices = [r["device"] for r in results]
    if any(d != devices[0] for d in devices):
        raise SystemExit(f"chip_smoke: phases disagree on the device: {devices}")
    if args.rehearse:
        print(json.dumps({"rehearsal": True,
                          "phases_passed": [r["phase"] for r in results],
                          "device": devices[0]}))
        return 0
    if devices[0]["platform"] != "tpu" or devices[0]["count"] != args.chips:
        raise SystemExit(f"chip_smoke: ran on {devices[0]}, not on "
                         f"{args.chips} TPU chip(s)")
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
