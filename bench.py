#!/usr/bin/env python3
"""vitax benchmark: images/sec/chip + MFU for the training step.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
"platform": ..., "device_kind": ..., "n_devices": N, ...}
(vs_baseline is null when nothing comparable exists: no stored baseline, or a
knob set differing from the stored one).
A benchmark never hides a missing device: a run that finds no TPU fails
(exit non-zero) unless the process was explicitly pinned to the host CPU with
JAX_PLATFORMS=cpu (the test suite, CPU rehearsals), and any exception
propagates — there is no stand-in number and no exit 0 on failure.

Default config is ViT-L/14 (BASELINE.json config 3 shape) sized for one chip;
--preset tiny|b16|l14|10b selects others; --preset data benchmarks the host
input pipeline (native C++ vs PIL decode+augment) and needs no accelerator.
FLOP accounting: matmul FLOPs (patchify + qkv/proj/mlp/head) plus attention
score/value einsums, x3 for fwd+bwd (the standard 6ND convention); remat
recompute is NOT counted as useful work (true MFU).

--write_baseline persists the measured numbers into BASELINE_MEASURED.json
(merged per preset); subsequent runs report vs_baseline against that file.
"""

import argparse
import datetime
import json
import os
import sys
import threading
import time

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE_MEASURED.json")

# FLOPs/MFU accounting is shared with the training-loop telemetry
# (vitax/telemetry/flops.py) so bench MFU and the run-log MFU are the same
# number.
from vitax.telemetry.flops import detect_peak_tflops, mfu as mfu_of  # noqa: E402

# The perf-knob surface (argparse group / resolved payload) is shared with
# tools/profile_step.py, tools/aot_topology.py and tools/autotune.py —
# stdlib-only imports, safe before backend selection.
from vitax.tune.knobs import (  # noqa: E402
    add_knob_args, knob_payload, knobs_from_args)


def apply_preset_file(args, n_dev: int) -> None:
    """--preset_file: fill every knob still at its sentinel default from a
    committed autotune preset (presets/<model>_<topology>.json). Explicit
    CLI flags win; the preset's RESOLVED knobs pin everything else, so the
    run reproduces the winning knob set exactly (TUNED.json defaults cannot
    leak underneath). Needs the live device count: batch is stored per-chip."""
    if not getattr(args, "preset_file", ""):
        return
    from vitax.tune.preset import apply_preset_to_args, load_preset
    preset = load_preset(args.preset_file)
    applied = apply_preset_to_args(preset, args, n_dev)
    print(f"bench: preset {args.preset_file} "
          f"({preset['model_preset']}@{preset['topology']}) applied "
          f"{applied}", file=sys.stderr, flush=True)

# --metrics_dir: also append the emitted payload to <dir>/bench.jsonl
# (schema-1 telemetry event, kind="bench"). Fail-soft by contract: an
# unwritable dir warns and never sinks the measured number.
_metrics_dir = ""


def _append_metrics_record(result: dict) -> None:
    if not _metrics_dir:
        return
    try:
        os.makedirs(_metrics_dir, exist_ok=True)
        record = dict(result, schema=1, kind="bench", time=time.time())
        with open(os.path.join(_metrics_dir, "bench.jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps(record, default=str) + "\n")
    except OSError as e:
        print(f"bench: --metrics_dir {_metrics_dir!r} is not writable "
              f"({e}); continuing without the JSONL record",
              file=sys.stderr, flush=True)


def emit(result: dict) -> None:
    """Print the ONE JSON line."""
    print(json.dumps(result), flush=True)
    _append_metrics_record(result)


def read_baseline() -> dict:
    if os.path.exists(BASELINE_FILE):
        try:
            with open(BASELINE_FILE) as f:
                return json.load(f)
        except (json.JSONDecodeError, OSError):
            return {}
    return {}


def write_baseline(preset: str, entry: dict) -> None:
    base = read_baseline()
    entry = dict(entry, measured_at=datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds"))
    base[preset] = entry
    tmp = BASELINE_FILE + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:  # tmp+rename: a kill mid-write must not
        json.dump(base, f, indent=2, sort_keys=True)  # truncate the
        f.write("\n")                                 # accumulated baselines
    os.replace(tmp, BASELINE_FILE)


def host_payload() -> dict:
    """The device block of an accelerator-free (host pipeline) payload: no
    JAX device takes part, and the payload says so."""
    return {"platform": "host",
            "device_kind": f"{os.cpu_count() or 1}-core host cpu",
            "n_devices": 0}


def init_backend():
    """Bring the JAX backend up; returns the payload's device block
    {"platform", "device_kind", "n_devices"} as JAX reports it.

    A run that finds no TPU fails, unless the process was explicitly pinned
    to the host CPU (JAX_PLATFORMS=cpu — tests and rehearsals): with the
    variable unset JAX falls back to the CPU silently where it finds no
    chip, and a benchmark must never report that as a result."""
    import jax

    from vitax.platform import (backend_platform, device_kind,
                                setup_compile_cache)
    setup_compile_cache()
    platform = backend_platform()
    pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform != "tpu" and not pinned_cpu:
        raise SystemExit(
            f"bench: no TPU found (JAX reports platform {platform!r}); a "
            f"benchmark does not fall back. Set JAX_PLATFORMS=cpu explicitly "
            f"for a CPU smoke run.")
    return {"platform": platform, "device_kind": device_kind(),
            "n_devices": jax.device_count()}


def _rounded(x, digits: int = 4):
    return None if x is None else round(x, digits)


def train_presets(n_dev: int) -> dict:
    """Benchmark model shapes (shared with tools/profile_step.py so traces
    explain exactly the configs the bench measures)."""
    return {
        "tiny": dict(image_size=224, patch_size=16, embed_dim=192, num_heads=3,
                     num_blocks=12, batch_size=64 * n_dev),
        # BASELINE.json config 2 shape (ViT-B/16, pure-DP benchmark)
        "b16": dict(image_size=224, patch_size=16, embed_dim=768, num_heads=12,
                    num_blocks=12, batch_size=64 * n_dev),
        # ViT-B/16 with a top-1 Switch MoE MLP (8 experts) in every block:
        # measures the routing/dispatch overhead vs the dense b16 row (per-
        # token useful FLOPs are identical under top-1 routing, so the MFU
        # accounting below stays valid; router FLOPs are negligible)
        "b16_moe": dict(image_size=224, patch_size=16, embed_dim=768,
                        num_heads=12, num_blocks=12, batch_size=64 * n_dev,
                        moe_experts=8),
        "l14": dict(image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
                    num_blocks=24, batch_size=32 * n_dev),
        "10b": dict(image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
                    num_blocks=32, batch_size=8 * n_dev),
        # largest 10B-family slice that fits one v5e chip: same 5120-dim
        # blocks, depth cut to 2. Depth 4 does NOT fit — measured 15.2 GB f32
        # state + 10.2 GB temps (tests/test_memory_analysis.py::
        # test_10b_slice_fits_single_chip_hbm holds the preset to the limit).
        # Batch 64/chip is the measured single-chip throughput frontier
        # (MFU 0.579 on v5e; 96 OOMs; the
        # flagship's pod operating point of 8/chip measures 73-79 img/s).
        "10b_slice": dict(image_size=224, patch_size=14, embed_dim=5120,
                          num_heads=32, num_blocks=2, batch_size=64 * n_dev),
    }


TUNED_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "TUNED.json")


def _tuned(preset: str) -> dict:
    """Measured per-preset knob winners (tools/apply_ladder.py writes
    TUNED.json from the chip watcher's ladder results, so defaults track
    the hardware measurements without a code edit)."""
    try:
        with open(TUNED_FILE) as f:
            return json.load(f).get(preset, {})
    except (OSError, json.JSONDecodeError):
        return {}


def default_scan_blocks(preset: str, allow_tuned: bool = True) -> bool:
    """Per-preset scan-vs-unrolled default: the TUNED.json winner when the
    ladder has been measured; else l14 measured 250.1 img/s/chip fully
    unrolled vs 194.3 under lax.scan on v5e (batch 32, dots_attn_saveable —
    the scan's per-block dus-stacking caps wgrad fusions at 85-100 TF/s vs
    164+ unconstrained), so l14 defaults to the unrolled path and other
    presets keep the scan. allow_tuned=False pins the pre-TUNED fallback."""
    t = _tuned(preset) if allow_tuned else {}
    if "scan_blocks" in t:
        return bool(t["scan_blocks"])
    return preset != "l14"


def default_scan_unroll(preset: str, allow_tuned: bool = True) -> int:
    """Per-preset scan unroll (only meaningful when the scan path is used):
    the TUNED.json winner when measured, else 1."""
    t = _tuned(preset) if allow_tuned else {}
    return int(t.get("scan_unroll", 1))


def default_remat_window(preset: str, allow_tuned: bool = True) -> int:
    """Per-preset remat window (the group-remat wgrad experiment): the
    TUNED.json winner when measured, else the family fallback. The 10B
    family keeps the none_saveable scan (it cannot unroll its residuals
    away) and its single-chip slice measured +25% from window-2 group
    remat (round-4 ladder: 145.5 vs 116.3 img/s/chip) — the full
    flagship preset inherits that measured family winner; everything else
    defaults to per-block remat (0)."""
    t = _tuned(preset) if allow_tuned else {}
    if "remat_window" in t:
        return int(t["remat_window"])
    # measured-winner class default, so it is gated on allow_tuned exactly
    # like TUNED entries: with an explicit A/B knob pinning the others
    # (allow_tuned=False), the window must fall back to 0 — a window-2
    # default would contradict e.g. --no_grad_ckpt or --no_scan_blocks and
    # trip validate() asserts the user never opted into.
    # "10b_slice" ONLY (not the 32-block flagship): the +25% was measured on
    # the depth-2 slice where window 2 spans the whole model; the flagship's
    # single-chip fit depends on minimal none_saveable residency, so it
    # keeps 0 until a window-2 run is measured at its shape (ADVICE r4)
    return 2 if (allow_tuned and preset == "10b_slice") else 0


def resolve_bench_knobs(scan_blocks, scan_unroll: int, remat_window: int,
                        remat_policy, preset: str,
                        other_explicit: bool = False):
    """Resolve the full (scan_blocks, scan_unroll, remat_window,
    remat_policy) knob set from CLI values + per-preset defaults. Shared
    with tools/profile_step.py so traces explain exactly the configs the
    bench measures.

    ONE rule keeps A/Bs pure: tuned defaults (TUNED.json winners) apply
    ONLY when NO knob was given explicitly. Any explicit knob pins every
    other default to its pre-TUNED fallback, so an A/B run differs from
    the historical reference by exactly the knobs on its command line —
    never by a default that TUNED flipped since.

    remat_window: -1 = unset; 0 = explicit per-block remat; >1 = the
    windowed-remat experiment, which forces the scan path even for presets
    whose measured default is unrolled (l14)."""
    explicit = (scan_blocks is not None or bool(scan_unroll)
                or remat_window >= 0 or remat_policy is not None
                or other_explicit)  # any A/B lever: --no_grad_ckpt,
    # --no_flash_attention, --batch_size — tuned knobs must not leak into
    # (or crash: remat_window>1 needs grad_ckpt) a pure-knob comparison
    tuned_ok = not explicit
    if remat_window < 0:
        remat_window = default_remat_window(preset, allow_tuned=tuned_ok)
    if remat_policy is None:
        remat_policy = default_remat_policy(preset, allow_tuned=tuned_ok)
    if remat_window > 1:
        assert scan_blocks is not False, (
            "--remat_window needs the scan path (drop --no_scan_blocks)")
        assert scan_unroll in (0, 1), (
            "--remat_window subsumes --scan_unroll (the window IS the "
            "unrolled group); drop one of the two")
        # pin the unroll (Config.validate rejects the combination)
        return True, 1, remat_window, remat_policy
    assert not (scan_blocks is False and scan_unroll), (
        "--no_scan_blocks contradicts --scan_unroll (unroll is a scan knob)")
    if scan_blocks is None:
        # an explicit --scan_unroll is a request for the scan path
        scan_blocks = (True if scan_unroll
                       else default_scan_blocks(preset, allow_tuned=tuned_ok))
    if not scan_unroll:
        scan_unroll = default_scan_unroll(preset, allow_tuned=tuned_ok)
    return scan_blocks, scan_unroll, remat_window, remat_policy


def default_remat_policy(preset: str, allow_tuned: bool = True) -> str:
    """Per-preset remat default: the TUNED.json winner's policy when the
    ladder has been measured (a win under a non-default policy must flip the
    policy along with the scan knobs); else measured on v5e l14:
    dots_attn_saveable 192.9 > dots_saveable 190.2 > none_saveable
    img/s/chip; the 10B flagship keeps none_saveable — minimal HBM residency
    is what makes it fit. allow_tuned=False pins the pre-TUNED fallback
    (explicit knob A/Bs must differ from their reference by ONE knob)."""
    if allow_tuned:
        tuned = _tuned(preset).get("remat_policy")
        if tuned:
            return tuned
    return "none_saveable" if preset.startswith("10b") else "dots_attn_saveable"


def _write_random_jpegs(dir_path: str, n: int, rng):
    """The shared synthetic corpus both data benches measure on (280-500px
    random-content JPEGs, quality 90): one recipe keeps their numbers
    comparable. Returns [(path, side), ...]."""
    import numpy as np
    from PIL import Image
    out = []
    for i in range(n):
        side = int(rng.integers(280, 500))
        arr = rng.integers(0, 256, size=(side, side, 3), dtype=np.uint8)
        p = os.path.join(dir_path, f"img_{i:05d}.jpg")
        Image.fromarray(arr).save(p, quality=90)
        out.append((p, side))
    return out


def counter_rate(work, min_time: float = 0.5) -> float:
    """Counts/sec of a pure-Python spin thread while `work()` runs repeatedly
    on the calling thread for >= min_time — the GIL-release microbenchmark
    shared by the data_scaling bench and tests/test_native.py. A C call that
    drops the GIL lets the counter timeslice (~0.5x idle on one core); a
    held GIL pins it near zero."""
    box = {"n": 0, "stop": False}

    def spin():
        n = 0
        while not box["stop"]:
            n += 1
        box["n"] = n

    t = threading.Thread(target=spin, daemon=True)
    t.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < min_time:
        work()
    dt = time.perf_counter() - t0
    box["stop"] = True
    t.join()
    return box["n"] / dt


def bench_data_pipeline(args) -> None:
    """Host input-pipeline throughput: native C++ batch decode+augment vs the
    threaded-PIL fallback, on synthetic JPEGs (VERDICT round-1 item 7 — proves
    SURVEY section 7 hard-part #3). Accelerator-free."""
    import tempfile

    import numpy as np
    from PIL import Image

    from vitax.data.imagefolder import ImageFolderDataset
    from vitax.data.transforms import train_transform

    if not _native_available():
        raise SystemExit("bench: native library unavailable (C++ toolchain "
                         "missing or build failed)")

    rng = np.random.default_rng(0)
    n_images = args.data_images
    batch = args.batch_size or 256
    if not args.data_threads:
        args.data_threads = os.cpu_count() or 1
    with tempfile.TemporaryDirectory() as root:
        cls = os.path.join(root, "class0")
        os.makedirs(cls)
        _write_random_jpegs(cls, n_images, rng)

        transform = train_transform(image_size=224, seed=0)

        def run(use_native: bool) -> float:
            ds = ImageFolderDataset(root, transform, use_native=use_native)
            idx = [i % n_images for i in range(batch)]
            ds.load_batch(idx[: min(16, batch)])  # warm caches / native build
            t0 = time.perf_counter()
            reps = max(1, args.steps // 10)
            for _ in range(reps):
                ds.load_batch(idx, n_threads=args.data_threads)
            return batch * reps / (time.perf_counter() - t0)

        native_ips = run(True)
        pil_ips = run(False)

    baseline = read_baseline()
    base = baseline.get("data", {})
    vs = (round(native_ips / base["native_images_per_sec"], 4)
          if base.get("native_images_per_sec") else None)
    if args.write_baseline:
        # the data->train link (VERDICT round-2 weakness 6): for every train
        # preset already measured, record whether ONE host's native pipeline
        # keeps ALL of that host's chips fed (ratio > 1 = never input-bound;
        # the host must supply images_per_sec_chip x local chip count)
        feeds = {}
        for preset, entry in baseline.items():
            ips_chip = entry.get("images_per_sec_chip") if isinstance(
                entry, dict) else None
            if ips_chip:
                host_consumption = ips_chip * entry.get("n_devices", 1)
                feeds[preset] = round(native_ips / host_consumption, 2)
        write_baseline("data", {
            "native_images_per_sec": round(native_ips, 1),
            "pil_images_per_sec": round(pil_ips, 1),
            "speedup": round(native_ips / pil_ips, 2) if pil_ips else 0.0,
            "threads": args.data_threads,
            "feed_ratio_vs_train_preset": feeds,
        })
    emit({
        "metric": f"host data pipeline images/sec (native C++ decode+augment, "
                  f"{args.data_threads} threads; PIL fallback={pil_ips:.0f})",
        "value": round(native_ips, 1),
        "unit": "images/sec",
        "vs_baseline": vs,
        **host_payload(),
    })


def bench_data_scaling(args) -> None:
    """Decode-path scaling evidence (VERDICT r3 item 8), accelerator-free:

    1. thread ladder — repeated native batch decode+augment at n_threads in
       {1, 2, 4, ...} up to 2x the host's cores. On a 1-core host (this CI
       image) the ladder is honestly flat — the recorded host_cpus makes
       that caveat explicit in the JSON; run on a many-core host to see the
       C++ pool scale.
    2. GIL-release proof — a pure-Python counter thread runs while the main
       thread decodes. ctypes CDLL calls drop the GIL for the duration of
       the C call, so the counter must keep advancing at a healthy fraction
       of its idle rate even on ONE core (OS timeslicing); a GIL-holding
       decode would freeze it near zero. This is the contention property
       that makes the loader's thread-pool design valid, provable without
       multiple cores.
    """
    import tempfile
    import numpy as np

    if not _native_available():
        raise SystemExit("bench: native library unavailable (C++ toolchain "
                         "missing or build failed)")

    from vitax.data import native
    from vitax.data.transforms import train_transform

    rng = np.random.default_rng(0)
    n_images = min(args.data_images, 128)
    cores = os.cpu_count() or 1
    with tempfile.TemporaryDirectory() as root:
        transform = train_transform(image_size=224, seed=0)
        corpus = _write_random_jpegs(root, n_images, rng)
        paths = [p for p, _ in corpus]
        params = [transform.native_params(side, side, i)
                  for i, (_, side) in enumerate(corpus)]

        def ladder_point(n_threads: int) -> float:
            native.process_batch(paths[:16], params[:16], 224, 0,
                                 n_threads=n_threads)  # warm
            t0 = time.perf_counter()
            reps = 3
            for _ in range(reps):
                _, failed = native.process_batch(paths, params, 224, 0,
                                                 n_threads=n_threads)
                assert not failed, failed
            return n_images * reps / (time.perf_counter() - t0)

        threads = [1, 2, 4]
        while threads[-1] < 2 * cores and threads[-1] < 64:
            threads.append(threads[-1] * 2)
        ladder = {t: round(ladder_point(t), 1) for t in threads}

        # --- GIL-release proof (counter_rate is shared with
        # tests/test_native.py::test_decode_releases_gil) ---
        idle = counter_rate(lambda: time.sleep(0.05))
        during_batch = counter_rate(
            lambda: native.process_batch(paths, params, 224, 0, n_threads=1))
        during_single = counter_rate(
            lambda: native.process_file(paths[0], params[0], 224, 0))
        gil = {
            "counter_rate_idle": round(idle),
            "counter_rate_during_batch_decode": round(during_batch),
            "counter_rate_during_single_decode": round(during_single),
            # on 1 core a GIL-free C call timeslices with the counter
            # (ratio ~0.5); a GIL-holding call would pin this near 0
            "batch_ratio": round(during_batch / idle, 3) if idle else 0.0,
            "single_ratio": round(during_single / idle, 3) if idle else 0.0,
        }

    best = max(ladder.values())
    base = read_baseline().get("data_scaling", {})
    base_best = (max(base.get("images_per_sec_by_threads", {}).values(),
                     default=None)
                 if base.get("host_cpus") == cores else None)  # like-for-like
    if args.write_baseline:
        write_baseline("data_scaling", {
            "host_cpus": cores,
            "images_per_sec_by_threads": {str(k): v for k, v in ladder.items()},
            "gil_release": gil,
        })
    emit({
        "metric": f"host decode images/sec (native C++; {cores}-core host; "
                  f"ladder {ladder}; GIL-release ratios "
                  f"batch={gil['batch_ratio']}, single={gil['single_ratio']})",
        "value": best,
        "unit": "images/sec",
        "vs_baseline": round(best / base_best, 4) if base_best else None,
        **host_payload(),
    })


def _native_available() -> bool:
    try:
        from vitax.data import native
        return native.available()
    except Exception:  # noqa: BLE001
        return False


def bench_e2e(args) -> None:
    """END-TO-END on-chip throughput: real JPEGs on disk -> native C++
    decode+augment -> ShardedLoader prefetch thread -> uint8 host->device
    transfer -> jitted train step, with host decode OVERLAPPING device
    compute — the reference's per-step reality (MpDeviceLoader feeding every
    iteration, run_vit_training.py:74,88). bench_train measures a
    device-resident constant batch (pure step time); this measures the whole
    machine. The same run takes a device-resident measurement afterwards, so
    the JSON carries the e2e/resident ratio + host_cpus — on a 1-core host
    the feed-limited presets (l14/b16) are honestly input-bound
    (round-4 feed ratios, ROADMAP A4: 10b_slice 0.95, l14 0.44)."""
    import tempfile

    device = init_backend()
    n_dev, device_kind = device["n_devices"], device["device_kind"]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from vitax.config import Config
    from vitax.data.imagefolder import ImageFolderDataset
    from vitax.data.loader import ShardedLoader, ShardedSampler
    from vitax.data.transforms import train_transform
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import batch_pspec, build_mesh
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step

    train_preset = args.e2e_train_preset
    apply_preset_file(args, n_dev)
    kn = knobs_from_args(args)
    kw = kn.apply_to_preset_kw(train_presets(n_dev)[train_preset])
    (args.scan_blocks, args.scan_unroll, args.remat_window,
     args.remat_policy) = resolve_bench_knobs(
        args.scan_blocks, args.scan_unroll, args.remat_window,
        args.remat_policy, train_preset,
        other_explicit=kn.other_explicit())
    cfg = Config(num_classes=1000, warmup_steps=0,
                 remat_policy=args.remat_policy, grad_ckpt=args.grad_ckpt,
                 scan_blocks=args.scan_blocks, scan_unroll=args.scan_unroll,
                 remat_window=args.remat_window,
                 use_flash_attention=args.use_flash_attention, **kw).validate()

    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh))
    tx, schedule = build_optimizer(cfg, max_iteration=10_000)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    step_fn = make_train_step(cfg, model, tx, mesh, sspecs, schedule=schedule)
    rng_key = jax.random.key(1)
    host_cpus = os.cpu_count() or 1
    n_threads = args.data_threads or host_cpus

    rng = np.random.default_rng(0)
    n_images = max(args.data_images, 2 * cfg.batch_size)
    with tempfile.TemporaryDirectory() as root:
        cls = os.path.join(root, "class0")
        os.makedirs(cls)
        _write_random_jpegs(cls, n_images, rng)
        # the production input path: uint8 out of the host transform,
        # normalization inside the compiled step (--device_normalize)
        ds = ImageFolderDataset(
            root, train_transform(cfg.image_size, 0, normalize=False))
        sampler = ShardedSampler(len(ds), cfg.batch_size, shuffle=True,
                                 seed=0, process_index=0, process_count=1)
        loader = ShardedLoader(ds, sampler, mesh, num_workers=n_threads)

        def batches():
            epoch = 0
            while True:
                for b in loader.epoch(epoch):
                    yield b
                epoch += 1

        it = batches()
        for _ in range(max(args.warmup // 2, 2)):  # compile + warm the pool
            state, metrics = step_fn(state, next(it), rng_key)
        float(jax.device_get(metrics["loss"]))

        steps = args.steps
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, next(it), rng_key)
        final_loss = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        loader.close()
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
    e2e_ips = cfg.batch_size * steps / dt

    # device-resident reference on the SAME process/state: the denominator
    # for the overlap efficiency (how much of the pure step rate survives
    # when the input pipeline must feed every iteration)
    sh = NamedSharding(mesh, batch_pspec())
    const_batch = {
        "image": jax.device_put(jnp.asarray(rng.integers(
            0, 256, size=(cfg.batch_size, cfg.image_size, cfg.image_size, 3)),
            jnp.uint8), sh),
        "label": jax.device_put(jnp.asarray(rng.integers(
            0, cfg.num_classes, size=(cfg.batch_size,)), jnp.int32), sh),
    }
    for _ in range(3):
        state, metrics = step_fn(state, const_batch, rng_key)
    float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    resident_steps = max(args.steps // 2, 5)
    for _ in range(resident_steps):
        state, metrics = step_fn(state, const_batch, rng_key)
    float(jax.device_get(metrics["loss"]))
    resident_ips = cfg.batch_size * resident_steps / (time.perf_counter() - t0)

    overlap_eff = e2e_ips / resident_ips if resident_ips else 0.0
    peak = detect_peak_tflops(device_kind)  # None on the host CPU: no MFU
    e2e_mfu = _rounded(mfu_of(cfg, dt / steps, n_dev, peak))
    base = read_baseline().get("e2e", {})
    same = (base.get("train_preset") == train_preset
            and base.get("host_cpus") == host_cpus
            and base.get("batch_size") == cfg.batch_size
            and base.get("data_threads") == n_threads)
    vs = (round(e2e_ips / base["e2e_images_per_sec_chip"] / n_dev, 4)
          if same and base.get("e2e_images_per_sec_chip") else None)
    if args.write_baseline:
        write_baseline("e2e", {
            "train_preset": train_preset,
            "e2e_images_per_sec_chip": round(e2e_ips / n_dev, 2),
            "resident_images_per_sec_chip": round(resident_ips / n_dev, 2),
            "overlap_efficiency": round(overlap_eff, 4),
            "host_cpus": host_cpus,
            "data_threads": n_threads,
            "n_devices": n_dev,
            "batch_size": cfg.batch_size,
            "device_kind": device_kind,
        })
    emit({
        "metric": f"end-to-end images/sec/chip (JPEG decode+augment -> "
                  f"train step, {train_preset}, {device_kind}, "
                  f"overlap_eff={overlap_eff:.3f}, host_cpus={host_cpus}, "
                  f"resident={resident_ips / n_dev:.1f}/s)",
        "value": round(e2e_ips / n_dev, 2),
        "unit": "images/sec/chip",
        "vs_baseline": vs,
        **device,
        "mfu": e2e_mfu,
        "peak_tflops_per_chip": peak,
        # same resolved-knob contract as bench_train: an e2e number must
        # also say what it ran (historically this payload had no knobs)
        "knobs": knob_payload(cfg, n_dev),
    })


def bench_train(args) -> None:
    device = init_backend()
    n_dev, device_kind = device["n_devices"], device["device_kind"]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vitax.config import Config
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh, batch_pspec
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step
    from jax.sharding import NamedSharding

    apply_preset_file(args, n_dev)
    kn = knobs_from_args(args)
    kw = kn.apply_to_preset_kw(train_presets(n_dev)[args.preset])
    (args.scan_blocks, args.scan_unroll, args.remat_window,
     args.remat_policy) = resolve_bench_knobs(
        args.scan_blocks, args.scan_unroll, args.remat_window,
        args.remat_policy, args.preset,
        other_explicit=kn.other_explicit())
    cfg = Config(num_classes=1000, warmup_steps=0, remat_policy=args.remat_policy,
                 grad_ckpt=args.grad_ckpt, scan_blocks=args.scan_blocks,
                 scan_unroll=args.scan_unroll, remat_window=args.remat_window,
                 use_flash_attention=args.use_flash_attention, **kw).validate()

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
            rng.integers(0, cfg.num_classes, size=(cfg.batch_size,)), jnp.int32), sh),
    }
    rng_key = jax.random.key(1)

    # the fence is a device_get of the loss: the host needs the value anyway
    for _ in range(max(args.warmup, 1)):  # >=1: compile before the timed loop
        state, metrics = step_fn(state, batch, rng_key)
    float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step_fn(state, batch, rng_key)
    final_loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    step_time = dt / args.steps
    images_per_sec = cfg.batch_size / step_time
    images_per_sec_chip = images_per_sec / n_dev
    peak = detect_peak_tflops(device_kind)  # None on the host CPU: no MFU
    mfu = _rounded(mfu_of(cfg, step_time, n_dev, peak))

    base_entry = read_baseline().get(args.preset, {})
    knobs = ("batch_size", "remat_policy", "scan_blocks", "scan_unroll",
             "remat_window", "grad_ckpt", "use_flash_attention",
             "moe_impl", "att_dropout", "grad_accum_steps",
             "param_gather_dtype", "grad_reduce_dtype", "gather_overlap",
             "fused_optimizer")
    # compare only like-for-like: a knob change (e.g. the scan->unrolled
    # default flip) must not masquerade as a same-config speedup. Entries
    # written before a knob existed compare at the Config FIELD DEFAULT —
    # that is the value they were actually measured at — never at the
    # current run's value (which would make every experiment "match")
    field_defaults = Config()
    same_config = all(
        base_entry.get(k, getattr(field_defaults, k, None)) == getattr(cfg, k)
        for k in knobs)
    base = base_entry.get("images_per_sec_chip") if same_config else None
    # None (JSON null) whenever there is nothing comparable: differing knob
    # sets AND missing/never-measured baselines must be visible, not
    # masquerade as "exactly matches baseline" (ADVICE r3)
    vs_baseline = round(images_per_sec_chip / base, 4) if base else None
    if args.write_baseline:
        write_baseline(args.preset, {
            "images_per_sec_chip": round(images_per_sec_chip, 2),
            "step_time_ms": round(step_time * 1e3, 2),
            "mfu": mfu,
            "device_kind": device_kind,
            "n_devices": n_dev,
            "batch_size": cfg.batch_size,
            "remat_policy": cfg.remat_policy,
            # record every A/B knob so an experiment run can never
            # masquerade as the default-config baseline in the JSON
            "scan_blocks": cfg.scan_blocks,
            "scan_unroll": cfg.scan_unroll,
            "remat_window": cfg.remat_window,
            "grad_ckpt": cfg.grad_ckpt,
            "use_flash_attention": cfg.use_flash_attention,
            "moe_impl": cfg.moe_impl,
            "att_dropout": cfg.att_dropout,
            "grad_accum_steps": cfg.grad_accum_steps,
            "param_gather_dtype": cfg.param_gather_dtype,
            "grad_reduce_dtype": cfg.grad_reduce_dtype,
            "gather_overlap": cfg.gather_overlap,
            "fused_optimizer": cfg.fused_optimizer,
        })

    # optional collective audit: same report as `tools/comm_audit.py --json`,
    # landed in the BENCH payload next to the perf knobs so a measured number
    # always records what dtype its collectives moved (ISSUE: comm-precision
    # observability). Costs one extra AOT compile, hence opt-in.
    comm = None
    if args.comm_audit:
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            import comm_audit as comm_audit_mod
            rep = comm_audit_mod.audit_config(cfg)
            comm = {
                "param_gather_dtype": cfg.resolved_param_gather_dtype,
                "grad_reduce_dtype": cfg.grad_reduce_dtype,
                "all_gather_bytes": rep["all_gather_bytes"],
                "collective_bytes": {
                    op: t["bytes"] for op, t in rep["totals"].items()},
                "f32_block_param_gathers": len(rep["f32_block_param_gathers"]),
                "overlap": rep["overlap"],
            }
        except Exception as e:  # audit must never sink a measured number
            comm = {"error": f"{type(e).__name__}: {e}"}

    emit({
        "metric": f"images/sec/chip (ViT-{args.preset}, train step, "
                  f"{device_kind}, mfu={mfu}, "
                  f"step_time={step_time * 1e3:.1f}ms, remat={cfg.remat_policy})",
        "value": round(images_per_sec_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": vs_baseline,
        **device,
        # headline efficiency number, machine-readable (same analytic FLOPs
        # model as the training-loop telemetry, vitax/telemetry/flops.py);
        # null on a CPU run — a CPU has no peak to be a share of
        "mfu": mfu,
        "peak_tflops_per_chip": peak,
        # the RESOLVED knob set this number was measured under — ground
        # truth for tools/apply_ladder.py and tools/perf_gate.py
        # (reconstructing knobs from CLI flags drifts once TUNED.json
        # changes the defaults). KNOB_PAYLOAD_KEYS exactly; batch is
        # PER-CHIP: img/s/chip numbers only compare at equal per-chip batch,
        # independent of how many devices the host had
        "knobs": knob_payload(cfg, n_dev),
        **({"comm": comm} if comm is not None else {}),
    })


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="l14",
                   choices=["tiny", "b16", "b16_moe", "l14", "10b", "10b_slice",
                            "data", "data_scaling", "e2e"])
    p.add_argument("--e2e_train_preset", default="10b_slice",
                   choices=["tiny", "b16", "b16_moe", "l14", "10b_slice"],
                   help="which train preset --preset e2e drives from the "
                        "native JPEG loader (default: the preset this "
                        "host's core count can feed)")
    # the shared knob-flag group (vitax/tune/knobs.py): same surface as
    # tools/profile_step.py, tools/aot_topology.py and tools/autotune.py,
    # plus --preset_file to replay a committed autotune winner
    add_knob_args(p)
    p.add_argument("--comm_audit", action="store_true",
                   help="embed the tools/comm_audit.py collective report "
                        "(op/dtype/bytes per step) in the BENCH payload; "
                        "costs one extra AOT compile")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=8)
    p.add_argument("--data_images", type=int, default=256,
                   help="synthetic JPEG count for --preset data")
    p.add_argument("--data_threads", type=int, default=0,
                   help="0 = one per CPU core (oversubscription only hurts)")
    p.add_argument("--write_baseline", action="store_true",
                   help="persist measured numbers into BASELINE_MEASURED.json")
    p.add_argument("--metrics_dir", type=str, default="",
                   help="also append the emitted payload to "
                        "<metrics_dir>/bench.jsonl (schema-1 telemetry "
                        "event); fail-soft: an unwritable dir warns and "
                        "never sinks the measurement")
    args = p.parse_args()

    global _metrics_dir
    _metrics_dir = args.metrics_dir

    if args.preset == "data":
        bench_data_pipeline(args)
    elif args.preset == "data_scaling":
        bench_data_scaling(args)
    elif args.preset == "e2e":
        bench_e2e(args)
    else:
        bench_train(args)


if __name__ == "__main__":
    main()
