"""Traffic kind `train_loop`: the trainer itself,
`vitax.train.loop.train(cfg)`, in this process, as
`python -m vitax.train --fake_data --metrics_dir DIR` runs it: the sharded
loader's prefetch queue and host-to-device hand-off, the loop with its
log-step fences, the step records. The first kind that drives the loop users
run; the resident kinds step one compiled program from a loop of their own.

Parameters (the traffic mix's file):
  per_chip_batch     images per chip and step
  loop_defaults      the `Config` defaults the cell relies on and does NOT
                     set (`log_step_interval`, `prefetch_batches`,
                     `num_workers`): a program whose default differs is
                     refused, not steered
  log_interval       only in a `rehearse` block: a shorter log interval for
                     the tiny run on the CPU
  warm_intervals     log intervals before the window opens
  step_s_hint        seconds a step is expected to take: the window holds
                     n = max(1, ceil(seconds / (interval x hint))) intervals
  reference_sample   images the plain reference is run on
  trace_host_level   0: a traced run's profiler records device events alone
                     (`harness.profiler`)
  control            tests and the builder's control run only:
                     "float8_reference" gives the plain reference the
                     weights rounded to float8_e4m3 (3 bits of mantissa, the
                     nearest format below the bf16 the configuration
                     states); the run has to come out not correct

`Config` gets the fields the configuration sets, the batch, the seed,
`fake_data`, `metrics_dir`, a `ckpt_dir` inside the work directory (nothing
is saved: the run ends by `max_steps` inside its first epoch, before any
save or evaluation is due) and `max_steps`; every other field keeps its
default. The data is `FakeImageNetDataset` through `ShardedLoader`: float32
zero images, label 0.

The window is the loop's own: it opens at the fence of log step
`interval x warm_intervals` and closes at the fence of the log step n
intervals later, both read from the step records' `loop_marks`
(vitax/train/loop.py, module docstring). At either fence the device has run
every step dispatched and the next is not dispatched yet, so the device
operations inside the window are exactly its `interval x n` steps, and the
drain after the opening fence (the record's write, the next batch's
hand-off and dispatch, with the device idle) is inside it.

What `correct` can and cannot hold on this data. Every image of every batch
is the same zeros under label 0, so a loader or loop that dropped rows,
repeated a stale batch or handed over the wrong rows would give the same
loss and gradient norm: which rows reach the step is invisible here, and is
left to the host-fed cells (PERF.md section 7). What is held: the step that
`train()` compiled IS the program lowered here for a float32 batch of
`per_chip_batch` x chips images (one key of the persistent compile cache for
both, `CacheKeys`: another batch shape or dtype, another `Config`, another
program text is another key), so the facts, bytes and kernels reported are
the timed program's and every step took that many rows; its step-1 loss and
gradient norm against the float32 reference on the same weights; one record
and `log_step_interval` marks an interval with no hole; no compile in the
window; a falling loss.

A traced run wraps the whole of `train()` in `harness.profiler` with the host
tracer off (`trace_host_level` 0 in the traffic file), so the profiler's
start and stop lie outside the window and the trace holds device events
alone. The loop's own `--profile_dir` window was the first choice and was
measured (PERF.md, PR 37): every float32 batch is laid out for the chip by
host-side transposes in the runtime's threads, 200,000 host trace events a
batch, and with the host tracer on the first step after the opening fence
waited 0.9-1.3 s for its batch where an untraced run loses 0.04.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import time

from benchmark import flops as arithmetic   # this kind's FLOPs and parameters
from benchmark import harness
from benchmark.generators.train_decoder_packed import round_to_float8
from benchmark.generators.train_resident import GRAD_NORM_RTOL, LOSS_RTOL
from benchmark.reference import vit as reference

# LOSS_RTOL (2e-3) and GRAD_NORM_RTOL (1e-2) are the resident cell's, set there
# on random images. On this cell's one zero image (my chip runs, PR 37, PERF.md
# section 6) the loop's step 1 read, over 41 sound runs, a loss gap of at most
# 1.58e-3 (root mean square 5.1e-4) and a norm gap of at most 3.3e-3 (9.7e-4):
# one image's rounding is not averaged over a batch of distinct ones. The
# float8 reference (`control`, two seeds) read a loss gap of 2.97e-3 and
# 1.59e-2, over its limit in both, and a norm gap of 1.32e-2 and 3.8e-3, over
# its limit in one: the loss is what refuses the control, with 1.3 times of
# room under the limit and 1.5 above it.


class CacheKeys(logging.Handler):
    """The keys the persistent compile cache was asked for while open, as
    JAX's compiler logs them at DEBUG, hit and miss alike: `seen` holds
    (module name, key, hit). A key is the hash of the program's text, its
    compile options and the backend, so two compiles under one key are one
    program."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.seen = []
        self.log = logging.getLogger("jax._src.compiler")

    def emit(self, record):
        said = str(record.msg).lower()
        if (said.startswith("persistent compilation cache")
                and len(record.args or ()) == 2):
            self.seen.append((*record.args, " hit " in said))

    def __enter__(self):
        self.was = (self.log.level, self.log.propagate)
        self.log.addHandler(self)
        self.log.setLevel(logging.DEBUG)
        self.log.propagate = False
        return self

    def __exit__(self, *exc):
        self.log.removeHandler(self)
        self.log.setLevel(self.was[0])
        self.log.propagate = self.was[1]


def build_config(config_kwargs: dict, traffic: dict, n_devices: int,
                 seed: int):
    from vitax.config import Config
    defaults = {f.name: f.default for f in dataclasses.fields(Config)}
    for name, relied_on in traffic["loop_defaults"].items():
        if defaults[name] != relied_on:
            raise SystemExit(
                f"benchmark: the cell relies on Config.{name} = {relied_on} "
                f"by default; this program's default is {defaults[name]}")
    shorter = ({"log_step_interval": int(traffic["log_interval"])}
               if "log_interval" in traffic else {})
    return Config(**config_kwargs, **shorter,
                  batch_size=int(traffic["per_chip_batch"]) * n_devices,
                  seed=seed, fake_data=True).validate()


def max_iteration(cfg) -> int:
    """The schedule's length as `train()` derives it from the fake split, so
    that the step lowered here is the program the loop compiles."""
    from vitax.data.fake import TRAIN_SPLIT_LEN
    return TRAIN_SPLIT_LEN // cfg.batch_size * cfg.num_epochs


def lower_step(geom):
    """The loop's step program lowered from abstract shapes: the state, a
    batch as `ShardedLoader` hands it over (float32 images, int32 labels,
    sharded over the batch axes) and the loop's key."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    from vitax.programs.builder import build_program
    cfg = geom.cfg
    sh = NamedSharding(geom.mesh, batch_pspec())
    s = cfg.image_size
    batch = {"image": jax.ShapeDtypeStruct((cfg.batch_size, s, s, 3),
                                           jnp.float32, sharding=sh),
             "label": jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32,
                                           sharding=sh)}
    key = jax.eval_shape(lambda: jax.random.key(0))
    return build_program("train", geom).lower(geom.abstract_state, batch, key)


def setup(run: harness.Run) -> dict:
    import jax
    import jax.numpy as jnp
    from vitax.programs.builder import Geometry
    try:
        from vitax.telemetry.record import LOOP_MARKS  # noqa: F401
    except ImportError:
        raise SystemExit("benchmark: this program's train loop stamps no "
                         "`loop_marks`: the cell's window cannot be found")

    cfg = build_config(run.config_kwargs, run.traffic, jax.device_count(),
                       run.seed)
    interval = cfg.log_step_interval
    opens = interval * int(run.traffic["warm_intervals"])
    closes = opens + interval * max(1, math.ceil(
        run.seconds / (interval * float(run.traffic["step_s_hint"]))))
    cfg = dataclasses.replace(
        cfg, metrics_dir=os.path.join(run.work_dir, "metrics"),
        ckpt_dir=os.path.join(run.work_dir, "ckpt"),
        max_steps=closes).validate()

    t0 = time.time()
    geom = Geometry.assemble(cfg, max_iteration(cfg), materialize=True)
    jax.block_until_ready(geom.state)
    run.records["state_s"] = time.time() - t0

    t0 = time.time()
    with CacheKeys() as keys:
        compiled = lower_step(geom).compile()
    run.program.update(harness.program_facts(compiled))
    run.program["params"] = arithmetic.param_count(run.config)
    run.records["compile_or_cache_s"] = time.time() - t0

    # the loader's batch is one image, all zeros under label 0, many times
    # over: the mean loss and its gradient are one image's. The reference
    # takes pixels and normalises them, so it is fed the pixel value that
    # normalises to the loader's zero.
    t0 = time.time()
    sample, s = int(run.traffic["reference_sample"]), cfg.image_size
    zero_pixel = 255.0 * jnp.asarray(reference.IMAGENET_MEAN, jnp.float32)
    params = geom.state.params
    if run.traffic.get("control") == "float8_reference":
        params = round_to_float8(params)
    with jax.default_matmul_precision(reference.PRECISION):
        ref_loss, ref_norm = reference.loss_and_grad_norm(
            params, jnp.broadcast_to(zero_pixel, (sample, s, s, 3)),
            jnp.zeros((sample,), jnp.int32), **reference.shape_of(run.config))
        ref_loss, ref_norm = float(ref_loss), float(ref_norm)
    run.records["reference_s"] = time.time() - t0
    del params
    geom.state = None   # freed: the loop makes its own from the same seed
    return {"cfg": cfg, "opens": opens, "closes": closes,
            "ref_loss": ref_loss, "ref_norm": ref_norm,
            "step_keys": keys.seen}


def step_records(metrics_dir: str) -> list:
    with open(os.path.join(metrics_dir, "metrics.jsonl"),
              encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return sorted((r for r in records if "kind" not in r),
                  key=lambda r: r["step"])


def window(run: harness.Run, live: dict,
           compiles: harness.CompileCounter) -> None:
    from vitax.train.loop import train
    cfg, opens, closes = live["cfg"], live["opens"], live["closes"]
    t0 = time.time()
    with harness.profiler(run), CacheKeys() as keys:
        train(cfg)
    run.records["train_s"] = time.time() - t0
    check_step_is_the_lowered_one(run, live["step_keys"], keys.seen)

    records = step_records(cfg.metrics_dir)
    by_step = {r["step"]: r for r in records}
    rows = [row for r in records for row in r.get("loop_marks", [])]
    at = {row[0]: row for row in rows}
    logged = [1] + list(range(cfg.log_step_interval, closes + 1,
                              cfg.log_step_interval))
    run.check(sorted(by_step) == logged,
              f"step records at {sorted(by_step)}, expected {logged}")
    run.check([row[0] for row in rows] == list(range(1, closes + 1))
              and all(r["loop_marks"][-1][0] == r["step"] for r in records),
              "the records' loop_marks do not run 1, 2, ... up to each "
              "record's own step")
    stamps = [t for row in rows for t in row[1:]]
    run.check(all(a <= b for a, b in zip(stamps, stamps[1:])),
              "the loop's marks do not tile its time: a mark lies before "
              "the one it follows")
    if run.failures:    # no window to speak of
        return

    first, opening, closing = by_step[1], by_step[opens], by_step[closes]
    t_open, t_close = at[opens][5], at[closes][5]
    steps = closes - opens
    failed = 0 if math.isfinite(closing["loss"]) else steps
    run.records.update({
        "window_open_t": t_open, "window_close_t": t_close,
        "window_s": t_close - t_open,
        "steps": steps, "global_batch": cfg.batch_size,
        "images": steps * cfg.batch_size,
        "attempted": steps, "failed": failed,
        "compiles_in_window": closing["compiles"] - opening["compiles"],
        "loss_first": opening["loss"], "loss_last": closing["loss"],
        "loop_marks": rows,
    })
    loss_gap = abs(first["loss"] - live["ref_loss"]) / abs(live["ref_loss"])
    norm_gap = (abs(first["grad_norm"] - live["ref_norm"])
                / abs(live["ref_norm"]))
    run.checks.update({
        "loss_step1": first["loss"], "loss_reference": live["ref_loss"],
        "loss_rel_gap": loss_gap, "loss_rtol": LOSS_RTOL,
        "grad_norm_step1": first["grad_norm"],
        "grad_norm_reference": live["ref_norm"],
        "grad_norm_rel_gap": norm_gap, "grad_norm_rtol": GRAD_NORM_RTOL})
    run.check(loss_gap <= LOSS_RTOL,
              f"the loop's step-1 loss {first['loss']} is off the reference "
              f"{live['ref_loss']} by more than {LOSS_RTOL} of it")
    run.check(norm_gap <= GRAD_NORM_RTOL,
              f"the loop's step-1 gradient norm {first['grad_norm']} is off "
              f"the reference {live['ref_norm']} by more than "
              f"{GRAD_NORM_RTOL} of it")
    run.check(failed == 0, f"the closing record's loss is {closing['loss']}")
    run.check(run.records["compiles_in_window"] == 0,
              f"{run.records['compiles_in_window']} traces or compiles "
              f"inside the window")
    run.check(closing["loss"] < opening["loss"],
              f"the loss did not come down over the window on a constant "
              f"batch: {opening['loss']} at step {opens}, {closing['loss']} "
              f"at step {closes}")
    if run.device.get("platform") == "tpu":
        run.check(run.program["attention_kernels"] > 0,
                  "no flash_* attention kernel (tpu_custom_call) in the "
                  "compiled step")
        run.check(run.program["fused_optimizer_kernels"] > 0,
                  "no fused optimizer kernel (tpu_custom_call) in the "
                  "compiled step")
    live_peak = harness.live_peak_bytes()
    run.records["live_peak_bytes"] = live_peak
    # the runtime's peak leaves out temporaries: hold the compiler's own
    # accounting of the step beside it and report the larger
    run.records["memory_peak_bytes"] = max(live_peak or 0,
                                           run.program["step_bytes"])


def check_step_is_the_lowered_one(run: harness.Run, lowered: list,
                                  in_train: list) -> None:
    """`program_facts`, `step_bytes` and the kernel checks read the step
    lowered in `setup`; the window times the one `train()` compiled. They
    are one program where the cache was asked for both under one key."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return      # `--rehearse` keeps CPU programs out of the chip's cache
    run.check(len(lowered) == 1,
              f"the compile of the lowered step asked the persistent cache "
              f"for {len(lowered)} keys, not one: the loop's step cannot be "
              f"held to it")
    if len(lowered) != 1:
        return
    name, key, _ = lowered[0]
    loops = [(k, hit) for n, k, hit in in_train if n == name]
    run.checks.update({
        "step_cache_key": key,
        "loop_step_cache_keys": sorted({k for k, _ in loops}),
        "loop_step_cache_hit": bool(loops) and all(h for _, h in loops)})
    run.check(bool(loops) and all(k == key for k, _ in loops),
              f"`train()` compiled {name} under "
              f"{sorted({k for k, _ in loops})}, the step lowered here for "
              f"a float32 batch of {run.traffic['per_chip_batch']} images a "
              f"chip has {key}: the facts, bytes and kernels reported are "
              f"another program's")


def finish(run: harness.Run, live: dict) -> None:
    live.clear()


def lower_described(config_kwargs: dict, traffic: dict, devices):
    """The loop's step lowered for described devices (not attached, the
    production kernels forced), from abstract shapes
    (benchmark/size_cells.py). Nothing runs."""
    from vitax.programs.builder import Geometry
    cfg = build_config(config_kwargs, traffic, len(devices), 0)
    geom = Geometry.assemble(cfg, max_iteration(cfg), devices=devices,
                             force_tpu_kernels=True)
    return (lower_step(geom),
            f"train step, batch {cfg.batch_size}, float32 images")
