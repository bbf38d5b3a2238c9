"""Traffic kind `train_packed`: the trainer's default step program for the
native-resolution packed model on a constant, device-resident packed batch.

Parameters (the traffic mix's file):
  rows_per_chip      packed rows per chip and step
  row_tokens         tokens a row (the model's `pack_tokens`)
  images_per_row     image slots a row (`pack_images`; the static shape)
  rows               the measured batch's layout: for each row of ONE chip's
                     share, the (h, w) patch grids of its images in packing
                     order. Data, not drawn from `--seed`: the seed makes
                     weights, pixels and labels only, so images and tokens a
                     step are the same in every run of the cell
  check_rows         the check batch's layout (same static shape): what the
                     plain reference is run on, image by image
  run_ahead, warm_steps, expect_decreasing, reference_grad_norm
                     as in `train_resident`
  rehearse           overrides of the keys above and, under `config`, of
                     the `native_res` block for `--rehearse` (run.py, over
                     the family's own `rehearse` block): control flow only

The program is what `python -m vitax.train --pack_tokens ...` builds for a
`Config` that names only the model's shape (the configuration file's
`Config` fields, its `native_res` block, and the row shape above):
`Geometry.assemble` (vitax/programs/builder.py, the program's one
constructor) -> `build_program("train", ...)`, lowered once. The layout
arrays come from the trainer's own packer (`vitax/data/packing.py:
row_layout`); pixels and labels are made on the device from the seed.
`images` is what the step itself counted (its `images` metric) x steps.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_packed as arithmetic   # this kind's FLOPs
from benchmark import harness
from benchmark.generators import train_resident
from benchmark.reference import moonvit as reference

# The three limits below, each from two readings on the chip (PERF.md section
# 6, PR 26): the largest gap the program showed over its seeds, and the gap of
# the reference itself when every matmul operand is rounded to float8_e4m3 (3
# bits of mantissa: the nearest format below the bf16 the configuration
# states), which has to fail. The program computes in bf16 (8 bits of
# mantissa) with float32 accumulation, a float32 pool, head and loss.
#
# Step-0 loss against the float32 reference, image by image, on the same
# weights and pixels; at initialisation the loss is ln(classes) plus a small
# term. Program: 1.5e-5 to 2.9e-4 of it. float8 reference: 2.4e-2.
LOSS_RTOL = 2e-3
# The gradient's global norm sums bf16 rounding over 27 layers' backward and
# the recomputed forward. Program: 4e-5 to 7.9e-4. float8 reference: 0.47.
GRAD_NORM_RTOL = 1e-2
# Per-image logits: the largest absolute gap over images and classes, as a
# share of the largest reference logit in magnitude (about 1.5). Program:
# 4.6e-3 to 6.6e-3. float8 reference: 0.74.
LOGITS_RTOL = 3e-2
MAX_ITERATION = train_resident.MAX_ITERATION


def build_config(config_kwargs: dict, traffic: dict, n_devices: int,
                 seed: int):
    from vitax.config import Config
    return Config(**config_kwargs,
                  pack_tokens=int(traffic["row_tokens"]),
                  pack_images=int(traffic["images_per_row"]),
                  batch_size=int(traffic["rows_per_chip"]) * n_devices,
                  seed=seed).validate()


def layout(cfg, rows, n_devices: int) -> dict:
    """The trainer's packer on one chip's rows, repeated for every chip."""
    from vitax.data.packing import row_layout
    rows = [[tuple(g) for g in row] for row in rows] * n_devices
    assert len(rows) == cfg.batch_size, (len(rows), cfg.batch_size)
    for row in rows:
        assert all(h * w <= cfg.max_image_tokens for h, w in row), row
    return row_layout(rows, cfg.pack_tokens, cfg.pack_images)


def make_inputs(cfg, mesh, seed: int, layouts: dict) -> dict:
    """A packed batch per layout: pixels (zero at padding, as the packer
    leaves them) and labels made on the device from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec

    sharding = NamedSharding(mesh, batch_pspec())
    r, t, s = cfg.batch_size, cfg.pack_tokens, cfg.pack_images
    patch_dim = 3 * cfg.patch_size ** 2

    def draw(key, lay):
        k_pix, k_lab = jax.random.split(key)
        pixels = jax.random.bits(k_pix, (r, t, patch_dim), jnp.uint8)
        labels = jax.random.randint(k_lab, (r, s), 0, cfg.num_classes,
                                    jnp.int32)
        return dict(
            lay, label=labels * (lay["label_mask"] > 0),
            patches=pixels * (lay["segment_ids"] > 0)[..., None].astype(
                jnp.uint8))

    out = {}
    for i, (name, lay) in enumerate(sorted(layouts.items())):
        lay = {k: jax.device_put(v, sharding) for k, v in lay.items()}
        out[name] = jax.jit(draw, out_shardings=sharding)(
            jax.random.fold_in(jax.random.key(seed + 17), i), lay)
    return out


def setup(run: harness.Run) -> dict:
    import jax
    import jax.numpy as jnp
    from vitax.programs.builder import Geometry, build_program
    from vitax.train.step import packed_inputs

    n_dev = jax.device_count()
    config, traffic = run.config, run.traffic
    cfg = build_config(run.config_kwargs, traffic, n_dev, run.seed)
    t0 = time.time()
    geom = Geometry.assemble(cfg, MAX_ITERATION, materialize=True)
    state, geom.state = geom.state, None    # the step donates it
    mesh, model = geom.mesh, geom.model
    step = build_program("train", geom)
    inputs = make_inputs(cfg, mesh, run.seed, {
        "batch": layout(cfg, traffic["rows"], n_dev),
        "check": layout(cfg, traffic["check_rows"], n_dev)})
    rng = jax.random.key(cfg.seed + 1)
    jax.block_until_ready((state, inputs))
    run.records["state_s"] = time.time() - t0

    t0 = time.time()
    compiled = step.lower(state, inputs["batch"], rng).compile()
    run.records["compile_or_cache_s"] = time.time() - t0
    run.program.update(harness.program_facts(compiled))
    run.program["packed_attention_kernels"] = sum(
        "flash_packed_" in ln for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln)
    run.program["params"] = arithmetic.param_count(config)

    # the reference first (the step donates the state it is given): each
    # image of the check batch alone, unpacked, in float32
    t0 = time.time()
    shape = reference.shape_of(config)
    images, labels = reference.unpack(jax.device_get(inputs["check"]))
    with jax.default_matmul_precision(reference.PRECISION):
        ref_logits = reference.logits(state.params, images, **shape)
        if traffic["reference_grad_norm"]:
            ref_loss, ref_norm = reference.loss_and_grad_norm(
                state.params, images, labels, **shape)
            ref_norm = float(ref_norm)
        else:
            ref_loss = reference.loss(state.params, images, labels, **shape)
            ref_norm = None
        ref_loss = float(ref_loss)
    run.records["reference_s"] = time.time() - t0

    t0 = time.time()
    forward = jax.jit(lambda params, batch: model.apply(
        params, packed_inputs(batch), True))
    got = forward(state.params, inputs["check"])
    exists = np.asarray(jax.device_get(inputs["check"]["label_mask"])) > 0
    got_logits = np.asarray(jax.device_get(got))[exists]
    ref_logits = np.asarray(jax.device_get(ref_logits))
    logits_gap = float(np.max(np.abs(got_logits - ref_logits))
                       / np.max(np.abs(ref_logits)))
    del forward, got
    state, metrics = compiled(state, inputs["check"], rng)
    loss0 = float(metrics["loss"])
    norm0 = float(metrics["grad_norm"])
    run.records["first_step_s"] = time.time() - t0
    run.checks.update({
        "check_images": len(images), "logits_rel_gap": logits_gap,
        "logits_rtol": LOGITS_RTOL, "loss_step0": loss0,
        "loss_reference": ref_loss,
        "loss_rel_gap": abs(loss0 - ref_loss) / abs(ref_loss),
        "loss_rtol": LOSS_RTOL, "grad_norm_step0": norm0,
        "grad_norm_reference": ref_norm, "grad_norm_rtol": GRAD_NORM_RTOL})
    run.check(np.isfinite(got_logits).all() and logits_gap <= LOGITS_RTOL,
              f"per-image logits are off the reference's by {logits_gap} of "
              f"its largest logit, more than {LOGITS_RTOL}")
    run.check(run.checks["loss_rel_gap"] <= LOSS_RTOL,
              f"step-0 loss {loss0} is off the reference {ref_loss} by more "
              f"than {LOSS_RTOL} of it")
    if ref_norm is not None:
        gap = abs(norm0 - ref_norm) / abs(ref_norm)
        run.checks["grad_norm_rel_gap"] = gap
        run.check(gap <= GRAD_NORM_RTOL,
                  f"step-0 gradient norm {norm0} is off the reference "
                  f"{ref_norm} by more than {GRAD_NORM_RTOL} of it")

    t0 = time.time()
    warm = int(traffic["warm_steps"])
    for _ in range(warm):
        state, metrics = compiled(state, inputs["batch"], rng)
    jax.block_until_ready((state, metrics))
    run.records["warm_steps_s"] = time.time() - t0
    # what the step itself counted on the measured batch (the same every
    # step), held against the layout the traffic file gives
    counts = {k: float(metrics[k]) for k in
              ("tokens", "padding_tokens", "images", "token_pairs")}
    want = arithmetic.layout_counts(traffic["rows"])
    run.records["packed_counts"] = counts
    run.check(all(counts[k] == want[k] * n_dev for k in want),
              f"the step counted {counts}, the layout holds {want} a chip")
    del inputs["check"]
    return {"cfg": cfg, "compiled": compiled, "state": state, "rng": rng,
            "batch": inputs["batch"],
            "step_est": run.records["warm_steps_s"] / max(warm, 1)}


def window(run: harness.Run, live: dict, compiles: harness.CompileCounter) -> None:
    """`train_resident`'s window (run-ahead fences, finite and falling loss,
    no compile, kernels present, memory), then the counts in this cell's
    units: images are what the step counted, not rows."""
    train_resident.window(run, live, compiles)
    run.records["images"] = int(
        run.records["steps"] * run.records["packed_counts"]["images"])
    if run.device.get("platform") == "tpu":
        run.check(run.program["packed_attention_kernels"] > 0,
                  "no flash_packed_* kernel (tpu_custom_call) in the "
                  "compiled step")


def finish(run: harness.Run, live: dict) -> None:
    live.clear()


def lower_described(config_kwargs: dict, traffic: dict, devices):
    """The cell's step lowered for described devices, from abstract shapes
    (benchmark/size_cells.py). Nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    from vitax.programs.builder import Geometry, build_program
    cfg = build_config(config_kwargs, traffic, len(devices), 0)
    geom = Geometry.assemble(cfg, MAX_ITERATION, devices=devices,
                             force_tpu_kernels=True)
    step, state = build_program("train", geom), geom.abstract_state
    sh = NamedSharding(geom.mesh, batch_pspec())
    lay = layout(cfg, traffic["rows"], len(devices))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
             for k, v in lay.items()}
    r, t, s = cfg.batch_size, cfg.pack_tokens, cfg.pack_images
    batch["patches"] = jax.ShapeDtypeStruct(
        (r, t, 3 * cfg.patch_size ** 2), jnp.uint8, sharding=sh)
    batch["label"] = jax.ShapeDtypeStruct((r, s), jnp.int32, sharding=sh)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return (step.lower(state, batch, key),
            f"packed train step, {r} rows of {t} tokens")
