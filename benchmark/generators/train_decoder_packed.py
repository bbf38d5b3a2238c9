"""Traffic kind `train_decoder_packed`: the trainer's default step program for
the token decoder family on a constant, device-resident packed batch of
documents.

Parameters (the traffic mix's file):
  rows_per_chip      packed rows per chip and step
  row_tokens         tokens a row (the model's `pack_tokens`)
  docs_per_row       document slots a row (`pack_images`; the static shape)
  rows               the batch's layout: for each row of ONE chip's share,
                     the lengths of its documents in packing order. Data, not
                     drawn from `--seed`: the seed makes weights and token ids
                     (uniform over the vocabulary rows held) only, so tokens,
                     targets and attention pairs a step are the same in
                     every run of the cell
  logit_positions    positions (equally many a document, drawn from the
                     seed) at which logits are compared with the reference
  run_ahead, warm_steps, expect_decreasing
                     as in `train_resident`
  control            tests and the builder's control run only:
                     "float8_weights" feeds the PROGRAM the weights rounded
                     to float8_e4m3 (3 bits of mantissa, the nearest format
                     below the bf16 the configuration states) while the
                     reference keeps the seeded ones: `correct` must come out
                     false
  rehearse           overrides of the keys above for `--rehearse` (run.py,
                     beside the family's own `rehearse` block)

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

`correct` compares, at the timed widths and sizes and on the measured batch
itself: the timed step's step-0 loss and global gradient norm; the program's
gradients of the router, of the held experts' gate matrices and of the head
gate in a sliding and in a full sparse layer (a second program over the same model and
loss: the timed step hands out no gradients), element by element as
||got - want|| / ||want||; and its logits at the seeded positions in the
same way, each against the plain reference (benchmark/reference/laguna.py,
float32, document by document);
the step's counters against the layout; the kernels in the compiled step; no
compile in the window; losses finite and falling. The reference's float32
gradients do not fit beside 8.3 GB of train state, so it runs on the seeded
parameters BEFORE the optimizer state is made: `Geometry.assemble` makes the
whole state as the trainer does, the two Adam moments (zeros at step 0) are
freed for the comparison and made again by the optimizer's own `init`.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_laguna as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_resident
from benchmark.reference import laguna as reference

# The limits below, each from two readings on the chip (PERF.md section 6, PR
# 32): the largest gap the program showed over its seeds, and the gap of the
# program fed weights rounded to float8_e4m3, which has to fail. The program
# computes in bf16 (8 bits of mantissa) with float32 accumulation, a float32
# router, softmax and loss. A norm hardly sees that precision (a gradient
# norm of the float8 control is off by 0.3% like the program's own, its loss
# by 9e-5), so the gradients and logits are compared element by element: 0
# where they agree, 1 where nothing of the reference is left.
#
# Step-0 loss against the float32 reference on the same weights and ids (at
# initialisation ln(vocabulary rows) plus a small term) and the gradient's
# global norm: the limits of the harness's accepted train cells.
LOSS_RTOL = 2e-3
GRAD_NORM_RTOL = 1e-2
# ||got - want|| / ||want|| of single matrices' gradients, a sliding and a
# full layer each. The head gate's is a plain sum over tokens. Program:
# 0.027-0.034; float8 control: 0.216-0.228. The router's and the held
# experts' gate matrices' hang on which tokens chose which expert: a token whose
# ninth-best router score lies within bf16's rounding of its eighth goes to
# another expert than in the float32 reference, and the derivative of the
# normalised weights is a difference of nearly equal terms. The same program
# in float32 agrees to 2e-3 on every leaf (tests/test_decoder.py). Router:
# program 0.177-0.261, control 0.66-0.77; experts: 0.144-0.189, 0.56-0.61.
LEAF_GAP_RTOL = {"router": 0.45, "experts_gate": 0.40, "head_gate": 0.10}
# ... and of the logits at the seeded positions. Program: 0.016-0.028;
# control: 0.160-0.164. (Single logits move by up to 9% of the largest one
# where a token changed expert; the control's by 18%: the norm over the
# positions tells the two apart, the largest gap hardly.)
LOGITS_GAP_RTOL = 0.07
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("moe_route", "moe_dispatch", "expert_ffn", "shared_expert",
          "moe_combine", "rope1d", "head_gate", "lm_head_loss")


def build_config(config_kwargs: dict, traffic: dict, n_devices: int,
                 seed: int):
    from vitax.config import Config
    return Config(**config_kwargs,
                  pack_tokens=int(traffic["row_tokens"]),
                  pack_images=int(traffic["docs_per_row"]),
                  batch_size=int(traffic["rows_per_chip"]) * n_devices,
                  seed=seed).validate()


def layout(cfg, rows, n_devices: int) -> dict:
    """The trainer's packer on one chip's rows, repeated for every chip."""
    from vitax.data.packing import document_layout
    rows = [list(row) for row in rows] * n_devices
    assert len(rows) == cfg.batch_size, (len(rows), cfg.batch_size)
    return document_layout(rows, cfg.pack_tokens, cfg.pack_images)


def make_inputs(cfg, mesh, seed: int, lay: dict) -> dict:
    """The packed batch: token ids drawn on the device from the seed, uniform
    over the vocabulary rows held, 0 at padding (as the packer leaves them)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec

    sharding = NamedSharding(mesh, batch_pspec())
    lay = {k: jax.device_put(v, sharding) for k, v in lay.items()}

    def draw(key, lay):
        ids = jax.random.randint(key, lay["segment_ids"].shape, 0,
                                 cfg.vocab_rows, jnp.int32)
        return dict(lay, tokens=ids * (lay["segment_ids"] > 0))

    return jax.jit(draw, out_shardings=sharding)(
        jax.random.key(seed + 17), lay)


def round_to_float8(params):
    """The control: every float32 weight through float8_e4m3fn and back."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree: the
    router, the held experts' gate matrices and the head gate of the first
    sliding and of the first full layer that is sparse."""
    from vitax.models.decoder import layer_runs
    out, tree = {}, grads["params"]
    for i, ((kind, _, mlp), _) in enumerate(layer_runs(
            cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps)):
        short = kind.split("_")[0]
        if mlp != "sparse" or f"{short}.router" in out:
            continue
        blocks = tree[f"run{i}"]["blocks"]
        out[f"{short}.router"] = blocks["moe"]["router"]["kernel"][0]
        out[f"{short}.experts_gate"] = \
            blocks["moe"]["experts_gate"]["kernel"][0]
        out[f"{short}.head_gate"] = blocks["attn"]["head_gate"]["kernel"][0]
    return out


def setup(run: harness.Run) -> dict:
    import jax
    import jax.numpy as jnp
    from vitax.programs.builder import Geometry, build_program
    from vitax.train.step import decoder_inputs, decoder_loss

    n_dev = jax.device_count()
    config, traffic = run.config, run.traffic
    cfg = build_config(run.config_kwargs, traffic, n_dev, run.seed)
    t0 = time.time()
    geom = Geometry.assemble(cfg, MAX_ITERATION, materialize=True)
    state, geom.state = geom.state, None    # the step donates it
    mesh, model = geom.mesh, geom.model
    step = build_program("train", geom)
    batch = make_inputs(cfg, mesh, run.seed,
                        layout(cfg, traffic["rows"], n_dev))
    rng = jax.random.key(cfg.seed + 1)
    jax.block_until_ready((state, batch))
    # room for the reference: the moments come back before the first step
    for leaf in jax.tree.leaves(state.opt_state):
        leaf.delete()
    run.records["state_s"] = time.time() - t0

    t0 = time.time()
    compiled = step.lower(geom.abstract_state, batch, rng).compile()
    run.records["compile_or_cache_s"] = time.time() - t0
    run.program.update(harness.program_facts(compiled))
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    run.program["causal_attention_kernels"] = sum(
        "flash_causal_" in ln for ln in calls)
    run.program["window_attention_kernels"] = sum(
        "flash_window_" in ln for ln in calls)
    run.program["params"] = arithmetic.param_count(config)
    run.program["op_scopes"] = scopes.index(compiled.as_text(), SCOPES)

    # where the logits are compared: equally many positions a document,
    # drawn from the seed
    host = jax.device_get(batch)
    docs = reference.unpack(host["tokens"], host["segment_ids"])
    draw = np.random.default_rng(run.seed)
    per_doc = max(int(traffic["logit_positions"]) // len(docs), 1)
    ats = [np.sort(draw.integers(0, len(d), per_doc)) for d in docs]
    rows_cols = np.array([
        (r, int(np.argmax(host["segment_ids"][r] == s)) + int(p))
        for (r, s), at in zip(_document_slots(host["segment_ids"]), ats)
        for p in at])

    # the reference first, beside the parameters alone: document by
    # document, in float32
    t0 = time.time()
    shape = reference.shape_of(config)
    held = (cfg.expert_first, cfg.experts_held)
    with jax.default_matmul_precision(reference.PRECISION):
        ref_loss, ref_grads, ref_logits = reference.loss_grads_and_logits(
            state.params, [jnp.asarray(d) for d in docs],
            [jnp.asarray(at) for at in ats], experts_held=held, **shape)
        ref_logits = np.concatenate(
            [np.asarray(jax.device_get(x)) for x in ref_logits])
        ref_global = float(jax.jit(lambda g: reference.global_norm(
            reference.leaf_norms(g)))(ref_grads))
        ref_watched = jax.device_get(watched_leaves(ref_grads, cfg))
    del ref_grads
    run.records["reference_s"] = time.time() - t0

    # the program: its own model and loss once more for what the timed step
    # hands out no value of (single gradients, logits), then the timed step
    # itself for the loss and the global norm
    t0 = time.time()
    if traffic.get("control") == "float8_weights":
        state = state.replace(params=round_to_float8(state.params))

    def program_check(params, batch, rows, cols):
        def loss_and_logits(p):
            logits = model.apply(p, decoder_inputs(batch), True)
            return decoder_loss(logits, batch), logits[rows, cols]
        (_, picked), grads = jax.value_and_grad(
            loss_and_logits, has_aux=True)(params)
        return watched_leaves(grads, cfg), picked

    got_watched, got_logits = jax.device_get(jax.jit(program_check)(
        state.params, batch, jnp.asarray(rows_cols[:, 0]),
        jnp.asarray(rows_cols[:, 1])))
    logits_gap = reference.relative_gap(got_logits, ref_logits)
    from vitax.parallel.sharding import shardings_of
    state = state.replace(opt_state=jax.jit(
        geom.tx.init, out_shardings=shardings_of(
            mesh, geom.state_specs.opt_state))(state.params))
    state, metrics = compiled(state, batch, rng)
    loss0 = float(metrics["loss"])
    norm0 = float(metrics["grad_norm"])
    run.records["first_step_s"] = time.time() - t0
    leaf_gaps = {k: reference.relative_gap(got_watched[k], v)
                 for k, v in ref_watched.items()}
    run.checks.update({
        "logit_positions": len(rows_cols), "logits_gap": logits_gap,
        "logits_max_abs_gap": float(
            np.max(np.abs(got_logits - ref_logits))
            / np.max(np.abs(ref_logits))),
        "logits_gap_rtol": LOGITS_GAP_RTOL, "loss_step0": loss0,
        "loss_reference": ref_loss,
        "loss_rel_gap": abs(loss0 - ref_loss) / abs(ref_loss),
        "loss_rtol": LOSS_RTOL, "grad_norm_step0": norm0,
        "grad_norm_reference": ref_global,
        "grad_norm_rel_gap": abs(norm0 - ref_global) / abs(ref_global),
        "grad_norm_rtol": GRAD_NORM_RTOL, "leaf_gaps": leaf_gaps,
        "leaf_gap_rtol": LEAF_GAP_RTOL})
    run.check(np.isfinite(got_logits).all()
              and logits_gap <= LOGITS_GAP_RTOL,
              f"logits at {len(rows_cols)} positions are off the "
              f"reference's by {logits_gap} of their norm, more than "
              f"{LOGITS_GAP_RTOL}")
    run.check(run.checks["loss_rel_gap"] <= LOSS_RTOL,
              f"step-0 loss {loss0} is off the reference {ref_loss} by more "
              f"than {LOSS_RTOL} of it")
    run.check(run.checks["grad_norm_rel_gap"] <= GRAD_NORM_RTOL,
              f"step-0 gradient norm {norm0} is off the reference "
              f"{ref_global} by more than {GRAD_NORM_RTOL} of it")
    for name, gap in sorted(leaf_gaps.items()):
        limit = LEAF_GAP_RTOL[name.split(".")[1]]
        run.check(gap <= limit,
                  f"the gradient of {name} is off the reference's by {gap} "
                  f"of its norm, more than {limit}")

    t0 = time.time()
    warm = int(traffic["warm_steps"])
    for _ in range(warm):
        state, metrics = compiled(state, batch, rng)
    jax.block_until_ready((state, metrics))
    run.records["warm_steps_s"] = time.time() - t0
    # what the step itself counted on the batch (the layout's part the same
    # every step), held against the layout the traffic file gives
    counts = {k: float(metrics[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "window_pairs", "expert_slots_here")}
    want = arithmetic.layout_counts(traffic["rows"], cfg.window_tokens)
    want["images"] = want.pop("documents")
    run.records["packed_counts"] = counts
    run.records["expert_load"] = np.asarray(
        jax.device_get(metrics["expert_load"])).tolist()
    run.check(all(counts[k] == want[k] * n_dev for k in want),
              f"the step counted {counts}, the layout holds {want} a chip")
    return {"cfg": cfg, "compiled": compiled, "state": state, "rng": rng,
            "batch": batch,
            "step_est": run.records["warm_steps_s"] / max(warm, 1)}


def _document_slots(segment_ids: np.ndarray):
    """(row, segment id) of every document, in `reference.unpack`'s order."""
    return [(r, s) for r, row in enumerate(np.asarray(segment_ids))
            for s in range(1, int(row.max()) + 1)]


def window(run: harness.Run, live: dict, compiles: harness.CompileCounter) -> None:
    """`train_resident`'s window (run-ahead fences, finite and falling loss,
    no compile, kernels present, memory), then the counts in this cell's
    units: a sample is a document as the step counted it."""
    train_resident.window(run, live, compiles)
    run.records["images"] = int(
        run.records["steps"] * run.records["packed_counts"]["images"])
    if run.device.get("platform") == "tpu":
        run.check(run.program["causal_attention_kernels"] > 0
                  and run.program["window_attention_kernels"] > 0,
                  "no flash_causal_* or no flash_window_* kernel "
                  "(tpu_custom_call) in the compiled step")


def finish(run: harness.Run, live: dict) -> None:
    live.clear()


def lower_described(config_kwargs: dict, traffic: dict, devices):
    """The cell's step lowered for described devices, from abstract shapes
    (benchmark/size_cells.py). Nothing runs."""
    import jax
    from vitax.programs.builder import (Geometry, abstract_batch,
                                        build_program)
    cfg = build_config(config_kwargs, traffic, len(devices), 0)
    geom = Geometry.assemble(cfg, MAX_ITERATION, devices=devices,
                             force_tpu_kernels=True)
    step, state = build_program("train", geom), geom.abstract_state
    key = jax.eval_shape(lambda: jax.random.key(0))
    return (step.lower(state, abstract_batch(cfg, geom.mesh), key),
            f"decoder train step, {cfg.batch_size} rows of "
            f"{cfg.pack_tokens} tokens")
