"""Traffic kind `train_hybrid_packed`: the trainer's default step program for
a token decoder whose layers are state-space mixers and attention (the
Granite 4.0-H shape) on a constant, device-resident packed batch of
documents.

Parameters (the traffic mix's file): those of `train_decoder_packed`
(`rows_per_chip`, `row_tokens`, `docs_per_row`, `rows`: the layout, data and
not drawn from `--seed`; `logit_positions`, `run_ahead`, `warm_steps`,
`expect_decreasing`, `control`, `rehearse`), whose batch, layout and
float8 control this kind shares. It is a kind of its own because that one
runs Laguna's reference and demands window kernels.

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

`correct` holds THE COMPILED STEP THE WINDOW TIMES, on its first call, at
the timed widths and sizes and on the measured batch itself, to the plain
reference (benchmark/reference/granite.py: float32, the recurrence token by
token, document by document): its step-0 loss and global gradient norm, and,
element by element as ||got - want|| / ||want||, its gradients of `A_log`
and `dt_bias` over all the mamba layers, of the convolution's kernel and the
in-projection in the first and in the last mamba layer and of `wq` in the
attention layer. The step hands out no gradient, but its first call leaves
one behind: Adam's first moment after one step from zero is (1 - b1) x the
clip's factor x the gradient (`step_gradients`), in float32, written by the
fused optimizer itself. So a precision lost anywhere between the weights
and the optimizer (model, loss, `vitax/train/step.py`, the fused update)
shows in the numbers that refuse the float8 control, and no second backward
program is built. The logits at the seeded positions, which the step hands
out in no form, come from a forward pass of the same model and are compared
in the same way. Then the step's counters against the layout; a
`flash_causal_*` kernel and the fused optimizer in the compiled step; no
compile in the window; losses finite and falling. As in
`train_decoder_packed` the reference's float32 gradients do not fit beside
the train state, so it runs on the seeded parameters BEFORE the optimizer
state is made: the two Adam moments (zeros at step 0) are freed for the
comparison and made again by the optimizer's own `init`.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_granite as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_decoder_packed, train_resident
from benchmark.generators.train_decoder_packed import (  # noqa: F401
    build_config, finish, layout, lower_described, make_inputs,
    round_to_float8)
from benchmark.reference import granite as reference

# The limits below, from readings on the chip (PERF.md section 6, PR 35): the
# largest gap the timed step showed over its seeds, and the gap of the same
# step fed weights rounded to float8_e4m3 (`control: float8_weights`), which
# has to fail. The program computes in bf16 (8 bits of mantissa) with
# float32 accumulation, float32 delta, A, running sums and states, a float32
# softmax and loss. A norm hardly sees that precision, so gradients and
# logits are compared element by element: 0 where they agree, 1 where
# nothing of the reference is left.
#
# Step-0 loss against the float32 reference on the same weights and ids. At
# initialisation the loss is ln(vocabulary rows) plus a term of second order
# in the logits (standard deviation 0.11), and the ids are drawn without
# regard to the weights, so an error e in the logits moves the mean over
# 3,976 targets by about e x 0.11 / sqrt(3,976) in either direction: a draw,
# not a bias. Timed step over 32 seeds: 4e-9 to 9.4e-6 of the loss, root
# mean square 4.2e-6 (the estimate gives 3.7e-6 for e = 0.02). The control's
# seven draws read 5.3e-6, 8.9e-6, 2.4e-5, 4.0e-5, 7.3e-5, 8.6e-5 and 1.1e-4
# (estimate: 4e-5): the loss has NO upper reading that holds on every seed,
# and is not what refuses the control. The limit is 2.7 times the timed
# step's largest reading, six times its root mean square; the accepted
# cells' 2e-3 would leave 220 times.
LOSS_RTOL = 2.5e-5
# The gradient's global norm. Timed step: 6.7e-4 to 7.8e-4 over 32 seeds, a
# bias (bf16's rounding adds its own norm in quadrature) that hardly moves
# with the seed. The control reads 3.8e-4 to 1.14e-3, around the timed
# step's own: a norm does not see in which direction a gradient turned, so
# this number has no upper reading either. Four times the largest reading;
# the accepted cells' limit is 1e-2.
GRAD_NORM_RTOL = 3e-3
# ||got - want|| / ||want|| of single leaves' gradients AS THE TIMED STEP
# COMPUTED THEM (`step_gradients`). in_proj, conv (first and last mamba
# layer) and wq (the attention layer) are sums over tokens of bf16 products
# and hardly move with the seed. Timed step, 17 seeds: 0.0287-0.0331; float8
# control, 4 seeds: 0.312-0.354 (a second program over the same model read
# the same in PR 35's first round: 0.028-0.032 against 0.31-0.35). A_log
# and dt_bias are 64 numbers a layer, sums over tokens, channels and states
# of terms of both signs that all but cancel, so bf16's rounding of x, B
# and C shows in them first and in ONE layer a seed moves them (0.018-0.086
# in 64 readings of single layers, first round). They are therefore
# compared over all nine mamba layers at once, 576 numbers each. Timed
# step: 0.025-0.041; control: 0.323-0.429. Each limit leaves about three
# times of room on both sides.
LEAF_GAP_RTOL = {"in_proj": 0.10, "conv": 0.10, "wq": 0.10,
                 "A_log": 0.12, "dt_bias": 0.12}
# ... and of the logits at the seeded positions, from a forward pass of the
# model (the step hands out no logits; its loss is held above). Program:
# 0.0197-0.0203 over 32 seeds; control: 0.218-0.221.
LOGITS_GAP_RTOL = 0.07
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("ssm_conv", "ssd_chunk", "ssd_state", "ssm_gate_norm",
          "lm_head_loss")
COUNTERS = ("tokens", "padding_tokens", "images", "targets", "causal_pairs",
            "ssd_pairs", "ssd_live_chunks")


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree:
    `A_log` and `dt_bias` of all the mamba layers together; the convolution's
    kernel and the in-projection in the first and in the last mamba layer;
    `wq` of the first attention layer."""
    import jax.numpy as jnp
    from vitax.models.decoder import layer_runs
    runs = [(grads["params"][f"run{i}"]["blocks"], kind)
            for i, ((kind, _, _), _) in enumerate(layer_runs(
                cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps))]
    mamba = [blocks["mixer"] for blocks, kind in runs if kind == "mamba"]
    out = {"mamba.A_log": jnp.concatenate(
               [m["A_log"]["scale"] for m in mamba]),
           "mamba.dt_bias": jnp.concatenate(
               [m["dt_bias"]["bias"] for m in mamba])}
    for name, mixer, j in (("first", mamba[0], 0), ("last", mamba[-1], -1)):
        out[f"{name}.conv"] = mixer["conv"]["kernel"][j]
        out[f"{name}.in_proj"] = mixer["in_proj"]["kernel"][j]
    attention = next(blocks for blocks, kind in runs if kind != "mamba")
    out["attention.wq"] = attention["attn"]["wq"]["kernel"][0]
    return out


def step_gradients(opt_state, grad_norm: float, cfg) -> dict:
    """The watched gradients as the compiled step itself computed them, read
    from what its FIRST call left in the optimizer's state. From zero
    moments Adam's first moment is (1 - b1) x clip x gradient, where clip is
    the factor the step's own global norm gave (vitax/ops/fused_optimizer.py
    `fused_adamw_kernel`: `g = g_ref * s; mu = (1 - b1) * g + b1 * mu_ref`;
    optax's chain does the same). The moments are float32."""
    import jax
    from vitax.ops.fused_optimizer import find_adam_state
    from vitax.train.state import ADAMW_HPARAMS
    clip = cfg.clip_grad_norm
    factor = (1.0 - ADAMW_HPARAMS["b1"]) * (
        clip / grad_norm if clip > 0 and grad_norm >= clip else 1.0)
    moments = jax.device_get(jax.jit(lambda mu: watched_leaves(mu, cfg))(
        find_adam_state(opt_state).mu))
    return {name: m / factor for name, m in moments.items()}


def setup(run: harness.Run) -> dict:
    import jax
    import jax.numpy as jnp
    from vitax.programs.builder import Geometry, build_program
    from vitax.train.step import decoder_inputs

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
    run.program["causal_attention_kernels"] = sum(
        "flash_causal_" in ln for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln)
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
        for (r, s), at in zip(train_decoder_packed._document_slots(
            host["segment_ids"]), ats)
        for p in at])

    # the reference first, beside the parameters alone: document by
    # document, token by token, in float32
    t0 = time.time()
    shape = reference.shape_of(config)
    with jax.default_matmul_precision(reference.PRECISION):
        ref_loss, ref_grads, ref_logits = reference.loss_grads_and_logits(
            state.params, [jnp.asarray(d) for d in docs],
            [jnp.asarray(at) for at in ats], **shape)
        ref_logits = np.concatenate(
            [np.asarray(jax.device_get(x)) for x in ref_logits])
        ref_global = float(jax.jit(lambda g: reference.global_norm(
            reference.leaf_norms(g)))(ref_grads))
        ref_watched = jax.device_get(watched_leaves(ref_grads, cfg))
    del ref_grads
    run.records["reference_s"] = time.time() - t0

    # the logits from a forward pass of the model; then the timed step
    # itself: its loss, its global norm, and the gradients its first call
    # left in the first moment
    t0 = time.time()
    if traffic.get("control") == "float8_weights":
        state = state.replace(params=round_to_float8(state.params))
    got_logits = jax.device_get(jax.jit(
        lambda params, batch, rows, cols: model.apply(
            params, decoder_inputs(batch), True)[rows, cols])(
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
    got_watched = step_gradients(state.opt_state, norm0, cfg)
    run.records["first_step_s"] = time.time() - t0
    leaf_gaps = {k: reference.relative_gap(got_watched[k], v)
                 for k, v in ref_watched.items()}
    run.checks.update({
        "logit_positions": len(rows_cols), "logits_gap": logits_gap,
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
                  f"the timed step's gradient of {name} is off the "
                  f"reference's by {gap} of its norm, more than {limit}")

    t0 = time.time()
    warm = int(traffic["warm_steps"])
    for _ in range(warm):
        state, metrics = compiled(state, batch, rng)
    jax.block_until_ready((state, metrics))
    run.records["warm_steps_s"] = time.time() - t0
    # what the step itself counted on the batch (the same every step), held
    # against the layout the traffic file gives
    counts = {k: float(metrics[k]) for k in COUNTERS}
    want = arithmetic.layout_counts(traffic["rows"], cfg.pack_tokens,
                                    config["mamba_chunk_size"])
    want["images"] = want.pop("documents")
    run.records["packed_counts"] = counts
    run.check(all(counts[k] == want[k] * n_dev for k in want),
              f"the step counted {counts}, the layout holds {want} a chip")
    return {"cfg": cfg, "compiled": compiled, "state": state, "rng": rng,
            "batch": batch,
            "step_est": run.records["warm_steps_s"] / max(warm, 1)}


def window(run: harness.Run, live: dict, compiles: harness.CompileCounter) -> None:
    """`train_resident`'s window (run-ahead fences, finite and falling loss,
    no compile, kernels present, memory), then the counts in this cell's
    units: a sample is a document as the step counted it."""
    train_resident.window(run, live, compiles)
    run.records["images"] = int(
        run.records["steps"] * run.records["packed_counts"]["images"])
    if run.device.get("platform") == "tpu":
        run.check(run.program["causal_attention_kernels"] > 0,
                  "no flash_causal_* kernel (tpu_custom_call) in the "
                  "compiled step")
