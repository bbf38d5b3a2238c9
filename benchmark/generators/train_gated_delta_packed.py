"""Traffic kind `train_gated_delta_packed`: the trainer's default step program
for a token decoder whose layers are Gated-DeltaNet mixers and full
attention in Olmo's norm-after block (the Olmo-Hybrid shape) on a constant,
device-resident packed batch of documents.

Parameters (the traffic mix's file): those of `train_decoder_packed`
(`rows_per_chip`, `row_tokens`, `docs_per_row`, `rows`: the layout, data and
not drawn from `--seed`; `logit_positions`, `run_ahead`, `warm_steps`,
`expect_decreasing`, `control`, `rehearse`), whose batch, layout and
float8 control this kind shares, and `layout`: what the step's counters have
to read. It is a kind of its own because `train_hybrid_packed` runs Granite's
reference and `train_latent_packed` Ling's, and each watches its own leaves.

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

`correct` holds THE COMPILED STEP THE WINDOW TIMES, on its first call, at
the timed widths and sizes and on the measured batch itself, to the plain
reference (benchmark/reference/olmo_hybrid.py: float32, the delta rule token
by token, document by document, the same shares): its step-0 loss and
global gradient norm, and, element by element as ||got - want|| / ||want||,
its gradients of `A_log` and `dt_bias` over all the linear_attention layers
at once, of `wa`, `wb`, the taps, `wq` and `wz` in the first and in the last
of them, of `wq` and `q_norm` in the attention layer and of the first
layer's norm after the mixer, read from Adam's first moment after the step's
first call as `train_hybrid_packed` reads them (`step_gradients`). The logits
at the seeded positions come from a forward pass of the same model and are
compared in the same way. Then the step's counters against the traffic
file's rows, the delta rule's on the yardstick's fixed grid
(benchmark/flops_ling.py: KDA_GRID, 64 tokens; the traffic file states them
as `layout`); a `flash_causal_*` kernel and the fused optimizer in the
compiled step; no compile in the window; losses finite and falling. The
reference runs on the seeded parameters BEFORE the optimizer state is made,
as in the other decoder kinds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_olmo as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_decoder_packed, train_resident
from benchmark.generators.train_decoder_packed import (  # noqa: F401
    build_config, finish, layout, lower_described, make_inputs,
    round_to_float8)
from benchmark.generators.train_hybrid_packed import (  # noqa: F401
    window)
from benchmark.reference import olmo_hybrid as reference

# The limits below, from readings on the chip (PERF.md section 6, PR 44; my
# chip runs): the largest gap the timed step showed over fourteen seeds, and
# the gap of the same step fed weights rounded to float8_e4m3 (`control:
# float8_weights`, three seeds), which has to fail. The program computes in
# bf16 (8 bits of mantissa) with float32 accumulation, a float32 log-decay,
# running sums, states and triangular inverse, a float32 softmax and loss. A
# norm hardly sees that precision, so gradients and logits are compared
# element by element: 0 where they agree, 1 where nothing of the reference is
# left.
#
# Why the program's own readings are near a tenth and not Granite's 0.03:
# every half of every layer ADDS a normed output of unit scale to a residual
# stream that began as an embedding of scale 0.02, so from the first layer on
# the stream is what the halves added and each layer's bf16 rounding reaches
# the next undiluted (Ling's cell reads the same size for the same reason).
# In float32 the program agrees with the reference to 2e-3 on every leaf
# (tests/test_olmo_decoder.py).
#
# Step-0 loss against the float32 reference on the same weights and ids: the
# difference of two means over 4,025 targets whose errors have both signs.
# Timed step 1.8e-5 to 1.13e-4, control 1.1e-4, 8.2e-4 and 1.5e-3: a number
# WITHOUT an upper reading that holds on every seed. It keeps the limit of
# the harness's accepted train cells (`train_decoder_packed`), 18 times the
# largest sound reading: it catches a loss that lost a term, not a precision.
LOSS_RTOL = 2e-3
# The gradient's global norm, likewise WITHOUT an upper reading: timed step
# 2.7e-4 to 4.2e-3 (median 1.4e-3; bf16's rounding adds its own norm in
# quadrature, by a seed's draw), control 8.9e-4 to 1.9e-3, inside the sound
# readings (a norm does not see in which direction a gradient turned). Twice
# the accepted cells' limit, 4.8 times the largest sound reading, because one
# seed in fourteen already stands at 0.42 of theirs; a gradient that lost a
# layer's term moves the norm by a tenth and more.
GRAD_NORM_RTOL = 2e-2
# ||got - want|| / ||want|| of single leaves' gradients AS THE TIMED STEP
# COMPUTED THEM (`step_gradients`). Timed step over fourteen seeds | float8
# control, three seeds; each limit is the geometric middle of its leaf's
# largest sound reading and smallest control reading, 1.9 to 3.4 times of
# room on both sides:
#   linear.A_log, linear.dt_bias (45 numbers over the three layers, sums of
#     terms of both signs that all but cancel, so sound runs AND controls
#     swing with the seed)  0.046-0.144 | 0.510-1.003   limit 0.27
#   first.conv       0.082-0.130 | 0.759-0.786   limit 0.31
#   first.post_norm  0.083-0.126 | 0.763-0.767   limit 0.31
#   first.wa         0.081-0.123 | 0.755-0.784   limit 0.30
#   first.wb         0.083-0.126 | 0.769-0.777   limit 0.31
#   first.wq         0.083-0.131 | 0.765-0.779   limit 0.32
#   first.wz         0.083-0.128 | 0.764-0.774   limit 0.31
#   last.conv        0.098-0.182 | 0.825-0.843   limit 0.39
#   last.wa          0.090-0.102 | 0.954-0.960   limit 0.31
#   last.wb          0.072-0.078 | 0.839-0.846   limit 0.26
#   last.wq (q meets the state through the L2 norm and the delta rule's
#     solve, a difference of nearly equal terms: the leaf that swings most)
#                    0.128-0.255 | 0.965-0.978   limit 0.50
#   last.wz          0.057-0.062 | 0.707-0.710   limit 0.21
#   attention.wq     0.068-0.073 | 0.813-0.826   limit 0.24
#   attention.q_norm 0.066-0.074 | 0.796-0.818   limit 0.24
LEAF_GAP_RTOL = {
    "linear.A_log": 0.27, "linear.dt_bias": 0.27, "first.conv": 0.31,
    "first.post_norm": 0.31, "first.wa": 0.30, "first.wb": 0.31,
    "first.wq": 0.32, "first.wz": 0.31, "last.conv": 0.39, "last.wa": 0.31,
    "last.wb": 0.26, "last.wq": 0.50, "last.wz": 0.21, "attention.wq": 0.24,
    "attention.q_norm": 0.24}
# ... and of the logits at the seeded positions, from a forward pass of the
# model (the step hands out no logits; its loss is held above). Program:
# 0.0281-0.0322 over fourteen seeds; control: 0.431-0.437.
LOGITS_GAP_RTOL = 0.115
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("kda_conv", "kda_gate", "kda_chunk", "kda_state", "kda_out_norm",
          "post_norm", "qk_norm", "lm_head_loss")
COUNTERS = ("tokens", "padding_tokens", "images", "targets", "causal_pairs",
            "kda_pairs", "kda_live_chunks")
LINEAR = arithmetic.LINEAR
LAYER_KERNELS = ("wa", "wb", "conv", "wq", "wz")


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree:
    `A_log` and `dt_bias` of all the linear_attention layers together; `wa`,
    `wb`, the convolution's taps, `wq` and `wz` in the first and in the last
    of them; `wq` and `q_norm` of the first attention layer; the first
    layer's norm after the mixer."""
    import jax.numpy as jnp
    from vitax.models.decoder import layer_runs
    runs = [(grads["params"][f"run{i}"]["blocks"], kind)
            for i, ((kind, _, _), _) in enumerate(layer_runs(
                cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps))]
    linear = [blocks["mixer"] for blocks, kind in runs if kind == LINEAR]
    out = {"linear.A_log": jnp.concatenate(
               [m["A_log"]["scale"].reshape(-1) for m in linear]),
           "linear.dt_bias": jnp.concatenate(
               [m["dt_bias"]["bias"].reshape(-1) for m in linear])}
    for name, mixer, j in (("first", linear[0], 0), ("last", linear[-1], -1)):
        for leaf in LAYER_KERNELS:
            out[f"{name}.{leaf}"] = mixer[leaf]["kernel"][j]
    attention = next(blocks for blocks, kind in runs if kind != LINEAR)
    out["attention.wq"] = attention["attn"]["wq"]["kernel"][0]
    out["attention.q_norm"] = attention["attn"]["q_norm"]["scale"][0]
    out["first.post_norm"] = runs[0][0]["norm1"]["scale"][0]
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
        limit = LEAF_GAP_RTOL[name]
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
    # against the rows the traffic file gives, the delta rule's on the
    # yardstick's own grid of 64 tokens
    counts = {k: float(metrics[k]) for k in COUNTERS}
    want = arithmetic.layout_counts(traffic["rows"], cfg.pack_tokens)
    run.check(want == traffic["layout"],
              f"the traffic file states the layout {traffic['layout']}, its "
              f"rows hold {want}")
    want["images"] = want.pop("documents")
    run.records["packed_counts"] = counts
    run.check(all(counts[k] == want[k] * n_dev for k in want),
              f"the step counted {counts}, the layout holds {want} a chip")
    return {"cfg": cfg, "compiled": compiled, "state": state, "rng": rng,
            "batch": batch,
            "step_est": run.records["warm_steps_s"] / max(warm, 1)}
