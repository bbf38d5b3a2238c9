"""Traffic kind `train_latent_packed`: the trainer's default step program for
a token decoder whose layers are delta-rule mixers (Kimi Delta Attention)
and latent attention (MLA) with routed and shared experts (the
Ling-3.0-flash shape) on a constant, device-resident packed batch of
documents.

Parameters (the traffic mix's file): those of `train_decoder_packed`
(`rows_per_chip`, `row_tokens`, `docs_per_row`, `rows`: the layout, data and
not drawn from `--seed`; `logit_positions`, `run_ahead`, `warm_steps`,
`expect_decreasing`, `control`, `rehearse`), whose batch, layout and
float8 control this kind shares. It is a kind of its own because that one
runs Laguna's reference and demands window kernels, and
`train_hybrid_packed` Granite's.

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

`correct` holds THE COMPILED STEP THE WINDOW TIMES, on its first call, at
the timed widths and sizes and on the measured batch itself, to the plain
reference (benchmark/reference/ling.py: float32, the delta rule token by
token, document by document, every head's key written out, the router with
explicit groups, the same shares): its step-0 loss and global gradient
norm, and, element by element as ||got - want|| / ||want||, its gradients
of `A_log`, `dt_bias`, `wf`, `wb` and the convolution over all the kda
layers, of `wkva`, `wkvb` and `wq` of the latent layer, and of the router
and the held experts' gate matrices of the first sparse layer, read from
Adam's first moment after the step's first call as `train_hybrid_packed`
reads them (`step_gradients`). The logits at the seeded positions come from
a forward pass of the same model and are compared in the same way. Then the
step's counters against the traffic file's rows, the delta rule's on the
yardstick's fixed grid (benchmark/flops_ling.py: KDA_GRID, 64 tokens; the
traffic file states them as `layout`); a `flash_latent_*` kernel and the
fused optimizer in the compiled step; no compile in the window; losses
finite and falling. The reference runs on the seeded parameters BEFORE the optimizer
state is made, as in the two other decoder kinds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_ling as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_decoder_packed, train_resident
from benchmark.generators.train_decoder_packed import (  # noqa: F401
    build_config, finish, layout, lower_described, make_inputs,
    round_to_float8)
from benchmark.reference import ling as reference

# The limits below, from readings on the chip (PERF.md section 6, PR 41; my
# chip runs): the largest gap the timed step showed over its seeds, and the
# gap of the same step fed weights rounded to float8_e4m3 (`control:
# float8_weights`), which has to fail. The program computes in bf16 (8 bits
# of mantissa) with float32 accumulation, a float32 log-decay, running sums,
# states and triangular inverse, a float32 router, softmax and loss. A norm
# hardly sees that precision, so gradients and logits are compared element by
# element: 0 where they agree, 1 where nothing of the reference is left.
#
# Why the program's own readings are near a tenth and more, and not Granite's
# 0.03 (tools/ling_gap_witness.py reads these on the cell's own weights and
# batch; its readings, and what they leave open, are in PERF.md section 6):
# - Six of the seven layers are delta-rule mixers whose output is normed a
#   head and is, at seeded weights, some twenty times the embedding it is
#   added to, so each layer's bf16 rounding reaches the next undiluted: the
#   logits, forward only, read 0.035-0.044, the gradients of the mixers'
#   leaves two to three times that. In float32 the program agrees with the
#   reference to 2e-3 on every leaf (tests/test_latent_decoder.py).
# - The router's and the held experts' gradients are sums over the ~400
#   (token, held expert) slots a layer routed HERE, one 64th of the layer's.
#   A token whose eighth and ninth ranked score lie within the rounding of
#   the router's input changes expert (a sixth of the tokens in the first
#   sparse layer); where that expert is a held one, one side's sum has a
#   whole term the other's lacks (25 slots of 411). Those slots carry half
#   of the squared gap: with their tokens routed nowhere on both sides the
#   router reads 0.22 where it read 0.31 and the gate matrices 0.20 where
#   they read 0.28. What is left is twice the mixers' reading.
#
# Step-0 loss against the float32 reference on the same weights and ids. Its
# gap is the difference of two means over 4,055 targets whose errors have
# both signs, so it swings from seed to seed on both sides: timed step 3.4e-6
# to 1.4e-4, control 2.8e-4 and 9.8e-4. The limit is 2.6 times the largest
# sound reading and under the larger control's: it refuses that control and
# not the smaller one, which the leaves below refuse by a wide margin.
LOSS_RTOL = 3.7e-4
# The gradient's global norm is a number WITHOUT an upper reading: timed step
# 1.6e-3 to 2.8e-3, control 9.5e-4 and 5.7e-3 (a norm hardly sees float8).
# It keeps the limit of the harness's accepted train cells
# (`train_decoder_packed`), 3.6 times the largest sound reading: it catches
# a gradient that lost a term, not a precision.
GRAD_NORM_RTOL = 1e-2
# ||got - want|| / ||want|| of single leaves' gradients AS THE TIMED STEP
# COMPUTED THEM (`step_gradients`); the kda leaves over all six kda layers at
# once. Timed step over its seeds | float8 control, 2 seeds:
#   kda conv, wb, wf, dt_bias   0.088-0.117 | 0.78-0.96    limit 0.30
#   kda A_log (96 numbers, sums of terms of both signs that all but
#     cancel; one seed in sixteen read 0.213)  0.066-0.213 | 0.93-1.49
#                                                           limit 0.45
#   latent wkva, wkvb           0.049-0.068 | 0.468-0.500  limit 0.18
#   latent wq                   0.066-0.103 | 0.678-0.680  limit 0.27
#   held experts' gate matrices 0.199-0.281 | 0.99-1.00    limit 0.50
# Each of these is near the geometric middle of its two readings: 1.6 to 2.6
# times of room on both sides.
#   router                      0.211-0.391 | 1.02-1.10    limit 0.70
# The router's is a number WITHOUT an upper reading: its control reads 2.6
# times its largest sound reading, under three, because a sound run already
# lacks the terms of the slots that changed side (above). Its limit lies
# between the sound readings and 1, what a gradient with nothing of the
# reference left reads, with the more room on the sound side (1.8 times)
# since the reading swings twofold with the seed's number of changed slots.
LEAF_GAP_RTOL = {"A_log": 0.45, "dt_bias": 0.30, "wf": 0.30, "wb": 0.30,
                 "conv": 0.30, "wkva": 0.18, "wkvb": 0.18, "wq": 0.27,
                 "router": 0.70, "experts_gate": 0.50}
# ... and of the logits at the seeded positions, from a forward pass of the
# model (the step hands out no logits; its loss is held above). Program:
# 0.035-0.044 over its seeds; control: 0.400-0.415.
LOGITS_GAP_RTOL = 0.13
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("kda_conv", "kda_gate", "kda_chunk", "kda_state", "kda_out_norm",
          "mla_latent", "moe_route", "moe_dispatch", "expert_ffn",
          "shared_expert", "moe_combine", "rope1d", "head_gate",
          "lm_head_loss")
COUNTERS = ("tokens", "padding_tokens", "images", "targets", "causal_pairs",
            "kda_pairs", "kda_live_chunks", "expert_slots_here",
            "tokens_choosing_held_group")
KDA_LEAVES = (("A_log", "scale"), ("dt_bias", "bias"), ("wf", "kernel"),
              ("wb", "kernel"), ("conv", "kernel"))


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree:
    `A_log`, `dt_bias`, `wf`, `wb` and the convolution's kernel of all the
    kda layers together; `wkva`, `wkvb` and `wq` of the first latent layer;
    the router and the held experts' gate matrices of the first sparse
    layer."""
    import jax.numpy as jnp
    from vitax.models.decoder import layer_runs
    runs = [(grads["params"][f"run{i}"]["blocks"], kind, mlp)
            for i, ((kind, _, mlp), _) in enumerate(layer_runs(
                cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps))]
    kda = [blocks["mixer"] for blocks, kind, _ in runs if kind == "kda"]
    out = {f"kda.{name}": jnp.concatenate(
        [m[name][leaf].reshape(-1) for m in kda]) for name, leaf in KDA_LEAVES}
    latent = next(blocks for blocks, kind, _ in runs
                  if kind == "latent_attention")["attn"]
    for name in ("wkva", "wkvb", "wq"):
        out[f"latent.{name}"] = latent[name]["kernel"][0]
    moe = next(blocks for blocks, _, mlp in runs if mlp == "sparse")["moe"]
    out["sparse.router"] = moe["router"]["kernel"][0]
    out["sparse.experts_gate"] = moe["experts_gate"]["kernel"][0]
    return out


def step_gradients(opt_state, grad_norm: float, cfg) -> dict:
    """The watched gradients as the compiled step itself computed them, read
    from what its FIRST call left in the optimizer's state: from zero
    moments Adam's first moment is (1 - b1) x clip x gradient, where clip is
    the factor the step's own global norm gave (`train_hybrid_packed`'s
    reading, over this kind's leaves). The moments are float32."""
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
    text = compiled.as_text()
    run.program["latent_attention_kernels"] = sum(
        "flash_latent_" in ln for ln in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln)
    run.program["params"] = arithmetic.param_count(config)
    run.program["op_scopes"] = scopes.index(text, SCOPES)
    del text

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
    held = (cfg.expert_first, cfg.experts_held)
    with jax.default_matmul_precision(reference.PRECISION):
        ref_loss, ref_grads, ref_logits = reference.loss_grads_and_logits(
            state.params, [jnp.asarray(d) for d in docs],
            [jnp.asarray(at) for at in ats], experts_held=held, **shape)
        ref_logits = np.concatenate(
            [np.asarray(jax.device_get(x)) for x in ref_logits])
        ref_global = float(jax.jit(lambda g: reference.global_norm(
            reference.leaf_norms(g)))(ref_grads))
        ref_watched = jax.device_get(jax.jit(
            lambda g: watched_leaves(g, cfg))(ref_grads))
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
    # what the step itself counted on the batch (the layout's part the same
    # every step), held against the rows the traffic file gives, the delta
    # rule's on the yardstick's own grid of 64 tokens
    counts = {k: float(metrics[k]) for k in COUNTERS}
    want = arithmetic.layout_counts(traffic["rows"], cfg.pack_tokens)
    run.check(want == traffic["layout"],
              f"the traffic file states the layout {traffic['layout']}, its "
              f"rows hold {want}")
    want["images"] = want.pop("documents")
    run.records["packed_counts"] = counts
    run.records["expert_load"] = np.asarray(
        jax.device_get(metrics["expert_load"])).tolist()
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
        run.check(run.program["latent_attention_kernels"] > 0,
                  "no flash_latent_* kernel (tpu_custom_call) in the "
                  "compiled step")
