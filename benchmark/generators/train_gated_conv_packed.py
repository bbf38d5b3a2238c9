"""Traffic kind `train_gated_conv_packed`: the trainer's default step program
for a token decoder whose layers are gated short convolutions and
grouped-query attention with a norm a head, a dense SwiGLU first and routed
experts ranked by a bias the trainer balances after (the LFM2-MoE shape) on a constant,
device-resident packed batch of documents.

Parameters (the traffic mix's file): those of `train_decoder_packed`
(`rows_per_chip`, `row_tokens`, `docs_per_row`, `rows`: the layout, data and
not drawn from `--seed`; `logit_positions`, `run_ahead`, `warm_steps`,
`expect_decreasing`, `control`, `rehearse`), whose batch, layout and float8
control this kind shares, and `layout`: what the step's counters have to
read. It is a kind of its own because each decoder kind runs its own
reference and watches its own leaves.

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

The router biases start at the 0 they are seeded with and the trainer's own
rule (vitax/train/step.py: balance_router_bias) moves them every step of the
warm-up and of the window, as in any job. ISSUE 48 allowed a pre-pass that
brings them into balance in set-up and said when to leave it out: six seeds
with the bias at 0 spread 0.062% and 0.066% between the quartiles (from
balance 0.029% and 0.019%; my chip runs, PR 48, PERF.md section 6), far under
its 0.3%, so there is none: this model's seeded router sends its fullest
expert 1.14 to 1.33 times the mean, not the 3 to 5 of Laguna's.

`correct` holds THE COMPILED STEP THE WINDOW TIMES, on its first call, at
the timed widths and sizes and on the measured batch itself, to the plain
reference (benchmark/reference/lfm2_moe.py: float32, document by document,
the convolution as shifted adds, every held expert on every token, the same
share): its step-0 loss and global gradient norm, and, element by element as
||got - want|| / ||want||, its gradients of the taps, `in_proj` and
`out_proj` in the first and in the last conv layer, of `wq` and both head
norms of the attention layer, of the router of every sparse layer and of the
first sparse layer's held experts' gate matrices, read from Adam's first
moment after the step's first call as `train_hybrid_packed` reads them
(`step_gradients`). The logits at the seeded positions come from a forward
pass of the same model and are compared in the same way. Then the step's counters against
the traffic file's rows; a `flash_causal_*` kernel and the fused optimizer in
the compiled step; no compile in the window; losses finite and falling. The
reference runs on the seeded parameters BEFORE the optimizer state is made, as in the other decoder kinds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_lfm2 as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_decoder_packed, train_resident
from benchmark.generators.train_decoder_packed import (  # noqa: F401
    build_config, finish, layout, lower_described, make_inputs,
    round_to_float8)
from benchmark.generators.train_hybrid_packed import (  # noqa: F401
    window)
from benchmark.reference import lfm2_moe as reference

# The limits below, from readings on the chip (PERF.md section 6, PR 48; my
# chip runs): the largest gap the timed step showed over thirty-one runs of
# thirteen seeds, and the gap of the same step fed weights rounded to
# float8_e4m3 (`control: float8_weights`), which has to fail. The program
# computes in bf16 (8 bits of mantissa) with float32 accumulation, float32
# gates and taps, a float32 router, softmax and loss. A norm hardly sees that
# precision, so gradients and logits are compared element by element: 0 where
# they agree, 1 where nothing of the reference is left. In float32 the program
# agrees with the reference to 2e-3 on every leaf
# (tests/test_lfm2_decoder.py).
#
# Step-0 loss against the float32 reference on the same weights and ids: the
# difference of two means over 16,230 targets whose errors have both signs.
# Timed step 6.7e-8 to 3.5e-5, control 9.8e-6 to 1.2e-4: a number WITHOUT an
# upper reading that holds on every seed. It keeps the limit of the harness's
# accepted train cells (`train_decoder_packed`), 57 times the largest sound
# reading: it catches a loss that lost a term, not a precision.
LOSS_RTOL = 2e-3
# The gradient's global norm, likewise WITHOUT an upper reading: timed step
# 1.48e-3 to 1.57e-3, control 1.4e-3 to 1.8e-3 (a norm does not see in which
# direction a gradient turned). The accepted cells' limit, 6.5 times the
# largest sound reading; a gradient that lost a layer's term moves the norm by
# a tenth and more.
GRAD_NORM_RTOL = 1e-2
# ||got - want|| / ||want|| of single leaves' gradients AS THE TIMED STEP
# COMPUTED THEM (`step_gradients`), by the leaf's name after its layer. Timed
# step over its seeds | float8 control; each limit is near the geometric
# middle of its leaf's largest sound reading and smallest control reading, 1.6
# to 2.7 times of room on both sides:
#   conv, in_proj, out_proj (first and last conv layer)
#                          0.034-0.041 | 0.266-0.342   limit 0.10
#   attention wq           0.042-0.046 | 0.398-0.399   limit 0.13
#   attention q_norm, k_norm (64 numbers each)
#                          0.030-0.057 | 0.357-0.415   limit 0.14
#   held experts' gate matrices, first sparse layer
#                          0.144-0.164 | 0.602-0.612   limit 0.31
#   router, each of the four sparse layers (a token whose fifth score lies
#     within bf16's rounding of its fourth goes to another expert than in the
#     float32 reference, and the derivative of the normalised weights is a
#     difference of nearly equal terms; the gap grows with depth, 0.19-0.23 in
#     the first sparse layer, 0.24-0.28 in the last)
#                          0.186-0.279 | 0.749-0.824   limit 0.45
LEAF_GAP_RTOL = {"conv": 0.10, "in_proj": 0.10, "out_proj": 0.10, "wq": 0.13,
                 "q_norm": 0.14, "k_norm": 0.14, "router": 0.45,
                 "experts_gate": 0.31}
# ... and of the logits at the seeded positions, from a forward pass of the
# model (the step hands out no logits; its loss is held above). Program:
# 0.017-0.035; control: 0.204-0.210.
LOGITS_GAP_RTOL = 0.085
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("gconv_in", "gconv_out", "gconv", "qk_norm", "moe_route",
          "moe_dispatch", "expert_ffn", "moe_combine", "rope1d",
          "lm_head_loss")
COUNTERS = ("tokens", "padding_tokens", "images", "targets", "causal_pairs",
            "expert_slots_here", "route_load_max_over_mean")
CONV = arithmetic.CONV


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree: the
    taps, `in_proj` and `out_proj` of the first and of the last conv layer;
    `wq`, `q_norm` and `k_norm` of the first attention layer; the router of
    every sparse layer (`sparse<layer>.router`) and the held experts' gate
    matrices of the first."""
    from vitax.models.decoder import layer_runs
    out, at, convs = {}, 0, []
    for i, ((kind, _, mlp), length) in enumerate(layer_runs(
            cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps)):
        blocks = grads["params"][f"run{i}"]["blocks"]
        if kind == CONV:
            convs += [(blocks["mixer"], j) for j in range(length)]
        elif "attention.wq" not in out:
            for name, leaf in (("wq", "kernel"), ("q_norm", "scale"),
                               ("k_norm", "scale")):
                out[f"attention.{name}"] = blocks["attn"][name][leaf][0]
        if mlp == "sparse":
            for j in range(length):
                out[f"sparse{at + j}.router"] = \
                    blocks["moe"]["router"]["kernel"][j]
            if not any(k.endswith("experts_gate") for k in out):
                out[f"sparse{at}.experts_gate"] = \
                    blocks["moe"]["experts_gate"]["kernel"][0]
        at += length
    for name, (mixer, j) in (("first", convs[0]), ("last", convs[-1])):
        for leaf in ("conv", "in_proj", "out_proj"):
            out[f"{name}.{leaf}"] = mixer[leaf]["kernel"][j]
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
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    run.program["causal_attention_kernels"] = sum(
        "flash_causal_" in ln for ln in calls)
    run.program["params"] = arithmetic.param_count(config)
    run.program["op_scopes"] = scopes.index(text, SCOPES)
    del text, calls

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
    # every step), held against the rows the traffic file gives
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
