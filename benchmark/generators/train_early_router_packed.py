"""Traffic kind `train_early_router_packed`: the trainer's default step program
for a token decoder whose router reads the stream BEFORE the attention and
takes a softmax over the chosen logits, whose experts are gated by a ReLU and
whose full layers rotate nothing among sliding layers that do (the
SmallThinker shape) on a constant, device-resident packed batch of documents.

Parameters (the traffic mix's file): those of `train_decoder_packed`
(`rows_per_chip`, `row_tokens`, `docs_per_row`, `rows`: the layout, data and
not drawn from `--seed`; `logit_positions`, `run_ahead`, `warm_steps`,
`expect_decreasing`, `control`, `rehearse`), whose batch, layout, float8
control and window (a `flash_causal_*` and a `flash_window_*` kernel in the
compiled step on a TPU) this kind shares, and `layout`: what the step's
counters have to read. It is a kind of its own because each decoder kind
runs its own reference and watches its own leaves.

The program is what `python -m vitax.train --model_family decoder ...` builds
for a `Config` that names only the model's shape (the configuration file's
nested `decoder` block and the row shape above): `Geometry.assemble` ->
`build_program("train", ...)`, lowered once. A sample (`images` in the
records, for `train_images_per_s_chip`) is a DOCUMENT as the step itself
counted it.

THE SHARE THE CHIP HOLDS IS MADE A FAIR ONE IN SET-UP (`hold_a_fair_share`).
A chip of the deployment holds 8 of 64 experts, and a router trained in
balance sends them an eighth of the slots. A SEEDED router is not in balance:
this model's router reads the first norm of a stream that the seeded layers
before it have written, the tokens of a long document lie close together
there, and from the second layer on nearly all of them choose the same few
experts; whether those are among experts 0-7 is a lottery of the seed. Six
seeds held 33,545 to 54,310 slots (the fair share: 48,360) and their
`train_images_per_s_chip` spread 2.5% between the quartiles, five times what
the cell may (my chip runs, PR 51; PERF.md section 6). So, layer by layer in
depth order, set-up counts with the program's own forward pass how many real
tokens chose each of ALL the routed experts, finds the 8 whose loads sum
nearest to an eighth of the slots, and RELABELS the router's outputs (a
permutation of the router kernel's columns) so that those are the experts the
chip holds; the next layer is counted on the stream the relabelled layer
writes. Experts are exchangeable at a seeded start (their kernels are drawn
alike), so the relabelled state is as much "weights made from the seed" as
the state before it: the same seed gives the same state, the reference runs
on it too, and nothing of the program changes. What it does not mend: how the
slots are spread over the held eight (`expert_load_max_over_mean` stays what
the seed makes it) and how the router drifts while the window trains it.

`correct` holds THE COMPILED STEP THE WINDOW TIMES, on its first call, at
the timed widths and sizes and on the measured batch itself, to the plain
reference (benchmark/reference/smallthinker.py: float32, the packed row whole
with its document mask written out, every held expert on every token, the
same share): its step-0 loss and global gradient norm, and, element by
element as ||got - want|| / ||want||, its gradients of `norm1` (where the
early router's cotangent lands beside the attention's), `wq` and `wk` in the
full layer and in the first sliding layer, of the first layer's router and
of its held experts' gate matrices, read from Adam's first
moment after the step's first call as `train_hybrid_packed` reads them
(`step_gradients`). The logits at the seeded positions come from a forward
pass of the same model and are compared in the same way. Then the step's
counters against the traffic file's rows; both attention kernels and the
fused optimizer in the compiled step; no compile in the window; losses
finite and falling. The reference runs on the seeded parameters BEFORE the
optimizer state is made, as in the other decoder kinds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops_smallthinker as arithmetic   # this kind's FLOPs
from benchmark import harness, scopes
from benchmark.generators import train_decoder_packed, train_resident
from benchmark.generators.train_decoder_packed import (  # noqa: F401
    build_config, finish, layout, lower_described, make_inputs,
    round_to_float8, window)
from benchmark.reference import smallthinker as reference

# The limits below, from readings on the chip (PERF.md section 6, PR 51; my
# chip runs): the largest gap the timed step showed over its seeds, and the
# gap of the same step fed weights rounded to float8_e4m3 (`control:
# float8_weights`), which has to fail. The program computes in bf16 (8 bits
# of mantissa) with float32 accumulation, a float32 router, softmax and loss.
# A norm hardly sees that precision, so gradients and logits are compared
# element by element: 0 where they agree, 1 where nothing of the reference is
# left. In float32 the program agrees with the reference to 2e-3 on every
# leaf (tests/test_smallthinker_decoder.py).
#
# Step-0 loss against the float32 reference on the same weights and ids, and
# the gradient's global norm: neither has an upper reading that holds on
# every seed (a mean over 16,116 targets whose errors have both signs; a
# norm does not see in which direction a gradient turned). Both keep the
# limits of the harness's accepted train cells, which catch a loss or a
# gradient that lost a term, not a precision.
LOSS_RTOL = 2e-3
GRAD_NORM_RTOL = 1e-2
# ||got - want|| / ||want|| of single leaves' gradients AS THE TIMED STEP
# COMPUTED THEM (`step_gradients`), by the leaf's name after its layer kind.
# Timed step over its seeds | float8 control; each limit lies between its
# leaf's largest sound reading and its smallest control reading:
#   norm1 (full and first sliding layer: 2,560 numbers each, where the early
#     router's cotangent lands beside the attention's)
#                          0.010-0.015 | 0.123-0.135   limit 0.045
#   wq, wk (the same two layers)
#                          0.030-0.055 | 0.194-0.239   limit 0.10
#   held experts' gate matrices, first layer
#                          0.048-0.054 | 0.235-0.251   limit 0.115
#   router of the FIRST layer, whose seeded router is in balance (a token
#     whose seventh logit lies within bf16's rounding of its sixth goes to
#     another expert than in the float32 reference)
#                          0.060-0.090 | 0.327-0.334   limit 0.17
#   The routers of layers 1-3 are NOT judged: from the second layer on a
#     seeded router sends nearly every token to the same few experts, one
#     flipped choice there moves a large share of the leaf, and over ten sound
#     seeds they read 0.045-0.269 where the control's read 0.285-0.977: no
#     limit lies between the two. The control is refused by nine limits
#     without them.
LEAF_GAP_RTOL = {"norm1": 0.045, "wq": 0.10, "wk": 0.10, "router": 0.17,
                 "experts_gate": 0.115}
# ... and of the logits at the seeded positions, from a forward pass of the
# model (the step hands out no logits; its loss is held above). Program:
# 0.0075-0.0100 over ten seeds; control, three seeds: 0.102-0.107.
LOGITS_GAP_RTOL = 0.03
MAX_ITERATION = train_resident.MAX_ITERATION
# the program's named scopes a per-layer metric may read (benchmark/scopes.py)
SCOPES = ("moe_route", "moe_dispatch", "expert_ffn", "moe_combine", "rope1d",
          "lm_head_loss")
COUNTERS = ("tokens", "padding_tokens", "images", "targets", "causal_pairs",
            "window_pairs", "causal_computed_pairs", "window_computed_pairs",
            "expert_slots_here", "expert_rows_computed", "expert_hidden_live")


def watched_leaves(grads, cfg) -> dict:
    """The gradients `correct` compares, from a parameter-shaped tree: in the
    first full and in the first sliding layer `norm1` (the first norm's
    scale: the router's cotangent reaches it beside the attention's), `wq`
    and `wk`; the router and the held experts' gate matrices of the first
    layer (the deeper layers' routers: see `LEAF_GAP_RTOL`)."""
    from vitax.models.decoder import layer_runs
    out, at = {}, 0
    for i, ((kind, _, _), length) in enumerate(layer_runs(
            cfg.layer_kinds, cfg.layer_heads, cfg.layer_mlps)):
        blocks = grads["params"][f"run{i}"]["blocks"]
        short = kind.split("_")[0]              # full | sliding
        if f"{short}.norm1" not in out:
            out[f"{short}.norm1"] = blocks["norm1"]["scale"][0]
            for name in ("wq", "wk"):
                out[f"{short}.{name}"] = blocks["attn"][name]["kernel"][0]
        if at == 0:
            out["layer0.router"] = blocks["moe"]["router"]["kernel"][0]
            out["layer0.experts_gate"] = \
                blocks["moe"]["experts_gate"]["kernel"][0]
        at += length
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


def logit_positions(segment_ids: np.ndarray, total: int, seed: int):
    """Where the logits are compared: equally many positions a document,
    drawn from the seed. [(row, columns in that row, ascending by
    document)]."""
    draw = np.random.default_rng(seed)
    slots = train_decoder_packed._document_slots(segment_ids)
    per_doc = max(total // len(slots), 1)
    by_row = {}
    for r, s in slots:
        at = np.flatnonzero(segment_ids[r] == s)
        by_row.setdefault(r, []).append(
            at[0] + np.sort(draw.integers(0, len(at), per_doc)))
    return [(r, np.concatenate(cols)) for r, cols in sorted(by_row.items())]


def nearest_share(load: np.ndarray, held: int) -> list:
    """The `held` experts whose loads sum nearest to `held` / len(load) of all
    the slots: the largest loads that still fit first, then swaps of one held
    expert for one that is not while a swap brings the sum nearer."""
    load = np.asarray(load, np.int64)
    target = load.sum() * held / len(load)
    order = [int(e) for e in np.argsort(-load, kind="stable")]
    chosen, total = [], 0
    for e in order:
        if len(chosen) < held and total + load[e] <= target:
            chosen.append(e)
            total += int(load[e])
    for e in reversed(order):           # short of experts: the smallest loads
        if len(chosen) < held and e not in chosen:
            chosen.append(e)
            total += int(load[e])
    nearer = True
    while nearer:
        nearer = False
        for a in chosen:
            for b in order:
                new = total - int(load[a]) + int(load[b])
                if b not in chosen and abs(new - target) < abs(total - target):
                    chosen[chosen.index(a)], total, nearer = b, new, True
                    break
            if nearer:
                break
    return sorted(chosen)


def hold_a_fair_share(model, cfg, params, batch):
    """The seeded parameters with every layer's router relabelled so that the
    experts the chip holds are the ones whose loads sum nearest to its share
    of the slots (the module docstring says why), and [(layer's loads over all
    routed experts before its relabelling, the experts chosen)]."""
    import jax
    import jax.numpy as jnp
    from vitax.train.step import decoder_inputs
    k, first, held = cfg.experts_per_token, cfg.expert_first, cfg.experts_held
    runs = [(f"run{i}", n) for i, (_, n) in enumerate(model.runs())]

    def router_of(params, run):
        return params["params"][run]["blocks"]["moe"]["router"]["kernel"]

    @jax.jit
    def loads(params, batch):
        """(layers, experts routed): the real tokens that chose each expert,
        from what each layer's first norm hands its router in the program's
        own forward pass."""
        _, cols = model.apply(
            params, decoder_inputs(batch), True, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "norm1")
        real = (batch["segment_ids"] > 0)[None, :, :, None, None]
        out = []
        for run, _ in runs:
            a = cols["intermediates"][run]["blocks"]["norm1"]["__call__"][0]
            z = jnp.einsum("lrtd,lde->lrte", a.astype(jnp.float32),
                           router_of(params, run))
            _, chosen = jax.lax.top_k(z, k)
            out.append(jnp.sum(jax.nn.one_hot(
                chosen, cfg.experts_routed, dtype=jnp.int32) * real,
                axis=(1, 2, 3)))
        return jnp.concatenate(out)

    relabel = jax.jit(lambda kernel, j, perm: kernel.at[j].set(
        jnp.take(kernel[j], perm, axis=1)))
    said, layer = [], 0
    for run, length in runs:
        for j in range(length):
            load = np.asarray(jax.device_get(loads(params, batch)))[layer]
            chosen = nearest_share(load, held)
            others = [e for e in range(cfg.experts_routed)
                      if e not in chosen]
            perm = others[:first] + chosen + others[first:]
            tree = {**params["params"]}
            blocks = {**tree[run]["blocks"]}
            blocks["moe"] = {**blocks["moe"], "router": {"kernel": relabel(
                router_of(params, run), j, jnp.asarray(perm))}}
            tree[run] = {**tree[run], "blocks": blocks}
            params = {**params, "params": tree}
            said.append((load.tolist(), chosen))
            layer += 1
    return params, said


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
    params, said = hold_a_fair_share(model, cfg, state.params, batch)
    state = state.replace(params=params)
    share = sum(sum(load) for load, _ in said) * cfg.experts_held \
        / cfg.experts_routed
    run.records["fair_share"] = {
        "slots_a_fair_share": share,
        "held_before": [sum(load[cfg.expert_first:][:cfg.experts_held])
                        for load, _ in said],
        "held_after": [sum(load[e] for e in chosen) for load, chosen in said],
        "experts_chosen": [chosen for _, chosen in said]}
    run.records["fair_share_s"] = time.time() - t0

    t0 = time.time()
    compiled = step.lower(geom.abstract_state, batch, rng).compile()
    run.records["compile_or_cache_s"] = time.time() - t0
    run.program.update(harness.program_facts(compiled))
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    for kind in ("causal", "window"):
        run.program[f"{kind}_attention_kernels"] = sum(
            f"flash_{kind}_" in ln for ln in calls)
    run.program["params"] = arithmetic.param_count(config)
    run.program["op_scopes"] = scopes.index(text, SCOPES)
    del text, calls

    host = jax.device_get(batch)
    picked = logit_positions(np.asarray(host["segment_ids"]),
                             int(traffic["logit_positions"]), run.seed)
    rows_cols = np.array([(r, c) for r, cols in picked for c in cols])

    # the reference first, beside the parameters alone: a row at a time,
    # whole, in float32
    t0 = time.time()
    shape = reference.shape_of(config)
    held = (cfg.expert_first, cfg.experts_held)
    with jax.default_matmul_precision(reference.PRECISION):
        ref_loss, ref_grads, ref_logits = reference.loss_grads_and_logits(
            state.params,
            reference.rows_of(host["tokens"], host["segment_ids"]),
            [jnp.asarray(cols) for _, cols in picked], experts_held=held,
            **shape)
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
    want = arithmetic.layout_counts(traffic["rows"], cfg.pack_tokens,
                                    cfg.window_tokens)
    run.check(want == traffic["layout"],
              f"the traffic file states the layout {traffic['layout']}, its "
              f"rows hold {want}")
    want["images"] = want.pop("documents")
    run.records["packed_counts"] = counts
    run.records["expert_load"] = np.asarray(
        jax.device_get(metrics["expert_load"])).tolist()
    # the step sums its counts in float32 (vitax/train/step.py:
    # decoder_counts): exact below 2 ** 24, within two float32 steps of the
    # layout's past it (76,081,260 causal pairs read 76,081,256)
    def counted(k):
        n = want[k] * n_dev
        room = 2 * float(np.spacing(np.float32(n))) if n >= 2 ** 24 else 0.0
        return abs(counts[k] - n) <= room
    run.check(all(counted(k) for k in want),
              f"the step counted {counts}, the layout holds {want} a chip")
    run.check(0 < counts["expert_hidden_live"]
              <= counts["expert_slots_here"] * cfg.expert_dim,
              f"the ReLU gates left {counts['expert_hidden_live']} hidden "
              f"units live of {counts['expert_slots_here']} slots x "
              f"{cfg.expert_dim}")
    return {"cfg": cfg, "compiled": compiled, "state": state, "rng": rng,
            "batch": batch,
            "step_est": run.records["warm_steps_s"] / max(warm, 1)}
