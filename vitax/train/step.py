"""Jitted train/eval steps.

The reference's hot loop (run_vit_training.py:259-291, SURVEY.md section 3.2) —
forward, CE loss, backward, FSDP collectives, grad clip, AdamW update, LR step —
is ONE compiled XLA program here. GSPMD inserts the per-layer all-gathers and
grad reduce-scatters from the parameter shardings; the loss mean over the
globally-sharded batch compiles to the cross-replica reduction the reference
performs by hand (xm.mesh_reduce, run_vit_training.py:205-206).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from vitax.config import Config
from vitax.ops.flash_blocked import computed_pairs
from vitax.ops.fused_optimizer import fused_clip_adamw, fused_optimizer_active
from vitax.parallel.mesh import BATCH_AXES, Mesh, batch_pspec
from vitax.parallel.sharding import (
    gather_over_fsdp, gather_overlap_active, make_comm_precision, shardings_of)
from vitax.train.state import ADAMW_HPARAMS, TrainState

PyTree = Any


def _needs_dropout(cfg: Config) -> bool:
    return (cfg.pos_dropout > 0) or (cfg.att_dropout > 0) or (cfg.mlp_dropout > 0)


def _make_logits_anchor(mesh: Mesh):
    """Anchor (B, C) logits batch-sharded: under 3-axis-batch meshes (dp x
    fsdp x ep) the CE softmax backward and the eval argmax iota otherwise
    land on mixed layouts the partitioner reaches only by involuntary full
    rematerialization (same family as the activation anchors in
    vitax/models/vit.py). Identity on single-device meshes."""
    if mesh.size == 1:
        return lambda logits: logits
    sharding = NamedSharding(mesh, P(batch_pspec()[0], None))
    return lambda logits: jax.lax.with_sharding_constraint(logits, sharding)


def _select_by_name(cols, name: str):
    """Leaves of the 'intermediates' collection whose path contains `name` —
    sown values are selected BY NAME so any future sow (e.g. a debug metric)
    cannot silently join the training objective."""
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cols)
            if any(getattr(k, "key", None) == name for k in path)]


def aux_from_frac_prob(fracs, probs, cfg: Config):
    """Switch load-balance loss from the sown per-block (E,) ingredients:
    mean over blocks of E * sum_e(frac_e * prob_e). Works on stacked
    (L, ..., E) leaves (the scan path) and per-block lists (unrolled /
    pipeline paths) alike — the leading axes all reduce into the sum, and
    the division by num_blocks restores the per-block mean."""
    assert fracs and len(fracs) == len(probs), (len(fracs), len(probs))
    total = sum(jnp.sum(f * p) for f, p in zip(fracs, probs))
    return cfg.moe_experts * total / cfg.num_blocks


def _forward_fn(cfg: Config, model, mesh: Mesh, state_specs=None):
    """Unified forward: (params, images, det=True, rng=None, with_aux=False)
    -> logits, or (logits, moe_aux) when with_aux.

    model.apply, or the GPipe pipeline over the "pp" mesh axis when
    --pp_size > 1 (vitax/parallel/pipeline.py — same param tree, different
    block application; dropout keys and the MoE aux ingredients are threaded
    through the pipeline body). The block-param specs (P("pp", ...) +
    optional "fsdp" dims) come from the state spec tree so the pipeline's
    just-in-time ZeRO-3 gathers match the actual layout."""
    if getattr(cfg, "pp_size", 1) > 1 and mesh.shape.get("pp", 1) > 1:
        from vitax.parallel.pipeline import make_pp_forward
        block_specs = None
        if state_specs is not None:
            block_specs = state_specs.params["params"]["blocks"]
        return make_pp_forward(cfg, model, mesh, block_specs=block_specs)
    if gather_overlap_active(cfg, mesh):
        # double-buffered ZeRO-3 gather schedule: the scan carry prefetches
        # the next group's gathered params so the collective overlaps the
        # current group's compute (subsumes the windowed path — groups are
        # --remat_window blocks when the window is active, else one block)
        from vitax.models.vit import make_overlap_forward
        assert state_specs is not None, (
            "gather_overlap needs the state spec tree for the stacked "
            "block-param layout")
        return make_overlap_forward(
            cfg, model, mesh, state_specs.params["params"]["blocks"])
    if getattr(cfg, "remat_window", 0) > 1:
        # group-remat functional scan (the wgrad dus-stacking experiment;
        # same param tree, different checkpoint placement)
        from vitax.models.vit import make_windowed_forward
        return make_windowed_forward(cfg, model)

    def forward(params, images, det=True, rng=None, with_aux=False):
        rngs = {"dropout": rng} if (rng is not None and not det) else None
        if not with_aux:
            return model.apply(params, images, det, rngs=rngs)
        logits, cols = model.apply(params, images, det, rngs=rngs,
                                   mutable=["intermediates"])
        fracs = _select_by_name(cols, "moe_frac_tokens")
        probs = _select_by_name(cols, "moe_mean_prob")
        if with_aux == "raw":
            # uncombined per-block ingredients, for callers that average
            # them across grad-accum microbatches BEFORE the product
            return logits, (tuple(fracs), tuple(probs))
        return logits, aux_from_frac_prob(fracs, probs, cfg)

    return forward


def prepare_images(images: jax.Array) -> jax.Array:
    """Device-side ToTensor+Normalize for uint8 batches (the host pipeline's
    reference transforms, run_vit_training.py:44-45/:53-54, moved inside the
    compiled step so batches cross host->device as uint8 — 4x less transfer).
    Float inputs (fake data, --host_normalize, bench tensors) pass through."""
    if images.dtype != jnp.uint8:
        return images
    from vitax.data.transforms import IMAGENET_MEAN, IMAGENET_STD
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32)
    std = jnp.asarray(IMAGENET_STD, jnp.float32)
    return (images.astype(jnp.float32) / 255.0 - mean) / std


def prepare_patches(patches: jax.Array) -> jax.Array:
    """`prepare_images` for pre-cut patches (R, T, p*p*3), flattened as
    (row, column, channel): uint8 -> normalised float32 per channel."""
    if patches.dtype != jnp.uint8:
        return patches
    pixels = patches.reshape(*patches.shape[:-1], -1, 3)
    return prepare_images(pixels).reshape(patches.shape)


def packed_inputs(batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The packed model's input (vitax/models/vit.py) from a packed batch
    (vitax/data/packing.py): its patches normalised, labels left out."""
    return {"patches": prepare_patches(batch["patches"]),
            "segment_ids": batch["segment_ids"],
            "positions": batch["positions"], "grid_hw": batch["grid_hw"]}


def packed_loss(logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
    """Mean cross-entropy over the IMAGES of a packed batch (rows hold
    different numbers of them), from per-image logits (R, S, classes)."""
    ce = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                         batch["label"])
    mask = batch["label_mask"].astype(jnp.float32)
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def decoder_inputs(batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The decoder's input (vitax/models/decoder.py) from a packed batch of
    documents (vitax/data/packing.py: document_layout)."""
    return {k: batch[k] for k in ("tokens", "segment_ids", "positions")}


def next_token_targets(batch: Dict[str, jax.Array]):
    """(labels (R, T), mask (R, T) float32): position t's target is token
    t + 1 where that belongs to the same document; a document's last token
    and padding have none."""
    seg, tokens = batch["segment_ids"], batch["tokens"]
    following = jnp.pad(seg[:, 1:], ((0, 0), (0, 1)))
    mask = (seg > 0) & (following == seg)
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    return jnp.where(mask, labels, 0), mask.astype(jnp.float32)


def decoder_loss(logits: jax.Array, batch: Dict[str, jax.Array]) -> jax.Array:
    """Mean next-token cross-entropy over the TARGETS of a packed batch
    (within each document), from logits (R, T, vocabulary rows)."""
    with jax.named_scope("lm_head_loss"):
        labels, mask = next_token_targets(batch)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# The step of the router bias's balance rule (auxiliary-loss-free balancing,
# DeepSeek-V3, arXiv:2412.19437 section 2.1.2: its bias update speed). A
# constant of the trainer; no configuration names one.
BALANCE_RATE = 1e-3


def balance_router_bias(bias: jax.Array, route_load: jax.Array,
                        rate: float = BALANCE_RATE) -> jax.Array:
    """The trainer's rule for a sparse layer's `router_bias`, once a step and
    outside autodiff: bias_e += rate * sign(mean(load) - load_e), `route_load`
    the real tokens of the step that chose each of ALL the routed experts
    (vitax/models/experts.py sows it; trailing axis: experts). An expert
    chosen less than the mean is ranked higher from the next step on."""
    load = route_load.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load).astype(bias.dtype)


def route_loads(cols) -> Dict[str, jax.Array]:
    """{path of a sparse layer's module: its sown `route_load`} from a
    model's `intermediates`: a scanned run's is stacked (layers, experts)."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cols):
        keys = [getattr(k, "key", None) for k in path]
        if "route_load" in keys:
            out["/".join(keys[1:keys.index("route_load")])] = leaf
    return out


def balance_router_biases(params: PyTree, loads: Dict[str, jax.Array]
                          ) -> PyTree:
    """`balance_router_bias` on every `router_bias` leaf of `params`, each
    from the load its own layer sowed."""
    def moved(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if "router_bias" not in keys:
            return leaf
        return balance_router_bias(
            leaf, loads["/".join(keys[1:keys.index("router_bias")])])
    return jax.tree_util.tree_map_with_path(moved, params)


def route_load_max_over_mean(loads: Dict[str, jax.Array]) -> jax.Array:
    """Of `route_loads`: the fullest routed expert over the mean, the worst
    sparse layer's: what the balance rule brings towards 1."""
    load = jnp.concatenate([x.reshape(-1, x.shape[-1])
                            for x in loads.values()]).astype(jnp.float32)
    return jnp.max(jnp.max(load, axis=-1)
                   / jnp.maximum(jnp.mean(load, axis=-1), 1.0))


def decoder_counts(cfg: Config, batch: Dict[str, jax.Array]
                   ) -> Dict[str, jax.Array]:
    """What a decoder step's batch held, counted on the device from the
    segment ids: `tokens` valid, `padding_tokens`, `images` (documents: the
    step's samples), `targets`, and the attention's useful work as (query,
    key) pairs a layer: `causal_pairs` = sum n (n + 1) / 2 over documents,
    `window_pairs` = the same with at most `window_tokens` keys a query;
    and beside each what the kernels compute for it, `causal_computed_pairs`
    / `window_computed_pairs` (vitax/ops/flash_blocked.py: computed_pairs).
    A model with mamba layers (vitax/models/ssm.py) also counts its scan's
    work on the grid of `ssm_chunk` tokens: `ssd_pairs`, the pairs of a
    query and a key not after it in one chunk and one document, and
    `ssd_live_chunks`; a model with kda or linear_attention layers (the delta
    rule of vitax/models/kda.py) the same two on a grid of 64 tokens fixed
    for counting (`count_chunk`): `kda_pairs` and `kda_live_chunks`."""
    seg = batch["segment_ids"]
    n = jnp.sum(seg[..., None] == jnp.arange(1, cfg.pack_images + 1),
                axis=1, dtype=jnp.int32).astype(jnp.float32)      # (R, S)
    w = jnp.minimum(n, float(max(cfg.window_tokens, 1)))
    valid = jnp.sum(seg > 0, dtype=jnp.int32)
    documents = jnp.sum(n > 0, dtype=jnp.int32)
    ssd = {}

    def on_grid(chunk: int):    # (pairs, live chunks) of a scan in chunks
        chunks = seg.reshape(seg.shape[0], -1, chunk)
        m = jnp.sum(chunks[..., None] == jnp.arange(1, cfg.pack_images + 1),
                    axis=2, dtype=jnp.int32).astype(jnp.float32)  # (R, C, S)
        return (jnp.sum(m * (m + 1) / 2),
                jnp.sum(jnp.any(chunks > 0, axis=-1), dtype=jnp.int32))

    if "mamba" in cfg.layer_kinds:
        ssd["ssd_pairs"], ssd["ssd_live_chunks"] = on_grid(cfg.ssm_chunk)
    if {"kda", "linear_attention"} & set(cfg.layer_kinds):
        from vitax.models.kda import count_chunk
        ssd["kda_pairs"], ssd["kda_live_chunks"] = on_grid(
            count_chunk(cfg.pack_tokens))
    return dict(
        ssd,
        tokens=valid, padding_tokens=seg.size - valid, images=documents,
        targets=valid - documents,
        causal_pairs=jnp.sum(n * (n + 1) / 2),
        window_pairs=jnp.sum(w * (w + 1) / 2 + (n - w) * w),
        causal_computed_pairs=computed_pairs(seg, causal=True),
        window_computed_pairs=computed_pairs(seg, causal=True,
                                             window=cfg.window_tokens))


def _microbatch_split(batch: PyTree, k_steps: int, mesh: Mesh) -> PyTree:
    """Reshape every (B, ...) leaf to (K, B/K, ...) with a STRIDED sample
    assignment: reshape to (B/K, K, ...) then swap the leading axes, so
    microbatch k holds samples {k, k + K, k + 2K, ...}. Under the batch
    sharding, element (j, k) = sample j*K + k stays inside the owning
    device's contiguous [d*B/D, (d+1)*B/D) range — the split costs no
    cross-device data movement. CE and the MoE router/aux ingredients are
    per-sample, so WHICH samples share a microbatch cannot change the
    summed gradient."""
    def split(x):
        xs = x.reshape(x.shape[0] // k_steps, k_steps, *x.shape[1:])
        xs = xs.swapaxes(0, 1)
        if mesh.size > 1:
            spec = P(None, batch_pspec()[0], *(None,) * (x.ndim - 1))
            xs = jax.lax.with_sharding_constraint(
                xs, NamedSharding(mesh, spec))
        return xs
    return jax.tree.map(split, batch)


def _make_update_fn(cfg: Config, tx, mesh: Mesh, state_specs, schedule):
    """The optimizer phase: update(grads, opt_state, params) ->
    (new_params, new_opt_state, grad_norm), the train step's own.

    ONE global-norm reduction per step feeds both the clip and the grad_norm
    metric (the old step re-reduced the tree optax's clip_by_global_norm had
    already walked). The clip applies optax's exact formula off that shared
    norm, so the value chain is bit-identical to the chained transform.

    With the fused optimizer active (vitax/ops/fused_optimizer.py), clip +
    AdamW + weight decay + param step run as one Pallas pass per leaf group,
    in place, shard-local under the FSDP specs."""
    fused = fused_optimizer_active(cfg)
    if fused and schedule is None:
        raise ValueError(
            "fused optimizer is active but no lr schedule was provided — "
            "pass build_optimizer's second return value as schedule=")

    def update(grads, opt_state, params):
        grad_norm = optax.global_norm(grads)
        if fused:
            new_params, new_opt_state = fused_clip_adamw(
                grads, opt_state, params,
                grad_norm=grad_norm,
                schedule=schedule,
                clip_norm=cfg.clip_grad_norm,
                weight_decay=cfg.weight_decay,
                mesh=mesh if mesh.size > 1 else None,
                param_specs=state_specs.params,
                **ADAMW_HPARAMS)
            return new_params, new_opt_state, grad_norm
        if cfg.clip_grad_norm > 0:
            # optax.clip_by_global_norm's update_fn, verbatim, off the
            # shared reduction
            trigger = jnp.squeeze(grad_norm < cfg.clip_grad_norm)
            grads = jax.tree.map(
                lambda t: jax.lax.select(
                    trigger, t,
                    (t / grad_norm.astype(t.dtype)) * cfg.clip_grad_norm),
                grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state, grad_norm

    return update


def make_train_step(
    cfg: Config,
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    state_specs: PyTree,
    donate: bool = True,
    schedule=None,
) -> Callable[[TrainState, Dict[str, jax.Array], jax.Array], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted train step: (state, batch, rng) -> (state, metrics).

    - `donate` on state: params/opt-state buffers are reused in place.
      `donate=False` exists for the program-invariant verifier only
      (vitax/analysis/rules.py donation-honored rule compiles it as the
      deliberately-broken negative arm); production callers always donate.
    - `schedule` is build_optimizer's second return value (the pure lr
      schedule). Required when the fused optimizer is active — the fused
      path evaluates it directly instead of optax's scale_by_schedule; the
      optax path ignores it.
    - ZeRO-2 mode (`--no_reshard_after_forward`): params are constrained to a
      fully-gathered (over "fsdp") layout at the top of the step, so the
      all-gather happens once and the gathered weights stay live through
      backward; grads and optimizer state remain sharded.
    - `--grad_accum_steps K > 1`: a lax.scan over K microbatches of B/K
      accumulates fp32 grads inside this same compiled program — one clip +
      AdamW update (and one loss/grad_norm metric) per loader batch, peak
      activations ~ one microbatch. The ZeRO-2 gather above happens ONCE
      (scan-invariant) and is reused by all K microbatches. K == 1 traces
      the exact pre-accumulation program (no scan wrapper, no extra rng
      fold) — the compiled step is unchanged.
    - Comm precision (`--param_gather_dtype` / `--grad_reduce_dtype`,
      vitax/parallel/sharding.py cast_to_compute): when active, the f32
      master tree is downcast to bf16 while still sharded, so every FSDP
      param collective moves bf16 bytes. The cast sits INSIDE autodiff for
      the value_and_grad paths (its convert-vjp upcasts cotangents to f32
      and pins the grad-reduction dtype); the ZeRO-2 step-top gather casts
      outside autodiff and upcasts grads explicitly via `finalize_grads`.
      With the policy off (or
      --param_gather_dtype float32) the traced program is bit-for-bit the
      pre-policy one.
    """
    state_shardings = shardings_of(mesh, state_specs)
    batch_sharding = NamedSharding(mesh, batch_pspec())
    rng_sharding = NamedSharding(mesh, P())
    dropout = _needs_dropout(cfg)
    forward = _forward_fn(cfg, model, mesh, state_specs)
    comm = make_comm_precision(cfg, mesh, state_specs.params)
    update_fn = _make_update_fn(cfg, tx, mesh, state_specs, schedule)

    moe = cfg.moe_experts > 0
    anchor_logits = _make_logits_anchor(mesh)

    def decoder_loss_fn(params, batch, rng):
        """(loss, (per-layer per-expert load (sparse layers, held experts),
        the sums over the sparse layers of what they sow under `SOWN_SUMS`,
        below (None where no layer sows it), the load over ALL routed experts
        by layer where the router has a bias)): sown by
        vitax/models/experts.py."""
        del rng                      # no dropout arm (Config.validate)
        if comm is not None:
            params = comm.cast(params)
        logits, cols = model.apply(params, decoder_inputs(batch), True,
                                   mutable=["intermediates"])
        loads = _select_by_name(cols, "expert_load")
        load = (jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in loads])
                if loads else jnp.zeros((0, 0), jnp.int32))
        sown = (load, *(_sown_sum(cols, name) for name in SOWN_SUMS),
                route_loads(cols))
        return decoder_loss(logits, batch), sown

    def loss_fn(params, batch, rng):
        if comm is not None:
            # idempotent: leaves the ZeRO-2 path pre-cast (already bf16)
            # untouched; elsewhere the convert-vjp rides the backward
            params = comm.cast(params)
        if cfg.packed:  # no dropout arm (Config.validate): rng unused
            return packed_loss(forward(params, packed_inputs(batch), True),
                               batch)
        images = prepare_images(batch["image"])
        det = not dropout
        r = rng if dropout else None
        if moe:
            # the per-block MoE load-balance ingredients ride the
            # "intermediates" collection (vitax/models/moe.py); weighted
            # into the objective (Switch Transformer)
            logits, aux = forward(params, images, det, rng=r, with_aux=True)
        else:
            logits = forward(params, images, det, rng=r)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            anchor_logits(logits), batch["label"]).mean()
        if moe:
            loss = loss + cfg.moe_aux_weight * aux
        return loss

    zero2 = not cfg.reshard_after_forward and not cfg.run_without_fsdp
    gathered_shardings = (
        shardings_of(mesh, gather_over_fsdp(state_specs.params)) if zero2 else None)

    k_steps = int(getattr(cfg, "grad_accum_steps", 1) or 1)
    if k_steps > 1:
        assert getattr(cfg, "pp_size", 1) == 1, (
            "grad accumulation under pipeline parallelism is rejected by "
            "Config.validate()")
        assert cfg.batch_size % k_steps == 0, (cfg.batch_size, k_steps)
        batch_devices = 1
        for ax in BATCH_AXES:
            batch_devices *= mesh.shape.get(ax, 1)
        assert (cfg.batch_size // k_steps) % batch_devices == 0, (
            f"microbatch {cfg.batch_size}/{k_steps} = "
            f"{cfg.batch_size // k_steps} not divisible by the "
            f"{batch_devices} batch-sharding devices (dp x fsdp x ep)")
        # grads accumulate at the SHARDED param layout (fp32): each
        # microbatch's backward reduce-scatters into the accumulator rather
        # than holding a gathered grad tree live — under ZeRO-2 the gathered
        # layout applies to params only.
        accum_shardings = state_shardings.params

    def accum_value_and_grad_dense(params, mbs, step_rng):
        """Manual accumulation (dense objective): per-microbatch
        value_and_grad inside the scan body — backward runs per iteration,
        so residuals live for ONE microbatch — summed into an fp32 carry.
        Exact vs K=1 by linearity of the gradient in the loss mean."""
        grad0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)

        def accum(carry, xs):
            gsum, loss_sum = carry
            mb, k = xs
            loss_k, g_k = jax.value_and_grad(loss_fn)(
                params, mb, jax.random.fold_in(step_rng, k))
            if comm is not None:
                # ZeRO-2 pre-cast params yield bf16 microbatch grads: pin
                # the per-microbatch reduction dtype and upcast before the
                # f32 accumulation (no-op on the already-f32 grad paths)
                g_k = comm.finalize_grads(g_k)
            gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                gsum, g_k)
            if mesh.size > 1:
                gsum = jax.lax.with_sharding_constraint(
                    gsum, accum_shardings)
            return (gsum, loss_sum + loss_k), None

        if mesh.size > 1:
            grad0 = jax.lax.with_sharding_constraint(grad0, accum_shardings)
        (gsum, loss_sum), _ = jax.lax.scan(
            accum, (grad0, jnp.zeros((), jnp.float32)),
            (mbs, jnp.arange(k_steps, dtype=jnp.uint32)))
        scale = 1.0 / k_steps
        return loss_sum * scale, jax.tree.map(lambda g: g * scale, gsum)

    def accum_loss_moe(params, mbs, step_rng):
        """MoE objective, differentiated THROUGH the microbatch scan: the
        load-balance aux couples microbatches (its ingredients are
        full-batch means taken before the frac*prob product), so the exact
        full-batch gradient cannot be formed one microbatch at a time. The
        scan body emits per-microbatch CE and RAW aux ingredients as
        stacked outputs; the objective combines their means AFTER the scan
        — identical to K=1 up to fp reassociation. jax.checkpoint on the
        body keeps residuals at one microbatch (the backward recomputes
        each microbatch's forward — ~+1F vs the dense manual path).

        Comm-precision caveat: the cast happens once outside the scan, so
        the scan's cross-microbatch cotangent accumulation for the (scan-
        invariant) params runs in bf16 under the bf16 policy — the one path
        that trades accumulation precision for the comm win. Use
        --param_gather_dtype float32 with MoE + grad accumulation if exact
        f32 accumulation matters more than gather bytes."""
        if comm is not None:
            params = comm.cast(params)

        def mb_terms(p, mb, k):
            images = prepare_images(mb["image"])
            r = jax.random.fold_in(step_rng, k) if dropout else None
            logits, (fracs, probs) = forward(p, images, not dropout, rng=r,
                                             with_aux="raw")
            ce = optax.softmax_cross_entropy_with_integer_labels(
                anchor_logits(logits), mb["label"]).mean()
            return ce, fracs, probs

        mb_ckpt = jax.checkpoint(mb_terms, prevent_cse=False)

        def body(carry, xs):
            mb, k = xs
            return carry, mb_ckpt(params, mb, k)

        _, (ces, frac_stacks, prob_stacks) = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (mbs, jnp.arange(k_steps, dtype=jnp.uint32)))
        fracs = [jnp.mean(f, axis=0) for f in frac_stacks]
        probs = [jnp.mean(p, axis=0) for p in prob_stacks]
        return (jnp.mean(ces)
                + cfg.moe_aux_weight * aux_from_frac_prob(fracs, probs, cfg))

    def accum_value_and_grad(params, batch, step_rng):
        mbs = _microbatch_split(batch, k_steps, mesh)
        if moe:
            return jax.value_and_grad(accum_loss_moe)(params, mbs, step_rng)
        return accum_value_and_grad_dense(params, mbs, step_rng)

    def train_step(state: TrainState, batch, rng):
        step_rng = jax.random.fold_in(rng, state.step)
        if zero2:
            # cast the SHARDS, then gather: the step-top all-gather (once per
            # step, reused by backward and all grad-accum microbatches) moves
            # bf16 bytes and the gathered tree holds half the live memory
            params = state.params if comm is None else comm.cast(state.params)
            params = jax.lax.with_sharding_constraint(params, gathered_shardings)
        else:
            params = state.params
        expert_load, routed = None, {}
        if cfg.decoder:
            (loss, (expert_load, *sown_sums, routed)), grads = (
                jax.value_and_grad(decoder_loss_fn, has_aux=True)(
                    params, batch, step_rng))
        elif k_steps > 1:
            loss, grads = accum_value_and_grad(params, batch, step_rng)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, step_rng)
        if comm is not None:
            grads = comm.finalize_grads(grads)
        new_params, new_opt_state, grad_norm = update_fn(
            grads, state.opt_state, state.params)
        if routed:
            # the router biases take no gradient: the optimizer leaves them
            # (but for its decay), the balance rule moves them
            new_params = balance_router_biases(new_params, routed)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state)
        metrics = {
            "loss": loss,
            # the same reduction that fed the clip — not a second pass
            "grad_norm": grad_norm,
            # post-step schedule position: the reference logs lr AFTER
            # lr_scheduler.step() (run_vit_training.py:288); the host resolves
            # the value via the pure schedule fn
            "lr_step": new_state.step,
        }
        if cfg.decoder:
            metrics.update(decoder_counts(cfg, batch))
            metrics.update(expert_load=expert_load,
                           expert_slots_here=jnp.sum(expert_load))
            # rows: blocks x B a sparse layer, >= its slots
            metrics.update({name: value for name, value in zip(
                SOWN_SUMS, sown_sums) if value is not None})
            if routed:
                metrics.update(
                    route_load_max_over_mean=route_load_max_over_mean(routed))
        elif cfg.packed:
            # what a packed step did is in its batch, not in the config:
            # counted on the device from the segment ids and the label mask
            seg = batch["segment_ids"]
            per_image = jnp.sum(
                seg[..., None] == jnp.arange(1, cfg.pack_images + 1),
                axis=1, dtype=jnp.int32)                       # (R, S)
            valid = jnp.sum(per_image)
            metrics.update(
                tokens=valid, padding_tokens=seg.size - valid,
                images=jnp.sum(batch["label_mask"] > 0, dtype=jnp.int32),
                # sum of n_i^2: the attention's useful work (telemetry MFU),
                # and the score pairs the packed kernels compute for it
                token_pairs=jnp.sum(jnp.square(per_image.astype(jnp.float32))),
                computed_pairs=computed_pairs(seg))
        return new_state, metrics

    jitted = jax.jit(
        train_step,
        in_shardings=(state_shardings, batch_sharding, rng_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )

    # Work counts for the telemetry throughput records (images/s, tokens/s).
    # They are static functions of the config, so they are attached HOST-SIDE
    # after the jitted call: the compiled program gains no outputs, no device
    # ops, and no device->host syncs (tests/test_telemetry.py pins the
    # lowered program's equality against the bare step).
    images_per_step = cfg.batch_size
    tokens_per_step = cfg.batch_size * cfg.num_patches

    def step_with_counts(state, batch, rng):
        new_state, metrics = jitted(state, batch, rng)
        if not cfg.packed:  # a packed step counted its own, on the device
            metrics = dict(metrics, images=images_per_step,
                           tokens=tokens_per_step)
        return new_state, metrics

    step_with_counts.lower = jitted.lower  # AOT surface (tools/, tests/)
    step_with_counts.trace = jitted.trace  # jaxpr surface (VTX-R008)
    return step_with_counts


def make_eval_step(cfg: Config, model, mesh: Mesh, state_specs: PyTree):
    """Jitted eval step: (state, batch) -> {"correct", "correct_top5"}
    prediction counts over the global batch (reference eval_on_val's
    device-side accumulator + mesh_reduce, run_vit_training.py:306-318, as
    one compiled reduction; top-5 rides the same compiled program via
    lax.top_k — with < 5 classes, k clamps and top-5 equals top-k)."""
    state_shardings = shardings_of(mesh, state_specs)
    batch_sharding = NamedSharding(mesh, batch_pspec())
    forward = _forward_fn(cfg, model, mesh, state_specs)
    comm = make_comm_precision(cfg, mesh, state_specs.params)

    anchor_logits = _make_logits_anchor(mesh)
    k5 = min(5, cfg.num_classes)

    def eval_step(state: TrainState, batch):
        params = state.params if comm is None else comm.cast(state.params)
        logits = forward(params, prepare_images(batch["image"]), True)
        # same batch-sharded logits anchor as the train loss (the argmax
        # iota is the eval-side victim of the mixed layout)
        logits = anchor_logits(logits)
        pred = jnp.argmax(logits, axis=-1)
        _, top5 = jax.lax.top_k(logits, k5)
        in_top5 = jnp.any(top5 == batch["label"][:, None], axis=-1)
        return {
            "correct": jnp.sum((pred == batch["label"]).astype(jnp.int32)),
            "correct_top5": jnp.sum(in_top5.astype(jnp.int32)),
        }

    return jax.jit(
        eval_step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=None,
    )


def _sown_sum(cols, name: str):
    """The sum of what the layers sowed under `name`; None where none did.
    (Here at the end: a line added above shifts the line numbers that the
    kernels' Mosaic payloads embed, benchmark/lowered.py.)"""
    sown = _select_by_name(cols, name)
    return sum(jnp.sum(x) for x in sown) if sown else None


# What the sparse layers sow that a step sums over them into its metrics
# under the same name (vitax/models/experts.py): the sorted rows their loops
# worked on, the tokens that kept the held experts' group (a grouped router),
# the (live row, hidden unit) pairs a ReLU gate left above 0 (ReGLU experts).
SOWN_SUMS = ("expert_rows_computed", "tokens_choosing_held_group",
             "expert_hidden_live")
