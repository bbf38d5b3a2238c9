"""Pipeline parallelism: GPipe stages over the "pp" mesh axis.

Capability beyond the reference (SURVEY.md section 2.3 lists PP as absent).
TPU-first formulation: the model's blocks are ALREADY a stacked (L, ...)
parameter tree (the lax.scan layout) — pipeline parallelism is nothing more
than sharding that leading layer axis over a mesh axis
(`PartitionSpec("pp", ...)`, vitax/parallel/sharding.py:param_pspec) and
running the stage schedule inside `jax.shard_map`:

- Stage s holds layers [s*L/S, (s+1)*L/S) — its shard of the stacked tree.
- The local batch is split into M microbatches (`--pp_microbatches`,
  default S). At tick t (t = 0..M+S-2), stage s processes microbatch t-s
  (bubble ticks compute masked garbage — lockstep SPMD, standard GPipe),
  then hands its activation to stage s+1 via `jax.lax.ppermute` — one ICI
  hop, overlapped with the next tick's compute by XLA's scheduler.
- The last stage's valid outputs are the tick outputs [S-1, S-1+M); a psum
  over "pp" (one nonzero contributor) replicates them so the head/loss run
  under plain GSPMD afterwards.
- Topology placement: "pp" is the second-to-last mesh axis ("ep" is last
  and batch-like), so pp neighbors are mesh-ADJACENT device ids — on pods
  the per-tick stage hop always rides the closest ICI links and never the
  host boundary; the dp/fsdp axes (larger strides) carry the cross-host
  traffic, which is amortized once per step (grad reduction), not once per
  tick. tests/test_multiprocess.py exercises exactly that composition.
- Backward is plain autodiff through the scan/ppermute: bubble-tick
  computations receive zero cotangents (their outputs are masked), so only
  real microbatches contribute gradients, which land on each stage's own
  param shard.

Composes with dp, fsdp/ZeRO-3, AND tp/sp: block params may carry "fsdp"
placements on their weight dims in addition to "pp" on the layer dim, and
"tp" placements on their Megatron dims.
- sp rides as another MANUAL axis of the pipeline shard_map: activations
  keep their token dim sharded over "sp" through the whole schedule, and
  the ring/ulysses LOCAL bodies run directly inside the already-manual
  region (vitax_pp_impl — no nested shard_map: in jax 0.9 a nested
  partial-manual map hoists its closure constants into sdy wrappers whose
  all-axes sharding encodings violate Shardy's manual-before-free ordering).
- tp stays a GSPMD-AUTO axis: the shard_map manualizes every mesh axis
  except "tp" (with vma tracking on, so autodiff residual specs are
  inferred precisely), and the compiler partitions the block matmuls from
  the weights' own Megatron placements exactly as on the scan path.
  Attention under tp uses the dense einsum path (GSPMD shards it over the
  tp-global head dim; a Pallas kernel cannot be auto-partitioned — at ViT
  sequence lengths the dense path measured ~1.9% of step time at 10B
  dims on v5e — the round-5 attention A/B).
Inside the pipeline body each block's leaves are all-gathered
over "fsdp" right before use — the manual form of the per-block gather
GSPMD emits on the scan path — and autodiff's transpose of that gather is
a reduce-scatter, so gradients land back on the ZeRO-3 shards. With remat
the gather sits inside the checkpointed block, so the backward re-gathers
instead of keeping gathered weights live: full ZeRO-3 memory semantics
inside GPipe. Embed/head run data-parallel outside the pipeline, reusing
the SAME param tree as the scan path functionally — init and checkpoints
are identical between pp and non-pp topologies, so Orbax cross-topology
restore covers pp<->fsdp resizes.

v2 additions over the original GPipe body:
- Dropout rides the pipeline: per-(tick, layer, data-shard) keys are folded
  from the step rng inside the body, so masks are deterministic given
  (seed, step) and distinct across microbatches, layers, and batch shards.
  Position dropout applies outside the shard_map (plain GSPMD).
- MoE blocks work under pp: each block's sown load-balance ingredients
  (frac_tokens, mean_prob — LINEAR in the tokens) are masked on bubble
  ticks, averaged over microbatches and data shards, and only then combined
  into the nonlinear Switch aux product — so the pipeline's aux equals the
  scan path's exactly.

v3 (round 5): expert parallelism composes too (--ep_size > 1 with
--pp_size > 1): "ep" is already a manual axis of the pipeline shard_map, so
the MoeMlp runs its own tiled all_to_all pair over it and declares expert
params at the local (E/ep, ...) shard shape (vitax/models/moe.py MoeMlp
.ep_axis/.ep_size) — the hand-written form of the batch<->expert exchange
GSPMD derives from dispatch_sharding on the scan path. einsum impl only
(config.validate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from vitax.config import Config
from vitax.parallel.mesh import BATCH_AXES, shard_map
from vitax.platform import backend_platform


def _gather_over(x, spec: P, axis_name: str):
    """All-gather the dims of `x` that `spec` places on `axis_name` (tiled:
    the gathered dim returns to its full size in place)."""
    for dim, ax in enumerate(spec):
        if ax == axis_name:
            x = jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)
    return x


def _drop_tp(spec: P) -> P:
    """Strip "tp" placements from a PartitionSpec: when tp is a GSPMD-auto
    axis, partial-manual shard_map in_specs may only name manual axes; the
    tp sharding rides on the arrays' own NamedShardings."""
    def fix(entry):
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a != "tp")
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if entry == "tp" else entry
    return P(*(fix(e) for e in spec))


def make_pp_forward(cfg: Config, model, mesh: Mesh, block_specs=None):
    """(params, images, det=True, rng=None, with_aux=False) -> logits or
    (logits, moe_aux), GPipe-pipelined over "pp".

    `model` is the same VisionTransformer the scan path uses — its param tree
    is reused leaf-for-leaf; this function only changes HOW blocks are
    applied. `block_specs` is the PartitionSpec tree of the stacked block
    params (P("pp", ...) with optional "fsdp" dims) — when omitted, a
    pp-only layout is assumed (stage params whole per device).
    """
    import flax.linen as nn

    from vitax.models.vit import _REMAT_POLICIES, Block

    S = mesh.shape["pp"]
    M = cfg.pp_microbatches or S
    assert cfg.num_blocks % S == 0, (cfg.num_blocks, S)
    Lps = cfg.num_blocks // S  # layers per stage
    dp_like = (mesh.shape["dp"] * mesh.shape["fsdp"] * mesh.shape["ep"])
    assert cfg.batch_size % (dp_like * M) == 0, (
        f"batch {cfg.batch_size} must divide by data-axes*microbatches "
        f"({dp_like}*{M})")
    moe = cfg.moe_experts > 0
    # tp present: partial-manual shard_map (tp stays GSPMD-auto) with vma
    # tracking (see the shard_map call below); absent: full-manual,
    # round-3 behavior. sp is ALWAYS manual: the ring/ulysses bodies run
    # directly in the pipeline body over the in-scope "sp" axis.
    tp_auto = mesh.shape["tp"] > 1
    if (tp_auto and cfg.dtype == "bfloat16"
            and backend_platform() == "cpu"):
        # a warning here would be followed by a native XLA abort the user
        # can't connect back to it — fail loudly instead
        raise ValueError(
            "pp x tp with bf16 on the CPU backend crashes XLA's "
            "operand_upcaster pass (CPU bf16-dot emulation mishandles "
            "partitioner-generated copies in the pipeline's scan loops). "
            "This pass does not exist in TPU's native-bf16 compile "
            "pipeline. Use --dtype float32 for CPU runs of this mesh.")
    sp = mesh.shape["sp"]
    if sp > 1:
        assert cfg.num_patches % sp == 0, (
            f"pp x sp needs num_patches {cfg.num_patches} divisible by "
            f"sp {sp}")
    has_block_dropout = cfg.att_dropout > 0 or cfg.mlp_dropout > 0

    # the model's attention impl may be shard_map-wrapped (multi-device
    # meshes); inside pipeline_body the batch/pp/sp axes are ALREADY manual,
    # so swap to the pp-body variant: the raw local kernel when tp/sp are
    # absent, the LOCAL ring/ulysses body under sp (the "sp" axis is in
    # scope), or None under tp (dense einsum path — GSPMD partitions it
    # over the tp-auto head dim). Same selection, incl. the dryrun's
    # interpret-mode forcing.
    bk = model.block_kwargs()
    _impl = bk["attention_impl"]
    bk["attention_impl"] = getattr(
        _impl, "vitax_pp_impl", getattr(_impl, "vitax_local_impl", _impl))
    if sp > 1:
        # under manual sp the Block's dense fallback would softmax each
        # LOCAL N/sp token shard as if it were the full sequence —
        # shape-correct, silently wrong. The body impl must be sp-aware
        # (ring/ulysses local); it is None when make_attention_impl bailed
        # (e.g. num_heads % tp != 0) or the model was built without one.
        assert bk["attention_impl"] is not None, (
            "pp x sp needs an sp-aware attention impl in the pipeline body "
            "(ring/ulysses via make_attention_impl); got None — check "
            "num_heads divisibility by tp (and sp*tp for ulysses)")
        # att_dropout under manual sp must ride an sp-aware DROPOUT body
        # (both ring and ulysses carry one at tp=1, round 5); the dense
        # fallback would softmax local token shards — silently wrong
        assert cfg.att_dropout == 0.0 or getattr(
            bk["attention_impl"], "vitax_dropout", None) is not None, (
            "pp x sp with --att_dropout > 0 needs a body impl with an "
            "in-kernel dropout variant (ring/ulysses carry one at tp=1; "
            "under tp the body impl has none)")
    # mesh-level sharding anchors are meaningless on the per-device values
    # inside shard_map (and NamedSharding constraints are illegal there)
    bk["token_sharding"] = None
    bk["moe_dispatch_sharding"] = None
    if moe and mesh.shape["ep"] > 1:
        # expert parallelism inside the manual body: the MoeMlp runs its own
        # tiled all_to_all pair over the in-scope "ep" axis and declares its
        # expert params at the local (E/ep, ...) shard shape — the manual
        # form of the a2a GSPMD derives from dispatch_sharding on the scan
        # path (vitax/models/moe.py)
        bk["moe_ep_axis"] = "ep"
        bk["moe_ep_size"] = mesh.shape["ep"]
    block = Block(**bk)

    # manual-axis view of the block specs: tp placements are stripped when
    # tp is GSPMD-auto (the arrays' own NamedShardings carry them), then
    # per-layer specs drop the leading (stacked/"pp") dim of each leaf spec
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    manual_block_specs = (None if block_specs is None else
                          (jax.tree.map(_drop_tp, block_specs,
                                        is_leaf=is_spec)
                           if tp_auto else block_specs))
    layer_specs = (None if manual_block_specs is None else jax.tree.map(
        lambda s: P(*s[1:]), manual_block_specs, is_leaf=is_spec))

    def make_one_block(det: bool, collect_aux: bool):
        def one_block(carry, xs):
            layer_params, key = xs
            if layer_specs is not None and mesh.shape["fsdp"] > 1:
                # pin the gathers to the loop iteration: the sharded layer
                # slice alone is loop-invariant enough for XLA's LICM to
                # hoist the per-block all-gathers out of the layer scan,
                # materializing the whole STAGE's gathered parameters at
                # once (28.7 GB vs 10.1 GB temps at the 10B flagship shape —
                # caught by test_10b_shape_lowers_under_pipeline_fsdp). The
                # barrier makes the gather input depend on the loop carry.
                layer_params, carry = jax.lax.optimization_barrier(
                    (layer_params, carry))
                # ZeRO-3 inside the pipeline: gather this block's shards over
                # "fsdp" just-in-time (under remat this sits inside the
                # checkpointed region, so backward re-gathers rather than
                # holding gathered weights live; the gather's transpose
                # reduce-scatters the weight cotangents onto the shards).
                # NOTE specs lead the tree.map: P is a tuple subclass, so it
                # must be the is_leaf-guarded first tree
                layer_params = jax.tree.map(
                    lambda s, x: _gather_over(x, s, "fsdp"),
                    layer_specs, layer_params, is_leaf=is_spec)
            rngs = ({"dropout": key}
                    if (not det) and has_block_dropout else None)
            if collect_aux:
                y, cols = block.apply({"params": layer_params}, carry, det,
                                      rngs=rngs, mutable=["intermediates"])
                moe_cols = cols["intermediates"]["moe"]
                # sow stores a tuple of sown values (one per call)
                aux = (moe_cols["moe_frac_tokens"][0],
                       moe_cols["moe_mean_prob"][0])
            else:
                y = block.apply({"params": layer_params}, carry, det,
                                rngs=rngs)
                aux = None
            return y, aux
        if cfg.grad_ckpt:
            one_block = jax.checkpoint(
                one_block, policy=_REMAT_POLICIES[cfg.remat_policy],
                prevent_cse=False)
        return one_block

    def make_pipeline_body(det: bool, collect_aux: bool):
        one_block = make_one_block(det, collect_aux)

        def stage_fn(stage_params, x, tick_key):
            # per-layer dropout keys: the tick key folded with the GLOBAL
            # layer index (stage offset + local index), so every (microbatch,
            # layer) pair draws an independent mask stream
            s = jax.lax.axis_index("pp")
            layer_keys = jax.vmap(
                lambda i: jax.random.fold_in(tick_key, s * Lps + i)
            )(jnp.arange(Lps))
            y, aux = jax.lax.scan(one_block, x, (stage_params, layer_keys),
                                  unroll=min(cfg.scan_unroll, Lps))
            return y, aux  # aux: (frac (Lps, E), prob (Lps, E)) or None

        def pipeline_body(stage_params, key_data, x):
            # per-device view: stage_params = this stage's (Lps, ...) tree,
            # x = this dp-shard's (B_loc, N, D) activations (replicated over
            # pp), key_data = the step rng's raw key data (replicated)
            s = jax.lax.axis_index("pp")
            # distinct dropout streams per data shard (dp x fsdp x ep)
            shard_idx = (
                (jax.lax.axis_index("dp") * mesh.shape["fsdp"]
                 + jax.lax.axis_index("fsdp")) * mesh.shape["ep"]
                + jax.lax.axis_index("ep"))
            # sp shards hold DIFFERENT tokens of the same samples — their
            # mlp-dropout masks (drawn inside the body) must be independent
            # too (identity when sp == 1: idx*1 + 0). Pos dropout runs
            # OUTSIDE the pipeline shard_map (plain GSPMD in forward()),
            # so it is not affected by this fold.
            shard_idx = (shard_idx * mesh.shape["sp"]
                         + jax.lax.axis_index("sp"))
            base_key = jax.random.fold_in(
                jax.random.wrap_key_data(key_data), shard_idx)
            b_loc = x.shape[0]
            mbs = x.reshape(M, b_loc // M, *x.shape[1:])
            perm = [(i, (i + 1) % S) for i in range(S)]

            def tick(carry, t):
                buf, acc_f, acc_p = carry
                inj = jax.lax.dynamic_index_in_dim(
                    mbs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                x_in = jnp.where(s == 0, inj, buf)
                y, aux = stage_fn(stage_params, x_in,
                                  jax.random.fold_in(base_key, t))
                if collect_aux:
                    # bubble ticks (t-s outside [0, M)) computed garbage:
                    # their aux ingredients must not pollute the batch means
                    valid = jnp.logical_and(t >= s, t - s < M)
                    acc_f = acc_f + jnp.where(valid, aux[0], 0.0)
                    acc_p = acc_p + jnp.where(valid, aux[1], 0.0)
                y_out = jnp.where(s == S - 1, y, jnp.zeros_like(y))
                if S > 1:
                    # the final tick's carry is never read — skip its ICI hop
                    # (cond predicate is uniform across devices, so the
                    # collective stays SPMD-legal; cf. ring attention's
                    # "exactly sp-1 rotations")
                    buf = jax.lax.cond(
                        t < M + S - 2,
                        lambda v: jax.lax.ppermute(v, "pp", perm),
                        lambda v: v, y)
                else:
                    buf = y
                return (buf, acc_f, acc_p), y_out

            acc0 = (jnp.zeros((Lps, cfg.moe_experts), jnp.float32),) * 2 \
                if collect_aux else (jnp.float32(0.0),) * 2
            buf0 = jnp.zeros_like(mbs[0])
            if tp_auto:
                # under vma tracking (the partial-manual tp path) the
                # carry's type must declare it varies over pp — the tick
                # output does (each stage holds a different activation)
                buf0 = jax.lax.pcast(buf0, ("pp",), to="varying")
            (_, acc_f, acc_p), ys = jax.lax.scan(
                tick, (buf0, *acc0),
                jnp.arange(M + S - 1))
            outs = ys[S - 1:S - 1 + M]          # microbatch i at tick S-1+i
            outs = jax.lax.psum(outs, "pp")     # one nonzero contributor
            outs = outs.reshape(b_loc, *x.shape[1:])
            if not collect_aux:
                return outs, jnp.float32(0.0)
            # per-layer means over microbatches (equal sizes) and data
            # shards: frac/prob are linear in the tokens, so these means
            # equal the scan path's full-batch means exactly
            frac = jax.lax.pmean(acc_f / M, ("dp", "fsdp", "ep"))
            prob = jax.lax.pmean(acc_p / M, ("dp", "fsdp", "ep"))
            # nonlinear Switch product only AFTER the means; sum this
            # stage's layers, then all stages' (each stage contributes its
            # own Lps rows exactly once)
            aux = cfg.moe_experts * jnp.sum(frac * prob)
            aux = jax.lax.psum(aux, "pp") / cfg.num_blocks
            return outs, aux

        return pipeline_body

    # tokens ride the manual "sp" axis when sequence parallelism is active
    act_spec = P(BATCH_AXES, "sp" if sp > 1 else None, None)

    def stacked_specs(tree):
        return jax.tree.map(
            lambda leaf: P(*("pp",) + (None,) * (leaf.ndim - 1)), tree)

    dtype = model.dtype

    def forward(params, images, det: bool = True, rng=None,
                with_aux: bool = False):
        from vitax.models.vit import apply_embed, apply_tail
        p = params["params"]
        x = apply_embed(p, images, patch_size=cfg.patch_size,
                        embed_dim=cfg.embed_dim, dtype=dtype)
        any_dropout = max(cfg.pos_dropout, cfg.att_dropout,
                          cfg.mlp_dropout) > 0
        if not det and any_dropout:
            # match the scan path's failure mode: flax raises on a missing
            # "dropout" rng rather than silently training deterministically
            assert rng is not None, (
                "non-deterministic pp forward with dropout configured "
                "needs an rng")
        use_dropout = (not det) and any_dropout
        if use_dropout and cfg.pos_dropout > 0:
            # position dropout runs OUTSIDE the shard_map (plain GSPMD);
            # the module keeps pos-dropout semantics identical to the
            # scan path's nn.Dropout site (vit.py)
            x = nn.Dropout(rate=cfg.pos_dropout).apply(
                {}, x, deterministic=False,
                rngs={"dropout": jax.random.fold_in(rng, 0x706F5D)})

        if rng is None:  # the body's key input must always be an array
            rng = jax.random.key(0)
        pipeline_body = make_pipeline_body(not use_dropout, with_aux)

        stacked = p["blocks"]
        in_specs = (manual_block_specs if manual_block_specs is not None
                    else stacked_specs(stacked))
        # tp absent: manualize every axis with vma checking off — the
        # autodiff residuals' conservative all-axes out_specs are legal
        # there (round-3 behavior, bit-identical). tp present: manualize
        # everything BUT tp and turn vma tracking ON — the residual
        # out_specs must then be inferred precisely, since naming an auto
        # axis in out_specs is an error.
        run = shard_map(
            pipeline_body, mesh=mesh,
            in_specs=(in_specs, P(), act_spec),
            out_specs=(act_spec, P()),
            axis_names=(frozenset(mesh.axis_names) - {"tp"} if tp_auto
                        else frozenset(mesh.axis_names)),
            check_vma=tp_auto)
        x, aux = run(stacked, jax.random.key_data(rng), x)

        logits = apply_tail(p, x, num_classes=cfg.num_classes, dtype=dtype)
        return (logits, aux) if with_aux else logits

    return forward
