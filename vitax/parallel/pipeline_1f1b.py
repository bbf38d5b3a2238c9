"""1F1B pipeline schedule: interleaved forward/backward over the "pp" axis.

GPipe (vitax/parallel/pipeline.py) runs all M forward microbatches, then
autodiff replays them backward; 1F1B (Narayanan et al., PipeDream-Flush /
Megatron-LM) interleaves: once stage s has run its warmup forwards, each
tick performs ONE forward and ONE backward, bounding in-flight microbatch
activations at ~2(S-s) per stage instead of the full M+S-1 tick carries.

VERDICT (a pre-ledger A/B on an 8-device CPU mesh: bytes and CPU times):
in THIS framework the classic 1F1B memory win does not materialize, and
GPipe stays the default. Two reasons, both structural: (1) the pipeline
always runs recompute-everything remat, so GPipe's saved state is already
just the (mb, N, D) tick carries — the per-layer activations 1F1B exists to
evict are never stored in the first place; (2) at fixed global batch,
microbatches shrink as M grows, so both schedules' live sets are O(batch),
flat in M (measured: GPipe 16.7-21.1 MB temp vs 1F1B 17.1-26.3 MB across
M=2..16). Meanwhile the lockstep-SPMD 1F1B tick pays the tail (norm + head
+ loss) on EVERY stage every tick (garbage off the last stage) plus a
second ppermute — measured ~30% step-time overhead. The schedule is kept
selectable (--pp_schedule 1f1b) as the correctness-proven foundation for
the regime where it does pay: no-remat pipelines or M scaling the global
batch (gradient-accumulation style), where per-mb residuals are large and
fixed-size.

TPU-first formulation, lockstep SPMD inside one `jax.shard_map`:

- tick t, stage s: forward of microbatch f = t - s (valid when 0 <= f < M),
  and backward of microbatch b = t - (2S - 2 - s) (valid when 0 <= b < M) —
  the standard 1F1B timetable collapsed onto a single program counter;
  invalid slots compute masked garbage (cf. GPipe's bubble ticks). Total
  ticks: M + 2S - 2.
- The LAST stage closes the loop in-tick: its forward feeds norm + mean-pool
  + head + CE loss immediately, and the loss's cotangent seeds that same
  microbatch's backward — which is why forward and backward can interleave
  at all (the loss lives inside the pipelined region, unlike GPipe's).
- Backward recomputes the stage forward under `jax.vjp` from the SAVED STAGE
  INPUT (a ring buffer of 2S+1 slots — the +1 is a trash slot for masked
  writes). This is the reference checkpoint_module semantics
  (none_saveable): store one (mb, N, D) input per in-flight microbatch,
  recompute everything else.
- Activations hop forward (stage s -> s+1) and cotangents hop backward
  (s -> s-1) as two `ppermute`s per tick, both overlapped with compute by
  XLA's scheduler.
- ZeRO-3 composes exactly as in GPipe: block shards all-gather just-in-time
  inside the (recomputed) stage forward; `jax.vjp` transposes the gather to
  a reduce-scatter, so weight cotangents land back on the "fsdp" shards.
  The head/norm params are gathered the same way. dp/ep replication is
  closed with explicit psums on the accumulated grads.

v1 scope: dense blocks, no dropout (config.validate enforces both) — the
schedule is the point; the GPipe body keeps those features.

Scale limit, PER BACKEND (round 5 update of the round-4 note): on TPU the
stage forward remats per block (`_remat_blocks`), so `jax.vjp(stage_fwd)`
saves one (mb, N, D) carry per layer and re-runs the ZeRO-3 gathers in the
backward — GPipe's just-in-time memory semantics at the 10B shape (proven
by AOT-compiling this engine against a v5p topology,
tools/aot_topology.py --configs 10b_1f1b / AOT_TOPOLOGY.json). On the CPU
backend the per-block checkpoint stays OUT: the jax-0.9 CPU compiler
intermittently aborts on the rematted vjp-inside-shard_map structure
(re-reproduced round 5, ~1-in-3 across repeated 1f1b test runs), so CPU
compiles save gathered layer weights (~35 GB at the 10B pp2 x fsdp4
shape) — immaterial at the toy shapes CPU actually runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from vitax.config import Config
from vitax.parallel.mesh import BATCH_AXES, shard_map
from vitax.parallel.pipeline import _gather_over

import optax


def _remat_blocks(mesh: Mesh) -> bool:
    """Whether the 1F1B stage forward remats per block — decided by the
    COMPILE TARGET's platform (see the stage_fwd comment: the CPU XLA
    backend intermittently aborts on the rematted engine; TPU compiles it)."""
    return next(iter(mesh.devices.flat)).platform == "tpu"


def make_1f1b_value_and_grad(cfg: Config, model, mesh: Mesh, state_specs):
    """(params, batch) -> (loss, grads): the full fwd+bwd of the ViT under
    the 1F1B schedule. Drop-in for jax.value_and_grad(loss_fn) in
    make_train_step when --pp_schedule 1f1b.

    `state_specs.params` provides the PartitionSpec tree (P("pp", ...) on
    blocks, optional "fsdp" dims everywhere) used for the shard_map specs
    and the just-in-time gathers.
    """
    from vitax.models.vit import Block, apply_embed, apply_tail

    S = mesh.shape["pp"]
    M = cfg.pp_microbatches or S
    assert cfg.num_blocks % S == 0, (cfg.num_blocks, S)
    Lps = cfg.num_blocks // S
    W = 2 * S + 1  # ring capacity 2S in-flight + one trash slot
    dp_like = mesh.shape["dp"] * mesh.shape["fsdp"] * mesh.shape["ep"]
    assert cfg.batch_size % (dp_like * M) == 0, (
        f"batch {cfg.batch_size} must divide by data-axes*microbatches "
        f"({dp_like}*{M})")

    bk = model.block_kwargs()
    bk["attention_impl"] = getattr(
        bk["attention_impl"], "vitax_local_impl", bk["attention_impl"])
    bk["token_sharding"] = None
    bk["moe_dispatch_sharding"] = None
    block = Block(**bk)
    dtype = model.dtype

    param_specs = state_specs.params["params"]
    block_specs = param_specs["blocks"]
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    layer_specs = jax.tree.map(lambda s: P(*s[1:]), block_specs,
                               is_leaf=is_spec)
    tail_specs = {"norm": param_specs["norm"], "head": param_specs["head"]}

    def stage_fwd(stage_params, x):
        def one_block(carry, layer_params):
            if mesh.shape["fsdp"] > 1:
                # pin the gather inside the (rematted) scan iteration: XLA
                # LICM otherwise hoists loop-invariant all-gathers out of
                # the loop, materializing every layer's gathered weights at
                # once (the GPipe body's idiom, vitax/parallel/pipeline.py)
                layer_params, carry = jax.lax.optimization_barrier(
                    (layer_params, carry))
                layer_params = jax.tree.map(
                    lambda s, p: _gather_over(p, s, "fsdp"),
                    layer_specs, layer_params, is_leaf=is_spec)
            return block.apply({"params": layer_params}, carry, True), None
        # per-block checkpoint, TPU ONLY (round 5): jax.vjp(stage_fwd)
        # otherwise saves every layer's GATHERED weights as scan residuals
        # (~35 GB at the 10B pp2 x fsdp4 shape vs GPipe's 13 GB). With the
        # block rematted, the residual is one (mb, N, D) carry per layer and
        # the gather re-runs in the backward — GPipe's just-in-time
        # semantics. The gate is the COMPILE TARGET (mesh devices), not the
        # host: the round-4 intermittent XLA abort re-reproduced under jax
        # 0.9 on the CPU backend (1-in-~3 across repeated
        # tests/test_pipeline.py 1f1b runs — a CPU-compiler bug on this
        # engine's vjp-in-shard_map structure), while the TPU compiler
        # handles it (proven by AOT-compiling this engine at the 10B shape
        # against a v5p topology: tools/aot_topology.py --configs 10b_1f1b,
        # AOT_TOPOLOGY.json temp bytes ~ GPipe level).
        if _remat_blocks(mesh):
            one_block = jax.checkpoint(one_block, prevent_cse=False)
        y, _ = jax.lax.scan(one_block, x, stage_params,
                            unroll=min(cfg.scan_unroll, Lps))
        return y

    def tail_loss(tail_params, y, labels_mb):
        """norm + mean-pool + head + CE on one microbatch, normalized by the
        GLOBAL batch size so per-mb cotangents add up to the global-mean
        loss gradient."""
        if mesh.shape["fsdp"] > 1:
            tail_params = jax.tree.map(
                lambda s, p: _gather_over(p, s, "fsdp"),
                tail_specs, tail_params, is_leaf=is_spec)
        logits = apply_tail(tail_params, y, num_classes=cfg.num_classes,
                            dtype=dtype)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels_mb)
        return jnp.sum(ce) / cfg.batch_size

    def pipeline_body(stage_params, tail_params, x, labels):
        s = jax.lax.axis_index("pp")
        b_loc = x.shape[0]
        mb = b_loc // M
        mbs = x.reshape(M, mb, *x.shape[1:])
        lbs = labels.reshape(M, mb)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        # f32 accumulators regardless of param dtype: under the comm-precision
        # cast (vitax/parallel/sharding.py cast_to_compute) stage params — and
        # so the per-tick cotangents — are bf16; accumulating ticks in bf16
        # would lose low bits. At f32 params the astype below is a no-op and
        # the program is unchanged.
        def _grad_zeros(p):
            z_dtype = (jnp.float32 if jnp.issubdtype(p.dtype, jnp.floating)
                       else p.dtype)
            return jnp.zeros(p.shape, z_dtype)

        g_stage0 = jax.tree.map(_grad_zeros, stage_params)
        g_tail0 = jax.tree.map(_grad_zeros, tail_params)
        buf0 = jnp.zeros((W, mb, *x.shape[1:]), x.dtype)

        def tick(carry, t):
            ring, fmsg, bmsg, g_stage, g_tail, loss_acc = carry

            # ---- forward of microbatch f = t - s ----
            f = t - s
            valid_f = jnp.logical_and(f >= 0, f < M)
            inj = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(f, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(s == 0, inj, fmsg)
            # save the stage input for the recompute-backward; invalid ticks
            # write the trash slot so they can never clobber a live one
            slot = jnp.where(valid_f, f % (W - 1), W - 1)
            ring = jax.lax.dynamic_update_index_in_dim(ring, x_in, slot, 0)
            y = stage_fwd(stage_params, x_in)

            # ---- last stage: tail fwd + loss + cotangent seed (same tick:
            # t_b(S-1, m) == t_f(S-1, m) == S-1+m) ----
            lb = jax.lax.dynamic_index_in_dim(
                lbs, jnp.clip(f, 0, M - 1), 0, keepdims=False)
            loss_mb, tail_vjp = jax.vjp(tail_loss, tail_params, y, lb)
            g_tail_tick, y_cot_seed, _ = tail_vjp(jnp.float32(1.0))
            at_tail = jnp.logical_and(s == S - 1, valid_f)
            loss_acc = loss_acc + jnp.where(at_tail, loss_mb, 0.0)
            g_tail = jax.tree.map(
                lambda a, g: a + jnp.where(at_tail, g, 0.0).astype(a.dtype),
                g_tail, g_tail_tick)

            # ---- backward of microbatch b = t - (2S - 2 - s) ----
            b = t - (2 * S - 2 - s)
            valid_b = jnp.logical_and(b >= 0, b < M)
            cot_in = jnp.where(s == S - 1, y_cot_seed.astype(x.dtype), bmsg)
            x_saved = jax.lax.dynamic_index_in_dim(
                ring, jnp.where(valid_b, b % (W - 1), W - 1), 0,
                keepdims=False)
            _, stage_vjp = jax.vjp(stage_fwd, stage_params, x_saved)
            g_stage_tick, dx = stage_vjp(cot_in)
            g_stage = jax.tree.map(
                lambda a, g: a + jnp.where(valid_b, g, 0.0).astype(a.dtype),
                g_stage, g_stage_tick)
            dx_out = jnp.where(jnp.logical_and(s == 0, valid_b), dx, 0.0)

            # ---- ICI hops: activations forward, cotangents backward ----
            if S > 1:
                fmsg = jax.lax.ppermute(y, "pp", fwd_perm)
                bmsg = jax.lax.ppermute(dx, "pp", bwd_perm)
            else:
                fmsg, bmsg = y, dx
            return (ring, fmsg, bmsg, g_stage, g_tail, loss_acc), dx_out

        zeros_msg = jnp.zeros((mb, *x.shape[1:]), x.dtype)
        T = M + 2 * S - 2
        (_, _, _, g_stage, g_tail, loss_acc), dxs = jax.lax.scan(
            tick,
            (buf0, zeros_msg, zeros_msg, g_stage0, g_tail0,
             jnp.float32(0.0)),
            jnp.arange(T))

        # the stage-0 embed cotangent for mb m was emitted at tick 2S-2+m;
        # only stage 0 wrote nonzero there — slice the M live ticks FIRST,
        # then psum over "pp" (pipeline.py's outs idiom: don't all-reduce
        # the warmup ticks' zeros)
        x_cot = jax.lax.psum(dxs[2 * S - 2:2 * S - 2 + M], "pp")
        x_cot = x_cot.reshape(b_loc, *x.shape[1:])

        # close the data-parallel replication: dp/ep (and, for leaves with
        # no "fsdp"-sharded dim, fsdp too — that axis carries batch) saw
        # different data. Leaves WITH an "fsdp" dim were already summed over
        # fsdp by the gather transposes (psum_scatter) inside the vjps.
        def close_replicas(spec, g):
            axes = {a for part in spec if part is not None
                    for a in (part if isinstance(part, tuple) else (part,))}
            names = ("dp", "ep") + (() if "fsdp" in axes else ("fsdp",))
            return jax.lax.psum(g, names)

        g_stage = jax.tree.map(close_replicas, block_specs, g_stage,
                               is_leaf=is_spec)
        g_tail = jax.tree.map(close_replicas, tail_specs,
                              jax.lax.psum(g_tail, "pp"), is_leaf=is_spec)
        loss = jax.lax.psum(jax.lax.psum(loss_acc, "pp"),
                            ("dp", "fsdp", "ep"))
        return g_stage, g_tail, x_cot, loss

    act_spec = P(BATCH_AXES, None, None)
    label_spec = P(BATCH_AXES)

    def value_and_grad(params, batch, labels):
        p = params["params"]

        def embed_fn(embed_params):
            return apply_embed(embed_params, batch,
                               patch_size=cfg.patch_size,
                               embed_dim=cfg.embed_dim, dtype=dtype)

        embed_params = {"patch_embed": p["patch_embed"],
                        "pos_embed": p["pos_embed"]}
        x, embed_vjp = jax.vjp(embed_fn, embed_params)

        run = shard_map(
            pipeline_body, mesh=mesh,
            in_specs=(block_specs, tail_specs, act_spec, label_spec),
            out_specs=(block_specs, tail_specs, act_spec, P()),
            check_vma=False)
        tail_params = {"norm": p["norm"], "head": p["head"]}
        g_blocks, g_tail, x_cot, loss = run(
            p["blocks"], tail_params, x, labels)
        (g_embed,) = embed_vjp(x_cot.astype(x.dtype))

        grads = {"params": {
            "patch_embed": g_embed["patch_embed"],
            "pos_embed": g_embed["pos_embed"],
            "blocks": g_blocks,
            "norm": g_tail["norm"],
            "head": g_tail["head"],
        }}
        return loss, grads

    return value_and_grad
