"""Ring attention: sequence/context parallelism over the "sp" mesh axis.

Long-context capability beyond the reference (which fixes sequence length at
(image/patch)^2 = 256 tokens and scales only parameters — SURVEY.md section 5
'long-context: absent'): activations are sharded over the token axis, and
attention streams K/V blocks around the ring of "sp" neighbors via
`jax.lax.ppermute` (one ICI hop per step), merging per-block results with a
logsumexp merge (blockwise attention a la Ring Attention, arXiv:2310.01889).
Peak memory per chip is O(N/sp) activations and one K/V block; the (N, N)
score matrix never exists.

Design (TPU-first):
- The sp-step block loop is UNROLLED (sp is a mesh-axis size — small and
  static), and each step issues the K/V rotation for the NEXT block *before*
  computing the current one. The rotation has no data dependence on the block
  product, so XLA's latency-hiding scheduler turns each collective-permute
  into a start/done pair overlapped with the MXU work — double buffering,
  scheduled by the compiler.
- Exactly sp-1 rotations TOTAL: K and V ride one stacked buffer so each ring
  step is a single collective-permute (XLA does not reliably merge distinct
  ppermutes — the ulysses.py lesson), and the last block computes without a
  permute (there is no next block to fetch).
- The local block product runs on the Pallas kernels on TPU, selected by the
  same policy cascade as full-sequence dispatch
  (vitax/ops/attention.py:_select_path): the 4D whole-N kernel when a legal
  head grouping fits VMEM (no HBM relayouts — these would otherwise run once
  per ring step per tensor), the BH whole-N kernel as fallback, the
  streaming (blocked) kernel past MAX_SEQ_IN_VMEM local tokens. All return
  (o, lse) differentiable in both, so the merge is plain autodiff. Off-TPU
  (CPU tests) the dense jnp block product is used.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from vitax.parallel.mesh import BATCH_AXES, shard_map
from vitax.platform import backend_platform


def _dense_block(q, k, v, scale: float):
    """Dense jnp block product: q (B, nq, H, Dh) x k/v (B, nk, H, Dh) ->
    (o (B, nq, H, Dh) f32 softmax-normalized within the block, lse (B, H, nq))."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / l, v.astype(jnp.float32))
    lse = (m + jnp.log(l))[..., 0]  # (B, H, nq)
    return o, lse


def _kernel_block(q, k, v, scale: float):
    """Pallas block product via the shared with-lse kernel selector
    (vitax/ops/attention.py:block_kernel_with_lse — ONE policy site): 4D
    whole-N kernel when the local shape has a legal head grouping (no HBM
    relayouts, which would otherwise run once per ring step per tensor), BH
    whole-N fallback, streaming kernel past MAX_SEQ_IN_VMEM. All are
    differentiable in both outputs (the merge is plain autodiff)."""
    from vitax.ops.attention import block_kernel_with_lse

    b, nq, h, dh = q.shape
    kern = block_kernel_with_lse(nq, h, dh, q.dtype.itemsize)
    o, lse = kern(q, k, v, scale)
    return o.astype(jnp.float32), lse


def _merge(o, lse, o_blk, lse_blk):
    """Combine two softmax-normalized partial results via their logsumexps."""
    lse_new = jnp.logaddexp(lse, lse_blk)                    # (B, H, N)
    w = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]         # (B,N,H,1)
    w_blk = jnp.exp(lse_blk - lse_new).transpose(0, 2, 1)[..., None]
    return o * w + o_blk * w_blk, lse_new


def _ring_attention_local(q, k, v, *, axis_name: str, scale: float,
                          block_fn: Callable, step_args: Callable = None):
    """shard_map body. q, k, v: (B, N_loc, H, Dh) — the local token shard.
    Streams K/V blocks around the ring; each device visits all sp blocks.

    step_args(step) -> tuple of extra positional args appended to each
    block_fn call (the dropout path's per-step seedvec); None for the plain
    (q, k, v, scale) products. ONE copy of the ring machinery — the
    prefetch-before-compute ordering below is load-bearing for the
    latency hiding described in the module docstring."""
    sp = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # K and V ride ONE stacked (2, B, N_loc, H, Dh) buffer so each ring step
    # issues a SINGLE collective-permute — XLA does not reliably merge
    # distinct ppermutes into one transfer (the same lesson as ulysses.py's
    # stacked all-to-all), and two hops per step means two latencies to hide
    kv_blk = jnp.stack([k, v])
    o = lse = None
    for step in range(sp):
        last = step == sp - 1
        if not last:
            # issue the rotation BEFORE the block product — no data dependence,
            # so the collective-permute overlaps the MXU work (double buffer)
            kv_nxt = jax.lax.ppermute(kv_blk, axis_name, perm)
        extra = () if step_args is None else step_args(step)
        o_blk, lse_blk = block_fn(q, kv_blk[0], kv_blk[1], scale, *extra)
        o_blk = o_blk.astype(jnp.float32)
        o, lse = (o_blk, lse_blk) if o is None else _merge(o, lse, o_blk, lse_blk)
        if not last:
            kv_blk = kv_nxt
    return o.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp",
                        use_kernel: Optional[bool] = None):
    """Build a (B, N, H, Dh) -> (B, N, H, Dh) attention core with the token
    axis sharded over `axis_name`; batch over (dp, fsdp), heads over tp.

    use_kernel: True -> Pallas block product (interpret mode off-TPU),
    False -> dense jnp, None -> Pallas exactly on TPU.
    """
    if use_kernel is None:
        use_kernel = backend_platform() == "tpu"
    block_fn = _kernel_block if use_kernel else _dense_block
    spec = P(BATCH_AXES, axis_name, "tp", None)

    def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        scale = q.shape[-1] ** -0.5
        fn = shard_map(
            functools.partial(_ring_attention_local, axis_name=axis_name,
                              scale=scale, block_fn=block_fn),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)

    return ring_attention


def _dense_block_drop(q, k, v, seedvec, scale: float, rate: float):
    """Dense jnp block product with the shared counter-hash dropout mask at
    GLOBAL coordinates (seedvec = [seed, q0, k0]); numerator masked, l/lse
    unmasked, (1-rate) folded per block — linear, so the merge of per-block
    results equals dense softmax-then-drop exactly."""
    from vitax.ops.attention import dropout_keep_mask

    b, nq, h, dh = q.shape
    nk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    bh = jnp.arange(b * h, dtype=jnp.uint32)
    mask = jax.vmap(lambda i: dropout_keep_mask(
        seedvec[0], i, nq, nk, rate,
        q0=seedvec[1], k0=seedvec[2]))(bh).reshape(b, h, nq, nk)
    o = jnp.einsum("bhqk,bkhd->bqhd", p * mask / (l * (1.0 - rate)),
                   v.astype(jnp.float32))
    lse = (m + jnp.log(l))[..., 0]  # (B, H, nq)
    return o, lse


def _kernel_block_drop(q, k, v, seedvec, scale: float, rate: float):
    """Pallas dropout block product (block_dropout_kernel_with_lse — same
    selection cascade as _kernel_block)."""
    from vitax.ops.attention import block_dropout_kernel_with_lse

    b, nq, h, dh = q.shape
    kern = block_dropout_kernel_with_lse(nq, h, dh, q.dtype.itemsize)
    o, lse = kern(q, k, v, seedvec, scale, rate)
    return o.astype(jnp.float32), lse


def _ring_attention_local_drop(q, k, v, seed, *, axis_name: str,
                               scale: float, rate: float,
                               block_fn: Callable):
    """Ring body with in-kernel dropout: each (q-shard, kv-block) product
    masks its numerator at the pair's GLOBAL (q0, k0) token offsets, so the
    merged result equals dense masked attention for the same seed — every
    (q, k) element is computed by exactly one shard at its global
    coordinates (tests pin this against the dense oracle). The ring loop
    itself is _ring_attention_local's (one copy of the machinery); only the
    per-step seedvec differs."""
    from vitax.ops.attention import _seedvec

    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    n_loc = q.shape[1]
    q0 = idx.astype(jnp.int32) * n_loc

    def step_args(step):
        # after `step` rotations this shard holds the block that ORIGINATED
        # on shard (idx - step): its global token offset keys the mask
        src = (idx - step) % sp
        return (_seedvec(seed, q0, src.astype(jnp.int32) * n_loc), rate)

    def block_with_drop(q, kk, vv, scale, sv, rate):
        return block_fn(q, kk, vv, sv, scale, rate)

    return _ring_attention_local(q, k, v, axis_name=axis_name, scale=scale,
                                 block_fn=block_with_drop,
                                 step_args=step_args)


def make_ring_dropout(mesh: Mesh, rate: float, axis_name: str = "sp",
                      use_kernel: Optional[bool] = None):
    """Ring attention with in-kernel attention dropout (round 5): (q, k, v,
    seed) -> o with the token axis sharded over `axis_name`. The seed is
    folded over the batch/tp shard position but NOT over sp — sp shards
    must agree on the global mask for the ring-equals-dense property."""
    if use_kernel is None:
        use_kernel = backend_platform() == "tpu"
    block_fn = _kernel_block_drop if use_kernel else _dense_block_drop
    spec = P(BATCH_AXES, axis_name, "tp", None)

    def ring_dropout(q, k, v, seed):
        from vitax.ops.attention import fold_shard_seed

        scale = q.shape[-1] ** -0.5
        shard_axes = tuple(a for a in (*BATCH_AXES, "tp")
                           if mesh.shape.get(a, 1) > 1)

        def body(q, k, v, seed):
            seed = fold_shard_seed(mesh, shard_axes, seed)
            return _ring_attention_local_drop(
                q, k, v, seed, axis_name=axis_name, scale=scale, rate=rate,
                block_fn=block_fn)

        fn = shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec, P()),
            out_specs=spec, check_vma=False,
        )
        return fn(q, k, v, seed)

    return ring_dropout


def make_ring_dropout_pp(rate: float, axis_name: str = "sp",
                         use_kernel: Optional[bool] = None):
    """Ring dropout for use INSIDE the pipeline body (pp x sp, tp=1): the
    local ring body with the dropout block products. The seed comes from
    the pipeline's per-(tick, layer, shard) keys, which DIFFER across sp
    shards — valid here: each (q, k) element is computed exactly once, by
    its q-owner shard, with that shard's seed deciding the mask identically
    in forward and backward (no cross-shard mask agreement is needed; the
    global-offset coordinates still decorrelate the kv blocks)."""
    if use_kernel is None:
        use_kernel = backend_platform() == "tpu"
    block_fn = _kernel_block_drop if use_kernel else _dense_block_drop

    def ring_dropout_local(q, k, v, seed):
        scale = q.shape[-1] ** -0.5
        return _ring_attention_local_drop(
            q, k, v, seed, axis_name=axis_name, scale=scale, rate=rate,
            block_fn=block_fn)

    return ring_dropout_local


def make_ring_attention_pp(axis_name: str = "sp",
                           use_kernel: Optional[bool] = None,
                           with_tp: bool = False):
    """Ring attention for use INSIDE the pipeline body (pp x sp composition).

    The pipeline shard_map manualizes "sp" itself (vitax/parallel/pipeline.py
    — a NESTED shard_map would hoist its closure constants into
    manual-computation wrappers whose all-axes sharding encodings Shardy
    rejects in jax 0.9), so this is simply the LOCAL ring body called
    directly in the already-manual region: operands are the per-device
    (B_loc, N/sp, H, Dh) shards and the ppermute rotates over the in-scope
    "sp" axis. With tp active (with_tp — tp stays a GSPMD-auto axis in the
    body), the block product must be the dense einsum path: GSPMD partitions
    the einsums over the tp-global head dim, whereas a Pallas kernel cannot
    be auto-partitioned."""
    if use_kernel is None:
        use_kernel = backend_platform() == "tpu"
    block_fn = _kernel_block if (use_kernel and not with_tp) else _dense_block

    def ring_attention_local(q: jax.Array, k: jax.Array,
                             v: jax.Array) -> jax.Array:
        scale = q.shape[-1] ** -0.5
        return _ring_attention_local(q, k, v, axis_name=axis_name,
                                     scale=scale, block_fn=block_fn)

    return ring_attention_local
