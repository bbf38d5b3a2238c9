"""Blocked (streaming) flash attention for TPU — no whole-sequence VMEM limit.

The whole-N kernel in vitax/ops/attention.py keeps the full (N, N) score tile
in VMEM, which caps N at ~2048. This module streams KV blocks through VMEM with
the online-softmax recurrence (running max/sum), so VMEM use is
O(BQ*BK + BQ*Dh) regardless of N — the single-chip long-sequence path that
composes with cross-chip ring attention (vitax/parallel/ring_attention.py).
The reference has no long-sequence story at all (SURVEY.md section 5:
sequence length fixed at 256 tokens); this is capability beyond parity.

Kernel structure (see /opt/skills/guides/pallas_guide.md):
- forward: grid (BH, nq, nk), kv innermost/sequential; VMEM scratch carries
  the (BQ, Dh) accumulator and (BQ,) running max/sum across kv steps;
  @pl.when(k==0) resets, @pl.when(k==nk-1) finalizes o = acc/l and
  lse = m + log(l).
- backward: two kernels (no atomics on TPU) — dkv with grid (BH, nk, nq)
  accumulating dk/dv over q blocks, and dq with grid (BH, nq, nk); both
  recompute p = exp(s - lse) from the saved logsumexp, flash-style.
- inputs are padded to block multiples; invalid kv columns are masked to -inf
  before the softmax, padded q rows get lse=+inf so p==0 in the backward.
- logits/accumulators in float32 on the MXU (preferred_element_type), outputs
  cast back to the activation dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.attention import (_from_bh, _interpret, _to_bh,
                                 dropout_keep_mask)

NEG_INF = -1e30  # large-but-finite: avoids inf-inf=nan in max/exp chains

"""Block defaults from a sweep on the v5e that predates the ledger (ViT-L
width train steps, a hand-built program): the (512, 1024) pair
won at N=4,096 (79.3 ms vs 102.8 at the untuned (512, 512)) and was within
5% of best at N=9,216 (295.9 vs 280.2 at (1024, 1024)). A taller K block
amortizes the online-softmax rescale chain over more of the KV stream."""
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _col_mask(n_valid_ref, j, bk, s):
    """Mask (…, BK) score columns beyond the valid sequence length to NEG_INF."""
    n_valid = n_valid_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1) + j * bk
    return jnp.where(col < n_valid, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(n_valid_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, bq: int, bk: int,
                nk: int, rate: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (BQ, Dh)
    k = k_ref[0]  # (BK, Dh)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    s = _col_mask(n_valid_ref, j, bk, s)

    m_prev = m_ref[...]           # (BQ, 128) — col 0 is the live value
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)           # (BQ, 1)
    m_new = jnp.maximum(m_prev, m_cur)                   # broadcast over 128 lanes
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])        # (BQ, 1)
    p = jnp.exp(s - m_new[:, :1])                        # (BQ, BK)
    # dropout drops NUMERATOR terms only (the keep-mask at GLOBAL (q, k)
    # coordinates); l accumulates the unmasked p — dense softmax-then-drop
    # semantics, same as the whole-N dropout kernels (vitax/ops/attention.py)
    l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    if rate > 0.0:
        # seed_ref: (3,) uint32 [seed, q0_base, k0_base] — the bases shift
        # the whole mask to GLOBAL token coordinates (ring attention)
        p = p * dropout_keep_mask(
            seed_ref[0], jnp.uint32(pl.program_id(0)), bq, bk, rate,
            q0=seed_ref[1] + jnp.uint32(pl.program_id(1) * bq),
            k0=seed_ref[2] + jnp.uint32(j * bk))
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new[:, :1], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30) * (1.0 - rate)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:, :1] + jnp.log(jnp.maximum(
            l_ref[:, :1], 1e-30)))[:, 0][None, :]


def blocked_fwd_padded(q, k, v, n_valid, scale, bq, bk, seed=None,
                       rate: float = 0.0):
    """q,k,v: (BH, Np, Dh) padded to block multiples; returns (o, lse)."""
    bh, n_pad, dh = q.shape
    nq, nk = n_pad // bq, n_pad // bk
    if seed is None:
        seed = jnp.zeros((3,), jnp.uint32)
    qspec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0))
    lse_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          rate=rate),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # n_valid scalar
            pl.BlockSpec(memory_space=pltpu.SMEM),  # dropout seed scalar
            qspec, kspec, kspec,
        ],
        out_specs=[qspec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_blocked_fwd",
        interpret=_interpret(),
    )(n_valid, seed, q, k, v)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward: dkv kernel (grid b, k-block, q-block) and dq kernel (b, q, k)
# ---------------------------------------------------------------------------

def _dkv_kernel(n_valid_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale: float, bq: int, bk: int, nq: int, rate: float):
    jq = pl.program_id(2)

    @pl.when(jq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0]                      # (BQ, Dh)
    k = k_ref[0]                      # (BK, Dh)
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][0][:, None]      # (BQ, 1)
    delta = delta_ref[0][0][:, None]  # (BQ, 1)
    dlse = dlse_ref[0][0][:, None]    # (BQ, 1) — lse cotangent (ring merge)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    jk = pl.program_id(1)
    s = _col_mask(n_valid_ref, jk, bk, s)
    p = jnp.exp(s - lse)              # (BQ, BK); 0 for padded q rows (lse=+inf)

    if rate > 0.0:
        # regenerate the fwd's keep-mask at this tile's GLOBAL coordinates
        # (same VJP as the whole-N dropout kernels: delta = sum(do*o) still
        # equals the softmax-vjp inner product under the mask)
        ms = dropout_keep_mask(
            seed_ref[0], jnp.uint32(pl.program_id(0)), bq, bk, rate,
            q0=seed_ref[1] + jnp.uint32(jq * bq),
            k0=seed_ref[2] + jnp.uint32(jk * bk)) / (1.0 - rate)
        a = p * ms
    else:
        a = p
    dv_acc[...] += jax.lax.dot_general(  # A^T dO
        a, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(            # dO V^T
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if rate > 0.0:
        dp = dp * ms
    ds = p * (dp - delta + dlse) * scale  # d lse_i/d s_ij = p_ij
    dk_acc[...] += jax.lax.dot_general(  # dS^T Q
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(n_valid_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dlse_ref, dq_ref, dq_acc, *, scale: float, bq: int,
               bk: int, nk: int, rate: float):
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][0][:, None]
    delta = delta_ref[0][0][:, None]
    dlse = dlse_ref[0][0][:, None]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    s = _col_mask(n_valid_ref, jk, bk, s)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if rate > 0.0:
        dp = dp * (dropout_keep_mask(
            seed_ref[0], jnp.uint32(pl.program_id(0)), bq, bk, rate,
            q0=seed_ref[1] + jnp.uint32(pl.program_id(1) * bq),
            k0=seed_ref[2] + jnp.uint32(jk * bk)) / (1.0 - rate))
    ds = p * (dp - delta + dlse) * scale
    dq_acc[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def blocked_bwd_padded(q, k, v, o, lse, do, dlse, n_valid, scale, bq, bk,
                       seed=None, rate: float = 0.0):
    bh, n_pad, dh = q.shape
    nq, nk = n_pad // bq, n_pad // bk
    if seed is None:
        seed = jnp.zeros((3,), jnp.uint32)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (BH, 1, Np)
    lse3 = lse[:, None, :]
    dlse3 = dlse[:, None, :]

    qspec_q = pl.BlockSpec((1, bq, dh), lambda b, jk, jq: (b, jq, 0))
    kspec_k = pl.BlockSpec((1, bk, dh), lambda b, jk, jq: (b, jk, 0))
    row_q = pl.BlockSpec((1, 1, bq), lambda b, jk, jq: (b, 0, jq))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, nq=nq,
                          rate=rate),
        grid=(bh, nk, nq),
        in_specs=[smem, smem,
                  qspec_q, kspec_k, kspec_k, qspec_q, row_q, row_q, row_q],
        out_specs=[kspec_k, kspec_k],
        out_shape=[jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_blocked_dkv",
        interpret=_interpret(),
    )(n_valid, seed, q, k, v, do, lse3, delta, dlse3)

    qspec = pl.BlockSpec((1, bq, dh), lambda b, jq, jk: (b, jq, 0))
    kspec = pl.BlockSpec((1, bk, dh), lambda b, jq, jk: (b, jk, 0))
    row = pl.BlockSpec((1, 1, bq), lambda b, jq, jk: (b, 0, jq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          rate=rate),
        grid=(bh, nq, nk),
        in_specs=[smem, smem,
                  qspec, kspec, kspec, qspec, row, row, row],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, n_pad, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_blocked_dq",
        interpret=_interpret(),
    )(n_valid, seed, q, k, v, do, lse3, delta, dlse3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# padding wrapper + custom VJP
# ---------------------------------------------------------------------------

def _pad_len(n: int, block: int) -> int:
    return (n + block - 1) // block * block


def _pad_seq(x, n_pad):
    n = x.shape[1]
    if n == n_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))


def _blocked_fwd_impl(q, k, v, scale, bq, bk, seed=None, rate=0.0):
    n = q.shape[1]
    n_pad = _pad_len(n, math.lcm(bq, bk))  # both grids must tile evenly
    n_valid = jnp.asarray([n], jnp.int32)
    o, lse = blocked_fwd_padded(
        _pad_seq(q, n_pad), _pad_seq(k, n_pad), _pad_seq(v, n_pad),
        n_valid, scale, bq, bk, seed=seed, rate=rate)
    return o[:, :n], lse[:, :n]


def _blocked_bwd_impl(q, k, v, o, lse, do, dlse, scale, bq, bk, seed=None,
                      rate=0.0):
    n = q.shape[1]
    n_pad = _pad_len(n, math.lcm(bq, bk))
    n_valid = jnp.asarray([n], jnp.int32)
    pad = n_pad - n
    # padded q rows: lse=+inf makes p=exp(s-lse)=0, do=0 kills dv terms
    lse_p = jnp.pad(lse, ((0, 0), (0, pad)), constant_values=jnp.inf)
    dlse_p = jnp.pad(dlse, ((0, 0), (0, pad)))
    dq, dk, dv = blocked_bwd_padded(
        _pad_seq(q, n_pad), _pad_seq(k, n_pad), _pad_seq(v, n_pad),
        _pad_seq(o, n_pad), lse_p, _pad_seq(do, n_pad), dlse_p,
        n_valid, scale, bq, bk, seed=seed, rate=rate)
    return dq[:, :n], dk[:, :n], dv[:, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blocked_bh_with_lse(q, k, v, scale, bq, bk):
    """(BH, N, Dh) streaming attention returning (o, lse); differentiable in
    both outputs (the lse cotangent feeds the backward kernels) — composes with
    ring attention's logsumexp merge for local blocks beyond the whole-N
    kernel's VMEM ceiling."""
    return _blocked_fwd_impl(q, k, v, scale, bq, bk)


def _blocked_bh_fwd(q, k, v, scale, bq, bk):
    o, lse = _blocked_fwd_impl(q, k, v, scale, bq, bk)
    return (o, lse), (q, k, v, o, lse)


def _blocked_bh_bwd(scale, bq, bk, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _blocked_bwd_impl(q, k, v, o, lse, do, dlse, scale, bq, bk)


blocked_bh_with_lse.defvjp(_blocked_bh_fwd, _blocked_bh_bwd)


def _blocked_bh(q, k, v, scale, bq, bk):
    return blocked_bh_with_lse(q, k, v, scale, bq, bk)[0]


def blocked_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Streaming flash attention; (B, N, H, Dh) -> (B, N, H, Dh),
    differentiable, VMEM use independent of N."""
    from vitax.ops.attention import _from_bh, _to_bh

    n, dh = q.shape[1], q.shape[3]
    scale = dh ** -0.5
    bq = min(block_q, _pad_len(n, 128))
    bk = min(block_k, _pad_len(n, 128))
    o = _blocked_bh(_to_bh(q), _to_bh(k), _to_bh(v), scale, bq, bk)
    return _from_bh(o, q.shape)


# ---------------------------------------------------------------------------
# streaming attention with in-kernel dropout (round 5)
# ---------------------------------------------------------------------------
# The whole-N dropout kernels cap at MAX_SEQ_IN_VMEM; past it this variant
# keeps --att_dropout on the fused path too. The keep-mask is the SAME
# counter-hash as vitax/ops/attention.py, evaluated at each tile's GLOBAL
# (q, k) coordinates — the fwd's kv-streaming tiles and both backward
# kernels' differently-shaped tiles all regenerate identical decisions, so
# no mask residual exists anywhere. Dense semantics: mask the numerator
# terms, keep l/lse unmasked, divide by (1 - rate) at the end.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def blocked_bh_dropout_lse(q, k, v, seedvec, scale, rate, bq, bk):
    """(BH, N, Dh) streaming attention with attention dropout, returning
    (o, lse); differentiable in both outputs (ring attention's merge).
    seedvec: (3,) uint32 [seed, q0, k0] (vitax.ops.attention._seedvec)."""
    return _blocked_fwd_impl(q, k, v, scale, bq, bk, seed=seedvec,
                             rate=rate)


def _blocked_drop_fwd(q, k, v, seedvec, scale, rate, bq, bk):
    o, lse = _blocked_fwd_impl(q, k, v, scale, bq, bk, seed=seedvec,
                               rate=rate)
    return (o, lse), (q, k, v, o, lse, seedvec)


def _blocked_drop_bwd(scale, rate, bq, bk, res, cts):
    import numpy as np
    q, k, v, o, lse, seedvec = res
    do, dlse = cts
    dq, dk, dv = _blocked_bwd_impl(
        q, k, v, o, lse, do, dlse, scale, bq, bk, seed=seedvec, rate=rate)
    return dq, dk, dv, np.zeros(seedvec.shape, jax.dtypes.float0)


blocked_bh_dropout_lse.defvjp(_blocked_drop_fwd, _blocked_drop_bwd)


def blocked_bh_dropout(q, k, v, seed, scale, rate, bq, bk):
    """(BH, N, Dh) streaming attention with attention dropout; seed is a
    traced uint32 scalar."""
    from vitax.ops.attention import _seedvec
    return blocked_bh_dropout_lse(q, k, v, _seedvec(seed), scale, rate,
                                  bq, bk)[0]


def blocked_dropout_attention(q, k, v, seed, rate: float,
                              block_q: int = DEFAULT_BLOCK_Q,
                              block_k: int = DEFAULT_BLOCK_K):
    """Streaming flash attention with in-kernel attention dropout;
    (B, N, H, Dh) -> (B, N, H, Dh), differentiable in q/k/v."""
    from vitax.ops.attention import _from_bh, _to_bh

    n, dh = q.shape[1], q.shape[3]
    scale = dh ** -0.5
    bq = min(block_q, _pad_len(n, 128))
    bk = min(block_k, _pad_len(n, 128))
    o = blocked_bh_dropout(_to_bh(q), _to_bh(k), _to_bh(v), seed, scale,
                           rate, bq, bk)
    return _from_bh(o, q.shape)


# ---------------------------------------------------------------------------
# packed rows: segment-masked streaming attention that skips dead block pairs
# ---------------------------------------------------------------------------
# A packed row holds several images back to back (vitax/data/packing.py):
# `segment_ids` (R, T) int32 names each token's image (1, 2, ...) and 0 marks
# padding. Attention is block-diagonal: a token sees the tokens of its own
# image only, padding sees nothing and nothing sees it (its output and its
# gradients are zero). The three kernels are the streaming ones above with
# - the mask `segment_q == segment_k != 0` inside a block, in place of the
#   count of valid columns;
# - a table of live (q-block, k-block) pairs, from each block's smallest and
#   largest segment id, passed as scalar prefetch: a dead pair runs no
#   matmul, and its index map names the block the pipeline already holds, so
#   it moves no bytes either. The work follows sum(n_i^2), not T^2;
# - several heads of one row a grid step (they share the row's mask and its
#   table), so that what a dead step still costs is paid once for all of them.
# Matmul operands stay in the input dtype with float32 accumulation, as in
# the whole-N 4D kernels; softmax and score math is float32.

MASKED = 2 * NEG_INF  # a masked score: exp(MASKED - m) is 0 even while the
#   running max m is still NEG_INF (a row that has met no key of its own yet)

"""Measured block defaults (my chip runs, PR 26: v5e, 32 x 8,192 x 72 bf16,
the MoonViT cell's nine images, kernels alone): forward 9.07 ms at (512, 512)
with 4 heads a step, 5.91 at (512, 1024), 5.67 with 8 heads, 5.55 with 16;
(512, 2048) 5.45-5.64; (1024, 4096) 6.80. Backward (dK/dV + dQ) 13.73 ms at
(512, 512) x 4, 12.45 at (512, 1024) x 8, 12.15 x 16, 13.5-14.7 at 1,024 or
2,048 rows of q. The curve is flat past (512, 1024) x 8, which leaves VMEM
to spare; with every pair live the same kernels take 2.1 times as long."""
PACKED_BLOCK_Q = 512
PACKED_BLOCK_K = 1024
PACKED_HEADS_PER_STEP = 8
PACKED_VMEM_LIMIT = 64 * 1024 * 1024  # of the v5e's 128 MiB


def packed_block_tables(segment_ids, bq: int, bk: int, skip: bool = True,
                        causal: bool = False, window: int = 0):
    """What the kernels' grids read as scalar prefetch, from a row's
    `segment_ids` (R, T), T a multiple of both blocks. A (q-block, k-block)
    pair is live when the blocks' ranges of non-zero segment ids meet. For
    the grids that stream k blocks (forward, dQ): `live_qk`, and `kidx`, the
    k block to hold at each step (the latest live one, so a dead step
    fetches nothing); for the grid that streams q blocks (dK/dV): `live_kq`
    and `qidx`. All flat int32. `skip=False` calls every pair live (the
    tests' and the measurements' comparison arm). `causal`: a pair whose
    every key lies after its every query is dead too, and with `window` > 0
    one whose every key lies `window` or more positions back (a document is
    contiguous in its row, so positions in the row serve)."""
    r, t = segment_ids.shape
    nq, nk = t // bq, t // bk
    big = jnp.iinfo(jnp.int32).max

    def ranges(block):
        s = segment_ids.reshape(r, t // block, block)
        return jnp.min(jnp.where(s > 0, s, big), axis=-1), jnp.max(s, axis=-1)

    qlo, qhi = ranges(bq)
    klo, khi = ranges(bk)
    live = ((qlo[:, :, None] <= khi[:, None, :])
            & (klo[:, None, :] <= qhi[:, :, None]))          # (R, nq, nk)
    if causal:
        q0 = jnp.arange(nq, dtype=jnp.int32)[:, None] * bq   # a block's first
        k0 = jnp.arange(nk, dtype=jnp.int32)[None, :] * bk
        near = k0 <= q0 + bq - 1
        if window > 0:
            near = near & (k0 + bk - 1 > q0 - window)
        live = live & near[None]
    if not skip:
        live = jnp.ones_like(live)

    def held(live):  # along the streamed (last) axis
        n = live.shape[-1]
        latest = jax.lax.cummax(
            jnp.where(live, jnp.arange(n, dtype=jnp.int32), -1),
            axis=live.ndim - 1)
        first = jnp.argmax(live, axis=-1).astype(jnp.int32)[..., None]
        return jnp.where(latest >= 0, latest, first)

    live_kq = live.transpose(0, 2, 1)
    flat = lambda x: x.astype(jnp.int32).reshape(-1)  # noqa: E731
    return flat(live), flat(held(live)), flat(live_kq), flat(held(live_kq))


def _segment_mask(qseg_ref, kseg_ref):
    """(BQ, BK) bool from the q block's ids, broadcast over lanes
    (1, BQ, 128), and the k block's, broadcast over sublanes (1, 8, BK)."""
    seg_q = qseg_ref[0][:, :1]
    seg_k = kseg_ref[0][:1, :]
    return (seg_q == seg_k) & (seg_q > 0)


def _block_mask(qseg_ref, kseg_ref, i, j, causal: bool, window: int):
    """The (BQ, BK) mask of block pair (i, j): the segment mask and, for a
    decoder (`causal`), a key not after its query and, with `window` > 0,
    fewer than `window` positions back."""
    mask = _segment_mask(qseg_ref, kseg_ref)
    if not causal:
        return mask
    bq, bk = qseg_ref.shape[1], kseg_ref.shape[2]
    back = (i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            - j * bk - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    mask = mask & (back >= 0)
    return mask & (back < window) if window > 0 else mask


def _packed_fwd_kernel(live_ref, kidx_ref, q_ref, k_ref, v_ref, qseg_ref,
                       kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                       scale: float, hb: int, gpr: int, nq: int, nk: int,
                       causal: bool = False, window: int = 0,
                       grouped: bool = False):
    del kidx_ref  # read by the index maps
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)  # the k/v head q head h reads

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live_ref[((b // gpr) * nq + i) * nk + j] != 0)
    def _():
        mask = _block_mask(qseg_ref, kseg_ref, i, j, causal, window)

        def head(h, carry):
            q, k, v = q_ref[h], k_ref[kv(h)], v_ref[kv(h)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, MASKED)
            m_prev, l_prev = m_ref[h], l_ref[h]      # (BQ, 128), col 0 live
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
            p = jnp.exp(s - m_new[:, :1])
            l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new[:, :1], m_prev.shape)
            l_ref[h] = jnp.broadcast_to(l_new, l_prev.shape)
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(j == nk - 1)
    def _():
        def head(h, carry):
            # a row with no key (padding) has l == 0: its output is 0 and its
            # lse stays near NEG_INF, where the backward's exp(s - lse) is 0
            l = jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[h] = (m_ref[h][:, :1] + jnp.log(l))[:, 0][None, :]
            return carry

        jax.lax.fori_loop(0, hb, head, 0)


def _packed_p_ds(q, k, v, do, lse_row, delta_row, mask, scale):
    """What both backward kernels recompute for one head's block pair: the
    probabilities P = exp(S - lse) and dS = P * (dO V^T - delta) * scale,
    both (BQ, BK) in the input dtype (the MXU's operands). lse / delta
    arrive as (1, BQ) rows."""
    lse = lse_row[0][:, None]                         # (BQ, 1)
    delta = delta_row[0][:, None]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(jnp.where(mask, s, MASKED) - lse)
    dp = jax.lax.dot_general(                         # dO V^T
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p.astype(q.dtype), ds.astype(q.dtype)


def _packed_dkv_kernel(live_ref, qidx_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, qseg_ref, kseg_ref, dk_ref, dv_ref,
                       dk_acc, dv_acc, *, scale: float, hb: int, gpr: int,
                       nq: int, nk: int, causal: bool = False,
                       window: int = 0, grouped: bool = False):
    del qidx_ref
    b, jk, jq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)

    @pl.when(jq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(live_ref[((b // gpr) * nk + jk) * nq + jq] != 0)
    def _():
        mask = _block_mask(qseg_ref, kseg_ref, jq, jk, causal, window)

        def head(h, carry):
            p, ds = _packed_p_ds(q_ref[h], k_ref[kv(h)], v_ref[kv(h)],
                                 do_ref[h], lse_ref[h], delta_ref[h], mask,
                                 scale)
            dv_acc[kv(h)] += jax.lax.dot_general(     # P^T dO
                p, do_ref[h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[kv(h)] += jax.lax.dot_general(     # dS^T Q
                ds, q_ref[h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(jq == nq - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _packed_dq_kernel(live_ref, kidx_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, qseg_ref, kseg_ref, dq_ref, dq_acc,
                      *, scale: float, hb: int, gpr: int, nq: int, nk: int,
                      causal: bool = False, window: int = 0,
                      grouped: bool = False):
    del kidx_ref
    b, jq, jk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)

    @pl.when(jk == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(live_ref[((b // gpr) * nq + jq) * nk + jk] != 0)
    def _():
        mask = _block_mask(qseg_ref, kseg_ref, jq, jk, causal, window)

        def head(h, carry):
            _, ds = _packed_p_ds(q_ref[h], k_ref[kv(h)], v_ref[kv(h)],
                                 do_ref[h], lse_ref[h], delta_ref[h], mask,
                                 scale)
            dq_acc[h] += jax.lax.dot_general(         # dS K
                ds, k_ref[kv(h)], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(jk == nk - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _packed_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=PACKED_VMEM_LIMIT)


def _decoder_terms(causal, window, grouped) -> dict:
    """The kernels' keywords beyond the packed ViT's: none where all are off,
    so that model's kernels are traced as they were."""
    if not (causal or window or grouped):
        return {}
    return dict(causal=causal, window=window, grouped=grouped)


def _segment_tiles(segment_ids):
    """The ids as the kernels read them: (R, T, 128) for a q block's column,
    (R, 8, T) for a k block's row (the layouts of jax's own TPU flash
    attention: no relayout of an integer vector inside the kernel)."""
    r, t = segment_ids.shape
    return (jnp.broadcast_to(segment_ids[:, :, None], (r, t, 128)),
            jnp.broadcast_to(segment_ids[:, None, :], (r, 8, t)))


def _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip,
                causal=False, window=0, grouped=False, name="flash_packed"):
    """`grouped`: k and v hold ONE head for each grid step's `hb` query
    heads (grouped-query attention: (R * KV, T, Dh) beside q's (R * H, T,
    Dh), hb = H / KV), read inside the kernel: no repeated K or V exists."""
    bh, t, dh = q.shape
    nq, nk, gpr = t // bq, t // bk, heads // hb
    hk = 1 if grouped else hb
    live, kidx, _, _ = packed_block_tables(segment_ids, bq, bk, skip, causal,
                                           window)
    qseg, kseg = _segment_tiles(segment_ids)

    def at_k(b, i, j, live, kidx):
        return kidx[((b // gpr) * nq + i) * nk + j]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, i, j, *_: (b, i, 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, i, j, *t: (b, at_k(b, i, j, *t), 0))
    o, lse = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, **_decoder_terms(causal, window,
                                                         grouped)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nq, nk),
            in_specs=[
                qspec, kspec, kspec,
                pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b // gpr, i, 0)),
                pl.BlockSpec((1, 8, bk),
                             lambda b, i, j, *t: (b // gpr, 0, at_k(b, i, j, *t))),
            ],
            out_specs=[
                qspec,
                pl.BlockSpec((hb, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hb, bq, dh), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        compiler_params=_packed_params(),
        name=f"{name}_fwd",
        interpret=_interpret(),
    )(live, kidx, q, k, v, qseg, kseg)
    return o, lse


def _packed_bwd(q, k, v, o, lse, do, segment_ids, scale, bq, bk, hb, heads,
                skip, causal=False, window=0, grouped=False,
                name="flash_packed"):
    bh, t, dh = q.shape
    nq, nk, gpr = t // bq, t // bk, heads // hb
    hk = 1 if grouped else hb
    terms = _decoder_terms(causal, window, grouped)
    live_qk, kidx, live_kq, qidx = packed_block_tables(segment_ids, bq, bk,
                                                       skip, causal, window)
    qseg, kseg = _segment_tiles(segment_ids)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                       # (BH, 1, T)

    def at_q(b, jk, jq, live, qidx):
        return qidx[((b // gpr) * nk + jk) * nq + jq]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, jk, jq, *t: (b, at_q(b, jk, jq, *t), 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, jk, jq, *_: (b, jk, 0))
    row = pl.BlockSpec((hb, 1, bq), lambda b, jk, jq, *t: (b, 0, at_q(b, jk, jq, *t)))
    dk, dv = pl.pallas_call(
        functools.partial(_packed_dkv_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, **terms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nk, nq),
            in_specs=[
                qspec, kspec, kspec, qspec, row, row,
                pl.BlockSpec((1, bq, 128),
                             lambda b, jk, jq, *t: (b // gpr, at_q(b, jk, jq, *t), 0)),
                pl.BlockSpec((1, 8, bk), lambda b, jk, jq, *_: (b // gpr, 0, jk)),
            ],
            out_specs=[kspec, kspec],
            scratch_shapes=[pltpu.VMEM((hk, bk, dh), jnp.float32),
                            pltpu.VMEM((hk, bk, dh), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, q.dtype)] * 2,
        compiler_params=_packed_params(),
        name=f"{name}_dkv",
        interpret=_interpret(),
    )(live_kq, qidx, q, k, v, do, lse, delta, qseg, kseg)

    def at_k(b, jq, jk, live, kidx):
        return kidx[((b // gpr) * nq + jq) * nk + jk]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, jq, jk, *_: (b, jq, 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, jq, jk, *t: (b, at_k(b, jq, jk, *t), 0))
    row = pl.BlockSpec((hb, 1, bq), lambda b, jq, jk, *_: (b, 0, jq))
    dq = pl.pallas_call(
        functools.partial(_packed_dq_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, **terms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nq, nk),
            in_specs=[
                qspec, kspec, kspec, qspec, row, row,
                pl.BlockSpec((1, bq, 128), lambda b, jq, jk, *_: (b // gpr, jq, 0)),
                pl.BlockSpec((1, 8, bk),
                             lambda b, jq, jk, *t: (b // gpr, 0, at_k(b, jq, jk, *t))),
            ],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((hb, bq, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, t, dh), q.dtype),
        compiler_params=_packed_params(),
        name=f"{name}_dq",
        interpret=_interpret(),
    )(live_qk, kidx, q, k, v, do, lse, delta, qseg, kseg)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _packed_bh(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip):
    return _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads,
                       skip)[0]


def _packed_bh_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip):
    o, lse = _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip)
    return o, (q, k, v, o, lse, segment_ids)


def _packed_bh_bwd(scale, bq, bk, hb, heads, skip, res, do):
    import numpy as np
    q, k, v, o, lse, segment_ids = res
    dq, dk, dv = _packed_bwd(q, k, v, o, lse, do, segment_ids, scale, bq, bk,
                             hb, heads, skip)
    return dq, dk, dv, np.zeros(segment_ids.shape, jax.dtypes.float0)


_packed_bh.defvjp(_packed_bh_fwd, _packed_bh_bwd)


def packed_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           segment_ids: jax.Array,
                           block_q: int = PACKED_BLOCK_Q,
                           block_k: int = PACKED_BLOCK_K,
                           skip: bool = True) -> jax.Array:
    """Attention within each image of a packed row: (R, T, H, Dh) q/k/v and
    (R, T) int32 segment ids (0 = padding) -> (R, T, H, Dh), differentiable
    in q/k/v. Padding rows come back zero. `block_q`/`block_k`/`skip` exist
    for the tests (small rows that still span blocks; the every-pair arm)."""
    r, t, h, dh = q.shape
    bq = min(block_q, _pad_len(t, 128))
    bk = min(block_k, _pad_len(t, 128))
    t_pad = _pad_len(t, math.lcm(bq, bk))
    hb = math.gcd(h, PACKED_HEADS_PER_STEP)
    seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, t_pad - t)))
    qb, kb, vb = (_pad_seq(_to_bh(x), t_pad) for x in (q, k, v))
    o = _packed_bh(qb, kb, vb, seg, dh ** -0.5, bq, bk, hb, h, skip)
    return _from_bh(o[:, :t], q.shape)


# ---------------------------------------------------------------------------
# packed token documents: the same kernels with causal, window and KV groups
# ---------------------------------------------------------------------------
# A decoder's packed row holds documents back to back (vitax/data/packing.py:
# document_layout). The packed kernels above gain three mask terms (a key in
# the query's own document, not after it, and in a sliding layer fewer than
# `window` positions back), the block table the same terms by block (so a
# sliding layer runs only the block pairs inside its window), and grouped
# key/value heads: the H / KV query heads that share a key/value head are one
# grid step's heads and read that one head's block (`grouped`), so K and V
# are never repeated in memory. They carry names of their own,
# `flash_causal_*` and `flash_window_*`: one body, told apart in a trace.

"""Block defaults, not yet swept on the chip: a full layer takes the packed
kernels' (512, 1024); a sliding layer of window 512 takes (512, 512), where
a q block meets two k blocks (its own and the one before) and half of what
they compute is inside the window."""
CAUSAL_BLOCKS = (512, 1024)
WINDOW_BLOCKS = (512, 512)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _documents_bh(q, k, v, segment_ids, scale, bq, bk, group, heads, skip,
                  window):
    return _packed_fwd(q, k, v, segment_ids, scale, bq, bk, group, heads, skip,
                       **_documents_terms(window))[0]


def _documents_terms(window: int) -> dict:
    return dict(causal=True, window=window, grouped=True,
                name="flash_window" if window > 0 else "flash_causal")


def _documents_bh_fwd(q, k, v, segment_ids, scale, bq, bk, group, heads, skip,
                      window):
    o, lse = _packed_fwd(q, k, v, segment_ids, scale, bq, bk, group, heads,
                         skip, **_documents_terms(window))
    return o, (q, k, v, o, lse, segment_ids)


def _documents_bh_bwd(scale, bq, bk, group, heads, skip, window, res, do):
    import numpy as np
    q, k, v, o, lse, segment_ids = res
    dq, dk, dv = _packed_bwd(q, k, v, o, lse, do, segment_ids, scale, bq, bk,
                             group, heads, skip, **_documents_terms(window))
    return dq, dk, dv, np.zeros(segment_ids.shape, jax.dtypes.float0)


_documents_bh.defvjp(_documents_bh_fwd, _documents_bh_bwd)


def document_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             segment_ids: jax.Array, window: int = 0,
                             block_q: int = 0, block_k: int = 0,
                             skip: bool = True) -> jax.Array:
    """Causal attention within each document of a packed row: q (R, T, H, Dh),
    k and v (R, T, KV, Dh) with H a multiple of KV, (R, T) int32 segment ids
    (0 = padding) -> (R, T, H, Dh), differentiable in q/k/v. `window` > 0: a
    query sees the `window` latest keys of its document, itself included.
    Padding rows come back zero. `block_q`/`block_k`/`skip` exist for the
    tests."""
    r, t, h, dh = q.shape
    kv = k.shape[2]
    assert h % kv == 0, (h, kv)
    dq, dk = WINDOW_BLOCKS if window > 0 else CAUSAL_BLOCKS
    bq = min(block_q or dq, _pad_len(t, 128))
    bk = min(block_k or dk, _pad_len(t, 128))
    t_pad = _pad_len(t, math.lcm(bq, bk))
    seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, t_pad - t)))
    qb, kb, vb = (_pad_seq(_to_bh(x), t_pad) for x in (q, k, v))
    o = _documents_bh(qb, kb, vb, seg, dh ** -0.5, bq, bk, h // kv, h, skip,
                      int(window))
    return _from_bh(o[:, :t], q.shape)
