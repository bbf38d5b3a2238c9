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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.attention import _from_bh, _to_bh, dropout_keep_mask
from vitax.ops.common import interpret as _interpret

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
#   table), so that what a dead step still costs is paid once for all of them;
# - a second level of liveness inside a live pair (PR 33): the table holds a
#   bit for each (sq, sk) sub-tile of the pair, by the same rule one level
#   down, and the body runs the sub-tiles alone whose bit is set; one scalar
#   branch a sub-tile, shared by the step's heads, which are unrolled inside
#   it. What made small tiles pay is in the bodies: the forward keeps its
#   running max and sum in all 128 lanes and never narrows them to one
#   (`_lanes`); dK/dV computes its scores keys down, queries across, so that
#   no score tile is transposed and lse / delta are used as the rows they are.
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

"""Sub-tile shapes (sq, sk) of each kernel: inside a live block pair the body
walks tiles of this shape and runs those alone that hold a pair some query
may see (`packed_block_tables`, `sub`). Chosen by kernel and by `window` > 0
or not, which is all the code sees; `tools/sweep_packed_tiles.py` measures
them. My chip runs, PR 33 (v5e, bf16, kernels alone, ms a call; "whole" is
one sub-tile a pair; in brackets the area computed over the pairs needed).

The MoonViT cell's rows (32 x 8,192 x 72, nine images, blocks (512, 1024)).
PR 26's bodies: forward 5.62, dK/dV 7.26, dQ 5.84, and sub-tiles in them
LOST: forward 8.53 at (512, 512) and 14.96 at (256, 256), dK/dV 6.83 and
10.23, dQ 5.55 and 7.35. An update's fixed costs hid the area: narrowing the
running max and sum to one lane and broadcasting them again (forward), the
transposes of P and dS (dK/dV), a loop over heads that ran one head's
matmuls and softmax in turn. With the statistics held in all lanes, scores
keys-down in dK/dV and the heads unrolled:
  forward  whole 4.32 [1.55] | (256, 512) 3.99 [1.28] | (256, 1024) 4.11 |
           (512, 512) 4.19 | (256, 256) 4.36 [1.19] | (128, 256) 4.85 |
           (128, 128) 6.59 [1.09]
  dK/dV    whole 6.91 | (256, 256) 5.72 | (512, 128) 5.90 | (256, 512) 5.93 |
           (512, 512) 6.32 | (128, 256) 6.29 | (128, 128) 6.99
  dQ       whole 5.72 | (256, 512) 4.83 | (128, 512) 4.91 | (256, 256) 4.92 |
           (512, 512) 5.06 | (128, 256) 6.00 | sk = 128: 8.2-8.5
18.72 ms a layer before, 14.55 now. Blocks of (1024, 1024) with the same
tiles: forward 3.90, dQ 4.71, dK/dV 7.55: not taken.

The Laguna cell's row, full layers (48 x 8,192 x 128 over 8 key/value heads,
causal, blocks (512, 1024)); before: 5.28 / 6.25 / 5.35.
  forward  whole 3.98 [1.49] | (512, 512) 3.85 [1.33] | (256, 512) 3.92 |
           (256, 256) 4.36 [1.18] | (128, 128) 6.98
  dK/dV    whole 5.82 | (256, 256) 4.79 | (512, 128) 5.06 | (256, 512) 5.17 |
           (512, 512) 5.30 | (128, 128) 6.58
  dQ       whole 4.81 | (512, 512) 4.43 | (256, 256) 4.44 | (256, 512) 4.50 |
           (128, 256) 5.58
(512, 512) would suit its forward and dQ 2% better; one table serves both
models, and the MoonViT rows lose 5% there.

Sliding layers (64 x 8,192 x 128 over 8, window 512): see `WINDOW_BLOCKS`.
(128, x) tiles lose everywhere: a 128-row left operand pays an MXU weight
load for every 128 rows it streams."""


class Tiles(NamedTuple):
    """One value a kernel: forward, dK/dV, dQ."""
    fwd: tuple
    dkv: tuple
    dq: tuple


PACKED_TILES = Tiles(fwd=(256, 512), dkv=(256, 256), dq=(256, 512))
WINDOW_TILES = Tiles(fwd=(256, 256), dkv=(256, 256), dq=(256, 256))
# the matmuls a kernel runs on every score tile it computes (QK^T and PV;
# QK^T, dO V^T, P^T dO, dS^T Q; QK^T, dO V^T, dS K): the weights of
# `computed_pairs`
TILE_MATMULS = Tiles(fwd=2, dkv=4, dq=3)


def _sub_tiles(window: int, bq: int, bk: int) -> Tiles:
    """Each kernel's sub-tile shape for blocks (bq, bk): the constants above,
    cut to what divides the blocks."""
    return Tiles(*((math.gcd(sq, bq), math.gcd(sk, bk))
                   for sq, sk in (WINDOW_TILES if window > 0 else PACKED_TILES)))


def _blocks_for(t: int, block_q: int, block_k: int):
    """(bq, bk, padded T) for rows of `t` tokens."""
    bq = min(block_q, _pad_len(t, 128))
    bk = min(block_k, _pad_len(t, 128))
    return bq, bk, _pad_len(t, math.lcm(bq, bk))


def _pairs_live(segment_ids, bq: int, bk: int, causal: bool, window: int):
    """(R, T / bq, T / bk) bool: which (bq, bk) tiles of a row's score
    matrix hold a pair some query may see, by the tiles' ranges alone."""
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
        q0 = jnp.arange(nq, dtype=jnp.int32)[:, None] * bq   # a tile's first
        k0 = jnp.arange(nk, dtype=jnp.int32)[None, :] * bk
        near = k0 <= q0 + bq - 1
        if window > 0:
            near = near & (k0 + bk - 1 > q0 - window)
        live = live & near[None]
    return live


def packed_block_tables(segment_ids, bq: int, bk: int, skip: bool = True,
                        causal: bool = False, window: int = 0, sub=None):
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
    contiguous in its row, so positions in the row serve).

    `sub` = (sq, sk), dividing the blocks into at most 32 sub-tiles a pair:
    the same rule one level down. `live_qk` / `live_kq` then hold, for each
    pair, a bit for each of its sub-tiles (row-major: bit a * (bk / sk) + c
    for rows a * sq and columns c * sk on), set where the sub-tile holds a
    pair some query may see. A sub-tile is live only inside a live pair, and
    non-zero still reads "live"; `kidx` / `qidx` keep following the blocks'
    own ranges. Without `sub` a pair is its one sub-tile: 0 or 1."""
    r, t = segment_ids.shape
    nq, nk = t // bq, t // bk
    live = _pairs_live(segment_ids, bq, bk, causal, window)
    if not skip:
        live = jnp.ones_like(live)

    def held(live):  # along the streamed (last) axis
        n = live.shape[-1]
        latest = jax.lax.cummax(
            jnp.where(live, jnp.arange(n, dtype=jnp.int32), -1),
            axis=live.ndim - 1)
        first = jnp.argmax(live, axis=-1).astype(jnp.int32)[..., None]
        return jnp.where(latest >= 0, latest, first)

    bits = live.astype(jnp.int32)
    if sub is not None and sub != (bq, bk):
        sq, sk = sub
        na, nc = bq // sq, bk // sk
        assert bq % sq == 0 and bk % sk == 0 and na * nc <= 32, (bq, bk, sub)
        tiles = (_pairs_live(segment_ids, sq, sk, causal, window)
                 if skip else jnp.ones((r, t // sq, t // sk), bool))
        tiles = tiles.reshape(r, nq, na, nk, nc).transpose(0, 1, 3, 2, 4)
        bits = jax.lax.bitcast_convert_type(jnp.sum(
            tiles.reshape(r, nq, nk, na * nc).astype(jnp.uint32)
            << jnp.arange(na * nc, dtype=jnp.uint32), axis=-1,
            dtype=jnp.uint32), jnp.int32)
    live_kq = live.transpose(0, 2, 1)
    flat = lambda x: x.astype(jnp.int32).reshape(-1)  # noqa: E731
    return (flat(bits), flat(held(live)), flat(bits.transpose(0, 2, 1)),
            flat(held(live_kq)))


def _tile_mask(qseg_ref, kseg_ref, i, j, r0: int, c0: int, sq: int, sk: int,
               causal: bool, window: int, keys_down: bool = False):
    """The mask of the (sq, sk) sub-tile at rows `r0`, columns `c0` of block
    pair (i, j): the segment mask and, for a decoder (`causal`), a key not
    after its query and, with `window` > 0, fewer than `window` positions
    back. Queries down and keys across, (sq, sk), from the q block's ids
    broadcast over lanes (1, BQ, 128) and the k block's over sublanes
    (1, 8, BK); `keys_down`: the transpose, (sk, sq), from the q block's ids
    as (1, 8, BQ) and the k block's as (1, BK, 128)."""
    if keys_down:
        seg_q, seg_k = qseg_ref[0, :1, r0:r0 + sq], kseg_ref[0, c0:c0 + sk, :1]
        shape, bq, bk = (sk, sq), qseg_ref.shape[2], kseg_ref.shape[1]
    else:
        seg_q, seg_k = qseg_ref[0, r0:r0 + sq, :1], kseg_ref[0, :1, c0:c0 + sk]
        shape, bq, bk = (sq, sk), qseg_ref.shape[1], kseg_ref.shape[2]
    mask = (seg_q == seg_k) & (seg_q > 0)
    if not causal:
        return mask
    back = (i * bq + r0 - j * bk - c0
            + jax.lax.broadcasted_iota(jnp.int32, shape, int(keys_down))
            - jax.lax.broadcasted_iota(jnp.int32, shape, int(not keys_down)))
    mask = mask & (back >= 0)
    return mask & (back < window) if window > 0 else mask


def _each_live_tile(bits, bq: int, bk: int, sub, tile):
    """Inside a live block pair: `tile(r0, c0)` for every (sq, sk) sub-tile
    whose bit of `bits` (packed_block_tables) is set, rows before columns.
    The branch is a scalar's, taken once for all heads of the step."""
    sq, sk = sub
    if (sq, sk) == (bq, bk):
        return tile(0, 0)
    starts = [(r0, c0) for r0 in range(0, bq, sq) for c0 in range(0, bk, sk)]
    for n, (r0, c0) in enumerate(starts):
        pl.when(((bits >> n) & 1) != 0)(functools.partial(tile, r0, c0))


def _lanes(x, width: int):
    """A statistic held equal in all 128 lanes, (rows, 128), as (rows,
    width): a slice or whole copies, never a broadcast out of one lane."""
    if width <= 128:
        return x[:, :width]
    assert width % 128 == 0, width
    return pltpu.repeat(x, width // 128, axis=1)


def _packed_fwd_kernel(live_ref, kidx_ref, q_ref, k_ref, v_ref, qseg_ref,
                       kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                       scale: float, hb: int, gpr: int, nq: int, nk: int,
                       sub, causal: bool = False, window: int = 0,
                       grouped: bool = False):
    del kidx_ref  # read by the index maps
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)  # the k/v head q head h reads
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    sq, sk = sub

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    bits = live_ref[((b // gpr) * nq + i) * nk + j]

    @pl.when(bits != 0)
    def _():
        def tile(r0, c0):
            # rows are independent: one online-softmax update of the `sq`
            # rows at r0 with the `sk` keys at c0. The running max and sum
            # live in all 128 lanes of m_ref / l_ref and are used that way
            # (`_lanes`): picking one lane out and broadcasting it again,
            # three times an update, cost more than the scores of 512 keys.
            # The heads are unrolled, not looped over: one head's matmuls
            # run beside another's softmax.
            rows, cols = slice(r0, r0 + sq), slice(c0, c0 + sk)
            mask = _tile_mask(qseg_ref, kseg_ref, i, j, r0, c0, sq, sk,
                              causal, window)
            for h in range(hb):
                q, k, v = q_ref[h, rows], k_ref[kv(h), cols], v_ref[kv(h), cols]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, MASKED)
                m_prev, l_prev = m_ref[h, rows], l_ref[h, rows]    # (sq, 128)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - _lanes(m_new, sk))
                l_ref[h, rows] = alpha * l_prev + jnp.sum(p, axis=-1,
                                                          keepdims=True)
                m_ref[h, rows] = m_new
                acc_ref[h, rows] = (
                    acc_ref[h, rows] * _lanes(alpha, v.shape[-1])
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

        _each_live_tile(bits, bq, bk, sub, tile)

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


def _packed_dkv_kernel(live_ref, qidx_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, qseg_ref, kseg_ref, dk_ref, dv_ref,
                       dk_acc, dv_acc, *, scale: float, hb: int, gpr: int,
                       nq: int, nk: int, sub, causal: bool = False,
                       window: int = 0, grouped: bool = False):
    """Scores with keys down and queries across, (sk, sq): P^T and dS^T come
    out as the two products into dV and dK take them (no (sq, sk) tile is
    ever transposed), and lse / delta stay the (1, sq) rows they arrive as.
    `qseg_ref` holds the q block's ids as a row, `kseg_ref` the k block's as
    a column (`_tile_mask`, `keys_down`)."""
    del qidx_ref
    b, jk, jq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    sq, sk = sub

    @pl.when(jq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    bits = live_ref[((b // gpr) * nk + jk) * nq + jq]

    @pl.when(bits != 0)
    def _():
        def tile(r0, c0):
            rows, cols = slice(r0, r0 + sq), slice(c0, c0 + sk)
            mask = _tile_mask(qseg_ref, kseg_ref, jq, jk, r0, c0, sq, sk,
                              causal, window, keys_down=True)
            for h in range(hb):                       # unrolled, as forward
                q, do = q_ref[h, rows], do_ref[h, rows]
                k, v = k_ref[kv(h), cols], v_ref[kv(h), cols]
                s = jax.lax.dot_general(                        # K Q^T
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                p = jnp.exp(jnp.where(mask, s, MASKED) - lse_ref[h, :, rows])
                dp = jax.lax.dot_general(                       # V dO^T
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta_ref[h, :, rows]) * scale
                dv_acc[kv(h), cols] += jax.lax.dot_general(     # P^T dO
                    p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk_acc[kv(h), cols] += jax.lax.dot_general(     # dS^T Q
                    ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        _each_live_tile(bits, bq, bk, sub, tile)

    @pl.when(jq == nq - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _packed_dq_kernel(live_ref, kidx_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, qseg_ref, kseg_ref, dq_ref, dq_acc,
                      *, scale: float, hb: int, gpr: int, nq: int, nk: int,
                      sub, causal: bool = False, window: int = 0,
                      grouped: bool = False):
    del kidx_ref
    b, jq, jk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = (lambda h: 0) if grouped else (lambda h: h)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    sq, sk = sub

    @pl.when(jk == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    bits = live_ref[((b // gpr) * nq + jq) * nk + jk]

    @pl.when(bits != 0)
    def _():
        def tile(r0, c0):
            rows, cols = slice(r0, r0 + sq), slice(c0, c0 + sk)
            mask = _tile_mask(qseg_ref, kseg_ref, jq, jk, r0, c0, sq, sk,
                              causal, window)
            for h in range(hb):                       # unrolled, as forward
                k, v = k_ref[kv(h), cols], v_ref[kv(h), cols]
                lse = lse_ref[h, :, rows][0][:, None]               # (sq, 1)
                delta = delta_ref[h, :, rows][0][:, None]
                s = jax.lax.dot_general(
                    q_ref[h, rows], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                p = jnp.exp(jnp.where(mask, s, MASKED) - lse)
                dp = jax.lax.dot_general(                       # dO V^T
                    do_ref[h, rows], v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta) * scale
                dq_acc[h, rows] += jax.lax.dot_general(         # dS K
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        _each_live_tile(bits, bq, bk, sub, tile)

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


def _traced_once(fn):
    """`fn` jitted, every argument but the arrays static. A step traces a
    layer's forward five times over (the scan's, the custom_vjp's, the
    remat's), and the bodies, unrolled over sub-tiles and heads, are the dear
    part of a trace: the jit's cache hands the later traces the first one's
    jaxpr, and layers of one shape lower to one function. Whether the
    kernels run interpreted is part of the key."""
    jitted = jax.jit(fn, static_argnames=(
        "scale", "bq", "bk", "hb", "heads", "skip", "causal", "window",
        "grouped", "name", "tiles", "interpret"))

    @functools.wraps(fn)
    def call(*args, **kwargs):
        return jitted(*args, interpret=_interpret(), **kwargs)
    return call


@_traced_once
def _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip,
                causal=False, window=0, grouped=False, name="flash_packed",
                tiles=None, interpret=False):
    """`grouped`: k and v hold ONE head for each grid step's `hb` query
    heads (grouped-query attention: (R * KV, T, Dh) beside q's (R * H, T,
    Dh), hb = H / KV), read inside the kernel: no repeated K or V exists.
    `tiles`: the kernels' sub-tile shapes, a `Tiles`, where not `_sub_tiles`'
    (the tests and the sweep). v may be narrower than q and k (a latent
    layer's): the output has v's width."""
    bh, t, dh = q.shape
    dv = v.shape[-1]
    nq, nk, gpr = t // bq, t // bk, heads // hb
    hk = 1 if grouped else hb
    sub = (tiles or _sub_tiles(window, bq, bk)).fwd
    live, kidx, _, _ = packed_block_tables(segment_ids, bq, bk, skip, causal,
                                           window, sub)
    qseg, kseg = _segment_tiles(segment_ids)

    def at_k(b, i, j, live, kidx):
        return kidx[((b // gpr) * nq + i) * nk + j]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, i, j, *_: (b, i, 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, i, j, *t: (b, at_k(b, i, j, *t), 0))
    ospec, vspec = qspec, kspec
    if dv != dh:
        ospec = pl.BlockSpec((hb, bq, dv), lambda b, i, j, *_: (b, i, 0))
        vspec = pl.BlockSpec((hk, bk, dv), lambda b, i, j, *t: (b, at_k(b, i, j, *t), 0))
    o, lse = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, sub=sub,
                          **_decoder_terms(causal, window, grouped)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nq, nk),
            in_specs=[
                qspec, kspec, vspec,
                pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b // gpr, i, 0)),
                pl.BlockSpec((1, 8, bk),
                             lambda b, i, j, *t: (b // gpr, 0, at_k(b, i, j, *t))),
            ],
            out_specs=[
                ospec,
                pl.BlockSpec((hb, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((hb, bq, dv), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        compiler_params=_packed_params(),
        name=f"{name}_fwd",
        interpret=interpret,
    )(live, kidx, q, k, v, qseg, kseg)
    return o, lse


@_traced_once
def _packed_bwd(q, k, v, o, lse, do, segment_ids, scale, bq, bk, hb, heads,
                skip, causal=False, window=0, grouped=False,
                name="flash_packed", tiles=None, interpret=False):
    bh, t, dh = q.shape
    dv = v.shape[-1]
    nq, nk, gpr = t // bq, t // bk, heads // hb
    hk = 1 if grouped else hb
    terms = _decoder_terms(causal, window, grouped)
    tiles = tiles or _sub_tiles(window, bq, bk)
    _, _, live_kq, qidx = packed_block_tables(
        segment_ids, bq, bk, skip, causal, window, tiles.dkv)
    live_qk, kidx, _, _ = packed_block_tables(
        segment_ids, bq, bk, skip, causal, window, tiles.dq)
    seg_cols, seg_rows = _segment_tiles(segment_ids)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                       # (BH, 1, T)

    def at_q(b, jk, jq, live, qidx):
        return qidx[((b // gpr) * nk + jk) * nq + jq]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, jk, jq, *t: (b, at_q(b, jk, jq, *t), 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, jk, jq, *_: (b, jk, 0))
    dospec, vspec = qspec, kspec
    if dv != dh:
        dospec = pl.BlockSpec((hb, bq, dv), lambda b, jk, jq, *t: (b, at_q(b, jk, jq, *t), 0))
        vspec = pl.BlockSpec((hk, bk, dv), lambda b, jk, jq, *_: (b, jk, 0))
    row = pl.BlockSpec((hb, 1, bq), lambda b, jk, jq, *t: (b, 0, at_q(b, jk, jq, *t)))
    dk, dv_ = pl.pallas_call(
        functools.partial(_packed_dkv_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, sub=tiles.dkv, **terms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nk, nq),
            in_specs=[
                qspec, kspec, vspec, dospec, row, row,
                # keys down, queries across: the q ids a row, the k ids a column
                pl.BlockSpec((1, 8, bq),
                             lambda b, jk, jq, *t: (b // gpr, 0, at_q(b, jk, jq, *t))),
                pl.BlockSpec((1, bk, 128), lambda b, jk, jq, *_: (b // gpr, jk, 0)),
            ],
            out_specs=[kspec, vspec],
            scratch_shapes=[pltpu.VMEM((hk, bk, dh), jnp.float32),
                            pltpu.VMEM((hk, bk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, q.dtype)],
        compiler_params=_packed_params(),
        name=f"{name}_dkv",
        interpret=interpret,
    )(live_kq, qidx, q, k, v, do, lse, delta, seg_rows, seg_cols)

    def at_k(b, jq, jk, live, kidx):
        return kidx[((b // gpr) * nq + jq) * nk + jk]

    qspec = pl.BlockSpec((hb, bq, dh), lambda b, jq, jk, *_: (b, jq, 0))
    kspec = pl.BlockSpec((hk, bk, dh), lambda b, jq, jk, *t: (b, at_k(b, jq, jk, *t), 0))
    dospec, vspec = qspec, kspec
    if dv != dh:
        dospec = pl.BlockSpec((hb, bq, dv), lambda b, jq, jk, *_: (b, jq, 0))
        vspec = pl.BlockSpec((hk, bk, dv), lambda b, jq, jk, *t: (b, at_k(b, jq, jk, *t), 0))
    row = pl.BlockSpec((hb, 1, bq), lambda b, jq, jk, *_: (b, 0, jq))
    dq = pl.pallas_call(
        functools.partial(_packed_dq_kernel, scale=scale, hb=hb, gpr=gpr,
                          nq=nq, nk=nk, sub=tiles.dq, **terms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh // hb, nq, nk),
            in_specs=[
                qspec, kspec, vspec, dospec, row, row,
                pl.BlockSpec((1, bq, 128), lambda b, jq, jk, *_: (b // gpr, jq, 0)),
                pl.BlockSpec((1, 8, bk),
                             lambda b, jq, jk, *t: (b // gpr, 0, at_k(b, jq, jk, *t))),
            ],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((hb, bq, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, t, dh), q.dtype),
        compiler_params=_packed_params(),
        name=f"{name}_dq",
        interpret=interpret,
    )(live_qk, kidx, q, k, v, do, lse, delta, seg_cols, seg_rows)
    return dq, dk, dv_


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
    bq, bk, t_pad = _blocks_for(t, block_q, block_k)
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

"""Block defaults from the sweep that chose the sub-tiles (my chip runs, PR
33: the Laguna cell's row, kernels alone, ms a call, forward / dK/dV / dQ;
`tools/sweep_packed_tiles.py`). Full layers: (512, 1024) with `PACKED_TILES`
3.92 / 4.79 / 4.50; (512, 512) blocks 4.03 / 5.13 / 4.55 at their best tiles;
(1024, 1024) and (256, 1024) lost in PR 26's bodies already (5.36 / 7.26 /
6.23 and 6.38 / 6.71 / 5.76 whole). Sliding layers of window 512: until PR
33 (512, 512) blocks, whole, 5.82 / 4.07 / 3.82 (half of each pair outside
the window: 2.28 times the pairs needed). With sub-tiles the block is only
what the pipeline fetches and the tile decides the area, so the wider block
with fewer steps wins: (512, 1024) blocks with (256, 256) tiles 2.85 / 2.75 /
2.90 [1.66], with (512, 512) tiles 3.05 / 3.52 / 3.32, whole 3.72 / 4.88 /
4.38 [3.39]; (512, 512) blocks with (256, 256) tiles 3.02 / 2.95 / 3.04."""
CAUSAL_BLOCKS = (512, 1024)
WINDOW_BLOCKS = (512, 1024)


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


# A latent layer (MLA, vitax/models/decoder.py: LatentAttention): every head
# has a key of its own and q and k are wider than v. The same bodies, causal
# and ungrouped (several heads a grid step, each reading its own key, as the
# packed ViT's), under the names `flash_latent_*`.
_LATENT_TERMS = dict(causal=True, name="flash_latent")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _latent_bh(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip):
    return _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip,
                       **_LATENT_TERMS)[0]


def _latent_bh_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip):
    o, lse = _packed_fwd(q, k, v, segment_ids, scale, bq, bk, hb, heads, skip,
                         **_LATENT_TERMS)
    return o, (q, k, v, o, lse, segment_ids)


def _latent_bh_bwd(scale, bq, bk, hb, heads, skip, res, do):
    import numpy as np
    q, k, v, o, lse, segment_ids = res
    dq, dk, dv = _packed_bwd(q, k, v, o, lse, do, segment_ids, scale, bq, bk,
                             hb, heads, skip, **_LATENT_TERMS)
    return dq, dk, dv, np.zeros(segment_ids.shape, jax.dtypes.float0)


_latent_bh.defvjp(_latent_bh_fwd, _latent_bh_bwd)


def document_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             segment_ids: jax.Array, window: int = 0,
                             block_q: int = 0, block_k: int = 0,
                             skip: bool = True, scale: float = 0.) -> jax.Array:
    """Causal attention within each document of a packed row: q (R, T, H, Dh),
    k and v (R, T, KV, Dh) with H a multiple of KV, (R, T) int32 segment ids
    (0 = padding) -> (R, T, H, Dh), differentiable in q/k/v. `window` > 0: a
    query sees the `window` latest keys of its document, itself included.
    Scores times `scale` (0 = Dh ** -0.5). Padding rows come back zero.
    `block_q`/`block_k`/`skip` exist for the tests. Where v's width differs
    from q's and k's (a latent layer: KV == H, no window) the kernels are
    `flash_latent_*` and the output (R, T, H, v's width)."""
    r, t, h, dh = q.shape
    kv, dv = k.shape[2], v.shape[3]
    assert h % kv == 0, (h, kv)
    dq, dk = WINDOW_BLOCKS if window > 0 else CAUSAL_BLOCKS
    bq, bk, t_pad = _blocks_for(t, block_q or dq, block_k or dk)
    seg = jnp.pad(segment_ids.astype(jnp.int32), ((0, 0), (0, t_pad - t)))
    qb, kb, vb = (_pad_seq(_to_bh(x), t_pad) for x in (q, k, v))
    if dv != dh:
        assert kv == h and window == 0, (kv, h, window)
        o = _latent_bh(qb, kb, vb, seg, float(scale) or dh ** -0.5, bq, bk,
                       math.gcd(h, PACKED_HEADS_PER_STEP), h, skip)
        return _from_bh(o[:, :t], (r, t, h, dv))
    o = _documents_bh(qb, kb, vb, seg, float(scale) or dh ** -0.5, bq, bk,
                      h // kv, h, skip, int(window))
    return _from_bh(o[:, :t], q.shape)


def computed_pairs(segment_ids: jax.Array, causal: bool = False,
                   window: int = 0) -> jax.Array:
    """The (query, key) pairs a head that one layer's kernels compute on
    these rows (R, T), one run of each: the area of the live sub-tiles in
    the tables the kernels themselves read, a mean over the three kernels
    weighted by their matmuls (`TILE_MATMULS`). Over the pairs the mask
    lets through (sum n^2, or a decoder's causal / window pairs) it says how
    much of what the kernels compute some query sees. float32."""
    blocks = ((WINDOW_BLOCKS if window > 0 else CAUSAL_BLOCKS) if causal
              else (PACKED_BLOCK_Q, PACKED_BLOCK_K))
    bq, bk, t_pad = _blocks_for(segment_ids.shape[1], *blocks)
    seg = jnp.pad(segment_ids.astype(jnp.int32),
                  ((0, 0), (0, t_pad - segment_ids.shape[1])))
    total = 0.0
    for (sq, sk), matmuls in zip(_sub_tiles(window, bq, bk), TILE_MATMULS):
        bits = packed_block_tables(seg, bq, bk, True, causal, window,
                                   (sq, sk))[0]
        live = jnp.sum(jax.lax.population_count(bits)).astype(jnp.float32)
        total += matmuls * sq * sk * live
    return total / sum(TILE_MATMULS)
