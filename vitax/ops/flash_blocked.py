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

from vitax.ops.attention import _interpret, dropout_keep_mask

NEG_INF = -1e30  # large-but-finite: avoids inf-inf=nan in max/exp chains

"""Measured block defaults (round-5 ladder, tools/long_context_ladder.py ->
LADDER_LONGCTX.jsonl, v5e, ViT-L width train steps): the (512, 1024) pair
wins at N=4,096 (79.3 ms vs 102.8 at the untuned (512, 512)) and is within
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
