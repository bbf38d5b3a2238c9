"""Fused attention kernels for TPU (Pallas).

The reference relies on timm's dense attention (materializes the (B,H,N,N)
score tensor in HBM; reference run_vit_training.py:134-141 via timm Block).
Here the softmax(QK^T/sqrt(d))V core is a Pallas kernel that keeps scores in
VMEM — one HBM round-trip for Q/K/V/O instead of score-tensor traffic — with a
custom VJP whose backward is also a fused kernel (flash-attention style
recompute from the saved logsumexp).

Design notes (see /opt/skills/guides/pallas_guide.md):
- Two whole-N kernel families: the 4D-native kernel (default — operands
  viewed as (B, N, H*Dh), grid over (batch, head-groups), per-head lane
  slices, no HBM relayouts; measured +13% step throughput on ViT-L/14 v5e
  over the BH layout; where nothing sits between the qkv projection and
  the kernel it is entered through `flash_attention_qkv`, which reads the
  projection's (B, N, 3D) output in place and hands back one (B, N, 3D)
  gradient) and the BH kernel ((B*H, N, Dh), one head per program
  — the fallback when no head grouping fits VMEM, and the building block of
  ring attention's local products). ViT sequence lengths are short (256
  tokens at 224^2/patch 14), so whole-N blocks fit comfortably; beyond
  N = MAX_SEQ_IN_VMEM the streaming kernel (vitax/ops/flash_blocked.py,
  VMEM-independent of N) takes over, and ring attention handles cross-chip
  sequence sharding (vitax/parallel/ring_attention.py).
- logits accumulate in float32 on the MXU (preferred_element_type), softmax in
  float32, outputs cast back to the activation dtype.
- Under a multi-device mesh the kernel runs inside shard_map: batch over
  (dp, fsdp), heads over tp — attention is embarrassingly parallel in both, so
  no collectives are needed inside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from vitax.ops.common import interpret as _interpret
from vitax.parallel.mesh import BATCH_AXES, shard_map
from vitax.platform import backend_platform

MAX_SEQ_IN_VMEM = 2048  # (N, N) f32 scores: 16 MB at 2048 — VMEM ceiling


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Dense jnp attention core; (B, N, H, Dh) -> (B, N, H, Dh)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# in-kernel dropout RNG
# ---------------------------------------------------------------------------
# Counter-based: the keep/drop decision for score element (b, h, q, k) is a
# pure uint32 hash of (seed, b*H+h, q, k) — murmur3's fmix32 finalizer over
# golden-ratio-multiplied coordinates. Plain vector uint32 ops, so the SAME
# code runs inside Mosaic kernels (this jax version's interpret mode lacks
# pltpu.prng_seed) and as host-side jnp — which is what makes the fwd kernel,
# the bwd kernel's mask RECOMPUTE (no (N, N) mask residual), and the test
# oracle (tests/test_attention.py) bit-identical by construction, on CPU and
# TPU alike. Reference behavior matched: timm's attn_drop on the softmax
# probabilities (reference run_vit_training.py:140,346 via timm Block).

_FMIX_C1 = 0x85EBCA6B
_FMIX_C2 = 0xC2B2AE35
_GOLD_Q = 0x9E3779B1   # odd multipliers decorrelate the raster counter
_GOLD_K = 0x85EBCA77
_GOLD_BH = 0xC2B2AE3D


def _fmix32(x):
    """murmur3 fmix32 finalizer (uint32 avalanche)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_FMIX_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_FMIX_C2)
    x = x ^ (x >> 16)
    return x


def fold_shard_seed(mesh, axes, seed):
    """Fold the linearized shard position over `axes` into a dropout seed.

    Inside shard_map every shard sees the same LOCAL (batch, head) block
    indices, so without this two shards would draw identical masks; the
    fold gives each a decorrelated stream while staying deterministic given
    (seed, step). Shared by the shard_map dropout wrappers here and in
    vitax/parallel/ulysses.py — the mask-reproducibility contract (bwd
    regenerates the fwd's mask) requires exactly one fold idiom."""
    idx = jnp.uint32(0)
    for ax in axes:
        idx = (idx * jnp.uint32(mesh.shape[ax])
               + jax.lax.axis_index(ax).astype(jnp.uint32))
    return seed ^ _fmix32(idx * jnp.uint32(_GOLD_BH))


def dropout_keep_mask(seed, bh_index, nq: int, nk: int, rate: float,
                      transposed: bool = False, q0=0, k0=0):
    """f32 {0, 1} keep-mask for one (head, batch) score block.

    seed: traced uint32 scalar; bh_index: uint32 scalar identifying the
    global (batch, head) pair; transposed=True yields the (Nk, Nq) layout the
    4D kernel's transposed-score space uses — the SAME element decisions,
    so 4D and BH kernels drop identical (q, k) positions. q0/k0 offset the
    row/col indices to GLOBAL positions (may be traced scalars) — the
    streaming kernel's (q-block, k-block) tiles reproduce exactly the
    decisions the whole-(N, N) mask makes at those coordinates, which is
    what lets its bwd tiles regenerate the fwd's mask."""
    shape = (nk, nq) if transposed else (nq, nk)
    qdim, kdim = (1, 0) if transposed else (0, 1)
    qi = jax.lax.broadcasted_iota(jnp.uint32, shape, qdim) + jnp.uint32(q0)
    kj = jax.lax.broadcasted_iota(jnp.uint32, shape, kdim) + jnp.uint32(k0)
    x = (qi * jnp.uint32(_GOLD_Q) + kj * jnp.uint32(_GOLD_K)
         + bh_index.astype(jnp.uint32) * jnp.uint32(_GOLD_BH))
    bits = _fmix32(_fmix32(x ^ seed.astype(jnp.uint32)))
    # P(bits < T) = T / 2^32 = rate (T computed in python — exact, static)
    threshold = jnp.uint32(min(int(rate * 2 ** 32), 2 ** 32 - 1))
    return (bits >= threshold).astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float):
    q = q_ref[0]  # (N, Dh)
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l))[:, 0][None, :]


def _fwd(q, k, v, scale):
    """q, k, v: (BH, N, Dh) -> (o (BH, N, Dh), lse (BH, N))."""
    bh, n, dh = q.shape
    spec = pl.BlockSpec((1, n, dh), lambda i: (i, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(bh,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        ],
        name="flash_bh_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dlse_ref,
                dq_ref, dk_ref, dv_ref, *, scale: float):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    o = o_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][0][:, None]    # (N, 1)
    dlse = dlse_ref[0][0][:, None]  # (N, 1) — lse cotangent (zeros when the
    # lse output is unused; nonzero under ring attention's logsumexp merge)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)  # softmax probabilities, (N, N) f32

    # Matmul operands go in the INPUT dtype (bf16 under training) with f32
    # accumulation — f32 operands would run the MXU at half rate on v5e+
    # (profiled: the all-f32 version of this kernel was ~1.5x slower on l14);
    # softmax/score math above stays f32 for stability. With f32 inputs (tests)
    # the casts are no-ops and numerics are unchanged.
    pb = p.astype(q_ref.dtype)
    dob = do.astype(q_ref.dtype)
    dv = jax.lax.dot_general(  # P^T dO
        pb, dob, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(  # dO V^T
        dob, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)  # (N, 1) f32
    # d lse_i / d s_ij = p_ij, so the lse cotangent adds dlse_i inside the parens
    ds = (p * (dp - delta + dlse) * scale).astype(q_ref.dtype)

    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(  # dS^T Q
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(scale, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    bh, n, dh = q.shape
    spec = pl.BlockSpec((1, n, dh), lambda i: (i, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(bh,),
        in_specs=[spec, spec, spec, spec, lse_spec, spec, lse_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((bh, n, dh), q.dtype)] * 3,
        name="flash_bh_bwd",
        interpret=_interpret(),
    )(q, k, v, o, lse[:, None, :], do, dlse[:, None, :])
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_bh_with_lse(q, k, v, scale):
    """(BH, N, Dh) fused attention returning (o, lse); differentiable in BOTH
    outputs — the lse cotangent feeds the backward kernel, which is what lets
    ring attention merge per-block kernel results with plain autodiff
    (vitax/parallel/ring_attention.py)."""
    return _fwd(q, k, v, scale)


def _flash_bh_lse_fwd(q, k, v, scale):
    o, lse = _fwd(q, k, v, scale)
    return (o, lse), (q, k, v, o, lse)


flash_bh_with_lse.defvjp(_flash_bh_lse_fwd, _bwd)


def _flash_bh(q, k, v, scale):
    return flash_bh_with_lse(q, k, v, scale)[0]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Fused attention core; (B, N, H, Dh) -> (B, N, H, Dh), differentiable."""
    scale = q.shape[-1] ** -0.5
    return _from_bh(_flash_bh(_to_bh(q), _to_bh(k), _to_bh(v), scale), q.shape)


# ---------------------------------------------------------------------------
# 4D-native kernel: operates directly on (B, N, H, Dh) — no HBM transposes
# ---------------------------------------------------------------------------
# The BH kernels above need (B, N, H, Dh) -> (B*H, N, Dh) relayouts around
# every call; profiled at ~16 ms/step of pure HBM copies on ViT-L/14 v5e
# ("data formatting"). Here the operands are viewed as (B, N, H*Dh) — a free
# bitcast — the grid is (batch,), and each head is a static LANE slice of the
# block. Scores are computed in TRANSPOSED space (sT = K Q^T) so the per-head
# logsumexp is a (1, N) row — every slice/store stays a legal Mosaic layout
# (no vector transposes, no mid-tensor unit reshapes; probed 13% faster than
# the BH path forward on v5e).
#
# "No HBM transposes" holds INSIDE the kernel. Its hand-off is another
# matter: q, k and v are the three strided slices qkv[:, :, 0..2] of the
# fused projection's (B, N, 3, H, Dh) output, and a pallas_call operand must
# be dense, so XLA copies the projection's output into a layout that makes
# the slices cheap and copies each slice back, forward and rematted forward,
# and copies dq, dk, dv on their way into the padded sum that is the qkv
# cotangent: 2 x (B, N, 3D) + 9 x (B, N, D) copies a layer, a tenth of
# ViT-L/14's step on a v5e (ledger, PR 35). `flash_attention_qkv` below is
# the entry without that hand-off: it reads q, k and v where the projection
# wrote them — three block windows over the one (B, N, 3D) buffer, at
# column-block offsets 0, H/hb and 2H/hb — and its backward writes the
# (B, N, 3D) cotangent itself. Same kernel bodies, same names, same lse
# layouts; `flash_attention_4d` stays for callers that hold q, k and v apart
# (RoPE or dropout between projection and kernel, tp, ring attention).


def _fwd4_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, heads, scale,
                 pad_rows):
    dh = q_ref.shape[-1] // heads
    lse_rows = []
    for i in range(heads):  # static unroll: one (N, Dh) head per iteration
        q = q_ref[0][:, i * dh:(i + 1) * dh]
        k = k_ref[0][:, i * dh:(i + 1) * dh]
        v = v_ref[0][:, i * dh:(i + 1) * dh]
        sT = jax.lax.dot_general(  # (Nk, Nq)
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        m = jnp.max(sT, axis=0, keepdims=True)       # (1, Nq)
        p = jnp.exp(sT - m)
        l = jnp.sum(p, axis=0, keepdims=True)        # (1, Nq)
        o = jax.lax.dot_general(                     # (Nq, Dh)
            (p / l).astype(v.dtype), v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, :, i * dh:(i + 1) * dh] = o.astype(o_ref.dtype)
        lse_rows.append(m + jnp.log(l))
    if pad_rows:  # grouped-padded layout: block is (1, 1, P, Nq)
        n = q_ref.shape[1]
        lse_rows.append(jnp.zeros((pad_rows - heads, n), jnp.float32))
        lse_ref[0, 0] = jnp.concatenate(lse_rows, axis=0)  # (P, Nq)
    else:
        lse_ref[0] = jnp.concatenate(lse_rows, axis=0)     # (H, Nq)


def _bwd4_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dlse_ref,
                 dq_ref, dk_ref, dv_ref, *, heads, scale, pad_rows):
    dh = q_ref.shape[-1] // heads
    ones_row = jnp.ones((1, dh), jnp.float32)
    for i in range(heads):
        sl = slice(i * dh, (i + 1) * dh)
        q = q_ref[0][:, sl]                          # (Nq, Dh), input dtype
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        o = o_ref[0][:, sl].astype(jnp.float32)
        do = do_ref[0][:, sl].astype(jnp.float32)
        lse_blk = lse_ref[0, 0] if pad_rows else lse_ref[0]
        lse_row = lse_blk[i:i + 1, :]                # (1, Nq) f32

        sT = jax.lax.dot_general(                    # (Nk, Nq)
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        pT = jnp.exp(sT - lse_row)

        # matmuls take operands in the INPUT dtype with f32 accumulation —
        # f32 operands would run the MXU at half rate on v5e+; softmax/score
        # math stays f32 (with f32 inputs the casts are no-ops, so the
        # numerics tests compare exactly)
        pTb = pT.astype(q_ref.dtype)
        dob = do.astype(q_ref.dtype)
        dv = jax.lax.dot_general(                    # P^T dO: contract Nq
            pTb, dob, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nk, Dh)
        dpT = jax.lax.dot_general(                   # V dO^T: contract Dh
            v, dob, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nk, Nq)
        delta_row = jax.lax.dot_general(             # sum(dO*O, -1) as a row
            ones_row, do * o, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (1, Nq)
        inner = dpT - delta_row
        if dlse_ref is not None:  # None: the caller handed out no lse
            dlse_blk = dlse_ref[0, 0] if pad_rows else dlse_ref[0]
            inner = inner + dlse_blk[i:i + 1, :]     # (1, Nq) f32
        dsT = (pT * inner * scale).astype(q_ref.dtype)

        dq = jax.lax.dot_general(                    # dS K: contract Nk
            dsT, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nq, Dh)
        dk = jax.lax.dot_general(                    # dS^T Q: contract Nq
            dsT, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nk, Dh)

        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


# VMEM working-set estimate per program for the backward kernel (the larger
# one): 10 double-buffered (N, hb*Dh) blocks + per-head f32 score temps. The
# budget leaves Mosaic headroom of the ~16 MB/core.
_VMEM_BUDGET = 12 * 1024 * 1024


def _heads_per_program(n: int, h: int, dh: int, itemsize: int):
    """Head-group size: largest legal divisor of h fitting the VMEM budget,
    or None when no group does (the caller must then route the BH kernel).
    Legal = full-array blocks (hb == h), or the q/k/v/o block's lane dim
    hb*Dh is a multiple of 128 for a partial grid. Mosaic's OTHER tiling
    rule — the lse block's sublane dim must be a multiple of 8 — is
    satisfied by layout, not selection: groupings with hb % 8 != 0 store
    lse in the grouped-padded (B, H/hb, P, N) layout (_lse_pad_rows), whose
    (1, 1, P, N) blocks are always legal. (The sublane rule only bites on
    real TPU — interpret mode green-lit an illegal (1, 4, 256) lse block
    for h=32/dh=160, which the first on-chip 10b_slice compile caught.)"""
    for hb in range(h, 0, -1):
        if h % hb or not (hb == h or (hb * dh) % 128 == 0):
            continue
        est = 2 * 10 * n * hb * dh * itemsize + 4 * n * n * 4
        if est <= _VMEM_BUDGET:
            return hb
    return None  # even hb=1 busts the budget (large n: score temps dominate)


def _lse_pad_rows(hb: int, h: int) -> int:
    """Sublane padding P for the lse blocks of an hb-head grouping; 0 means
    the plain (B, H, N) layout with (1, hb, N) blocks is already legal
    (full-array coverage, or sublane dim a multiple of 8)."""
    if hb == h or hb % 8 == 0:
        return 0
    return -(-hb // 8) * 8  # round up to the f32 sublane tile


def _lse_layout4(b: int, n: int, h: int, hb: int):
    """(pad, BlockSpec, array shape) of the 4D kernels' lse for an hb-head
    grouping on a (batch, head-groups) grid: the grouped-padded
    (B, H/hb, P, N) with full-tile blocks where _lse_pad_rows says so, the
    plain (B, H, N) otherwise."""
    pad = _lse_pad_rows(hb, h)
    if pad:
        return (pad, pl.BlockSpec((1, 1, pad, n), lambda i, j: (i, j, 0, 0)),
                (b, h // hb, pad, n))
    return pad, pl.BlockSpec((1, hb, n), lambda i, j: (i, j, 0)), (b, h, n)


def _regroup_lse(x, hb: int, pad: int):
    """(B, H, N) -> the grouped-padded (B, H/hb, P, N) the kernel blocks
    need."""
    b, h, n = x.shape
    g = x.reshape(b, h // hb, hb, n)
    return jnp.pad(g, ((0, 0), (0, 0), (0, pad - hb), (0, 0)))


def flash4_supported(n: int, h: int, dh: int, itemsize: int) -> bool:
    """Whether the 4D-native kernel has a legal, VMEM-fitting head grouping
    for this shape — checked by _tpu_kernel before selecting it; the BH
    (relayout) kernel is the fallback (its per-(b,h) program holds ONE f32
    (N, N) score temp, so it survives to larger N)."""
    return _heads_per_program(n, h, dh, itemsize) is not None


def _fwd4(q, k, v, scale):
    b, n, h, dh = q.shape
    hb = _heads_per_program(n, h, dh, q.dtype.itemsize)
    assert hb is not None, (
        f"flash_attention_4d has no VMEM-fitting head grouping for "
        f"(n={n}, h={h}, dh={dh}) — gate on flash4_supported() first")
    pad, lse_spec, lse_shape = _lse_layout4(b, n, h, hb)
    q3, k3, v3 = (x.reshape(b, n, h * dh) for x in (q, k, v))  # free bitcasts
    spec = pl.BlockSpec((1, n, hb * dh), lambda i, j: (i, 0, j))
    o, lse = pl.pallas_call(
        functools.partial(_fwd4_kernel, heads=hb, scale=scale, pad_rows=pad),
        grid=(b, h // hb),
        in_specs=[spec, spec, spec],
        out_specs=[spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, h * dh), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        name="flash_4d_fwd",
        interpret=_interpret(),
    )(q3, k3, v3)
    if pad:
        lse = lse[:, :, :hb, :].reshape(b, h, n)
    return o.reshape(b, n, h, dh), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash4_with_lse(q, k, v, scale):
    """(B, N, H, Dh) fused attention returning (o, lse (B, H, N));
    differentiable in both outputs (lse cotangent as in flash_bh_with_lse)."""
    return _fwd4(q, k, v, scale)


def _flash4_fwd(q, k, v, scale):
    o, lse = _fwd4(q, k, v, scale)
    return (o, lse), (q, k, v, o, lse)


def _flash4_bwd(scale, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    b, n, h, dh = q.shape
    hb = _heads_per_program(n, h, dh, q.dtype.itemsize)
    pad, lse_spec, _ = _lse_layout4(b, n, h, hb)
    flat = (x.reshape(b, n, h * dh) for x in (q, k, v, o, do))
    q3, k3, v3, o3, do3 = flat
    spec = pl.BlockSpec((1, n, hb * dh), lambda i, j: (i, 0, j))
    if pad:
        lse, dlse = _regroup_lse(lse, hb, pad), _regroup_lse(dlse, hb, pad)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd4_kernel, heads=hb, scale=scale, pad_rows=pad),
        grid=(b, h // hb),
        in_specs=[spec, spec, spec, spec, lse_spec, spec, lse_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, n, h * dh), q.dtype)] * 3,
        name="flash_4d_bwd",
        interpret=_interpret(),
    )(q3, k3, v3, o3, lse, do3, dlse)
    return tuple(x.reshape(b, n, h, dh) for x in (dq, dk, dv))


flash4_with_lse.defvjp(_flash4_fwd, _flash4_bwd)


def flash_attention_4d(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Fused attention on native (B, N, H, Dh) layout — no HBM relayouts."""
    return flash4_with_lse(q, k, v, q.shape[-1] ** -0.5)[0]


# -- the fused-qkv entry: (B, N, 3*H*Dh) in, (B, N, H*Dh) out ----------------

def flash4_qkv_supported(n: int, h: int, dh: int, itemsize: int) -> bool:
    """Whether the fused-qkv entry applies: the 4D kernel's head grouping
    exists and its block's lane dim hb*Dh is a multiple of 128. A window of
    hb*Dh columns into a 3D-wide buffer is never a full-array block, so the
    grouping that is legal only as one (hb == h with D off the lane tile)
    keeps `flash_attention_4d`."""
    hb = _heads_per_program(n, h, dh, itemsize)
    return hb is not None and (hb * dh) % 128 == 0


def _qkv_geometry(qkv, heads: int):
    b, n, d3 = qkv.shape
    assert d3 % (3 * heads) == 0, (qkv.shape, heads)
    dh = d3 // (3 * heads)
    hb = _heads_per_program(n, heads, dh, qkv.dtype.itemsize)
    assert hb is not None and (hb * dh) % 128 == 0, (
        f"flash_attention_qkv has no legal head grouping for (n={n}, "
        f"h={heads}, dh={dh}) — gate on flash4_qkv_supported() first")
    return b, n, dh, hb, heads // hb


def _qkv_windows(n: int, width: int, groups: int):
    """q's, k's and v's block windows over the one (B, N, 3D) buffer: head
    group j of q sits at column block j, of k at groups + j, of v at
    2 * groups + j."""
    return [pl.BlockSpec((1, n, width),
                         lambda i, j, c=c: (i, 0, c * groups + j))
            for c in range(3)]


def _fwd4_qkv(qkv, heads):
    """-> (o (B, N, D), lse in the kernel's own layout, _lse_layout4)."""
    b, n, dh, hb, groups = _qkv_geometry(qkv, heads)
    pad, lse_spec, lse_shape = _lse_layout4(b, n, heads, hb)
    return pl.pallas_call(
        functools.partial(_fwd4_kernel, heads=hb, scale=dh ** -0.5,
                          pad_rows=pad),
        grid=(b, groups),
        in_specs=_qkv_windows(n, hb * dh, groups),
        out_specs=[pl.BlockSpec((1, n, hb * dh), lambda i, j: (i, 0, j)),
                   lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, heads * dh), qkv.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        name="flash_4d_fwd",
        interpret=_interpret(),
    )(qkv, qkv, qkv)


def _second_result(ref):
    """What the fused backward hands out beside the cotangent: one zeroed
    (8, 128) tile, so that its custom call is a tuple. The TPU profiler
    names an op event by the op's whole HLO text, operand names included,
    and whoever sums the events that hold `flash_` (attention_roofline)
    would count every op that reads a one-result `%flash_4d_bwd` directly —
    the projection's dW and dx products — as attention time; a tuple's
    readers name a `get-tuple-element`."""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        ref[...] = jnp.zeros_like(ref)


def _bwd4_qkv_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dqkv_ref,
                     second_ref, *, heads, scale, pad_rows):
    """One head group covers all heads: the block out is the whole
    (1, N, 3D) row of the cotangent, dq | dk | dv side by side."""
    _second_result(second_ref)
    d = q_ref.shape[-1]
    _bwd4_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, None,
                 dqkv_ref.at[:, :, 0:d], dqkv_ref.at[:, :, d:2 * d],
                 dqkv_ref.at[:, :, 2 * d:3 * d],
                 heads=heads, scale=scale, pad_rows=pad_rows)


def _bwd4_qkv_grouped_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                             dqkv_hbm, second_ref, buf, sem, *, heads,
                             groups, scale, pad_rows):
    """Several head groups: a group's dq, dk, dv are three column windows
    of the (B, N, 3D) cotangent, `groups` blocks apart, which no one output
    BlockSpec describes. The cotangent stays in HBM and each step sends its
    three (N, hb*Dh) pieces there itself, from one of two VMEM slots, and
    waits for a slot's writes only when it needs the slot again (two steps
    on), so a step's write-back runs under the next step's matmuls as the
    pipeline's own would."""
    _second_result(second_ref)
    i, j = pl.program_id(0), pl.program_id(1)
    step = i * groups + j
    last = pl.num_programs(0) * groups - 1
    slot = step % 2
    width = q_ref.shape[-1]

    def writes(slot):
        return [pltpu.make_async_copy(
            buf.at[slot, c],
            dqkv_hbm.at[pl.ds(i, 1), :,
                        pl.ds(pl.multiple_of((c * groups + j) * width, 128),
                              width)],
            sem.at[slot, c]) for c in range(3)]

    # a wait needs the semaphore and the size, not the place: these stand
    # for the writes this slot started two steps ago
    @pl.when(step >= 2)
    def _():
        for w in writes(slot):
            w.wait()

    _bwd4_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, None,
                 buf.at[slot, 0], buf.at[slot, 1], buf.at[slot, 2],
                 heads=heads, scale=scale, pad_rows=pad_rows)
    for w in writes(slot):
        w.start()

    @pl.when(step == last)
    def _():
        for w in writes(1 - slot) + writes(slot):  # groups > 1: last >= 1
            w.wait()


def _bwd4_qkv(qkv, heads, o, lse, do):
    b, n, dh, hb, groups = _qkv_geometry(qkv, heads)
    pad, lse_spec, _ = _lse_layout4(b, n, heads, hb)
    width = hb * dh
    spec = pl.BlockSpec((1, n, width), lambda i, j: (i, 0, j))
    kwargs = dict(heads=hb, scale=dh ** -0.5, pad_rows=pad)
    if groups == 1:
        kernel = functools.partial(_bwd4_qkv_kernel, **kwargs)
        out_spec = pl.BlockSpec((1, n, 3 * width), lambda i, j: (i, 0, 0))
        extra = {}
    else:
        kernel = functools.partial(_bwd4_qkv_grouped_kernel, groups=groups,
                                   **kwargs)
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
        extra = dict(
            scratch_shapes=[pltpu.VMEM((2, 3, 1, n, width), qkv.dtype),
                            pltpu.SemaphoreType.DMA((2, 3))],
            # the slots' hand-over from step to step needs the grid in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")))
    return pl.pallas_call(
        kernel,
        grid=(b, groups),
        in_specs=[*_qkv_windows(n, width, groups), spec, lse_spec, spec],
        out_specs=[out_spec, pl.BlockSpec((8, 128), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((8, 128), jnp.float32)],
        name="flash_4d_bwd",
        interpret=_interpret(),
        **extra,
    )(qkv, qkv, qkv, o, lse, do)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def flash_attention_qkv(qkv: jax.Array, heads: int) -> jax.Array:
    """Fused attention straight off the fused projection: qkv (B, N, 3*H*Dh)
    laid out [q | k | v], each H heads of Dh -> o (B, N, H*Dh). The
    cotangent is the (B, N, 3*H*Dh) gradient itself: nothing is sliced,
    copied, padded or concatenated between the projection and the kernel,
    forward or backward."""
    return _fwd4_qkv(qkv, heads)[0]


def _flash_qkv_fwd(qkv, heads):
    o, lse = _fwd4_qkv(qkv, heads)
    return o, (qkv, o, lse)


def _flash_qkv_bwd(heads, res, do):
    qkv, o, lse = res
    return (_bwd4_qkv(qkv, heads, o, lse, do),)


flash_attention_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


# ---------------------------------------------------------------------------
# dropout variants: fused attention with in-kernel attention dropout
# ---------------------------------------------------------------------------
# The reference trains with timm's attn_drop on the softmax probabilities
# (run_vit_training.py:140,346). Until round 5, --att_dropout > 0 silently
# dropped *training* to the dense O(N^2) path (VERDICT r4 missing #3). Here
# the keep-mask is generated INSIDE the kernel from (seed, b*H+h, q, k) via
# dropout_keep_mask — the backward kernel regenerates it exactly (no (N, N)
# mask residual in HBM), mirroring the flash-attention lse-recompute trick.
#
# VJP under dropout: with probs = softmax(s), ms = mask/(1-r), a = probs*ms,
# o = a @ v:  dv = a^T do;  dprobs = (do v^T) * ms;  and since
# dot(dprobs, probs) = do . (a @ v) = do . o, the standard delta = sum(do*o)
# row STILL equals the softmax-vjp inner product — the only changes vs the
# dense-kernel backward are the two ms multiplications.


def _fwd_kernel_drop(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                     scale: float, rate: float):
    # seed_ref: (3,) uint32 SMEM — [seed, q0, k0]; the offsets shift the mask
    # to GLOBAL token coordinates (ring attention's per-shard blocks)
    q = q_ref[0]  # (N, Dh)
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    mask = dropout_keep_mask(seed_ref[0], jnp.uint32(pl.program_id(0)),
                             q.shape[0], k.shape[0], rate,
                             q0=seed_ref[1], k0=seed_ref[2])
    o = jax.lax.dot_general(
        (p * mask).astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o / (l * (1.0 - rate))).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l))[:, 0][None, :]


def _bwd_kernel_drop(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                     dlse_ref, dq_ref, dk_ref, dv_ref, *, scale: float,
                     rate: float):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    o = o_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][0][:, None]    # (N, 1)
    dlse = dlse_ref[0][0][:, None]  # (N, 1) — nonzero under ring's merge

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    probs = jnp.exp(s - lse)        # softmax probabilities, (N, N) f32
    ms = dropout_keep_mask(seed_ref[0], jnp.uint32(pl.program_id(0)),
                           q.shape[0], k.shape[0], rate,
                           q0=seed_ref[1], k0=seed_ref[2]) / (1.0 - rate)
    a = probs * ms                  # dropped/scaled probabilities

    ab = a.astype(q_ref.dtype)
    dob = do.astype(q_ref.dtype)
    dv = jax.lax.dot_general(  # A^T dO
        ab, dob, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(  # dO V^T
        dob, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)  # = dot(dprobs, probs)
    # d lse_i/d s_ij = probs_ij (the UNMASKED softmax — lse ignores dropout)
    ds = (probs * (dp * ms - delta + dlse) * scale).astype(q_ref.dtype)

    dq_ref[0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _seedvec(seed, q0=0, k0=0):
    """(3,) uint32 [seed, q0, k0] for the dropout kernels' SMEM input."""
    z = jnp.uint32
    return jnp.stack([seed.astype(jnp.uint32),
                      jnp.asarray(q0, jnp.int32).astype(z),
                      jnp.asarray(k0, jnp.int32).astype(z)])


def _fwd_bh_drop(q, k, v, seedvec, scale, rate):
    bh, n, dh = q.shape
    spec = pl.BlockSpec((1, n, dh), lambda i: (i, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_drop, scale=scale, rate=rate),
        grid=(bh,),
        in_specs=[_seed_spec(), spec, spec, spec],
        out_specs=[spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        ],
        name="flash_bh_dropout_fwd",
        interpret=_interpret(),
    )(seedvec, q, k, v)
    return o, lse[:, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_bh_dropout_lse(q, k, v, seedvec, scale, rate):
    """(BH, N, Dh) fused attention with attention dropout, returning
    (o, lse); differentiable in both outputs (the lse cotangent feeds the
    backward — ring attention's merge needs it). seedvec: (3,) uint32
    [seed, q0, k0] (_seedvec)."""
    return _fwd_bh_drop(q, k, v, seedvec, scale, rate)


def _flash_bh_drop_fwd(q, k, v, seedvec, scale, rate):
    o, lse = _fwd_bh_drop(q, k, v, seedvec, scale, rate)
    return (o, lse), (q, k, v, o, lse, seedvec)


def _flash_bh_drop_bwd(scale, rate, res, cts):
    import numpy as np
    q, k, v, o, lse, seedvec = res
    do, dlse = cts
    bh, n, dh = q.shape
    spec = pl.BlockSpec((1, n, dh), lambda i: (i, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel_drop, scale=scale, rate=rate),
        grid=(bh,),
        in_specs=[_seed_spec(), spec, spec, spec, spec, lse_spec, spec,
                  lse_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((bh, n, dh), q.dtype)] * 3,
        name="flash_bh_dropout_bwd",
        interpret=_interpret(),
    )(seedvec, q, k, v, o, lse[:, None, :], do, dlse[:, None, :])
    return dq, dk, dv, np.zeros(seedvec.shape, jax.dtypes.float0)


flash_bh_dropout_lse.defvjp(_flash_bh_drop_fwd, _flash_bh_drop_bwd)


def flash_bh_dropout(q, k, v, seed, scale, rate, q0=0, k0=0):
    """(BH, N, Dh) fused attention with attention dropout; seed is a traced
    uint32 scalar (fold the step/layer rng in before calling)."""
    return flash_bh_dropout_lse(q, k, v, _seedvec(seed, q0, k0),
                                scale, rate)[0]


def _fwd4_kernel_drop(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      heads, heads_total, scale, rate, pad_rows):
    dh = q_ref.shape[-1] // heads
    n = q_ref.shape[1]
    lse_rows = []
    for i in range(heads):
        q = q_ref[0][:, i * dh:(i + 1) * dh]
        k = k_ref[0][:, i * dh:(i + 1) * dh]
        v = v_ref[0][:, i * dh:(i + 1) * dh]
        sT = jax.lax.dot_general(  # (Nk, Nq)
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        m = jnp.max(sT, axis=0, keepdims=True)       # (1, Nq)
        p = jnp.exp(sT - m)
        l = jnp.sum(p, axis=0, keepdims=True)        # (1, Nq)
        # same (b*H + h) block index convention as the BH layout, so both
        # kernel families drop identical (q, k) positions for a given seed
        bh = (pl.program_id(0) * heads_total
              + pl.program_id(1) * heads + i)
        maskT = dropout_keep_mask(seed_ref[0], jnp.uint32(bh), n, n, rate,
                                  transposed=True, q0=seed_ref[1],
                                  k0=seed_ref[2])    # (Nk, Nq)
        o = jax.lax.dot_general(                     # (Nq, Dh)
            ((p * maskT) / (l * (1.0 - rate))).astype(v.dtype), v,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        o_ref[0, :, i * dh:(i + 1) * dh] = o.astype(o_ref.dtype)
        lse_rows.append(m + jnp.log(l))
    if pad_rows:
        lse_rows.append(jnp.zeros((pad_rows - heads, n), jnp.float32))
        lse_ref[0, 0] = jnp.concatenate(lse_rows, axis=0)
    else:
        lse_ref[0] = jnp.concatenate(lse_rows, axis=0)


def _bwd4_kernel_drop(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                      dlse_ref, dq_ref, dk_ref, dv_ref, *, heads,
                      heads_total, scale, rate, pad_rows):
    dh = q_ref.shape[-1] // heads
    n = q_ref.shape[1]
    ones_row = jnp.ones((1, dh), jnp.float32)
    for i in range(heads):
        sl = slice(i * dh, (i + 1) * dh)
        q = q_ref[0][:, sl]
        k = k_ref[0][:, sl]
        v = v_ref[0][:, sl]
        o = o_ref[0][:, sl].astype(jnp.float32)
        do = do_ref[0][:, sl].astype(jnp.float32)
        lse_blk = lse_ref[0, 0] if pad_rows else lse_ref[0]
        dlse_blk = dlse_ref[0, 0] if pad_rows else dlse_ref[0]
        lse_row = lse_blk[i:i + 1, :]                # (1, Nq) f32
        dlse_row = dlse_blk[i:i + 1, :]

        sT = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        probsT = jnp.exp(sT - lse_row)               # (Nk, Nq)
        bh = (pl.program_id(0) * heads_total
              + pl.program_id(1) * heads + i)
        msT = dropout_keep_mask(seed_ref[0], jnp.uint32(bh), n, n, rate,
                                transposed=True, q0=seed_ref[1],
                                k0=seed_ref[2]) / (1.0 - rate)
        aT = probsT * msT

        aTb = aT.astype(q_ref.dtype)
        dob = do.astype(q_ref.dtype)
        dv = jax.lax.dot_general(                    # A^T dO: contract Nq
            aTb, dob, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nk, Dh)
        dpT = jax.lax.dot_general(                   # V dO^T: contract Dh
            v, dob, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (Nk, Nq)
        delta_row = jax.lax.dot_general(
            ones_row, do * o, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (1, Nq)
        dsT = (probsT * (dpT * msT - delta_row + dlse_row)
               * scale).astype(q_ref.dtype)

        dq_ref[0, :, sl] = jax.lax.dot_general(
            dsT, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_ref[0, :, sl] = jax.lax.dot_general(
            dsT, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


def _fwd4_drop(q, k, v, seedvec, scale, rate):
    b, n, h, dh = q.shape
    hb = _heads_per_program(n, h, dh, q.dtype.itemsize)
    assert hb is not None, (n, h, dh)
    pad, lse_spec, lse_shape = _lse_layout4(b, n, h, hb)
    q3, k3, v3 = (x.reshape(b, n, h * dh) for x in (q, k, v))
    spec = pl.BlockSpec((1, n, hb * dh), lambda i, j: (i, 0, j))
    o, lse = pl.pallas_call(
        functools.partial(_fwd4_kernel_drop, heads=hb, heads_total=h,
                          scale=scale, rate=rate, pad_rows=pad),
        grid=(b, h // hb),
        in_specs=[_seed_spec(), spec, spec, spec],
        out_specs=[spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, h * dh), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        name="flash_4d_dropout_fwd",
        interpret=_interpret(),
    )(seedvec, q3, k3, v3)
    if pad:
        lse = lse[:, :, :hb, :].reshape(b, h, n)
    return o.reshape(b, n, h, dh), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash4_dropout_lse(q, k, v, seedvec, scale, rate):
    """(B, N, H, Dh) fused attention with in-kernel attention dropout,
    returning (o, lse (B, H, N)); differentiable in both outputs."""
    return _fwd4_drop(q, k, v, seedvec, scale, rate)


def _flash4_drop_fwd(q, k, v, seedvec, scale, rate):
    o, lse = _fwd4_drop(q, k, v, seedvec, scale, rate)
    return (o, lse), (q, k, v, o, lse, seedvec)


def _flash4_drop_bwd(scale, rate, res, cts):
    import numpy as np
    q, k, v, o, lse, seedvec = res
    do, dlse = cts
    b, n, h, dh = q.shape
    hb = _heads_per_program(n, h, dh, q.dtype.itemsize)
    pad, lse_spec, _ = _lse_layout4(b, n, h, hb)
    flat = (x.reshape(b, n, h * dh) for x in (q, k, v, o, do))
    q3, k3, v3, o3, do3 = flat
    spec = pl.BlockSpec((1, n, hb * dh), lambda i, j: (i, 0, j))
    lse_in, dlse_in = lse, dlse
    if pad:
        lse_in, dlse_in = (_regroup_lse(lse, hb, pad),
                           _regroup_lse(dlse, hb, pad))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd4_kernel_drop, heads=hb, heads_total=h,
                          scale=scale, rate=rate, pad_rows=pad),
        grid=(b, h // hb),
        in_specs=[_seed_spec(), spec, spec, spec, spec, lse_spec, spec,
                  lse_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((b, n, h * dh), q.dtype)] * 3,
        name="flash_4d_dropout_bwd",
        interpret=_interpret(),
    )(seedvec, q3, k3, v3, o3, lse_in, do3, dlse_in)
    return (*(x.reshape(b, n, h, dh) for x in (dq, dk, dv)),
            np.zeros(seedvec.shape, jax.dtypes.float0))


flash4_dropout_lse.defvjp(_flash4_drop_fwd, _flash4_drop_bwd)


def flash4_dropout(q, k, v, seed, scale, rate, q0=0, k0=0):
    """(B, N, H, Dh) fused attention with in-kernel attention dropout."""
    return flash4_dropout_lse(q, k, v, _seedvec(seed, q0, k0),
                              scale, rate)[0]


def _tpu_dropout_kernel(cfg, n: int, force: bool = False,
                        local_heads: int = 0):
    """fn(q4, k4, v4, seed) -> o4 with in-kernel attention dropout at
    cfg.att_dropout (whole-N 4D/BH or streaming by shape), or None when
    kernels are disabled / off-TPU without force."""
    if not cfg.use_flash_attention or cfg.att_dropout <= 0.0:
        return None
    if not force and backend_platform() != "tpu":
        return None
    h = local_heads or cfg.num_heads
    dh = cfg.embed_dim // cfg.num_heads
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    path = _select_path(n, h, dh, itemsize)
    rate = float(cfg.att_dropout)
    if path == "4d":
        def drop4(q, k, v, seed):
            return flash4_dropout(q, k, v, seed, q.shape[-1] ** -0.5, rate)
        return drop4
    if path == "bh":
        def dropbh(q, k, v, seed):
            o = flash_bh_dropout(_to_bh(q), _to_bh(k), _to_bh(v), seed,
                                 q.shape[-1] ** -0.5, rate)
            return _from_bh(o, q.shape)
        return dropbh
    # streaming: the blocked kernels regenerate the same counter-hash mask
    # at global tile coordinates (vitax/ops/flash_blocked.py, round 5)
    from vitax.ops.flash_blocked import blocked_dropout_attention

    def dropstream(q, k, v, seed):
        return blocked_dropout_attention(q, k, v, seed, rate)
    return dropstream


def make_dense_dropout(rate: float):
    """Dense jnp full-sequence attention with the shared counter-hash dropout
    mask: (q, k, v, seed) -> o on (B, N, H, Dh). The off-TPU/kernels-disabled
    analog of _tpu_dropout_kernel — ring sp keeps a dense block product for
    the same purpose (_dense_block_drop); this gives the ulysses flavor the
    same anywhere-runnable dropout inner, with the same mask
    decisions at the same local (b*H + h, q, k) coordinates as the kernels
    (timm semantics: mask the softmax probabilities, rescale by 1/(1-rate))."""
    def dense_drop(q, k, v, seed):
        b, n, h, dh = q.shape
        scale = dh ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        p = jax.nn.softmax(s, axis=-1)
        bh = jnp.arange(b * h, dtype=jnp.uint32)
        mask = jax.vmap(
            lambda i: dropout_keep_mask(seed, i, n, n, rate))(bh)
        o = jnp.einsum("bhqk,bkhd->bqhd",
                       p * mask.reshape(b, h, n, n) / (1.0 - rate),
                       v.astype(jnp.float32))
        return o.astype(q.dtype)
    return dense_drop


def _select_path(n: int, h: int, dh: int, itemsize: int) -> str:
    """THE kernel-selection policy, shared by full-sequence dispatch
    (_tpu_kernel) and ring attention's local block products
    (block_kernel_with_lse): streaming past the VMEM sequence ceiling, 4D
    whole-N when a legal head grouping fits the budget, BH relayout
    otherwise (its whole-array blocks are always legal)."""
    if n > MAX_SEQ_IN_VMEM:
        return "streaming"
    if flash4_supported(n, h, dh, itemsize):
        return "4d"
    return "bh"


def block_kernel_with_lse(n: int, h: int, dh: int, itemsize: int):
    """Kernel for one (B, n, h, dh) attention block returning (o, lse (B,h,n)),
    differentiable in both outputs (the lse cotangent feeds the backward) —
    the with-lse variants of _select_path's cascade, used by ring attention.
    o comes back in the input dtype on every path — callers wanting f32
    accumulation (the logsumexp merge) must cast."""
    path = _select_path(n, h, dh, itemsize)
    if path == "4d":
        return flash4_with_lse
    if path == "streaming":
        from vitax.ops.flash_blocked import (
            DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, blocked_bh_with_lse)

        def streaming(q, k, v, scale):
            o, lse = blocked_bh_with_lse(
                _to_bh(q), _to_bh(k), _to_bh(v), scale,
                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
            return _from_bh(o, q.shape), lse.reshape(q.shape[0], h, n)
        return streaming

    def bh(q, k, v, scale):
        o, lse = flash_bh_with_lse(_to_bh(q), _to_bh(k), _to_bh(v), scale)
        return _from_bh(o, q.shape), lse.reshape(q.shape[0], h, n)
    return bh


def block_dropout_kernel_with_lse(n: int, h: int, dh: int, itemsize: int):
    """Dropout analog of block_kernel_with_lse, for ring attention's local
    block products: kern(q, k, v, seedvec, scale, rate) -> (o, lse (B,h,n)),
    differentiable in both outputs. seedvec carries [seed, q0, k0] so the
    mask is evaluated at GLOBAL token coordinates — every ring step's block
    reproduces exactly the decisions the whole-(N, N) mask makes there,
    which is what makes ring dropout equal dense masked attention."""
    path = _select_path(n, h, dh, itemsize)
    if path == "4d":
        return flash4_dropout_lse
    if path == "streaming":
        from vitax.ops.flash_blocked import (
            DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, blocked_bh_dropout_lse)

        def streaming(q, k, v, seedvec, scale, rate):
            o, lse = blocked_bh_dropout_lse(
                _to_bh(q), _to_bh(k), _to_bh(v), seedvec, scale, rate,
                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
            return _from_bh(o, q.shape), lse.reshape(q.shape[0], h, n)
        return streaming

    def bh(q, k, v, seedvec, scale, rate):
        o, lse = flash_bh_dropout_lse(_to_bh(q), _to_bh(k), _to_bh(v),
                                      seedvec, scale, rate)
        return _from_bh(o, q.shape), lse.reshape(q.shape[0], h, n)
    return bh


def _to_bh(x):  # (B, N, H, Dh) -> (B*H, N, Dh)
    b, n, h, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, n, dh)


def _from_bh(x, shape):  # (B*H, N, Dh) -> (B, N, H, Dh)
    b, n, h, dh = shape
    return x.reshape(b, h, n, dh).transpose(0, 2, 1, 3)


def _named(fn, name: str):
    """Tag an attention impl with a human-readable name for the startup log
    (shard_map outputs don't take attribute assignment, so wrap)."""
    def impl(q, k, v):
        return fn(q, k, v)
    impl.vitax_name = name
    return impl


def _tpu_kernel(cfg, n: int, force: bool = False, local_heads: int = 0):
    """(kernel, name) for full-sequence attention on this platform, or
    (None, None) when only the dense jnp path applies. The single source of
    the use_flash_attention / platform / VMEM-threshold policy.

    force=True skips the platform check (kernels run in Pallas interpret mode
    off-TPU) — used by the multichip dryrun so it exercises exactly this
    selection logic on the CPU mesh. local_heads is the PER-SHARD head count
    the kernel will actually see (num_heads/tp under shard_map, /(sp*tp)
    under Ulysses) — 4D-kernel support must be judged on that, not the
    global count."""
    if not cfg.use_flash_attention:
        return None, None
    if not force and backend_platform() != "tpu":
        return None, None
    h = local_heads or cfg.num_heads
    dh = cfg.embed_dim // cfg.num_heads
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    path = _select_path(n, h, dh, itemsize)
    if path == "streaming":
        # streaming kernel: VMEM use independent of N (vitax/ops/flash_blocked.py)
        from vitax.ops.flash_blocked import blocked_flash_attention
        return blocked_flash_attention, "pallas streaming (blocked)"
    if path == "4d":
        return flash_attention_4d, "pallas fused (4D whole-N)"
    # no legal VMEM-fitting head grouping (large N x D): the BH kernel's
    # per-(b,h) program holds a single (N, N) score temp and still fits
    return flash_attention, "pallas fused (whole-N, BH relayout)"


def _packed_impl(cfg, mesh: Optional[Mesh], force: bool):
    """The packed model's core, `impl(q, k, v, segment_ids)`: the
    segment-masked streaming kernels (vitax/ops/flash_blocked.py) wherever
    kernels run, shard_map-wrapped over the batch axes on a mesh (rows are
    independent; Config.validate admits dp/fsdp only). None -> the model's
    dense masked path."""
    if not cfg.use_flash_attention or not (
            force or backend_platform() == "tpu"):
        return None
    from vitax.ops.flash_blocked import packed_flash_attention as kernel

    name = "pallas streaming, segment-masked (packed rows)"
    if mesh is not None and mesh.size > 1:
        spec = P(BATCH_AXES, None, None, None)
        kernel = shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec, P(BATCH_AXES, None)),
            out_specs=spec, check_vma=False)
        name += " + shard_map"

    def impl(q, k, v, segment_ids):  # a fresh callable to carry the name
        return kernel(q, k, v, segment_ids)
    impl.vitax_name = name
    return impl


def _decoder_impl(cfg, mesh: Optional[Mesh], force: bool):
    """The token decoder's core, `impl(q, k, v, segment_ids, window, scale)`,
    `window` (0 = a full layer) and `scale` (on the scores; 0 = Dh ** -0.5)
    static: the packed kernels with their causal,
    window and grouped-KV terms (vitax/ops/flash_blocked.py:
    document_flash_attention), chosen and wrapped as `_packed_impl` does.
    None -> the model's dense masked path."""
    if not cfg.use_flash_attention or not (
            force or backend_platform() == "tpu"):
        return None
    from vitax.ops.flash_blocked import document_flash_attention

    name = "pallas streaming, causal / window, grouped KV (packed documents)"
    sharded = mesh is not None and mesh.size > 1
    if sharded:
        name += " + shard_map"

    def impl(q, k, v, segment_ids, window, scale=0.0):
        kernel = functools.partial(document_flash_attention, window=window,
                                   scale=scale)
        if sharded:
            spec = P(BATCH_AXES, None, None, None)
            kernel = shard_map(
                kernel, mesh=mesh,
                in_specs=(spec, spec, spec, P(BATCH_AXES, None)),
                out_specs=spec, check_vma=False)
        return kernel(q, k, v, segment_ids)
    impl.vitax_name = name
    return impl


def _fused_qkv_entry(cfg, mesh: Optional[Mesh], n: int):
    """`fused(qkv, heads)`, the hand-off-free entry of the 4D kernels that a
    whole-N impl advertises as `vitax_fused_qkv` (Attention.__call__ takes
    it when nothing sits between its projection and the kernel), or None
    where the shape keeps today's entry (flash4_qkv_supported). Only for a
    head axis that is whole on a shard: the caller checks tp == 1 and has
    left sp > 1 behind. On a mesh, the same shard_map over the batch axes
    with a (B, N, 3D) spec."""
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    if not flash4_qkv_supported(n, cfg.num_heads,
                                cfg.embed_dim // cfg.num_heads, itemsize):
        return None
    sharded = mesh is not None and mesh.size > 1

    def fused(qkv, heads):
        kernel = functools.partial(flash_attention_qkv, heads=heads)
        if sharded:
            spec = P(BATCH_AXES, None, None)
            kernel = shard_map(kernel, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False)
        return kernel(qkv)
    return fused


def make_attention_impl(cfg, mesh: Optional[Mesh] = None,
                        force_tpu_kernels: bool = False):
    """Choose the attention core for this config/mesh:

    - sp > 1: sequence parallelism — ring attention (default), or Ulysses
      all-to-all head<->token resharding with --sp_impl ulysses when
      num_heads divides over sp*tp (vitax/parallel/{ring_attention,ulysses}.py)
    - TPU: the whole-N fused Pallas kernel, or the streaming (blocked) kernel
      beyond MAX_SEQ_IN_VMEM (shard_map-wrapped on multi-device meshes)
    - otherwise: None -> dense jnp path (GSPMD still shards batch/heads)

    force_tpu_kernels=True makes the same selections off-TPU with the Pallas
    kernels in interpret mode (the multichip dryrun's production-path sweep).

    Where `_select_path` says "4d" and the head axis is whole on a shard
    (tp = 1, sp = 1) the impl also advertises `vitax_fused_qkv(qkv, heads)`,
    the 4D kernels' entry that reads the fused projection's (B, N, 3D)
    output as it stands and hands back one (B, N, 3D) gradient
    (flash_attention_qkv), and its `vitax_name` says so. The pipeline
    body's impls do not carry it.

    Attention dropout: every path that can run kernels runs dropout
    IN-KERNEL (exposed as impl.vitax_dropout, taking (q, k, v, seed)) — the
    whole-N and streaming kernels, the pipeline body (raw kernel on
    vitax_local_impl), ulysses sp (resharded inner kernel), and ring sp
    (global-coordinate masks per (q-shard, kv-block), which make the merged
    result equal dense masked attention) — each standalone AND inside the
    pipeline body. The sole dense-under-dropout surface is pp-under-tp
    (structural — warned below).
    """
    if getattr(cfg, "decoder", False):
        return _decoder_impl(cfg, mesh, force_tpu_kernels)
    if getattr(cfg, "packed", False):
        return _packed_impl(cfg, mesh, force_tpu_kernels)
    n = cfg.num_patches

    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1

    if cfg.use_flash_attention and cfg.att_dropout > 0.0:
        pp = getattr(cfg, "pp_size", 1)
        if pp > 1 and tp > 1:
            # the one remaining non-fused dropout surface: the pipeline
            # body under tp runs the dense einsum path for BOTH train and
            # eval (a Pallas kernel cannot ride a GSPMD-auto axis), so
            # dropout adds no further cliff there — but it is not fused.
            # (ring/ulysses sp — incl. under pp — and pp-without-tp all run
            # dropout in-kernel.)
            from vitax.utils.logging import master_print
            master_print(
                f"WARNING: --att_dropout {cfg.att_dropout} > 0 with the "
                f"pipeline body under tp runs unfused dense attention "
                f"(train AND eval — inherent to tp-in-pp, not to dropout). "
                f"Every kernel path (whole-N, streaming, ring/ulysses sp, "
                f"pp without tp) runs dropout fused.")

    if sp > 1:
        if n % sp != 0 or cfg.num_heads % tp != 0:
            return None  # indivisible: let GSPMD handle the dense path
        if getattr(cfg, "sp_impl", "ring") == "ulysses":
            if cfg.num_heads % (sp * tp) == 0:
                # all-to-all head<->token resharding; the inner kernel sees
                # the full sequence, so the Pallas cores apply on TPU
                from vitax.parallel.ulysses import (make_ulysses_attention,
                                                    make_ulysses_attention_pp,
                                                    make_ulysses_dropout)
                inner, _ = _tpu_kernel(cfg, n, force=force_tpu_kernels,
                                       local_heads=cfg.num_heads // (sp * tp))
                wrapped = _named(make_ulysses_attention(mesh, inner),
                                 "ulysses all-to-all (sp)")
                drop_inner = _tpu_dropout_kernel(
                    cfg, n, force=force_tpu_kernels,
                    local_heads=cfg.num_heads // (sp * tp))
                if drop_inner is None and cfg.att_dropout > 0.0:
                    # off-TPU / kernels disabled: dense inner with the same
                    # counter-hash mask, so BOTH sp flavors carry a dropout
                    # impl everywhere ring does — incl. the pp body at tp=1
                    # (ring's _dense_block_drop counterpart)
                    drop_inner = make_dense_dropout(float(cfg.att_dropout))
                if drop_inner is not None:
                    # sp with fused dropout (round 5): the resharded inner
                    # kernel runs the in-kernel mask on its full-sequence
                    # head slice (vitax/parallel/ulysses.py)
                    wrapped.vitax_dropout = make_ulysses_dropout(
                        mesh, drop_inner)
                # pp x sp: manualize only (sp, tp) inside the pipeline body
                wrapped.vitax_pp_impl = _named(
                    make_ulysses_attention_pp(inner, with_tp=tp > 1),
                    "ulysses all-to-all (sp, pp body)")
                if drop_inner is not None and tp == 1:
                    # pp x sp x dropout: the body's local a2a + dropout
                    # inner; the pipeline's per-(tick, layer, shard) keys
                    # provide the per-shard decorrelation
                    from vitax.parallel.ulysses import make_ulysses_dropout_pp
                    wrapped.vitax_pp_impl.vitax_dropout = (
                        make_ulysses_dropout_pp(drop_inner))
                return wrapped
            from vitax.utils.logging import master_print
            master_print(
                f"WARNING: --sp_impl ulysses needs num_heads divisible by "
                f"sp*tp ({cfg.num_heads} % {sp * tp} != 0); falling back to "
                f"ring attention")
        from vitax.parallel.ring_attention import (make_ring_attention,
                                                   make_ring_attention_pp,
                                                   make_ring_dropout)
        # local block product through the Pallas kernels on TPU (whole-N or
        # streaming by local length), dense jnp when kernels are disabled
        if not cfg.use_flash_attention:
            use_kernel = False
        else:
            use_kernel = True if force_tpu_kernels else None  # None = on-TPU
        wrapped = _named(make_ring_attention(mesh, use_kernel=use_kernel),
                         "ring attention (sp)")
        if cfg.att_dropout > 0.0:
            # ring dropout (round 5): global-coordinate masks per
            # (q-shard, kv-block) make the merged result equal dense masked
            # attention — works on both the kernel and dense block products
            wrapped.vitax_dropout = make_ring_dropout(
                mesh, float(cfg.att_dropout), use_kernel=use_kernel)
        # pp x sp: manualize only (sp, tp) inside the pipeline body
        wrapped.vitax_pp_impl = _named(
            make_ring_attention_pp(use_kernel=use_kernel, with_tp=tp > 1),
            "ring attention (sp, pp body)")
        if cfg.att_dropout > 0.0 and tp == 1:
            # pp x sp x dropout via the local ring body (seeded by the
            # pipeline's per-(tick, layer, shard) keys)
            from vitax.parallel.ring_attention import make_ring_dropout_pp
            wrapped.vitax_pp_impl.vitax_dropout = make_ring_dropout_pp(
                float(cfg.att_dropout), use_kernel=use_kernel)
        return wrapped

    if mesh is not None and mesh.size > 1 and cfg.num_heads % tp != 0:
        return None
    # under shard_map the kernel sees num_heads/tp heads per shard
    kernel, name = _tpu_kernel(cfg, n, force=force_tpu_kernels,
                               local_heads=cfg.num_heads // tp)
    if kernel is None:
        return None
    drop_kernel = _tpu_dropout_kernel(cfg, n, force=force_tpu_kernels,
                                      local_heads=cfg.num_heads // tp)
    fused_qkv = None
    if kernel is flash_attention_4d and tp == 1:
        fused_qkv = _fused_qkv_entry(cfg, mesh, n)
    if fused_qkv is not None:
        name += ", fused qkv"

    if mesh is None or mesh.size == 1:
        impl = _named(kernel, name)
        impl.vitax_fused_qkv = fused_qkv
        if drop_kernel is not None:
            impl.vitax_dropout = drop_kernel
            # single-device impls also serve as the pipeline BODY impl
            # (vitax_local_impl path below is only built for mesh > 1);
            # inside the body the per-(tick, layer, shard) flax keys already
            # decorrelate masks, so the raw kernel applies as-is
        return impl
    spec = P(BATCH_AXES, None, "tp", None)  # (B, N, H, Dh)
    wrapped = _named(shard_map(
        kernel, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ), name + " + shard_map")
    wrapped.vitax_fused_qkv = fused_qkv
    if drop_kernel is not None:
        shard_axes = tuple(a for a in (*BATCH_AXES, "tp")
                           if mesh.shape.get(a, 1) > 1)

        def drop_body(q, k, v, seed):
            return drop_kernel(q, k, v, fold_shard_seed(mesh, shard_axes,
                                                        seed))

        wrapped.vitax_dropout = shard_map(
            drop_body, mesh=mesh,
            in_specs=(spec, spec, spec, P()), out_specs=spec,
            check_vma=False,
        )
    # expose the unwrapped kernel for callers that run attention inside
    # their OWN shard_map (the pp pipeline body): when the mesh has no tp,
    # the body's operands are already fully local, so the raw kernel applies
    # (vitax_local_impl). Under tp > 1 no kernel variant is usable in the
    # body — vitax_pp_impl is explicitly None there (see below).
    wrapped.vitax_local_impl = _named(kernel, name)
    if drop_kernel is not None:
        # the RAW dropout kernel (no shard-index seed fold): inside the
        # pipeline body each (tick, layer, data-shard) draws its own flax
        # key (vitax/parallel/pipeline.py), so masks are already
        # decorrelated across shards — pp keeps the fused dropout path
        wrapped.vitax_local_impl.vitax_dropout = drop_kernel
    if mesh.shape.get("tp", 1) > 1:
        # pp body under tp: "tp" is a GSPMD-auto axis there and a Pallas
        # kernel cannot be auto-partitioned (and a nested tp shard_map hits
        # the jax-0.9 Shardy constant-hoisting bug — see
        # vitax/parallel/pipeline.py). None routes the Block to the dense
        # einsum path, which GSPMD partitions over the tp-global head dim.
        # MEASURED (round 5, v5e): at 10B dims the dense path costs ~1.9%
        # of step time (10b_slice 114.1 img/s dense vs 116.3 kernel at
        # matching knobs), so the unfused body is cheap at
        # flagship widths; the scan path keeps the kernel.
        wrapped.vitax_pp_impl = None
    else:
        wrapped.vitax_pp_impl = wrapped.vitax_local_impl
    return wrapped
