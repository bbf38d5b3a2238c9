"""Kimi Delta Attention over packed documents: a delta rule in chunked form.

The mixer of a `kda` layer of the token decoder (vitax/models/decoder.py), in
place of attention (Kimi Linear, arXiv:2510.26692, with a lower-bounded
gate). With `u` the normed input of a token and H heads of `head_size`
channels:

    q, k, v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))
                  depthwise, causal, `conv_width` taps, no bias
    q = l2norm(q) * head_size ** -0.5,   k = l2norm(k)             a head
    g = L * sigmoid(exp(A_log) * (W_f u + dt_bias))    in (L, 0), a head AND channel
    b = sigmoid(W_b u)                                 a head
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                                    S (head_size, head_size), float32
    out = W_o[ RMSNorm(o_t) * w * sigmoid(W_g u_t) ]   normed a head, gated a head

A document starts from S = 0 and its convolution sees no token of another
document; padding (`segment_ids` 0) gives zeros and receives nothing
(vitax/models/ssm.py's conventions, and its `causal_conv`).

The recurrence runs in chunks of `chunk` tokens (`kda`). With G the running
sum of g inside a chunk, u_t = b_t (v_t - k_t^T Diag(exp(g_t)) S_{t-1}) the
corrected value of a token and S_0 the state the chunk begins with,

    (I + A) U = Diag(b) (V - (K e^G) S_0),   A_ts = b_t (k_t e^{G_t}) . (k_s e^{-G_s}),  s < t
    o_t = (q_t e^{G_t}) S_0 + sum_{s <= t} (q_t e^{G_t}) . (k_s e^{-G_s}) u_s
    S_end = Diag(e^{G_end}) S_0 + sum_s (k_s e^{G_end - G_s}) u_s^T

The decay is per channel, so it does not factor out of a (query, key)
product as SSD's scalar does, and e^{-G} alone overflows: a product over
channels takes e^{G_t - m} on the query's side and e^{m - G_s} on the key's,
with m the running sum at the middle of the query's SUB-chunk of `sub`
tokens; both exponents then stay within |L| * sub / 2 (a key of an earlier
sub-chunk has a negative one). That is why the gate is bounded, and `sub`
follows from the bound (`chunk_tiling`). The unit lower-triangular (I + A) is
inverted as the nilpotent product (I - A)(I + A^2)(I + A^4)... in float32:
matmuls, no scalar loop (scope `kda_chunk`). One float32 state a head goes
from chunk to chunk in a `lax.scan` that solves U for the chunk, reads S_0
for the chunk's queries and adds the intra-chunk term (scope `kda_state`).

Document boundaries fall anywhere: a pair passes only within one document,
so the triangular system decouples by document; only tokens of the document
that owns the incoming state read it; the state a chunk ends with is made of
the tokens of its last token's document and passes through a chunk only if
the whole chunk lies in that document.

A Gated-DeltaNet layer (`GatedDeltaMixer`, arXiv:2412.06464) runs the same
rule with ONE decay a head, g = -exp(A_log) * softplus(W_a u + dt_bias) with
no lower bound, and a state of key_size x value_size. A scalar decay factors
out of a (query, key) product as SSD's does: A_ts = b_t (k_t . k_s)
e^{G_t - G_s}, the exponent a difference taken first and so at most 0,
whatever g is. `kda` takes that form where `g` comes a head, (R, T, H), and
not a head and channel: no sub-chunks and no bound, one masked (chunk,
chunk) decay a head; the solve, the inverse, the state's scan and the
document rules are the same.

g, G, every exp and every state are float32; the products take operands of
the model's dtype and accumulate in float32, as `ssd` does. This plain
`jax.numpy` form is the CPU's path and the tests' oracle; on a TPU, where the
shapes tile, `KDAMixer.rule` is the fused kernel pair of vitax/ops/kda.py
(`choose_kernels`, through `build_model_for` as the scan of a mamba layer is),
which keeps a chunk's decayed keys, scores and inverse in VMEM. Here the
per-chunk part is made `KDA_BLOCK_BYTES` worth of chunks at a time and again
in the backward (`jax.checkpoint`), as `ssd`'s is.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from vitax.models.ssm import (Leaf, a_log_init, conv_init, conv_silu,
                              dt_bias_init)
from vitax.models.vit import Array, Dtype, default_init
from vitax.ops.kda import KDA_CHUNK, chunk_tiling

# The grid the step's counters `kda_pairs` and `kda_live_chunks` count on
# (vitax/train/step.py: decoder_counts), and so the grid of the delta rule's
# useful FLOPs (vitax/telemetry/flops.py). A constant of its own and NOT
# `chunk_tiling`'s choice: a change of KDA_CHUNK shows in the step's time, not
# in what the step is said to need.
KDA_COUNT_CHUNK = 64
# float32 bytes of the (rows, chunks, sub-chunks, chunk, heads, head_size)
# key-side decay that a block of chunks may hold
KDA_BLOCK_BYTES = 256 * 2 ** 20


class KDAShape(NamedTuple):
    heads: int
    head_size: int
    conv_width: int
    gate_bound: float       # L < 0: g lies in (L, 0)

    @property
    def inner(self) -> int:
        return self.heads * self.head_size

    @property
    def conv(self) -> Tuple[int, int, Tuple[int, int, int]]:
        """The convolution's channels and taps, and the norm behind it: a
        head's size, the channels normed (q and k), those scaled too (q)."""
        return (3 * self.inner, self.conv_width,
                (self.head_size, 2 * self.inner, self.inner))


def kda_param_count(shape: KDAShape, embed_dim: int) -> int:
    """W_q, W_k, W_v, W_f and W_o; the three convolutions; A_log, dt_bias;
    W_b and W_g; the output norm's weight."""
    return (5 * embed_dim * shape.inner + 3 * shape.conv_width * shape.inner
            + shape.heads + shape.inner + 2 * embed_dim * shape.heads
            + shape.head_size)


class GatedDeltaShape(NamedTuple):
    heads: int
    key_size: int
    value_size: int
    conv_width: int

    @property
    def inner(self) -> int:         # q, k and v behind one convolution
        return self.heads * (2 * self.key_size + self.value_size)

    @property
    def conv(self) -> Tuple[int, int, Tuple[int, int, int]]:
        """As `KDAShape.conv`, the norm over a head's key."""
        wide = self.heads * self.key_size
        return self.inner, self.conv_width, (self.key_size, 2 * wide, wide)


def gated_delta_param_count(shape: GatedDeltaShape, embed_dim: int) -> int:
    """W_q, W_k, W_v; the convolution; W_a, W_b, A_log, dt_bias; W_z, the
    output norm's weight and W_o."""
    wide = shape.heads * shape.value_size
    return (embed_dim * shape.inner + shape.conv_width * shape.inner
            + 2 * embed_dim * shape.heads + 2 * shape.heads
            + embed_dim * wide + shape.value_size + wide * embed_dim)


def count_chunk(tokens: int) -> int:
    """The chunk of the counters' grid for rows of `tokens`."""
    return math.gcd(tokens, KDA_COUNT_CHUNK)


def unit_lower_inverse(a: Array, size: int = 0) -> Array:
    """(I + a)^-1 for `a` (..., c, c) strictly lower triangular, float32:
    with n = -a nilpotent, (I - n)^-1 = (I + n)(I + n^2)(I + n^4)...; `size`:
    n^size = 0 already (diagonal blocks of `size`; 0: the whole c)."""
    c = a.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    power = -a
    out = jnp.eye(c, dtype=a.dtype) + power
    for _ in range(max(math.ceil(math.log2(size or c)) - 1, 0)):
        power = jnp.matmul(power, power, precision=hi)
        out = out + jnp.matmul(out, power, precision=hi)
    return out


INVERSE_BASE = 4        # the blocks `unit_lower_inverse_merged` starts from


def unit_lower_inverse_merged(a: Array) -> Array:
    """(I + a)^-1 as `unit_lower_inverse` gives it, built from the diagonal
    blocks up: the blocks of INVERSE_BASE by the nilpotent product, then
    pairs of neighbours merged, [[P, 0], [-Q a21 P, Q]] for the inverses P
    and Q of a pair, until one block is left. Every intermediate is a block
    of the inverse itself, so nothing grows that the result does not hold:
    the nilpotent product's powers a^2, a^4, ... reach binomial sizes
    (entries near b on keys that repeat give C(c, c / 2) b^(c / 2)) and
    cancel in float32 only while c is small. Written on the whole (c, c)
    matrix, X the block-diagonal inverse so far: X <- X - X (a masked to the
    pairs' lower-left blocks) X; the zeros outside the blocks are exact, and
    the products keep the shapes the MXU tiles (as many of them as the
    nilpotent product of the whole chunk takes)."""
    c = a.shape[-1]
    s = math.gcd(c, INVERSE_BASE)
    assert c // s & (c // s - 1) == 0, f"a chunk of {c} is no 2^n x {s}"
    hi = jax.lax.Precision.HIGHEST

    def same(size):     # rows and columns of one diagonal block of `size`
        at = jnp.arange(c) // size
        return at[:, None] == at[None, :]

    inverse = unit_lower_inverse(jnp.where(same(s), a, 0.0), size=s)
    while s < c:
        below = jnp.where(same(2 * s) & ~same(s), a, 0.0)
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, below, precision=hi), inverse, precision=hi)
        s *= 2
    return inverse


def _chunk_block(per_chunk_bytes: int, chunks: int) -> int:
    """Chunks a block: the most that divide `chunks` within the budget."""
    most = max(KDA_BLOCK_BYTES // per_chunk_bytes, 1)
    return max(b for b in range(1, chunks + 1)
               if chunks % b == 0 and b <= most)


def kda(q: Array, k: Array, v: Array, g: Array, beta: Array,
        segment_ids: Array, chunk: int, sub: int, dtype: Dtype) -> Array:
    """The delta rule: q and k (R, T, H, K), v (R, T, H, V), g float32 and
    <= 0, a head and channel (R, T, H, K) or a head (R, T, H), beta (R, T, H)
    float32 in [0, 2], segment_ids (R, T) with T a multiple of `chunk` and
    `chunk` of `sub` -> o (R, T, H, V) float32. q, k, v, g and beta are zero
    at padding, and so is o. The shape of `g` chooses the decay's form: a
    scalar one needs no sub-chunks and no bound (`sub` plays no part)."""
    r, t, h, dk = q.shape
    dv = v.shape[-1]
    scalar = g.ndim == 3
    c, nc, a = chunk, t // chunk, chunk // sub
    f32 = jnp.float32
    seg = segment_ids.reshape(r, nc, c)
    last = seg[:, :, -1]                            # who owns what a chunk leaves
    owner = jnp.pad(last, ((0, 0), (1, 0)))[:, :nc]     # ... and what it is given
    at = jnp.arange(c)
    not_after = at[:, None] >= at[None, :]          # key not after query
    # a key meets the queries of its own and of later sub-chunks
    upto = (at[None, :] // sub) <= jnp.arange(a)[:, None]       # (a, c)

    @jax.checkpoint
    def block(args):
        seg, owner, q, k, v, g, beta = args         # (R, chunks a block, c, ...)
        n = seg.shape[1]
        with jax.named_scope("kda_chunk"):
            q32, k32 = q.astype(f32), k.astype(f32)
            run = jnp.cumsum(g, axis=2)                             # R n l h k
            by_sub = run.reshape(r, n, a, sub, h, dk)
            mid = by_sub[:, :, :, sub // 2]                         # R n a h k
            row = jnp.exp(by_sub - mid[:, :, :, None])              # R n a s h k
            col = jnp.exp(jnp.where(
                upto[None, None, :, :, None, None],
                mid[:, :, :, None] - run[:, :, None], -jnp.inf))    # R n a j h k
            keys = (k32[:, :, None] * col).astype(dtype)

            def scores(x32):        # (x_l e^{G_l}) . (k_j e^{-G_j}): R n h l j
                rows = (x32.reshape(r, n, a, sub, h, dk) * row).astype(dtype)
                return jnp.einsum("rnashk,rnajhk->rnhasj", rows, keys,
                                  preferred_element_type=f32).reshape(
                                      r, n, h, c, c)

            see = ((seg[:, :, :, None] == seg[:, :, None, :])
                   & (seg[:, :, :, None] > 0))[:, :, None]          # R n 1 l j
            bh = beta.transpose(0, 1, 3, 2)                         # R n h l
            qk = jnp.where(see & not_after, scores(q32), 0.0)
            kk = jnp.where(see & (not_after & ~not_after.T), scores(k32),
                           0.0) * bh[..., None]
            solve = unit_lower_inverse(kk).astype(dtype)            # R n h l s
            reads = ((seg == owner[..., None]) & (seg > 0))[..., None, None]
            from_start = jnp.where(reads, jnp.exp(run), 0.0)        # R n l h k
            b4 = beta[..., None]
            w = jnp.einsum("rnhls,rnshk->rnhlk", solve,
                           (k32 * from_start * b4).astype(dtype),
                           preferred_element_type=f32)
            u0 = jnp.einsum("rnhls,rnshv->rnhlv", solve,
                            (v.astype(f32) * b4).astype(dtype),
                            preferred_element_type=f32)
            mine = ((seg == seg[:, :, -1:]) & (seg > 0))[..., None, None]
            to_end = jnp.exp(jnp.where(mine, run[:, :, -1:] - run, -jnp.inf))
            return (qk.astype(dtype), w.astype(dtype), u0,
                    (q32 * from_start).astype(dtype).transpose(0, 1, 3, 2, 4),
                    (k32 * to_end).astype(dtype).transpose(0, 1, 3, 2, 4),
                    jnp.exp(run[:, :, -1]))                         # R n h k

    @jax.checkpoint
    def scalar_block(args):
        """`block` for one decay a head: e^{G_l - G_j} factors out of a
        (query, key) product, is taken after the difference and is at most
        1, whatever g is."""
        seg, owner, q, k, v, g, beta = args         # g (R, n, c, h)
        with jax.named_scope("kda_chunk"):
            q32, k32 = q.astype(f32), k.astype(f32)
            run = jnp.cumsum(g, axis=2)                             # R n l h
            by_head = run.transpose(0, 1, 3, 2)                     # R n h l
            see = ((seg[:, :, :, None] == seg[:, :, None, :])
                   & (seg[:, :, :, None] > 0))[:, :, None]          # R n 1 l j
            decay = jnp.exp(jnp.where(
                see & not_after,
                by_head[..., :, None] - by_head[..., None, :], -jnp.inf))

            def scores(x):          # x_l . k_j: R n h l j
                return jnp.einsum("rnlhk,rnjhk->rnhlj", x, k,
                                  preferred_element_type=f32)

            bh = beta.transpose(0, 1, 3, 2)                         # R n h l
            qk = scores(q) * decay
            kk = jnp.where(not_after.T, 0.0, scores(k) * decay) \
                * bh[..., None]
            # beta reaches 2 here and keys may repeat: built from blocks
            solve = unit_lower_inverse_merged(kk).astype(dtype)     # R n h l s
            reads = ((seg == owner[..., None]) & (seg > 0))[..., None]
            from_start = jnp.where(reads, jnp.exp(run), 0.0)[..., None]
            b4 = beta[..., None]
            w = jnp.einsum("rnhls,rnshk->rnhlk", solve,
                           (k32 * from_start * b4).astype(dtype),
                           preferred_element_type=f32)
            u0 = jnp.einsum("rnhls,rnshv->rnhlv", solve,
                            (v.astype(f32) * b4).astype(dtype),
                            preferred_element_type=f32)
            mine = ((seg == seg[:, :, -1:]) & (seg > 0))[..., None]
            to_end = jnp.exp(jnp.where(
                mine, run[:, :, -1:] - run, -jnp.inf))[..., None]
            return (qk.astype(dtype), w.astype(dtype), u0,
                    (q32 * from_start).astype(dtype).transpose(0, 1, 3, 2, 4),
                    (k32 * to_end).astype(dtype).transpose(0, 1, 3, 2, 4),
                    jnp.exp(run[:, :, -1])[..., None])              # R n h 1

    def chunks(x):      # (R, T, ...) -> (R, nc, c, ...)
        return x.reshape(r, nc, c, *x.shape[2:])

    if scalar:          # the decay and the two score products of a chunk
        block, cb = scalar_block, _chunk_block(12 * r * h * c * c, nc)
    else:
        cb = _chunk_block(4 * r * a * c * h * dk, nc)

    def blocked(x):     # (R, nc, ...) -> (nc / cb, R, cb, ...)
        return jnp.moveaxis(x.reshape(r, nc // cb, cb, *x.shape[2:]), 1, 0)

    def whole(x):       # and back
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape(r, nc, *x.shape[3:])

    args = (seg, owner, chunks(q), chunks(k), chunks(v), chunks(g),
            chunks(beta))
    if cb == nc:
        parts = block(args)
    else:
        parts = tuple(map(whole, jax.lax.map(block,
                                             tuple(map(blocked, args)))))
    qk, w, u0, q_start, k_end, decay_end = parts

    with jax.named_scope("kda_state"):
        through = jnp.where(((last == owner) & (last > 0))[..., None, None],
                            decay_end, 0.0)     # R nc h k (h 1: one a head)

        @jax.checkpoint
        def carry(state, inputs):
            qk, w, u0, q_start, k_end, through = inputs
            given = state.astype(dtype)
            u = (u0 - jnp.einsum("rhlk,rhkv->rhlv", w, given,
                                 preferred_element_type=f32)).astype(dtype)
            o = (jnp.einsum("rhlk,rhkv->rhlv", q_start, given,
                            preferred_element_type=f32)
                 + jnp.einsum("rhls,rhsv->rhlv", qk, u,
                              preferred_element_type=f32))
            state = state * through[..., None] + jnp.einsum(
                "rhlk,rhlv->rhkv", k_end, u, preferred_element_type=f32)
            return state, o

        _, o = jax.lax.scan(
            carry, jnp.zeros((r, h, dk, dv), f32),
            tuple(jnp.moveaxis(x, 1, 0)
                  for x in (qk, w, u0, q_start, k_end, through)))
        # (nc, R, h, c, v) -> (R, T, h, v)
        return o.transpose(1, 0, 3, 2, 4).reshape(r, t, h, dv)


class KDAMixer(nn.Module):
    shape: KDAShape
    norm_eps: float
    dtype: Dtype = jnp.bfloat16
    rule: Optional[Callable] = None     # the delta rule (None: the plain `kda`)
    conv: Optional[Callable] = None     # `conv_silu`'s; None: `conv_silu`

    @nn.compact
    def __call__(self, u: Array, segment_ids: Array) -> Array:
        s = self.shape
        r, t, d = u.shape
        h, dh = s.heads, s.head_size
        f32 = jnp.float32

        def linear(features, name, dtype=self.dtype):
            return nn.Dense(features, use_bias=False, dtype=dtype,
                            param_dtype=f32, kernel_init=default_init,
                            name=name)

        valid = (segment_ids > 0)[..., None]
        qkv = jnp.concatenate([linear(s.inner, n)(u)
                               for n in ("wq", "wk", "wv")], axis=-1)
        with jax.named_scope("kda_conv"):
            taps = Leaf((s.conv_width, 3 * s.inner), conv_init, "kernel",
                        name="conv")()
            qkv = (self.conv or conv_silu)(
                qkv, segment_ids, taps, None, self.dtype, s.conv[2])
            q, k, v = (x.reshape(r, t, h, dh)
                       for x in jnp.split(qkv, 3, axis=-1))

        with jax.named_scope("kda_gate"):
            a_log = Leaf((h,), a_log_init, name="A_log")()
            dt_bias = Leaf((s.inner,), dt_bias_init, "bias", name="dt_bias")()
            f = linear(s.inner, "wf")(u).astype(f32) + dt_bias
            g = s.gate_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None] * f.reshape(r, t, h, dh))
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid, jax.nn.sigmoid(
                linear(h, "wb")(u).astype(f32)), 0.0)

        o = (self.rule or kda)(q, k, v, g, beta, segment_ids,
                               *chunk_tiling(t, s.gate_bound), self.dtype)

        with jax.named_scope("kda_out_norm"):
            scale = Leaf((dh,), nn.initializers.ones, name="out_norm")()
            gate = jax.nn.sigmoid(linear(h, "head_gate")(u).astype(f32))
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + self.norm_eps)
            o = (o * scale * gate[..., None]).astype(self.dtype)
        return linear(d, "wo")(o.reshape(r, t, s.inner))


class GatedDeltaMixer(nn.Module):
    """Gated DeltaNet (arXiv:2412.06464, as the `fla` library's layer writes
    it with its gate and short convolutions on): `u` is what the block hands
    the mixer (the raw residual stream in a norm-after block).

        q, k, v = silu(conv([W_q u; W_k u; W_v u]))     heads of key_size,
                      key_size and value_size; depthwise, causal, no bias
        q = l2norm(q) * key_size ** -0.5,   k = l2norm(k)           a head
        g = -exp(A_log) * softplus(W_a u + dt_bias)     <= 0, ONE a head
        b = 2 * sigmoid(W_b u)                          in (0, 2), a head
        S_t = e^{g_t} (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
        o_t = S_t^T q_t                 S (key_size, value_size), float32
        out = W_o[ RMSNorm(o_t) * w * silu(W_z u_t) ]   normed a head (one
                      weight of value_size), gated a head AND channel

    The scopes are `KDAMixer`'s, for the same kinds of work."""

    shape: GatedDeltaShape
    norm_eps: float
    dtype: Dtype = jnp.bfloat16
    conv: Optional[Callable] = None     # as `KDAMixer.conv`

    @nn.compact
    def __call__(self, u: Array, segment_ids: Array) -> Array:
        s = self.shape
        r, t, d = u.shape
        h, dk, dv = s.heads, s.key_size, s.value_size
        f32 = jnp.float32

        def linear(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=f32, kernel_init=default_init,
                            name=name)

        valid = (segment_ids > 0)[..., None]
        qkv = jnp.concatenate([linear(h * n, name)(u) for name, n in (
            ("wq", dk), ("wk", dk), ("wv", dv))], axis=-1)
        with jax.named_scope("kda_conv"):
            taps = Leaf((s.conv_width, s.inner), conv_init, "kernel",
                        name="conv")()
            qkv = (self.conv or conv_silu)(
                qkv, segment_ids, taps, None, self.dtype, s.conv[2])
            q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
            q, k = q.reshape(r, t, h, dk), k.reshape(r, t, h, dk)
            v = v.reshape(r, t, h, dv)

        with jax.named_scope("kda_gate"):
            a_log = Leaf((h,), a_log_init, name="A_log")()
            dt_bias = Leaf((h,), dt_bias_init, "bias", name="dt_bias")()
            g = -jnp.exp(a_log) * jax.nn.softplus(
                linear(h, "wa")(u).astype(f32) + dt_bias)
            g = jnp.where(valid, g, 0.0)
            beta = jnp.where(valid, 2.0 * jax.nn.sigmoid(
                linear(h, "wb")(u).astype(f32)), 0.0)

        chunk = math.gcd(t, KDA_CHUNK)
        o = kda(q, k, v, g, beta, segment_ids, chunk, chunk, self.dtype)

        z = linear(h * dv, "wz")(u)
        with jax.named_scope("kda_out_norm"):
            scale = Leaf((dv,), nn.initializers.ones, name="out_norm")()
            gate = jax.nn.silu(z.astype(f32))
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + self.norm_eps)
            o = (o * scale * gate.reshape(r, t, h, dv)).astype(self.dtype)
        return linear(d, "wo")(o.reshape(r, t, h * dv))
