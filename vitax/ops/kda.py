"""The delta rule of vitax/models/kda.py as a pair of fused Pallas TPU kernels.

`kda_fused` computes what the plain `vitax.models.kda.kda` computes, chunk
after chunk, without a chunk's decayed keys, its (chunk, chunk) scores or the
triangular inverse ever reaching HBM. One grid step is one chunk of one row
and `hb` heads (a head is one or more whole lane tiles, so nothing is
selected inside a tile):

- forward (`kda_fwd`): from the chunk's tiles of q, k, v, the running
  log-decay G, beta and the segment ids: the sub-chunk middles m, the operands
  q e^{G-m}, k e^{G-m} and k e^{m-G}, the two masked score matrices, the unit
  lower inverse X = (I + A)^-1, w = X (b k e^G), u0 = X (b v), the read of the
  state the chunk began with, u = u0 - w S, o = (q e^G) S + QK u, and the
  state the chunk leaves, S <- through * S + (k e^{G_end - G})^T u. The float32
  state of every head lives in VMEM scratch across the chunk axis (sequential;
  rows parallel), transposed (value, key), so that a decay over key channels is
  a row of lanes. HBM sees the inputs once, o, and the float32 state every
  chunk began with (the backward's residual).
- backward (`kda_bwd`, `jax.custom_vjp`): the chunks in reverse, the state's
  cotangent carried in VMEM scratch; operands, scores and X are made again
  from the same tiles, once. With dU = QK^T dO + K_end dS', dW = -dU S^T and
  dX = dW (b k e^G)^T + dU (b v)^T, the inverse's cotangent is the closed form
  dA = -X^T dX X^T (two products where differentiating the doublings takes
  twenty), and from there into q, k, v, beta and G. The middles m are
  stabilisers: every product pairs e^{G_t - m} with e^{m - G_s} and their
  derivative is zero, but for the operands' rounding; the kernel hands that
  rounding to the middle's token as JAX does in the plain form, so that a
  chunk's d G sums to what the state's terms alone leave (dropping it costs
  the gradient of g 3% in bfloat16, where the plain form stands 1.8% from the
  float32 one). The caller's cumsum (g -> G) is differentiated by JAX outside.

Precision is the plain form's: G, every exp and every state float32; products
take operands rounded to the model's dtype where `kda` rounds them and
accumulate in float32; the inverse (and its cotangent) is float32 with
full-precision products, by the same doublings as `unit_lower_inverse`: the
six bfloat16 products of `Precision.HIGHEST` of every multiplication, which
`_dot_full` arranges as three contractions a lane tile deep (the cotangent's
two multiplications are Mosaic's own fp32 contraction). The heads of a grid
step go through every product in step: a head's chain of products is
sequential, and the four MXUs are fed by the other heads' (on the chip, one
head after the other took 1.7 times as long).

The bodies are large (16 heads unrolled, each with six float32 doublings of
three contractions) and a step calls them at nine sites: three runs of kda
layers, each with a forward, the remat's forward and a backward. Python would
run a body at every site of every program, also in a run that finds its
programs in the compile cache, which is asked only once the step is traced and
lowered (PR 42: +36 s of set-up). So `_forward` and `_backward`, which hold the
two `pl.pallas_call`s, are `jax.jit`s: a process traces each body once, every
site of every program shares that jaxpr, and a module lowers it once a set of
outputs and calls it (`func.call`, which XLA inlines; PERF.md, PR 43, on what
the jitted layouts cost a run of one layer).

`kda_tiling` says whether a mixer's shapes tile (head size a multiple of 128,
sub-chunks of whole sublane tiles, all heads' states within
`STATE_VMEM_BYTES`).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.common import (LANES, NT, TN, compiler_params, f32, interpret,
                              one_trace_context, thirds)

KDA_CHUNK = 64          # tokens a chunk, where the row's length allows
KDA_EXP_RANGE = 40.0    # the largest |exponent| a product's operand may take
SUBLANES = 16                           # of the model dtype's (16, 128) tile
# at most; unrolled in the body, in step. tools/bench_kda.py on the chip, the
# Ling cell's shape, forward / forward + backward: 16 heads 0.88 / 2.13 ms,
# 8 heads 0.93 / 2.23, 4 heads 1.08 / 2.51, 1 head 2.39 / 5.39 (the heads are
# what overlaps a head's chain of products; the plain form 2.83 / 12.37)
HEADS_PER_STEP = 16
STATE_VMEM_BYTES = 16 * 2 ** 20         # all heads' (K, K) float32 states
_HI = jax.lax.Precision.HIGHEST


def chunk_tiling(tokens: int, gate_bound: float) -> Tuple[int, int]:
    """(chunk, sub) for rows of `tokens`, of both forms of the rule: the
    longest chunk up to KDA_CHUNK that divides the row, and the longest
    power-of-two sub-chunk over which |gate_bound| * sub / 2 stays within
    KDA_EXP_RANGE."""
    chunk = math.gcd(tokens, KDA_CHUNK)
    sub = 1
    while (sub * 2 <= chunk and chunk % (sub * 2) == 0
           and abs(gate_bound) * sub <= KDA_EXP_RANGE):
        sub *= 2
    return chunk, sub


def kda_tiling(heads: int, head_size: int, chunk: int,
               sub: int) -> Union[int, str]:
    """Heads a grid step of the kernels for a mixer of these shapes, or why
    they cannot tile it."""
    if head_size % LANES:
        return f"head size {head_size} is no multiple of {LANES}"
    if sub % SUBLANES or chunk % sub:
        return (f"sub-chunks of {sub} in chunks of {chunk} are no whole "
                f"{SUBLANES}-row tiles")
    if 4 * heads * head_size * head_size > STATE_VMEM_BYTES:
        return "the states of a row do not fit VMEM"
    return max(b for b in range(1, HEADS_PER_STEP + 1) if heads % b == 0)


def _dot(a, b, dims=None, precision=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=f32, precision=precision)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=f32,
                               precision=precision)


def _dot_full(a2, b2):
    """a @ b in float32 with full-precision products, for a (m, c) and b
    (c, c) each given twice along the lanes, [a | a] and [b | b], and so
    returned: the six bfloat16 products of `Precision.HIGHEST` (hi hi, hi mid,
    mid hi, mid mid, hi lo, lo hi; float32 accumulation), arranged as three
    contractions 2 c deep, a whole lane tile where c is 64, in place of six c
    deep: [a_hi | a_hi] and [a_mid | a_mid] against [b_hi; b_mid], and
    [a_hi | a_lo] against [b_lo; b_hi]."""
    a_hi, a_mid, a_lo = thirds(a2)
    b_hi, b_mid, b_lo = thirds(b2)
    first = jax.lax.broadcasted_iota(
        jnp.int32, (1, a2.shape[1]), 1) < a2.shape[1] // 2
    high = jnp.concatenate([b_hi, b_mid])
    return (_dot(a_hi, high) + _dot(a_mid, high)
            + _dot(jnp.where(first, a_hi, a_lo),
                   jnp.concatenate([b_lo, b_hi])))


def unit_lower_inverses(matrices):
    """(I + a)^-1 for each `a` (c, c) strictly lower triangular, float32: the
    doublings of `vitax.models.kda.unit_lower_inverse`, product for product
    (`_dot_full`), the matrices in step: a product's successor waits on it,
    the same product of the next matrix does not."""
    c = matrices[0].shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    eye = jnp.where((rows == cols) | (rows + c == cols), 1.0, 0.0).astype(f32)
    powers = [-jnp.concatenate([a, a], axis=1) for a in matrices]
    outs = [eye + p for p in powers]
    for _ in range(max(math.ceil(math.log2(c)) - 1, 0)):
        powers = [_dot_full(p, p) for p in powers]
        outs = [o + _dot_full(o, p) for o, p in zip(outs, powers)]
    return [o[:, :c] for o in outs]


def unit_lower_inverse(a):
    return unit_lower_inverses([a])[0]


def unit_lower_inverse_vjps(xs, dxs):
    """The cotangent of each `a` where x = (I + a)^-1 and dx is x's:
    -x^T dx x^T, float32 with full-precision products, the matrices in step;
    the caller keeps the strictly lower part."""
    firsts = [_dot(x, dx, TN, _HI) for x, dx in zip(xs, dxs)]
    return [-_dot(t, x, NT, _HI) for t, x in zip(firsts, xs)]


def unit_lower_inverse_vjp(x, dx):
    return unit_lower_inverse_vjps([x], [dx])[0]


def _masks(segc_ref, segr_ref):
    """What a chunk's segment ids say of its pairs: (query l sees key s: one
    document, the key not after the query; the same with s < l), (c, c) bool."""
    segc, segr = segc_ref[0, 0], segr_ref[0, 0]             # (c, 1), (1, c)
    c = segc.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    see = (segc == segr) & (segc > 0)
    return see & (rows >= cols), see & (rows > cols)


class _Chunk:
    """A head's chunk as both kernels make it from the tiles: the operands,
    the masked scores, the inverse and what meets the state."""

    def __init__(self, q, k, v, run, beta, segc, last, owner, masks, sub,
                 dtype):
        c = q.shape[0]
        self.q32, self.k32, self.v32 = (x.astype(f32) for x in (q, k, v))
        self.beta = beta                                    # (c, 1)
        q32, k32 = self.q32, self.k32
        subs = [slice(at, at + sub) for at in range(0, c, sub)]
        mids = [run[s.start + sub // 2:s.start + sub // 2 + 1] for s in subs]
        # a query's side: e^{G - m} with m its own sub-chunk's middle
        self.row = jnp.concatenate(
            [jnp.exp(run[s] - m) for s, m in zip(subs, mids)])
        self.rq = (q32 * self.row).astype(dtype)
        self.rk = (k32 * self.row).astype(dtype)
        # a key's side, for the queries of sub-chunk a: e^{m_a - G} up to the
        # end of that sub-chunk, nothing after it
        self.col, self.keys = [], []
        for s, m in zip(subs, mids):
            col = jnp.exp(m - run[:s.stop])
            keys = (k32[:s.stop] * col).astype(dtype)
            if s.stop < c:
                col = jnp.concatenate([col, jnp.zeros((c - s.stop,)
                                                      + col.shape[1:], f32)])
                keys = jnp.concatenate([keys, jnp.zeros(
                    (c - s.stop,) + keys.shape[1:], dtype)])
            self.col.append(col)
            self.keys.append(keys)
        self.subs = subs
        both = [_dot(jnp.concatenate([self.rq[s], self.rk[s]]), keys, NT)
                for s, keys in zip(subs, self.keys)]        # (2 sub, c) each
        lower, strict = masks
        self.qk = jnp.where(lower, jnp.concatenate(
            [b[:sub] for b in both]), 0.0)
        self.kk = jnp.where(strict, jnp.concatenate(
            [b[sub:] for b in both]), 0.0)                  # before beta
        valid = segc > 0
        reads = (segc == owner) & valid
        mine = (segc == last) & valid
        end = run[c - 1:c]
        self.from_start = jnp.where(reads, jnp.exp(run), 0.0)
        self.to_end = jnp.exp(jnp.where(mine, end - run, -jnp.inf))
        passes = jnp.logical_and(last == owner, last > 0)
        self.through = jnp.where(passes, jnp.exp(end), 0.0)     # (1, K)
        self.kb32 = k32 * self.from_start * beta
        self.kb = self.kb32.astype(dtype)
        self.vb = (self.v32 * beta).astype(dtype)
        self.qs32 = q32 * self.from_start
        self.ke32 = k32 * self.to_end
        self.q_start = self.qs32.astype(dtype)
        self.k_end = self.ke32.astype(dtype)

    def solve(self, x):
        """w and u0 from x = (I + beta kk)^-1, float32."""
        self.x = x
        solve = x.astype(self.kb.dtype)
        self.w = _dot(solve, self.kb)
        self.u0 = _dot(solve, self.vb)

    def corrected(self, given, dtype):
        """u (c, V) in the model's dtype, from the transposed state the chunk
        began with, (V, K) in the model's dtype."""
        return (self.u0 - _dot(self.w.astype(dtype), given, NT)).astype(dtype)


def _head_chunks(refs, hb, width, at, sub, masks):
    """The chunks of a grid step's heads, their inverses made in step."""
    last_ref, owner_ref, q_ref, k_ref, v_ref, run_ref, beta_ref, segc_ref = refs
    chunks = []
    for i in range(hb):
        lanes = slice(i * width, (i + 1) * width)
        chunks.append(_Chunk(
            q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes],
            run_ref[0, :, lanes], beta_ref[0, 0, :, i:i + 1], segc_ref[0, 0],
            last_ref[at], owner_ref[at], masks, sub, q_ref.dtype))
    for ch, x in zip(chunks, unit_lower_inverses(
            [ch.kk * ch.beta for ch in chunks])):
        ch.solve(x)
    return chunks


def _fwd_kernel(last_ref, owner_ref, live_ref, q_ref, k_ref, v_ref, run_ref,
                beta_ref, segc_ref, segr_ref, o_ref, given_ref, state, *,
                hb: int, width: int, sub: int):
    r, ci, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = r * pl.num_programs(1) + ci
    dtype = q_ref.dtype

    @pl.when(ci == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], f32)

    @pl.when(live_ref[at] == 0)
    def _():                    # a chunk of padding: zeros, and no state
        o_ref[...] = jnp.zeros_like(o_ref)
        given_ref[...] = jnp.zeros_like(given_ref)
        state[j] = jnp.zeros(state.shape[1:], f32)

    @pl.when(live_ref[at] != 0)
    def _():
        refs = (last_ref, owner_ref, q_ref, k_ref, v_ref, run_ref, beta_ref,
                segc_ref)
        chunks = _head_chunks(refs, hb, width, at, sub,
                              _masks(segc_ref, segr_ref))
        # the heads in step through every product, as the inverses are
        began = [state[j, i] for i in range(hb)]            # (V, K) float32
        given = [b.astype(dtype) for b in began]
        u = [ch.corrected(g, dtype) for ch, g in zip(chunks, given)]
        read = [_dot(ch.q_start, g, NT) for ch, g in zip(chunks, given)]
        within = [_dot(ch.qk.astype(dtype), x) for ch, x in zip(chunks, u)]
        left = [_dot(x, ch.k_end, TN) for ch, x in zip(chunks, u)]
        for i, ch in enumerate(chunks):
            given_ref[0, 0, i] = began[i]
            o_ref[0, :, i * width:(i + 1) * width] = read[i] + within[i]
            state[j, i] = began[i] * ch.through + left[i]


def _bwd_kernel(last_ref, owner_ref, live_ref, q_ref, k_ref, v_ref, run_ref,
                beta_ref, segc_ref, segr_ref, do_ref, given_ref, dq_ref,
                dk_ref, dv_ref, drun_ref, dbeta_ref, dstate, *, hb: int,
                width: int, sub: int):
    r, step, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nc = pl.num_programs(1)
    at = r * nc + (nc - 1 - step)
    dtype = q_ref.dtype
    live = live_ref[at] != 0

    @pl.when(step == 0)
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(jnp.logical_not(live))
    def _():
        for ref in (dq_ref, dk_ref, dv_ref, drun_ref, dbeta_ref):
            ref[...] = jnp.zeros_like(ref)
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(live)
    def _():
        masks = _masks(segc_ref, segr_ref)
        lower, strict = masks
        refs = (last_ref, owner_ref, q_ref, k_ref, v_ref, run_ref, beta_ref,
                segc_ref)
        c = q_ref.shape[1]
        at_row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        heads = range(hb)

        def rounded(x):         # the cotangent of a value of the model's dtype
            return x.astype(dtype).astype(f32)

        def cast(xs):
            return [x.astype(dtype) for x in xs]

        # every line below is one product (or its elementwise tail) of all the
        # grid step's heads, in step as the inverses are
        ch = _head_chunks(refs, hb, width, at, sub, masks)
        began = [given_ref[0, 0, i] for i in heads]         # (V, K) float32
        given = cast(began)
        u = [ch[i].corrected(given[i], dtype) for i in heads]
        w = cast(ch[i].w for i in heads)
        qk = cast(ch[i].qk for i in heads)
        do = [do_ref[0, :, i * width:(i + 1) * width].astype(dtype)
              for i in heads]
        left = [dstate[j, i] for i in heads]                # dS', (V, K)
        left_d = cast(left)
        # o = q_start S + qk u;  S' = through S + k_end^T u
        du = [_dot(qk[i], do[i], TN) for i in heads]
        du = cast(du[i] + _dot(ch[i].k_end, left_d[i], NT) for i in heads)
        dqk = [jnp.where(lower, _dot(do[i], u[i], NT), 0.0) for i in heads]
        dq_start = [rounded(_dot(do[i], given[i])) for i in heads]  # (c, K)
        dk_end = [rounded(_dot(u[i], left_d[i])) for i in heads]
        # u = u0 - w S
        dgiven = [_dot(do[i], ch[i].q_start, TN) for i in heads]
        dgiven = [rounded(dgiven[i] - _dot(du[i], w[i], TN)) for i in heads]
        dw = cast(-_dot(du[i], given[i]) for i in heads)            # (c, K)
        for i in heads:
            dstate[j, i] = left[i] * ch[i].through + dgiven[i]
        # w = X kb, u0 = X vb, X = (I + beta kk)^-1
        solve = cast(ch[i].x for i in heads)
        dx = [_dot(dw[i], ch[i].kb, NT) for i in heads]
        dx = [rounded(dx[i] + _dot(du[i], ch[i].vb, NT)) for i in heads]
        dkb = [rounded(_dot(solve[i], dw[i], TN)) for i in heads]
        dvb = [rounded(_dot(solve[i], du[i], TN)) for i in heads]
        da = [jnp.where(strict, m, 0.0) for m in unit_lower_inverse_vjps(
            [ch[i].x for i in heads], dx)]
        dkk = cast(da[i] * ch[i].beta for i in heads)
        dqk = cast(dqk)
        # the scores: rows q e^{G-m}, k e^{G-m} against keys k e^{m-G}
        drows, by_sub = [[] for _ in heads], [[] for _ in heads]
        for a, s in enumerate(ch[0].subs):
            d = [jnp.concatenate([dqk[i][s], dkk[i][s]]) for i in heads]
            for i in heads:
                drows[i].append(_dot(d[i], ch[i].keys[a]))  # (2 sub, K)
            for i in heads:
                by_sub[i].append(rounded(_dot(d[i], jnp.concatenate(
                    [ch[i].rq[s], ch[i].rk[s]]), TN)) * ch[i].col[a])
        for i in heads:
            lanes = slice(i * width, (i + 1) * width)
            h = ch[i]
            dq_row = rounded(jnp.concatenate(
                [d[:sub] for d in drows[i]])) * h.row
            dk_row = rounded(jnp.concatenate(
                [d[sub:] for d in drows[i]])) * h.row
            dkeys = sum(by_sub[i][1:], by_sub[i][0])
            # the middles: what the keys' side takes from m_a the rows' side
            # gives back, equal but for the operands' rounding. Left in, as
            # JAX leaves it in the plain form: a chunk's d run then sums to
            # what only the state's terms leave, and the cumsum's transpose
            # outside spreads no rounding over the tokens before
            from_rows = h.q32 * dq_row + h.k32 * dk_row
            dmid = jnp.zeros(h.k32.shape, f32)
            for s, part in zip(h.subs, by_sub[i]):
                dmid = dmid + jnp.where(
                    at_row == s.start + sub // 2,
                    jnp.sum(h.k32 * part, axis=0, keepdims=True)
                    - jnp.sum(from_rows[s], axis=0, keepdims=True), 0.0)
            dq_ref[0, :, lanes] = (dq_row + dq_start[i] * h.from_start
                                   ).astype(dq_ref.dtype)
            dk_ref[0, :, lanes] = (
                dk_row + dkeys + dkb[i] * h.from_start * h.beta
                + dk_end[i] * h.to_end).astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = (dvb[i] * h.beta).astype(dv_ref.dtype)
            ends = dk_end[i] * h.ke32
            dthrough = jnp.sum(left[i] * began[i], axis=0, keepdims=True)
            drun_ref[0, :, lanes] = (
                from_rows - h.k32 * dkeys + dmid + dq_start[i] * h.qs32
                + dkb[i] * h.kb32 - ends + jnp.where(
                    at_row == c - 1, jnp.sum(ends, axis=0, keepdims=True)
                    + dthrough * h.through, 0.0))
            dbeta_ref[0, 0, :, i:i + 1] = (
                jnp.sum(da[i] * h.kk, axis=1, keepdims=True)
                + jnp.sum(dkb[i] * h.k32 * h.from_start + dvb[i] * h.v32,
                          axis=1, keepdims=True))


# --- the calls ---------------------------------------------------------------

def _layouts(beta, seg, chunk, hb):
    """The kernels' operands beside q, k, v and the running log-decay: beta a
    grid step's heads together (R, H / hb, T, hb), the segment ids as a column
    and a row a chunk, and a chunk's scalars (flat, for SMEM)."""
    r, t, h = beta.shape
    nc = t // chunk
    ends = seg[:, chunk - 1::chunk]                         # (R, nc)
    owner = jnp.pad(ends, ((0, 0), (1, 0)))[:, :nc]
    by_chunk = seg.reshape(r, nc, chunk)
    live = jnp.any(by_chunk > 0, axis=-1)
    scalars = tuple(a.astype(jnp.int32).reshape(r * nc)
                    for a in (ends, owner, live))
    return scalars, (beta.reshape(r, t, h // hb, hb).transpose(0, 2, 1, 3),
                     by_chunk[..., None], by_chunk[:, :, None, :])


def _specs(width, chunk, hb, chunk_of):
    """BlockSpecs by kind of operand; `chunk_of` maps the grid's second index
    to the chunk."""

    def at(f):
        return lambda i, c, j, *_: f(i, chunk_of(c), j)

    return dict(
        tile=pl.BlockSpec((1, chunk, hb * width),
                          at(lambda i, c, j: (i, c, j))),
        beta=pl.BlockSpec((1, 1, chunk, hb), at(lambda i, c, j: (i, j, c, 0))),
        segc=pl.BlockSpec((1, 1, chunk, 1), at(lambda i, c, j: (i, c, 0, 0))),
        segr=pl.BlockSpec((1, 1, 1, chunk), at(lambda i, c, j: (i, c, 0, 0))),
        state=pl.BlockSpec((1, 1, hb, width, width),
                           at(lambda i, c, j: (i, c, j, 0, 0))))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _forward(q, k, v, run, beta, seg, chunk, sub, hb, interpret):
    """(o (R, T, H * K) float32, the transposed state each chunk began with
    (R, nc, H, K, K) float32)."""
    r, t, h = beta.shape
    width, nc = q.shape[-1] // h, t // chunk
    scalars, extra = _layouts(beta, seg, chunk, hb)
    s = _specs(width, chunk, hb, lambda c: c)
    kernel = functools.partial(_fwd_kernel, hb=hb, width=width, sub=sub)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(r, nc, h // hb),
            in_specs=[s["tile"]] * 4 + [s["beta"], s["segc"], s["segr"]],
            out_specs=[s["tile"], s["state"]],
            scratch_shapes=[pltpu.VMEM((h // hb, hb, width, width), f32)]),
        out_shape=[jax.ShapeDtypeStruct((r, t, h * width), f32),
                   jax.ShapeDtypeStruct((r, nc, h, width, width), f32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        name="kda_fwd", interpret=interpret,
    )(*scalars, q, k, v, run, *extra)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _backward(q, k, v, run, beta, seg, given, do, chunk, sub, hb, interpret):
    """(dq, dk, dv, d run (R, T, H * K) float32, d beta (R, T, H) float32)."""
    r, t, h = beta.shape
    width, nc = q.shape[-1] // h, t // chunk
    scalars, extra = _layouts(beta, seg, chunk, hb)
    s = _specs(width, chunk, hb, lambda c: nc - 1 - c)
    kernel = functools.partial(_bwd_kernel, hb=hb, width=width, sub=sub)
    dq, dk, dv, drun, dbeta = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(r, nc, h // hb),
            in_specs=[s["tile"]] * 4 + [s["beta"], s["segc"], s["segr"],
                                        s["tile"], s["state"]],
            out_specs=[s["tile"]] * 4 + [s["beta"]],
            scratch_shapes=[pltpu.VMEM((h // hb, hb, width, width), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(run.shape, f32),
                   jax.ShapeDtypeStruct((r, h // hb, t, hb), f32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        name="kda_bwd", interpret=interpret,
    )(*scalars, q, k, v, run, *extra, do, given)
    return dq, dk, dv, drun, dbeta.transpose(0, 2, 1, 3).reshape(r, t, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _delta_rule(q, k, v, run, beta, seg, chunk, sub, hb, interpret):
    with jax.named_scope("kda_chunk"), one_trace_context():
        return _forward(q, k, v, run, beta, seg, chunk, sub, hb, interpret)[0]


def _delta_rule_fwd(q, k, v, run, beta, seg, chunk, sub, hb, interpret):
    with jax.named_scope("kda_chunk"), one_trace_context():
        o, given = _forward(q, k, v, run, beta, seg, chunk, sub, hb,
                            interpret)
    return o, (q, k, v, run, beta, seg, given)


def _delta_rule_bwd(chunk, sub, hb, interpret, res, do):
    q, k, v, run, beta, seg, given = res
    with jax.named_scope("kda_chunk"), one_trace_context():
        grads = _backward(q, k, v, run, beta, seg, given, do, chunk, sub, hb,
                          interpret)
    return (*grads, np.zeros(seg.shape, jax.dtypes.float0))


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def kda_fused(q, k, v, g, beta, segment_ids, chunk: int, sub: int, dtype):
    """`vitax.models.kda.kda` by the kernels above: the same arguments, the
    same o (R, T, H, V) float32, zero at padding. Keys and values are one
    width and the shapes must tile (`kda_tiling`)."""
    r, t, h, width = q.shape
    assert v.shape == q.shape, (q.shape, v.shape)
    hb = kda_tiling(h, width, chunk, sub)
    assert not isinstance(hb, str), hb
    with jax.named_scope("kda_chunk"):
        # the running sum of log-decay inside each chunk, its own token's in
        run = jnp.cumsum(g.astype(f32).reshape(r, t // chunk, chunk,
                                               h * width), axis=2)
    o = _delta_rule(*(x.reshape(r, t, h * width).astype(dtype)
                      for x in (q, k, v)), run.reshape(r, t, h * width),
                    beta.astype(f32), segment_ids.astype(jnp.int32), chunk,
                    sub, hb, interpret())
    return o.reshape(r, t, h, width)
