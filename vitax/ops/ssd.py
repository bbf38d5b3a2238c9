"""The state-space scan of vitax/models/ssm.py as fused Pallas TPU kernels.

`ssd_fused` computes what the plain `vitax.models.ssm.ssd` computes, chunk
after chunk, without a chunk's (chunk, chunk) intermediates ever reaching HBM.
One grid step is one chunk of one row and `hb` heads of one group:

- forward (`ssd_fwd`): from the chunk's tiles of x, delta, the running
  log-decay `a`, B, C and the segment ids, the masked product
  y_l = sum_{s <= l, same document} (C_l . B_s) exp(a_l - a_s) delta_s x_s,
  the read of the state the chunk began with (exp(a_l) C_l S), the D x skip
  and the state the chunk leaves, S <- through * S + sum_s exp(a_end - a_s)
  delta_s x_s (x) B_s. The states of all heads live in VMEM scratch across
  the chunk axis (sequential; rows parallel). HBM sees the inputs once, y, and
  the float32 state every chunk began with (the backward's residual).
- backward (`ssd_bwd`, `jax.custom_vjp`): the chunks in reverse, the state's
  cotangent carried in VMEM scratch; scores, mask and decay are made again
  from the same tiles. Gradients of x, B, C and, per token and head, of delta
  (through x delta), of the running log-decay and of D; the caller's cumsum
  and `delta * A` are differentiated by JAX outside.

The gradient of the running log-decay needs no (chunk, chunk) reduction: the
sum over keys of dM * M at query l is sum_p dy_lp y_lp (y without the skip),
the sum over queries at key s is sum_p xdt_sp dxdt_sp, and exp(a_end - a_s)
and exp(a_end) add a term at the chunk's last token.

Layout: x and y are the (R, T, H * P) slices the convolution writes and the
gate reads. What a head has one of a token (delta, the running log-decay, and
their gradients) travels as dense rows (R, H / hb, rows, T) and is turned into
columns inside the kernel, one (128, chunk) transpose a grid step each way: a
(T, hb) tile in HBM would be padded sixteenfold. `hp` = 128 / P heads share a
lane tile (P a multiple of 128: one head a tile); a head's (chunk, chunk)
operand multiplies the whole tile and a lane select keeps the head's own
columns, so nothing is sliced inside a lane tile. C B^T and the mask are made
once a chunk and group in VMEM; dB and dC are summed over a group's heads too.

Precision is the plain form's: delta, A, the running sums and every state
float32; the products over x, B, C take operands of the model's dtype and
accumulate in float32; scores times decay is rounded where `ssd` rounds it.

`scan_tiling` says whether a mixer's shapes tile (chunk and state size
multiples of 128, head size a divisor or a multiple of 128, the heads of a
group a multiple of `hp`, all heads' states within `STATE_VMEM_BYTES`).
"""

from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.common import (LANES, NT, TN, compiler_params, f32,
                              interpret)

HEADS_PER_STEP = 16                     # at most; unrolled in the body
ROW_BLOCK = 128     # queries at a time: a block meets only keys not after it
STATE_VMEM_BYTES = 16 * 2 ** 20         # all heads' (P, N) float32 states


def scan_tiling(heads: int, head_size: int, state_size: int, groups: int,
                chunk: int) -> Union[Tuple[int, int], str]:
    """(heads a grid step, heads a lane tile) of the kernels for a mixer of
    these shapes, or why they cannot tile it."""
    if chunk % LANES:
        return f"chunk {chunk} is no multiple of {LANES}"
    if state_size % LANES:
        return f"state size {state_size} is no multiple of {LANES}"
    if LANES % head_size and head_size % LANES:
        return f"head size {head_size} neither divides {LANES} nor is a multiple"
    hp = max(LANES // head_size, 1)
    per_group = heads // groups
    if heads % groups or per_group % hp:
        return f"{per_group} heads a group do not fill {hp}-head lane tiles"
    if 4 * heads * head_size * state_size > STATE_VMEM_BYTES:
        return "the states of a row do not fit VMEM"
    hb = max(b for b in range(hp, max(HEADS_PER_STEP, hp) + 1, hp)
             if per_group % b == 0)
    return hb, hp


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=f32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=f32)


def _sublanes(hb: int) -> int:
    """Rows a block of `hb` per-head rows takes: whole sublane tiles."""
    return -(-hb // 8) * 8


def _lane_head(width: int, p: int):
    """(1, width) int32: which of a tile's heads a lane belongs to."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // p


def _spread(cols, first: int, hp: int, head_of):
    """Columns `first` .. `first + hp` of an array of per-head columns, each
    over its own head's places: (Q, 128) and `head_of` (1, W) by lane -> a
    (Q, W) lane tile; (1, 128) and `head_of` (W, 1) by row -> the (W, 1)
    factors of a state tile's rows."""
    out = cols[:, first:first + 1]
    for i in range(1, hp):
        out = jnp.where(head_of == i, cols[:, first + i:first + i + 1], out)
    return out


def _head_sums(u, hp: int, lane_head):
    """(Q, W) -> one (Q, 1) sum over its own lanes for each head of a tile."""
    if hp == 1:
        return [jnp.sum(u, axis=1, keepdims=True)]
    return [jnp.sum(jnp.where(lane_head == i, u, 0.0), axis=1, keepdims=True)
            for i in range(hp)]


def _columns(rows_ref, turn):
    """The per-head rows of a grid step, (2 * hs, Q): the running log-decay
    in rows 0 .. hb, delta in rows hs .. hs + hb -> (them, and them as the
    columns of a (Q, 128) array: one transpose through `turn`)."""
    rows = rows_ref[0, 0]
    turn[0:rows.shape[0], :] = rows
    return rows, turn[...].T


def _chunk_terms(last_ref, owner_ref, at, segc_ref, cols):
    """What a chunk's segment ids say, and the decays to and from its ends:
    (valid (Q, 1), from_start, to_end (Q, 128), through (1, 128)), a head a
    column as in `cols`."""
    last, owner = last_ref[at], owner_ref[at]
    segc = segc_ref[0]                                      # (Q, 1)
    valid = segc > 0
    reads = (segc == owner) & valid
    mine = (segc == last) & valid
    q = cols.shape[0]
    a_end = cols[q - 1:q, :]
    from_start = jnp.where(reads, jnp.exp(cols), 0.0)
    to_end = jnp.where(mine, jnp.exp(a_end - cols), 0.0)
    passes = jnp.logical_and(last == owner, last > 0)
    through = jnp.where(passes, jnp.exp(a_end), 0.0)
    return valid, from_start, to_end, through


def _passes(segc_ref, segr_ref):
    """(Q, Q) bool: query l sees key s (one document, key not after query)."""
    segc, segr = segc_ref[0], segr_ref[0]
    q = segc.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return (segc == segr) & (segc > 0) & (rows >= cols)


def _masked_scores(b_ref, c_ref, segc_ref, segr_ref):
    """C B^T where a pair passes, else 0."""
    return jnp.where(_passes(segc_ref, segr_ref),
                     _dot(c_ref[0], b_ref[0], NT), 0.0)


def _row_blocks(q: int):
    """[(rows, keys)]: blocks of `ROW_BLOCK` queries, each with the keys up to
    its last row (a key after every query of a block passes for none)."""
    rows = ROW_BLOCK if q % ROW_BLOCK == 0 else q
    return [(slice(at, at + rows), slice(0, at + rows))
            for at in range(0, q, rows)]


def _decay(cols, rows, k: int, queries, keys):
    """exp(a_l - a_s) of head `k` for a block of queries and its keys, 1 where
    the key is after the query (masked elsewhere): the difference is never
    positive where a pair passes."""
    return jnp.exp(jnp.minimum(
        cols[queries, k:k + 1] - rows[k:k + 1, keys], 0.0))


def _fwd_kernel(last_ref, owner_ref, live_ref, x_ref, b_ref, c_ref, rows_ref,
                segc_ref, segr_ref, dlane_ref, y_ref, given_ref, state, scm,
                turn, *, hb: int, hp: int, p: int, bpg: int):
    r, ci, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = r * pl.num_programs(1) + ci
    w, hs = hp * p, _sublanes(hb)
    dtype = x_ref.dtype
    lane_head = _lane_head(w, p)

    @pl.when(ci == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], f32)

        @pl.when(j == 0)
        def _():
            turn[...] = jnp.zeros_like(turn)

    @pl.when(live_ref[at] == 0)
    def _():                    # a chunk of padding: zeros, and no state
        y_ref[...] = jnp.zeros_like(y_ref)
        given_ref[...] = jnp.zeros_like(given_ref)
        state[j] = jnp.zeros(state.shape[1:], f32)

    @pl.when(live_ref[at] != 0)
    def _():
        @pl.when(j % bpg == 0)
        def _():
            scm[...] = _masked_scores(b_ref, c_ref, segc_ref, segr_ref)

        rows, cols = _columns(rows_ref, turn)
        valid, from_start, to_end, through = _chunk_terms(
            last_ref, owner_ref, at, segc_ref, cols)
        row_head = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) // p
        for t in range(hb // hp):
            lanes = slice(t * w, (t + 1) * w)
            xf = x_ref[0, :, lanes].astype(f32)
            xdt = (xf * _spread(cols, hs + t * hp, hp, lane_head)
                   ).astype(dtype)
            blocks = []
            for queries, keys in _row_blocks(xf.shape[0]):
                y = None
                for i in range(hp):
                    m = (scm[queries, keys] * _decay(
                        cols, rows, t * hp + i, queries, keys)).astype(dtype)
                    part = _dot(m, xdt[keys])
                    y = part if i == 0 else jnp.where(lane_head == i, part, y)
                blocks.append(y)
            y = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)
            given = state[j, lanes, :]                      # (W, N) float32
            given_ref[0, 0, lanes, :] = given
            y = y + _dot(c_ref[0], given.astype(dtype), NT) \
                * _spread(from_start, t * hp, hp, lane_head)
            y_ref[0, :, lanes] = y + jnp.where(
                valid, xf * dlane_ref[:, lanes], 0.0)
            left = _dot((xdt.astype(f32)
                         * _spread(to_end, t * hp, hp, lane_head)
                         ).astype(dtype), b_ref[0], TN)    # (W, N)
            state[j, lanes, :] = given * _spread(
                through, t * hp, hp, row_head) + left


def _bwd_kernel(last_ref, owner_ref, live_ref, x_ref, b_ref, c_ref, rows_ref,
                segc_ref, segr_ref, dlane_ref, y_ref, dy_ref, given_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dstate, scm, dsc, db_acc,
                dc_acc, turn, *, hb: int, hp: int, p: int, bpg: int):
    r, step, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nc = pl.num_programs(1)
    at = r * nc + (nc - 1 - step)
    w, hs = hp * p, _sublanes(hb)
    dtype = x_ref.dtype
    lane_head = _lane_head(w, p)
    live = live_ref[at] != 0

    @pl.when(step == 0)
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

        @pl.when(j == 0)
        def _():
            turn[...] = jnp.zeros_like(turn)

    @pl.when(j % bpg == 0)
    def _():
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    @pl.when(jnp.logical_not(live))
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)
        drows_ref[...] = jnp.zeros_like(drows_ref)
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(live)
    def _():
        @pl.when(j % bpg == 0)
        def _():
            scm[...] = _masked_scores(b_ref, c_ref, segc_ref, segr_ref)
            dsc[...] = jnp.zeros_like(dsc)

        rows, cols = _columns(rows_ref, turn)
        valid, from_start, to_end, through = _chunk_terms(
            last_ref, owner_ref, at, segc_ref, cols)
        q = cols.shape[0]
        row_head = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) // p
        column = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        is_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
        # what leaves as rows: d delta through x delta in columns 0 .. hb,
        # d run in hs .. hs + hb, sum_p dy x in 2 hs .. 2 hs + hb
        out = jnp.zeros((q, LANES), f32)
        for t in range(hb // hp):
            lanes = slice(t * w, (t + 1) * w)
            xf = x_ref[0, :, lanes].astype(f32)
            delta = _spread(cols, hs + t * hp, hp, lane_head)
            xdt = (xf * delta).astype(dtype)
            xr = xdt.astype(f32)
            dy = dy_ref[0, :, lanes]
            dyb = dy.astype(dtype)
            blocks = _row_blocks(q)
            size = q // len(blocks)
            dxdt = [None] * len(blocks)     # by block of keys
            for queries, keys in blocks:
                mine = None
                for i in range(hp):
                    decay = _decay(cols, rows, t * hp + i, queries, keys)
                    m = (scm[queries, keys] * decay).astype(dtype)
                    own = dyb[queries] if hp == 1 else jnp.where(
                        lane_head == i, dyb[queries], jnp.zeros((), dtype))
                    dsc[queries, keys] += _dot(own, xdt[keys], NT) * decay
                    part = _dot(m, dyb[queries], TN)
                    mine = part if i == 0 else jnp.where(lane_head == i, part,
                                                         mine)
                for kb in range(keys.stop // size):
                    part = mine[kb * size:(kb + 1) * size]
                    dxdt[kb] = part if dxdt[kb] is None else dxdt[kb] + part
            dxdt = dxdt[0] if len(blocks) == 1 else jnp.concatenate(dxdt)
            # the read of the state the chunk began with
            given = given_ref[0, 0, lanes, :]
            read = (_spread(from_start, t * hp, hp, lane_head) * dy
                    ).astype(dtype)
            dc_acc[...] += _dot(read, given.astype(dtype))
            dgiven = _dot(read, c_ref[0], TN)              # (W, N)
            # the state the chunk leaves
            te = _spread(to_end, t * hp, hp, lane_head)
            dleft = dstate[j, lanes, :]
            dleft_b = dleft.astype(dtype)
            db_acc[...] += _dot((xr * te).astype(dtype), dleft_b)
            dxdt_end = _dot(b_ref[0], dleft_b, NT) * te
            dxdt = dxdt + dxdt_end
            keep = _spread(through, t * hp, hp, row_head)
            dstate[j, lanes, :] = dleft * keep + dgiven
            dthrough = jnp.sum(dleft * given * keep, axis=1, keepdims=True)

            d_valid = jnp.where(valid, dlane_ref[:, lanes], 0.0)    # (Q, W)
            skip = xf * d_valid
            dx_ref[0, :, lanes] = (dxdt * delta + dy * d_valid).astype(
                dx_ref.dtype)
            sums = zip(_head_sums(dxdt * xf, hp, lane_head),
                       _head_sums(jnp.where(valid, dy * xf, 0.0), hp,
                                  lane_head),
                       _head_sums(dyb.astype(f32) * (y_ref[0, :, lanes] - skip)
                                  - xr * dxdt, hp, lane_head),
                       _head_sums(jnp.sum(xr * dxdt_end, axis=0,
                                          keepdims=True), hp, lane_head))
            for i, (dd, yx, da, end) in enumerate(sums):
                k = t * hp + i
                ends = end + jnp.sum(
                    dthrough if hp == 1 else
                    jnp.where(row_head == i, dthrough, 0.0),
                    axis=0, keepdims=True)
                da = da + jnp.where(is_end, ends, 0.0)
                out = jnp.where(column == k, dd, out)
                out = jnp.where(column == hs + k, da, out)
                out = jnp.where(column == 2 * hs + k, yx, out)
        drows_ref[0, 0] = out.T[0:3 * hs, :]

    @pl.when(j % bpg == bpg - 1)
    def _():
        @pl.when(live)
        def _():
            ds = jnp.where(_passes(segc_ref, segr_ref), dsc[...],
                           0.0).astype(dtype)
            dc_acc[...] += _dot(ds, b_ref[0])
            db_acc[...] += _dot(ds, c_ref[0], TN)
        db_ref[0] = db_acc[...].astype(db_ref.dtype)
        dc_ref[0] = dc_acc[...].astype(dc_ref.dtype)


# --- the calls ---------------------------------------------------------------

def _layouts(delta, run, d_skip, seg, p, chunk, hb):
    """The kernels' operands beside x, b and c: the per-head rows (R, H / hb,
    2 * hs, T), a grid step's running log-decay over its delta (`_columns`),
    the segment ids as a column and a row, D a lane, and a chunk's scalars
    (flat, for SMEM)."""
    r, t, h = delta.shape
    nc, hs = t // chunk, _sublanes(hb)

    def rows(a):        # (R, T, H) -> (R, H / hb, hs, T)
        a = a.reshape(r, t, h // hb, hb).transpose(0, 2, 3, 1)
        return jnp.pad(a, ((0, 0), (0, 0), (0, hs - hb), (0, 0)))

    ends = seg[:, chunk - 1::chunk]                         # (R, nc)
    owner = jnp.pad(ends, ((0, 0), (1, 0)))[:, :nc]
    live = jnp.any(seg.reshape(r, nc, chunk) > 0, axis=-1)
    scalars = tuple(a.astype(jnp.int32).reshape(r * nc)
                    for a in (ends, owner, live))
    return scalars, (jnp.concatenate([rows(run), rows(delta)], axis=2),
                     seg[..., None], seg[:, None, :],
                     jnp.repeat(d_skip.astype(f32), p)[None])


def _specs(h, p, g, n, chunk, hb, chunk_of):
    """BlockSpecs by kind of operand; `chunk_of` maps the grid's second index
    to the chunk."""
    bpg = h // g // hb
    q, wide, hs = chunk, hb * p, _sublanes(hb)

    def at(f):
        return lambda i, c, j, *_: f(i, chunk_of(c), j)

    return dict(
        tile=pl.BlockSpec((1, q, wide), at(lambda i, c, j: (i, c, j))),
        group=pl.BlockSpec((1, q, n), at(lambda i, c, j: (i, c, j // bpg))),
        rows=lambda k: pl.BlockSpec((1, 1, k * hs, q),
                                    at(lambda i, c, j: (i, j, 0, c))),
        segc=pl.BlockSpec((1, q, 1), at(lambda i, c, j: (i, c, 0))),
        segr=pl.BlockSpec((1, 1, q), at(lambda i, c, j: (i, 0, c))),
        lane=pl.BlockSpec((1, wide), at(lambda i, c, j: (0, j))),
        state=pl.BlockSpec((1, 1, wide, n), at(lambda i, c, j: (i, c, j, 0))))


def _forward(x, b, c, delta, run, d_skip, seg, chunk, groups, hb, hp,
             interpret):
    """(y (R, T, H * P) float32, the state each chunk began with (R, nc,
    H * P, N) float32)."""
    r, t, h = delta.shape
    p, n = x.shape[-1] // h, b.shape[-1] // groups
    nc, nhb = t // chunk, h // hb
    scalars, extra = _layouts(delta, run, d_skip, seg, p, chunk, hb)
    s = _specs(h, p, groups, n, chunk, hb, lambda c: c)
    kernel = functools.partial(_fwd_kernel, hb=hb, hp=hp, p=p,
                               bpg=h // groups // hb)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(r, nc, nhb),
            in_specs=[s["tile"], s["group"], s["group"], s["rows"](2),
                      s["segc"], s["segr"], s["lane"]],
            out_specs=[s["tile"], s["state"]],
            scratch_shapes=[pltpu.VMEM((nhb, hb * p, n), f32),
                            pltpu.VMEM((chunk, chunk), f32),
                            pltpu.VMEM((LANES, chunk), f32)]),
        out_shape=[jax.ShapeDtypeStruct((r, t, h * p), f32),
                   jax.ShapeDtypeStruct((r, nc, h * p, n), f32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        name="ssd_fwd", interpret=interpret,
    )(*scalars, x, b, c, *extra)


def _backward(x, b, c, delta, run, d_skip, seg, y, given, dy, chunk, groups,
              hb, hp, interpret):
    """(dx, db, dc, then (R, T, H) float32: d delta through x delta, d run,
    sum_p dy x)."""
    r, t, h = delta.shape
    p, n = x.shape[-1] // h, b.shape[-1] // groups
    nc, nhb, hs = t // chunk, h // hb, _sublanes(hb)
    scalars, extra = _layouts(delta, run, d_skip, seg, p, chunk, hb)
    s = _specs(h, p, groups, n, chunk, hb, lambda c: nc - 1 - c)
    kernel = functools.partial(_bwd_kernel, hb=hb, hp=hp, p=p,
                               bpg=h // groups // hb)
    dx, db, dc, drows = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(r, nc, nhb),
            in_specs=[s["tile"], s["group"], s["group"], s["rows"](2),
                      s["segc"], s["segr"], s["lane"], s["tile"], s["tile"],
                      s["state"]],
            out_specs=[s["tile"], s["group"], s["group"], s["rows"](3)],
            scratch_shapes=[pltpu.VMEM((nhb, hb * p, n), f32),
                            pltpu.VMEM((chunk, chunk), f32),
                            pltpu.VMEM((chunk, chunk), f32),
                            pltpu.VMEM((chunk, n), f32),
                            pltpu.VMEM((chunk, n), f32),
                            pltpu.VMEM((LANES, chunk), f32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((r, nhb, 3 * hs, t), f32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        name="ssd_bwd", interpret=interpret,
    )(*scalars, x, b, c, *extra, y, dy, given)

    def tokens(k):      # rows k * hs .. of (R, H / hb, 3 hs, T) -> (R, T, H)
        return drows[:, :, k * hs:k * hs + hb].transpose(0, 3, 1, 2).reshape(
            r, t, h)

    return dx, db, dc, tokens(0), tokens(1), tokens(2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _scan(x, b, c, delta, run, d_skip, seg, chunk, groups, hb, hp, interpret):
    with jax.named_scope("ssd_chunk"):
        return _forward(x, b, c, delta, run, d_skip, seg, chunk, groups, hb,
                        hp, interpret)[0]


def _scan_fwd(x, b, c, delta, run, d_skip, seg, chunk, groups, hb, hp,
              interpret):
    with jax.named_scope("ssd_chunk"):
        y, given = _forward(x, b, c, delta, run, d_skip, seg, chunk, groups,
                            hb, hp, interpret)
    return y, (x, b, c, delta, run, d_skip, seg, y, given)


def _scan_bwd(chunk, groups, hb, hp, interpret, res, dy):
    x, b, c, delta, run, d_skip, seg, y, given = res
    with jax.named_scope("ssd_chunk"):
        dx, db, dc, ddelta, drun, dyx = _backward(
            x, b, c, delta, run, d_skip, seg, y, given, dy, chunk, groups, hb,
            hp, interpret)
        dd = jnp.sum(dyx, axis=(0, 1)).astype(d_skip.dtype)
    return (dx, db, dc, ddelta, drun, dd,
            np.zeros(seg.shape, jax.dtypes.float0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_fused(x, delta, a_head, b, c, d_skip, segment_ids, chunk: int, dtype):
    """`vitax.models.ssm.ssd` by the kernels above: the same arguments, the
    same y (R, T, H, P) float32, zero at padding (the skip too). The shapes
    must tile (`scan_tiling`)."""
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    tiling = scan_tiling(h, p, n, g, chunk)
    assert not isinstance(tiling, str), tiling
    hb, hp = tiling
    with jax.named_scope("ssd_chunk"):
        # the running sum of log-decay inside each chunk, its own token's in
        run = jnp.cumsum((delta * a_head).reshape(r, t // chunk, chunk, h),
                         axis=2).reshape(r, t, h)
    y = _scan(x.reshape(r, t, h * p).astype(dtype),
              b.reshape(r, t, g * n).astype(dtype),
              c.reshape(r, t, g * n).astype(dtype), delta, run, d_skip,
              segment_ids.astype(jnp.int32), chunk, g, hb, hp, interpret())
    return y.reshape(r, t, h, p)
