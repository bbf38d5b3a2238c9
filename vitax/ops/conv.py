"""The recurrent mixers' short convolution as one pass: a Pallas TPU kernel pair.

`conv_silu` computes what the mixers of vitax/models/ssm.py and
vitax/models/kda.py write with `causal_conv`, `silu`, the padding's select
and (a delta mixer) `l2norm`:

    pre_t = b + sum_j kernel[taps - 1 - j] x_{t-j} [t - j in t's document]
    a_t   = silu(pre_t) [t is no padding]
    y_t   = a_t                                     a state-space mixer
    y_t   = a_t * rsqrt(sum_head a_t^2 + eps) * c   q and k of a delta mixer,
                                                    c = head_size ** -0.5 for q

from the projection in the model's dtype, in float32 inside the kernel, and
rounds once, where the plain form rounds: the output. The plain form makes
`taps` shifted float32 passes over (T, channels), each with its own pad,
compare and select, and keeps their float32 intermediates for its backward.

- forward (`conv_silu_fwd`): a grid step is one row and `lanes` channels with
  the WHOLE token axis in VMEM, so a shift needs no halo from another step.
  The tokens go `rows` at a time through a float32 window in VMEM scratch
  that holds the block behind the sixteen rows before it: tap j reads the
  window j rows up. What the segment ids say of a token (it is no padding;
  the token j before it is of its document) is made once a row, at its first
  channel step, as float32 0 / 1 over all 128 lanes in VMEM scratch, and
  multiplied in. The norm's sum over a head's lanes is a product with a
  matrix of zeros and ones (lanes of one head; ones for a head of 128; three
  lane tiles square for Olmo's heads of 96, four to the 384 lanes), each
  float32 as its three bfloat16 terms: the MXU is idle otherwise, every lane
  gets its head's sum, and a lane knows from its channel whether it is q's,
  k's or v's.
- backward (`conv_silu_bwd`, `jax.custom_vjp`, residuals: the inputs): the
  blocks in reverse. pre, silu and the norm are made again from x; with
  d pre_t = d a_t silu'(pre_t), d x_s = sum_j kernel[taps - 1 - j]
  (d pre_{s+j} [s in s + j's document]) reads the masked d pre of tap j from
  a window j rows DOWN (the block after was made before), d kernel and d bias
  are sums over the tokens of a grid step, so they stay inside it.

Each `pl.pallas_call` sits under a `jax.jit` of its own and the rules trace
under the primal's context (`vitax/ops/common.py:one_trace_context`): a process
traces each body once, however many layers, remats and programs call it.

`conv_tiling` says whether a convolution's shapes tile (channels a multiple of
128, tokens of 16, whole normed heads in a grid step's whole lane tiles, a
grid step within `VMEM_BYTES`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.common import (LANES, compiler_params, f32, interpret,
                              one_trace_context, thirds)

L2_EPS = 1e-6       # a delta mixer's q and k: x * rsqrt(sum x^2 + eps)
HALO = 16           # rows a window keeps beside its block: a bfloat16 tile's
ROW_BLOCK = 128     # tokens through the window at a time, at most
LANE_BLOCK = 512    # channels a grid step, at most
VMEM_BYTES = 48 * 2 ** 20   # a grid step's blocks and scratch, of VMEM_LIMIT

# (head size, channels normed a head: the first so many, channels scaled by
# head_size ** -0.5 after the norm: the first so many)
Norm = Tuple[int, int, int]


def conv_tiling(channels: int, tokens: int, taps: int,
                norm: Optional[Norm] = None,
                out_bytes: int = 2) -> Union[Tuple[int, int], str]:
    """(channels a grid step, tokens a block) of the kernels for a
    convolution of these shapes, or why they cannot tile it."""
    if channels % LANES:
        return f"{channels} channels are no multiple of {LANES}"
    if tokens % HALO:
        return f"rows of {tokens} tokens are no whole {HALO}-row tiles"
    if not 1 <= taps <= HALO // 2:
        return f"{taps} taps reach past the {HALO // 2} rows a window keeps"
    group = _group(norm) * LANES    # a grid step holds whole heads
    if group > LANE_BLOCK or channels % group:
        return (f"heads of {norm[0]} fill whole lane tiles {group} channels "
                f"at a time, which {min(channels, LANE_BLOCK)} do not hold")
    rows = max(b for b in range(HALO, ROW_BLOCK + 1, HALO) if tokens % b == 0)

    def fits(lanes):    # the backward's: x, dy and dx twice, masks, windows
        blocks = 2 * tokens * (lanes * (4 + out_bytes) + LANES * 4)
        scratch = 4 * (taps * tokens * LANES + taps * (rows + HALO) * lanes)
        return blocks + scratch + 2 * group * group <= VMEM_BYTES

    wide = [b for b in range(group, LANE_BLOCK + 1, group)
            if channels % b == 0 and fits(b)]
    if not wide:
        return "a grid step's whole token axis does not fit VMEM"
    return max(wide), rows


def _fill_masks(seg_ref, masks, segw, taps: int, rows: int):
    """masks[0, t] = 1 where token t is no padding, masks[j, t] = 1 where the
    token j before t is of t's document (rows before the first: segment 0, as
    the plain form pads), float32 over all lanes."""
    blocks = seg_ref.shape[1] // rows

    def block(b, _):
        s = pl.multiple_of(b * rows, rows)
        before = pl.multiple_of(jnp.maximum(s - HALO, 0), HALO)
        tail = jnp.broadcast_to(seg_ref[0, pl.ds(before, HALO), :],
                                (HALO, LANES))
        segw[0:HALO] = jnp.where(b > 0, tail, 0)
        cur = jnp.broadcast_to(seg_ref[0, pl.ds(s, rows), :], (rows, LANES))
        segw[HALO:HALO + rows] = cur
        masks[0, pl.ds(s, rows), :] = (cur > 0).astype(f32)
        for j in range(1, taps):
            masks[j, pl.ds(s, rows), :] = (
                segw[HALO - j:HALO - j + rows] == cur).astype(f32)
        return 0

    jax.lax.fori_loop(0, blocks, block, 0)


def _window(x_ref, xw, b, s, rows: int):
    """The float32 window of block `b`: its sixteen rows before (zeros before
    the row's first token) and the block."""
    before = pl.multiple_of(jnp.maximum(s - HALO, 0), HALO)
    tail = x_ref[0, pl.ds(before, HALO), :].astype(f32)
    xw[0:HALO] = jnp.where(b > 0, tail, 0.0)
    xw[HALO:HALO + rows] = x_ref[0, pl.ds(s, rows), :].astype(f32)


def _fill_heads(ind, head: int):
    """ind[c, d] = 1 where lanes c and d of a group lie in one head of `head`
    lanes (a group: the fewest whole lane tiles that hold whole heads)."""
    rows, cols = (jax.lax.broadcasted_iota(jnp.int32, ind.shape, d)
                  for d in (0, 1))
    same = jnp.zeros(ind.shape, bool)
    for first in range(0, ind.shape[0], head):
        same |= ((rows >= first) & (rows < first + head)
                 & (cols >= first) & (cols < first + head))
    ind[...] = same.astype(ind.dtype)


def _heads_sum(parts, ind):
    """Each lane's sum over its head, for a group's lane tiles (rows, 128)
    float32 -> (rows, group): products with `ind`'s zeros and ones on the
    otherwise idle MXU, each float32 as the three bfloat16 terms that hold
    all its 24 bits, summed in float32."""
    total = None
    for i, p in enumerate(parts):
        held = ind[i * LANES:(i + 1) * LANES, :]
        for third in thirds(p):
            part = jnp.dot(third, held, preferred_element_type=f32)
            total = part if total is None else total + part
    return total


class _Tile:
    """A lane tile of a block as both kernels make it from the window: the
    masked taps' inputs, the pre-activation and the activation."""

    def __init__(self, xw, masks, k_ref, b_ref, s, lanes, taps, rows):
        at = pl.ds(s, rows)
        self.lanes = lanes
        self.valid = masks[0, at, :]
        # x_{t-j} where t - j is of t's document, else 0
        self.xm = [xw[HALO:HALO + rows, lanes]] + [
            xw[HALO - j:HALO - j + rows, lanes] * masks[j, at, :]
            for j in range(1, taps)]
        pre = self.xm[0] * k_ref[taps - 1:taps, lanes]
        for j in range(1, taps):
            pre = pre + self.xm[j] * k_ref[taps - 1 - j:taps - j, lanes]
        if b_ref is not None:
            pre = pre + b_ref[0:1, lanes]
        self.pre = pre
        self.sig = jax.nn.sigmoid(pre)
        self.act = pre * self.sig * self.valid

    def after_norm(self, norm: Norm, first):
        """(1, 128): what a lane's channel is multiplied by after its norm,
        0 where the channel is not normed; `first`: the grid step's first
        channel."""
        head, normed, scaled = norm
        channel = (first + self.lanes.start
                   + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1))
        return jnp.where(channel < scaled, head ** -0.5,
                         jnp.where(channel < normed, 1.0, 0.0)).astype(f32)


def _group(norm: Optional[Norm]) -> int:
    """Lane tiles that are normed together: the fewest that hold whole
    heads (one without a norm)."""
    return 1 if norm is None else math.lcm(norm[0], LANES) // LANES


def _prologue(seg_ref, masks, segw, ind, taps, rows, norm):
    """What a row's grid steps share, made at its first."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        _fill_masks(seg_ref, masks, segw, taps, rows)
        if norm is not None:
            _fill_heads(ind, norm[0])


def _either(norm: Optional[Norm], first, blocks):
    """`blocks(with_norm)`: with the norm where some channel of this grid
    step, which begins at channel `first`, has one."""
    if norm is None:
        return blocks(False)
    normed = first < norm[1]
    pl.when(normed)(lambda: blocks(True))
    pl.when(jnp.logical_not(normed))(lambda: blocks(False))


def _fwd_kernel(*refs, taps: int, rows: int, bias: bool,
                norm: Optional[Norm]):
    seg_ref, x_ref, k_ref = refs[:3]
    b_ref = refs[3] if bias else None
    y_ref, masks, segw, xw, ind = refs[3 + bias:]
    lanes = x_ref.shape[2]
    first = pl.program_id(1) * lanes
    _prologue(seg_ref, masks, segw, ind, taps, rows, norm)

    def blocks(with_norm: bool):
        def block(b, _):
            s = pl.multiple_of(b * rows, rows)
            _window(x_ref, xw, b, s, rows)
            tiles = [_Tile(xw, masks, k_ref, b_ref, s,
                           slice(i, i + LANES), taps, rows)
                     for i in range(0, lanes, LANES)]
            ys = [t.act for t in tiles]
            for g in range(0, len(tiles) if with_norm else 0, _group(norm)):
                group = tiles[g:g + _group(norm)]
                rs = jax.lax.rsqrt(_heads_sum(
                    [t.act * t.act for t in group], ind) + L2_EPS)
                for i, t in enumerate(group):
                    c = t.after_norm(norm, first)
                    ys[g + i] = jnp.where(
                        c > 0, t.act * rs[:, i * LANES:(i + 1) * LANES] * c,
                        t.act)
            for t, y in zip(tiles, ys):
                y_ref[0, pl.ds(s, rows), t.lanes] = y.astype(y_ref.dtype)
            return 0
        jax.lax.fori_loop(0, x_ref.shape[1] // rows, block, 0)

    _either(norm, first, blocks)


def _bwd_kernel(*refs, taps: int, rows: int, bias: bool,
                norm: Optional[Norm]):
    seg_ref, x_ref, k_ref = refs[:3]
    b_ref = refs[3] if bias else None
    dy_ref = refs[3 + bias]
    outs = refs[4 + bias:]
    dx_ref, dk_ref = outs[:2]
    db_ref = outs[2] if bias else None
    masks, segw, xw, ind, uw = outs[2 + bias:]
    lanes = x_ref.shape[2]
    nb = x_ref.shape[1] // rows
    first = pl.program_id(1) * lanes
    _prologue(seg_ref, masks, segw, ind, taps, rows, norm)

    def blocks(with_norm: bool):
        dk_ref[...] = jnp.zeros_like(dk_ref)
        if bias:
            db_ref[...] = jnp.zeros_like(db_ref)
        # nothing comes down to the row's last tokens from a block after
        uw[...] = jnp.zeros_like(uw)

        def block(step, _):
            b = nb - 1 - step
            s = pl.multiple_of(b * rows, rows)
            at = pl.ds(s, rows)
            _window(x_ref, xw, b, s, rows)
            tiles = [_Tile(xw, masks, k_ref, b_ref, s,
                           slice(i, i + LANES), taps, rows)
                     for i in range(0, lanes, LANES)]
            das = [dy_ref[0, at, t.lanes].astype(f32) for t in tiles]
            for g in range(0, len(tiles) if with_norm else 0, _group(norm)):
                # y = c a r, r = rsqrt(sum a^2 + eps) over the head:
                # d a = c r (dy - n sum(dy n)), n = a r
                group = tiles[g:g + _group(norm)]
                rs = jax.lax.rsqrt(_heads_sum(
                    [t.act * t.act for t in group], ind) + L2_EPS)
                units = [t.act * rs[:, i * LANES:(i + 1) * LANES]
                         for i, t in enumerate(group)]
                along = _heads_sum([das[g + i] * n
                                    for i, n in enumerate(units)], ind)
                for i, (t, n) in enumerate(zip(group, units)):
                    cols = slice(i * LANES, (i + 1) * LANES)
                    c = t.after_norm(norm, first)
                    das[g + i] = jnp.where(
                        c > 0, (das[g + i] - n * along[:, cols])
                        * (rs[:, cols] * c), das[g + i])
            for t, da in zip(tiles, das):
                cols = t.lanes
                dpre = da * t.valid * (t.sig * (1.0 + t.pre * (1.0 - t.sig)))
                if bias:
                    db_ref[0, 0:1, cols] += jnp.sum(dpre, axis=0,
                                                    keepdims=True)
                dx = dpre * k_ref[taps - 1:taps, cols]
                for j in range(taps):
                    row = slice(taps - 1 - j, taps - j)
                    dk_ref[0, row, cols] += jnp.sum(dpre * t.xm[j], axis=0,
                                                    keepdims=True)
                    if j == 0:
                        continue
                    # tap j's masked d pre: this block's behind the head of
                    # the block after, read j rows down
                    uw[j - 1, rows:rows + HALO, cols] = uw[j - 1, 0:HALO,
                                                           cols]
                    uw[j - 1, 0:rows, cols] = dpre * masks[j, at, :]
                    dx = dx + uw[j - 1, j:j + rows, cols] * k_ref[row, cols]
                dx_ref[0, at, cols] = dx.astype(dx_ref.dtype)
            return 0
        jax.lax.fori_loop(0, nb, block, 0)

    _either(norm, first, blocks)


# --- the calls ---------------------------------------------------------------

def _specs(t: int, taps: int, lanes: int):
    return dict(
        seg=pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)),
        tile=pl.BlockSpec((1, t, lanes), lambda i, j: (i, 0, j)),
        taps=pl.BlockSpec((taps, lanes), lambda i, j: (0, j)),
        bias=pl.BlockSpec((1, lanes), lambda i, j: (0, j)),
        dtaps=pl.BlockSpec((1, taps, lanes), lambda i, j: (i, 0, j)),
        dbias=pl.BlockSpec((1, 1, lanes), lambda i, j: (i, 0, j)))


def _scratch(t: int, taps: int, lanes: int, rows: int, norm):
    """The masks, the segment ids' window, x's window, the heads' matrix."""
    return [pltpu.VMEM((taps, t, LANES), f32),
            pltpu.VMEM((HALO + rows, LANES), jnp.int32),
            pltpu.VMEM((HALO + rows, lanes), f32),
            pltpu.VMEM((_group(norm) * LANES,) * 2, jnp.bfloat16)]


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _conv_forward(x, seg, kernel, bias, lanes, rows, norm, out_dtype,
                  interpret):
    r, t, c = x.shape
    taps = kernel.shape[0]
    s = _specs(t, taps, lanes)
    has_bias = bias is not None
    body = functools.partial(_fwd_kernel, taps=taps, rows=rows, bias=has_bias,
                             norm=norm)
    operands = (seg, x, kernel) + ((bias,) if has_bias else ())
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(r, c // lanes),
            in_specs=[s["seg"], s["tile"], s["taps"]]
            + [s["bias"]] * has_bias,
            out_specs=s["tile"],
            scratch_shapes=_scratch(t, taps, lanes, rows, norm)),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        name="conv_silu_fwd", interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _conv_backward(x, seg, kernel, bias, dy, lanes, rows, norm, interpret):
    """(dx in x's dtype, d kernel (taps, C) and d bias (1, C) float32)."""
    r, t, c = x.shape
    taps = kernel.shape[0]
    s = _specs(t, taps, lanes)
    has_bias = bias is not None
    body = functools.partial(_bwd_kernel, taps=taps, rows=rows, bias=has_bias,
                             norm=norm)
    operands = (seg, x, kernel) + ((bias,) if has_bias else ()) + (dy,)
    dx, dk, *db = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(r, c // lanes),
            in_specs=[s["seg"], s["tile"], s["taps"]]
            + [s["bias"]] * has_bias + [s["tile"]],
            out_specs=[s["tile"], s["dtaps"]] + [s["dbias"]] * has_bias,
            scratch_shapes=_scratch(t, taps, lanes, rows, norm) + [
                pltpu.VMEM((max(taps - 1, 1), rows + HALO, lanes), f32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((r, taps, c), f32)]
        + [jax.ShapeDtypeStruct((r, 1, c), f32)] * has_bias,
        compiler_params=compiler_params("parallel", "arbitrary"),
        name="conv_silu_bwd", interpret=interpret,
    )(*operands)
    return (dx, jnp.sum(dk, axis=0),
            jnp.sum(db[0], axis=0) if has_bias else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _conv(x, seg, kernel, bias, lanes, rows, norm, out_dtype, interpret):
    with one_trace_context():
        return _conv_forward(x, seg, kernel, bias, lanes, rows, norm,
                             out_dtype, interpret)


def _rule_fwd(x, seg, kernel, bias, lanes, rows, norm, out_dtype, interpret):
    with one_trace_context():
        y = _conv_forward(x, seg, kernel, bias, lanes, rows, norm, out_dtype,
                          interpret)
    return y, (x, seg, kernel, bias)


def _rule_bwd(lanes, rows, norm, out_dtype, interpret, res, dy):
    x, seg, kernel, bias = res
    with one_trace_context():
        dx, dk, db = _conv_backward(x, seg, kernel, bias,
                                    dy.astype(out_dtype), lanes, rows, norm,
                                    interpret)
    return dx, np.zeros(seg.shape, jax.dtypes.float0), dk, db


_conv.defvjp(_rule_fwd, _rule_bwd)


def conv_silu(x, segment_ids, kernel, bias, dtype,
              norm: Optional[Norm] = None):
    """`vitax.models.ssm.conv_silu` by the kernels above: the same arguments,
    the same y (R, T, C) in `dtype`, zero at padding. The shapes must tile
    (`conv_tiling`)."""
    dtype = jnp.dtype(dtype)
    tiling = conv_tiling(x.shape[2], x.shape[1], kernel.shape[0], norm,
                         dtype.itemsize)
    assert not isinstance(tiling, str), tiling
    # the jitted calls hold the kernels and nothing else: the segment ids as
    # the column and the bias as the row the kernels read are made here, so
    # that a remat's partial evaluation finds nothing to take out of them
    return _conv(x, segment_ids.astype(jnp.int32)[..., None],
                 kernel.astype(f32),
                 None if bias is None else bias.astype(f32)[None], *tiling,
                 norm, dtype, interpret())
