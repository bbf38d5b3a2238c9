"""A state-space mixer over packed documents: Mamba-2 in its chunked dual form.

The mixer of a `mamba` layer of the token decoder (vitax/models/decoder.py),
in place of attention. With `u` the normed input of a token, `x` of `heads`
heads of `head_size` channels, `B` and `C` of `groups` groups of
`state_size` (a head reads its group's):

    (z, xBC, dt) = W_in u
    xBC <- silu(conv(xBC) + b)       depthwise, causal, `conv_width` taps
    (x, B, C) = xBC
    delta = softplus(dt + dt_bias),  A = -exp(A_log)            a head
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t          (head_size, state_size) a head
    y_t = S_t C_t + D x_t
    out = W_out[ RMSNorm(y * silu(z)) * scale ]                 normed a group

A document starts from S = 0 and its convolution sees no token of another
document; padding (`segment_ids` 0) gives zeros and receives nothing.

The recurrence is computed as the state-space dual (SSD, arXiv:2405.21060):
a row is cut into chunks of `chunk` tokens. Inside a chunk the outputs are a
masked product, y_l += sum_{s <= l} (C_l . B_s) exp(a_l - a_s) delta_s x_s
with `a` the running sum of delta A in the chunk (scope `ssd_chunk`); each
chunk hands the state it ends with to the next, decayed over the chunks
between, and a token reads the state its chunk began with as
exp(a_l) C_l S (scope `ssd_state`). Document boundaries fall anywhere: the
masked product lets a pair through only within one document, the state a
chunk ends with is made of the tokens of the document its last token
belongs to, it passes through a later chunk only if that chunk lies wholly
inside the same document, and only tokens of that document read it.

delta, A, the running sums and every state are float32; the products over x,
B and C take operands of the model's dtype and accumulate in float32.

Two forms of the scan, one algorithm, chosen by what the code can see
(vitax/programs/kernels.py: `choose_kernels`, through `build_model_for` as the
attention core is; `SSDMixer.scan`):

- the fused kernels `ssd_fwd` / `ssd_bwd` (vitax/ops/ssd.py: `ssd_fused`) on
  a TPU (or forced, in interpret mode) where the shapes tile: chunk and state
  size multiples of 128, head size a divisor or a multiple of 128. A chunk's
  mask, decay and scores stay in VMEM; HBM sees the inputs, y and one float32
  state a chunk, forward and backward; the forward runs twice under the
  layer's remat;
- the plain `ssd` below everywhere else, and as the oracle of the tests. The
  (chunk, head, `chunk`, `chunk`) products of a whole row would not fit
  beside a full chip's train state, so IT takes chunks `SSD_BLOCK_BYTES`
  worth at a time (`jax.lax.map`) and makes each block's intermediates again
  in the backward (`jax.checkpoint`): the constant bounds this form only.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from vitax.models.vit import Array, Dtype, default_init
from vitax.ops.conv import L2_EPS

# float32 bytes of one (rows, chunks, heads, chunk, chunk) intermediate that a
# block of chunks may hold; several are alive in a block's backward
SSD_BLOCK_BYTES = 64 * 2 ** 20


class MixerShape(NamedTuple):
    heads: int
    head_size: int
    state_size: int
    conv_width: int
    groups: int
    chunk: int

    @property
    def inner(self) -> int:
        return self.heads * self.head_size

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.groups * self.state_size

    @property
    def projected(self) -> int:
        """Outputs of the in-projection: z, xBC and dt."""
        return self.inner + self.conv_channels + self.heads

    @property
    def conv(self) -> Tuple[int, int, None]:
        """The convolution's channels and taps, and no norm behind it."""
        return self.conv_channels, self.conv_width, None


def mixer_param_count(shape: MixerShape, embed_dim: int) -> int:
    return (embed_dim * shape.projected
            + shape.conv_channels * (shape.conv_width + 1)
            + 3 * shape.heads + shape.inner + shape.inner * embed_dim)


# --- initial values (the published Mamba-2's) -------------------------------

def a_log_init(key, shape, dtype=jnp.float32):
    """A = -exp(A_log) uniform in [-16, -1]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(dt_bias) log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def conv_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1 / sqrt(taps), a depthwise Conv1d's default."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class ConvTaps(nn.Module):
    """The depthwise convolution's (taps, channels) kernel and its bias."""

    taps: int
    channels: int

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", conv_init, (self.taps, self.channels),
                            jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.channels,),
                          jnp.float32)
        return kernel, bias


class Leaf(nn.Module):
    """One float32 array under a leaf name the sharding rules know
    (vitax/parallel/rules.py)."""

    shape: tuple
    init: Callable
    leaf: str = "scale"

    @nn.compact
    def __call__(self) -> Array:
        return self.param(self.leaf, self.init, self.shape, jnp.float32)


# --- pure functions ---------------------------------------------------------

def causal_conv(x: Array, segment_ids: Array, kernel: Array,
                bias: Array) -> Array:
    """x (R, T, channels): y_t = b + sum_j kernel[taps - 1 - j] x_{t - j}
    over the j < taps whose token t - j lies in t's own document. float32."""
    taps = kernel.shape[0]
    t = x.shape[1]
    x32 = x.astype(jnp.float32)
    y = x32 * kernel[taps - 1]
    for j in range(1, taps):
        same = jnp.pad(segment_ids, ((0, 0), (j, 0)))[:, :t] == segment_ids
        back = jnp.pad(x32, ((0, 0), (j, 0), (0, 0)))[:, :t]
        y = y + jnp.where(same[..., None], back, 0.0) * kernel[taps - 1 - j]
    return y + bias


def l2norm(x: Array) -> Array:
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def conv_silu(x: Array, segment_ids: Array, kernel: Array,
              bias: Optional[Array], dtype: Dtype,
              norm: Optional[Tuple[int, int, int]] = None) -> Array:
    """What a recurrent mixer makes of its projection x (R, T, channels):
    silu(causal_conv(x)), zero at padding; with `norm` = (head, normed,
    scaled) the first `normed` channels L2-normed a head of `head` channels
    and the first `scaled` of them times head ** -0.5 after it (a delta
    mixer's k and q); float32 up to the one rounding to `dtype`. The plain
    form: the CPU's path and the oracle of the kernel pair of
    vitax/ops/conv.py, which takes these arguments (`SSDMixer.conv`,
    `choose_kernels`)."""
    y = causal_conv(x, segment_ids, kernel, 0.0 if bias is None else bias)
    y = jnp.where((segment_ids > 0)[..., None], jax.nn.silu(y), 0.0)
    if norm is not None:
        head, normed, scaled = norm
        r, t, _ = y.shape
        q, k, v = jnp.split(y, [scaled, normed], axis=-1)
        q = l2norm(q.reshape(r, t, -1, head)) * head ** -0.5
        k = l2norm(k.reshape(r, t, -1, head))
        y = jnp.concatenate([q.reshape(r, t, scaled),
                             k.reshape(r, t, normed - scaled), v], axis=-1)
    return y.astype(dtype)


def _chunk_block(r: int, chunks: int, heads: int, chunk: int) -> int:
    """Chunks a block: the most that divide `chunks` within the budget."""
    most = max(SSD_BLOCK_BYTES // (4 * r * heads * chunk * chunk), 1)
    return max(b for b in range(1, chunks + 1)
               if chunks % b == 0 and b <= most)


def ssd(x: Array, delta: Array, a_head: Array, b: Array, c: Array,
        d_skip: Array, segment_ids: Array, chunk: int, dtype: Dtype) -> Array:
    """The scan: x (R, T, H, P), delta (R, T, H) float32 and positive,
    a_head (H,) float32 and negative, b and c (R, T, G, N), d_skip (H,),
    segment_ids (R, T) with T a multiple of `chunk` -> y (R, T, H, P)
    float32, zero at padding."""
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    e, q, nc = h // g, chunk, t // chunk
    f32 = jnp.float32
    seg = segment_ids.reshape(r, nc, q)
    with jax.named_scope("ssd_chunk"):
        # the running sum of log-decay inside each chunk, its own token's in
        run = jnp.cumsum((delta * a_head).reshape(r, nc, q, g, e), axis=2)
        xdt = (x.astype(f32) * delta[..., None]).astype(dtype).reshape(
            r, nc, q, g, e, p)
    bq, cq = b.reshape(r, nc, q, g, n), c.reshape(r, nc, q, g, n)
    before = jnp.tril(jnp.ones((q, q), bool))           # key not after query

    @jax.checkpoint
    def block(args):
        seg, run, xdt, bq, cq = args                    # (R, chunks a block, q, ...)
        with jax.named_scope("ssd_chunk"):
            see = ((seg[:, :, :, None] == seg[:, :, None, :])
                   & (seg[:, :, :, None] > 0) & before)             # R c l s
            scores = jnp.einsum("rclgn,rcsgn->rcgls", cq, bq,
                                preferred_element_type=f32)
            at = run.transpose(0, 1, 3, 4, 2)                       # R c g e q
            decay = jnp.exp(jnp.where(
                see[:, :, None, None], at[..., :, None] - at[..., None, :],
                -jnp.inf))                                          # R c g e l s
            y = jnp.einsum("rcgels,rcsgep->rclgep",
                           (scores[:, :, :, None] * decay).astype(dtype), xdt,
                           preferred_element_type=f32)
        with jax.named_scope("ssd_state"):
            # what the chunk's last document leaves at the chunk's end
            mine = (seg == seg[:, :, -1:]) & (seg > 0)
            to_end = jnp.where(mine[..., None, None],
                               jnp.exp(run[:, :, -1:] - run), 0.0)
            left = jnp.einsum(
                "rcsgep,rcsgn->rcgepn",
                (xdt.astype(f32) * to_end[..., None]).astype(dtype), bq,
                preferred_element_type=f32)
        return y, left

    cb = _chunk_block(r, nc, h, q)

    def blocked(a):     # (R, nc, ...) -> (nc / cb, R, cb, ...)
        return jnp.moveaxis(a.reshape(r, nc // cb, cb, *a.shape[2:]), 1, 0)

    def whole(a):       # and back
        a = jnp.moveaxis(a, 0, 1)
        return a.reshape(r, nc, *a.shape[3:])

    y, left = jax.lax.map(block, tuple(map(blocked,
                                           (seg, run, xdt, bq, cq))))
    y, left = whole(y), whole(left)

    with jax.named_scope("ssd_state"):
        last = seg[:, :, -1]                            # who owns what a chunk leaves
        owner = jnp.pad(last, ((0, 0), (1, 0)))[:, :nc]     # ... and what it is given
        through = jnp.where(((last == owner) & (last > 0))[..., None, None],
                            jnp.exp(run[:, :, -1]), 0.0)    # R nc g e

        def carry(state, inputs):
            through, left = inputs
            return state * through[..., None, None] + left, state

        _, given = jax.lax.scan(
            carry, jnp.zeros((r, g, e, p, n), f32),
            (jnp.moveaxis(through, 1, 0), jnp.moveaxis(left, 1, 0)))
        given = jnp.moveaxis(given, 0, 1)               # R nc g e p n
        reads = (seg == owner[..., None]) & (seg > 0)
        from_start = jnp.where(reads[..., None, None], jnp.exp(run), 0.0)
        y = y + jnp.einsum("rclgn,rcgepn->rclgep", cq, given.astype(dtype),
                           preferred_element_type=f32) * from_start[..., None]
        y = y.reshape(r, t, h, p)
        return y + x.astype(f32) * d_skip[:, None]


class SSDMixer(nn.Module):
    shape: MixerShape
    norm_eps: float
    dtype: Dtype = jnp.bfloat16
    scan: Optional[Callable] = None     # `ssd`'s arguments; None: `ssd`
    conv: Optional[Callable] = None     # `conv_silu`'s; None: `conv_silu`

    @nn.compact
    def __call__(self, u: Array, segment_ids: Array) -> Array:
        s = self.shape
        r, t, d = u.shape
        gn = s.groups * s.state_size
        f32 = jnp.float32

        def linear(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=f32, kernel_init=default_init,
                            name=name)

        z, xbc, dt = jnp.split(linear(s.projected, "in_proj")(u),
                               [s.inner, s.inner + s.conv_channels], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = (self.conv or conv_silu)(xbc, segment_ids, *ConvTaps(
                s.conv_width, s.conv_channels, name="conv")(), self.dtype)
        x, b, c = jnp.split(xbc, [s.inner, s.inner + gn], axis=-1)

        a_log = Leaf((s.heads,), a_log_init, name="A_log")()
        dt_bias = Leaf((s.heads,), dt_bias_init, "bias", name="dt_bias")()
        d_skip = Leaf((s.heads,), nn.initializers.ones, name="D")()
        with jax.named_scope("ssd_chunk"):
            delta = jax.nn.softplus(dt.astype(f32) + dt_bias)
            a_head = -jnp.exp(a_log)
        y = (self.scan or ssd)(
            x.reshape(r, t, s.heads, s.head_size), delta, a_head,
            b.reshape(r, t, s.groups, s.state_size),
            c.reshape(r, t, s.groups, s.state_size), d_skip, segment_ids,
            s.chunk, self.dtype)

        with jax.named_scope("ssm_gate_norm"):
            scale = Leaf((s.inner,), nn.initializers.ones,
                         name="gate_norm")()
            y = y.reshape(r, t, s.inner) * jax.nn.silu(z.astype(f32))
            grouped = y.reshape(r, t, s.groups, s.inner // s.groups)
            grouped = grouped * jax.lax.rsqrt(jnp.mean(
                jnp.square(grouped), axis=-1, keepdims=True) + self.norm_eps)
            y = (grouped.reshape(r, t, s.inner) * scale).astype(self.dtype)
        return linear(d, "out_proj")(y)
