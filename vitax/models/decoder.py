"""A packed token decoder in Flax: layers of unequal shapes in one stack.

The second model family (`Config.model_family == "decoder"`), built by
`build_decoder` through the same door as the ViT (vitax/programs/builder.py:
`build_model_for`). Its input is a packed batch of token documents
(vitax/data/packing.py: `document_layout`): `tokens`, `segment_ids` and
`positions`, all (R, T) int32; a row holds whole documents back to back, 0 in
`segment_ids` is padding and `positions` counts from 0 inside each document.

    h = embedding[tokens]
    each layer:  h += W_o[ g * Attn(RMSNorm(h)) ];  h += F(RMSNorm(h))
    logits = RMSNorm(h) @ head            (float32 logits)

- Attn: `layer_heads[i]` query heads and `kv_heads` key/value heads of
  `head_size`, each key/value head serving heads / kv_heads query heads; RoPE
  in the rotate-half convention on the leading `rope_fraction_*` of a head, by
  layer kind: plain on sliding layers, YaRN-scaled (`yarn_*`) on full ones,
  and a kind whose share is 0 rotates nothing (SmallThinker: full layers
  without positions among sliding layers that rotate the whole head);
  scores in float32; a key is visible when it is not after the query, in the
  same document and, in a `sliding_attention` layer, fewer than
  `window_tokens` positions back. `g = sigmoid(W_g x)`, one scalar a head
  (`head_gate`). No biases. `qk_norm`: q and k are RMS-normed over the
  WHOLE projected width (all the heads held, one weight of that width)
  before the heads are split, as Olmo 2 and 3 write it (scope `qk_norm`).
  `head_norm`: the same norm in its second form, over each head's
  `head_size` channels (one weight of `head_size` for q, one for k) after
  the split and before the rotation, as LFM2 writes it (the same scope).
- F: a SwiGLU of `ffn_dim` in a `dense` layer, the routed and shared experts
  of vitax/models/experts.py in a `sparse` one: routed by a sigmoid over all
  the scores or by a softmax over the chosen logits (`route_form`), gated by
  `silu` or by `relu` (`expert_activation`). `route_early`: the router reads
  the layer's FIRST norm's output, what the mixer reads, while the experts
  read the second's; its cotangent then reaches `norm1` beside the mixer's.
- A `mamba` layer has the state-space mixer of vitax/models/ssm.py in place
  of W_o[g * Attn], a `kda` layer the delta-rule mixer of
  vitax/models/kda.py (`layer_heads[i]` heads of `head_size`; it rotates
  nothing), a `linear_attention` layer the Gated-DeltaNet mixer of the same
  file (`layer_heads[i]` heads of `gdn_key_size` keys and `gdn_value_size`
  values; the name the published configurations give it in `layer_types`),
  a `conv` layer the gated short convolution of vitax/models/gconv.py (no
  heads, `layer_heads[i]` 0). `attention` is another word for
  `full_attention`.
- `norm_after`: Olmo's block. The norm sits on what each half ADDS,
  `h += RMSNorm(Mix(h)); h += RMSNorm(F(h))` (scope `post_norm`), and a half
  reads the raw residual stream; the weights keep the names `norm1` and
  `norm2`.
- A `latent_attention` layer (MLA, `LatentAttention`): keys and values come
  up from a normed latent of `latent_rank`; a head's query and key are
  `qk_nope_size` + `qk_rope_size` wide, the rotated `qk_rope_size` of the
  key ONE for all heads (the full layers' RoPE table), its value
  `v_head_size`; every earlier key of the document is visible.
- What a model may state beside its layers: `position_embedding` nope (no
  layer of any kind rotates anything), `attention_multiplier` on the scores in place of
  head_size ** -0.5, `embedding_multiplier` on the embedded tokens,
  `residual_multiplier` on what each half of a layer adds, logits divided by
  `logits_scaling`, and `tie_embeddings` (the head is the embedding table).

Layers differ in shape (heads by kind, dense or sparse), so one stacked
`lax.scan` cannot hold them: consecutive layers of one shape form a RUN, each
run is one `nn.scan` over its stacked parameters with per-block remat inside
(as in vitax/models/vit.py), and the runs follow one another. The kernels
come as ONE record, `kernels` (vitax/programs/kernels.py: `choose_kernels`;
`attention`, `scan`, `rule`, `conv`), from the model down to its blocks; a
member that is None, or no record, selects the plain `jax.numpy` form: the
dense masked path below, `ssd`, `kda`, `conv_silu`. A `conv` layer's mixer
has the plain form only.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from vitax.config import Config
from vitax.models.experts import SharedRoutedExperts, SwiGLU, Table
from vitax.models.gconv import GatedConvMixer, gated_conv_param_count
from vitax.models.kda import (GatedDeltaMixer, GatedDeltaShape, KDAMixer,
                              KDAShape, gated_delta_param_count,
                              kda_param_count)
from vitax.models.ssm import MixerShape, SSDMixer, mixer_param_count
from vitax.models.vit import Array, Dtype, default_init

SLIDING = "sliding_attention"
MAMBA = "mamba"
KDA = "kda"
LATENT = "latent_attention"
GATED_DELTA = "linear_attention"
GATED_CONV = "conv"
# kinds with no attention kernel
NO_ATTENTION = (MAMBA, KDA, GATED_DELTA, GATED_CONV)


# --- rotary position embedding (pure functions) -----------------------------

def rope_inv_freq(rot: int, theta: float) -> np.ndarray:
    """The rot / 2 inverse frequencies theta^(-2i / rot) of plain RoPE."""
    return 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)


def yarn_inv_freq(rot: int, theta: float, factor: float, orig_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies (arXiv:2309.00071, as the published
    `_compute_yarn_parameters` blends them): a frequency that turns more than
    `beta_fast` times over the original context is kept, one that turns
    fewer than `beta_slow` times is divided by `factor`, and between the two
    (the dimensions where the turn counts fall, rounded outwards) the blend is
    linear in the dimension's index."""
    def dim_of(turns):
        return rot * math.log(orig_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    plain = rope_inv_freq(rot, theta)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_tables(positions: Array, inv_freq: np.ndarray,
                attn_factor: float = 1.0) -> Tuple[Array, Array]:
    """cos and sin (R, T, 1, rot / 2), float32, times `attn_factor`."""
    angle = positions.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angle) * attn_factor, jnp.sin(angle) * attn_factor


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate-half RoPE on the leading 2 * cos.shape[-1] dimensions of each
    head of x (R, T, H, Dh); the rest passes through."""
    half = cos.shape[-1]
    x1, x2, rest = (x[..., :half].astype(jnp.float32),
                    x[..., half:2 * half].astype(jnp.float32),
                    x[..., 2 * half:])
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), rest], axis=-1)


def causal_masked_attention(q: Array, k: Array, v: Array, segment_ids: Array,
                            window: int, dtype: Dtype,
                            scale: float = 0.0) -> Array:
    """The dense fallback: q and k (R, T, H | KV, Dh), v (R, T, KV, Dv), each
    key/value head serving H / KV query heads; a key is visible from a query
    of its own document, not before it and (window > 0) fewer than `window`
    positions back; scores times `scale` (0 = Dh ** -0.5). Padding comes back
    zero, (R, T, H, Dv)."""
    r, t, h, dh = q.shape
    kv = k.shape[2]
    qg = q.reshape(r, t, kv, h // kv, dh)
    s = jnp.einsum("rtkgd,rskd->rkgts", qg, k,
                   preferred_element_type=jnp.float32) * (scale or dh ** -0.5)
    at = jnp.arange(t)
    back = at[:, None] - at[None, :]                       # query - key
    seg = segment_ids
    see = ((seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
           & (back >= 0)[None])
    if window > 0:
        see = see & (back < window)[None]
    see = see[:, None, None]
    p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
    p = jnp.where(see, p, 0.0).astype(dtype)
    return jnp.einsum("rkgts,rskd->rtkgd", p, v).reshape(r, t, h, v.shape[-1])


def layer_runs(kinds, heads, mlps) -> List[Tuple[Tuple[str, int, str], int]]:
    """[(shape, length)]: consecutive layers of one (kind, heads, mlp), the
    units the stack is scanned by (`run<i>` in the parameter tree)."""
    out: List[List] = []
    for shape in zip(kinds, heads, mlps):
        if out and out[-1][0] == shape:
            out[-1][1] += 1
        else:
            out.append([shape, 1])
    return [(shape, n) for shape, n in out]


# --- modules ----------------------------------------------------------------

class RMSNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * scale, normalised in float32."""

    eps: float
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: Array) -> Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _linear(features: int, dtype: Dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, kernel_init=default_init,
                    name=name)


class DecoderAttention(nn.Module):
    heads: int
    kv_heads: int
    head_size: int
    window: int                     # 0 = every earlier key of the document
    head_gate: bool
    dtype: Dtype = jnp.bfloat16
    attention_impl: Optional[Callable] = None
    scale: float = 0.0              # on the scores; 0 = head_size ** -0.5
    qk_norm: float = 0.0            # > 0: the eps of the norm on q and on k
    head_norm: bool = False         # the norm a head, behind the split

    @nn.compact
    def __call__(self, x: Array, segment_ids: Array,
                 rope: Optional[Tuple[Array, Array]]) -> Array:
        r, t, d = x.shape
        h, kv, dh = self.heads, self.kv_heads, self.head_size
        def normed(y, name, heads):
            """Over the whole width before the split, or over each head
            behind it (`head_norm`: RMSNorm runs over the last axis)."""
            if self.head_norm:
                y = y.reshape(r, t, heads, dh)
            if self.qk_norm:
                with jax.named_scope("qk_norm"):
                    y = RMSNorm(self.qk_norm, self.dtype, name=name)(y)
            return y.reshape(r, t, heads, dh)

        q = normed(_linear(h * dh, self.dtype, "wq")(x), "q_norm", h)
        k = normed(_linear(kv * dh, self.dtype, "wk")(x), "k_norm", kv)
        v = _linear(kv * dh, self.dtype, "wv")(x).reshape(r, t, kv, dh)
        if rope is not None:
            with jax.named_scope("rope1d"):
                q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        if self.attention_impl is None:
            out = causal_masked_attention(q, k, v, segment_ids, self.window,
                                          self.dtype, self.scale)
        else:
            out = self.attention_impl(q, k, v, segment_ids, self.window,
                                      self.scale)
        if self.head_gate:
            with jax.named_scope("head_gate"):
                gate = jax.nn.sigmoid(_linear(h, self.dtype, "head_gate")(
                    x).astype(jnp.float32))
                out = (out * gate[..., None]).astype(self.dtype)
        return _linear(d, self.dtype, "wo")(out.reshape(r, t, h * dh))


class LatentShape(NamedTuple):
    rank: int
    nope: int
    rope: int
    value: int


class LatentAttention(nn.Module):
    """Latent attention (MLA, without a query latent): `rope` is the (cos,
    sin) of the `shape.rope` rotated dimensions."""

    heads: int
    shape: LatentShape
    head_gate: bool
    norm_eps: float
    dtype: Dtype = jnp.bfloat16
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: Array, segment_ids: Array,
                 rope: Tuple[Array, Array]) -> Array:
        r, t, d = x.shape
        h, s = self.heads, self.shape
        q = _linear(h * (s.nope + s.rope), self.dtype, "wq")(x).reshape(
            r, t, h, s.nope + s.rope)
        with jax.named_scope("mla_latent"):
            latent, k_rope = jnp.split(_linear(
                s.rank + s.rope, self.dtype, "wkva")(x), [s.rank], axis=-1)
            latent = RMSNorm(self.norm_eps, self.dtype,
                             name="latent_norm")(latent)
            k_nope, v = jnp.split(_linear(
                h * (s.nope + s.value), self.dtype, "wkvb")(latent).reshape(
                    r, t, h, s.nope + s.value), [s.nope], axis=-1)
            # the rotated key is one for all heads; the kernels' operand
            # holds a head's whole key, so it is laid beside each head's own
            # part here, once
            k_rope = apply_rope(k_rope[:, :, None, :], *rope)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (r, t, h, s.rope))], axis=-1)
        with jax.named_scope("rope1d"):
            q = jnp.concatenate(
                [q[..., :s.nope], apply_rope(q[..., s.nope:], *rope)], axis=-1)
        if self.attention_impl is None:
            out = causal_masked_attention(q, k, v, segment_ids, 0, self.dtype)
        else:
            out = self.attention_impl(q, k, v, segment_ids, 0, 0.0)
        if self.head_gate:
            with jax.named_scope("head_gate"):
                gate = jax.nn.sigmoid(_linear(h, self.dtype, "head_gate")(
                    x).astype(jnp.float32))
                out = (out * gate[..., None]).astype(self.dtype)
        return _linear(d, self.dtype, "wo")(out.reshape(r, t, h * s.value))


class DecoderBlock(nn.Module):
    """One layer; `shape` = (kind, heads, mlp) is what a run's layers share."""

    shape: Tuple[str, int, str]
    kv_heads: int
    head_size: int
    window_tokens: int
    head_gate: bool
    norm_eps: float
    ffn_dim: int
    expert_dim: int
    shared_expert_dim: int
    experts_routed: int
    experts_held: int
    expert_first: int
    experts_per_token: int
    routed_scale: float
    dtype: Dtype = jnp.bfloat16
    kernels: Optional[Any] = None   # the Decoder's; None: every form plain
    token_sharding: Optional[Any] = None
    attention_scale: float = 0.0
    residual_multiplier: float = 1.0
    mixer: Optional[MixerShape] = None      # a mamba layer's
    kda: Optional[Tuple[int, float]] = None     # a kda layer's taps and bound
    latent: Optional[LatentShape] = None    # a latent_attention layer's
    # groups, kept, bias, what the weights' denominator adds
    route: Tuple[int, int, bool, float] = (0, 0, False, 0.0)
    # a linear_attention layer's key size, value size and taps
    gated_delta: Optional[Tuple[int, int, int]] = None
    norm_after: bool = False        # the norms on what a half adds
    qk_norm: bool = False
    head_norm: bool = False         # the norm on q and k is one a head
    gconv_width: int = 0            # a conv layer's taps
    route_form: str = "sigmoid"     # a sparse layer's router, its experts'
    expert_activation: str = "silu"     # gate, and whether the router
    route_early: bool = False           # reads norm1's output

    def _added(self, y: Array) -> Array:
        if self.residual_multiplier == 1.0:
            return y
        return y * jnp.asarray(self.residual_multiplier, y.dtype)

    def _normed(self, x: Array, name: str, before: bool) -> Array:
        """A half's norm where it sits: on the half's input in a pre-norm
        block (`before`), on what it adds in a norm-after one."""
        if before == self.norm_after:
            return x
        if before:
            return RMSNorm(self.norm_eps, self.dtype, name=name)(x)
        with jax.named_scope("post_norm"):
            return RMSNorm(self.norm_eps, self.dtype, name=name)(x)

    @nn.compact
    def __call__(self, x: Array, segment_ids: Array, rope_full=None,
                 rope_window=None):
        kind, heads, mlp = self.shape
        sliding = kind == SLIDING
        attention = getattr(self.kernels, "attention", None)
        conv = getattr(self.kernels, "conv", None)
        if self.token_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.token_sharding)
        y = self._normed(x, "norm1", True)
        route_from = y if self.route_early and mlp == "sparse" else None
        if kind == MAMBA:
            y = SSDMixer(self.mixer, self.norm_eps, self.dtype,
                         scan=getattr(self.kernels, "scan", None), conv=conv,
                         name="mixer")(y, segment_ids)
        elif kind == KDA:
            y = KDAMixer(KDAShape(heads, self.head_size, *self.kda),
                         self.norm_eps, self.dtype,
                         rule=getattr(self.kernels, "rule", None), conv=conv,
                         name="mixer")(y, segment_ids)
        elif kind == GATED_DELTA:
            y = GatedDeltaMixer(GatedDeltaShape(heads, *self.gated_delta),
                                self.norm_eps, self.dtype, conv=conv,
                                name="mixer")(y, segment_ids)
        elif kind == GATED_CONV:
            y = GatedConvMixer(self.gconv_width, self.dtype,
                               name="mixer")(y, segment_ids)
        elif kind == LATENT:
            y = LatentAttention(
                heads=heads, shape=self.latent, head_gate=self.head_gate,
                norm_eps=self.norm_eps, dtype=self.dtype,
                attention_impl=attention, name="attn",
            )(y, segment_ids, rope_full)
        else:
            y = DecoderAttention(
                heads=heads, kv_heads=self.kv_heads, head_size=self.head_size,
                window=self.window_tokens if sliding else 0,
                head_gate=self.head_gate, dtype=self.dtype,
                attention_impl=attention,
                scale=self.attention_scale,
                qk_norm=(self.norm_eps if self.qk_norm or self.head_norm
                         else 0.0),
                head_norm=self.head_norm, name="attn",
            )(y, segment_ids, rope_window if sliding else rope_full)
        x = x + self._added(self._normed(y, "norm1", False))
        y = self._normed(x, "norm2", True)
        if mlp == "dense":
            y = SwiGLU(self.ffn_dim, x.shape[-1], dtype=self.dtype,
                       name="mlp")(y)
        else:
            y = SharedRoutedExperts(
                experts_routed=self.experts_routed,
                experts_held=self.experts_held,
                expert_first=self.expert_first,
                experts_per_token=self.experts_per_token,
                expert_dim=self.expert_dim, shared_dim=self.shared_expert_dim,
                routed_scale=self.routed_scale, dtype=self.dtype,
                route_groups=self.route[0], groups_per_token=self.route[1],
                route_bias=self.route[2], weight_eps=self.route[3],
                route_form=self.route_form,
                activation=self.expert_activation, name="moe",
            )(y, segment_ids > 0, route_from)
        return x + self._added(self._normed(y, "norm2", False))


class Run(nn.Module):
    """Consecutive layers of one shape: scanned over stacked parameters
    (`blocks`, leading axis = the run's length) or unrolled, with per-block
    remat inside, as `VisionTransformer` runs its blocks."""

    length: int
    block_kwargs: Any               # a tuple of (name, value) pairs: hashable
    scan_blocks: bool
    scan_unroll: int
    remat: bool
    policy: Any

    @nn.compact
    def __call__(self, x: Array, *ctx) -> Array:
        kwargs = dict(self.block_kwargs)

        def body(block: DecoderBlock, carry: Array, *ctx):
            return block(carry, *ctx), None

        if self.remat:
            body = nn.remat(body, policy=self.policy, prevent_cse=False)
        if self.scan_blocks:
            scan = nn.scan(
                body,
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=self.length,
                in_axes=(nn.broadcast,) * len(ctx),
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
                unroll=min(self.scan_unroll, self.length))
            x, _ = scan(DecoderBlock(name="blocks", **kwargs), x, *ctx)
        else:
            for i in range(self.length):
                x, _ = body(DecoderBlock(name=f"blocks_{i}", **kwargs), x,
                            *ctx)
        return x


class Decoder(nn.Module):
    embed_dim: int
    vocab_rows: int
    layer_kinds: Tuple[str, ...]
    layer_heads: Tuple[int, ...]
    layer_mlps: Tuple[str, ...]
    kv_heads: int
    head_size: int
    window_tokens: int
    head_gate: bool
    norm_eps: float
    ffn_dim: int
    expert_dim: int
    shared_expert_dim: int
    experts_routed: int
    experts_held: int
    expert_first: int
    experts_per_token: int
    routed_scale: float
    rope_full: Tuple[float, ...]    # theta, fraction, yarn factor, original
    #   length, beta fast, beta slow, factor on cos and sin
    rope_window: Tuple[float, ...]  # theta, fraction
    pack_tokens: int
    dtype: Dtype = jnp.bfloat16
    scan_blocks: bool = True
    scan_unroll: int = 1
    grad_ckpt: bool = True
    remat_policy: str = "none_saveable"
    kernels: Optional[Any] = None   # vitax/programs/kernels.py: Kernels
    token_sharding: Optional[Any] = None
    rope: bool = True               # False: no layer rotates anything (NoPE)
    tie_embeddings: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_scale: float = 0.0    # 0 = head_size ** -0.5
    logits_scaling: float = 1.0
    mixer: Optional[MixerShape] = None
    kda: Optional[Tuple[int, float]] = None
    latent: Optional[LatentShape] = None
    route: Tuple[int, int, bool, float] = (0, 0, False, 0.0)
    gated_delta: Optional[Tuple[int, int, int]] = None
    norm_after: bool = False
    qk_norm: bool = False
    head_norm: bool = False
    gconv_width: int = 0
    route_form: str = "sigmoid"
    expert_activation: str = "silu"
    route_early: bool = False

    @property
    def attention_impl(self) -> Optional[Callable]:
        """What the code shared with the ViT reads of a model."""
        return getattr(self.kernels, "attention", None)

    def runs(self) -> List[Tuple[Tuple[str, int, str], int]]:
        return layer_runs(self.layer_kinds, self.layer_heads, self.layer_mlps)

    def span(self, kind: str) -> int:
        """The keys one query of a layer of `kind` can meet."""
        return (min(self.window_tokens, self.pack_tokens) if kind == SLIDING
                else self.pack_tokens)

    def _rope(self, positions: Array):
        """(the full layers' cos and sin, the sliding layers'); None for a
        kind that rotates nothing (a rotated share of 0)."""
        theta, frac, factor, orig, fast, slow, attn = self.rope_full
        rot = int(self.head_size * frac)
        theta_w, frac_w = self.rope_window
        rot_w = int(self.head_size * frac_w)
        full = (rope_inv_freq(rot, theta) if factor == 1.0 else
                yarn_inv_freq(rot, theta, factor, int(orig), fast, slow))
        return (rope_tables(positions, full, attn) if rot else None,
                rope_tables(positions, rope_inv_freq(rot_w, theta_w))
                if rot_w else None)

    @nn.compact
    def __call__(self, batch, deterministic: bool = True) -> Array:
        """A packed batch's `tokens`, `segment_ids`, `positions` (R, T) ->
        next-token logits (R, T, vocab_rows) float32."""
        del deterministic            # no dropout arm (Config.validate)
        seg = batch["segment_ids"]
        table = Table((self.vocab_rows, self.embed_dim), "embedding",
                      name="embed")()
        x = jnp.take(table.astype(self.dtype), batch["tokens"], axis=0)
        if self.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.embedding_multiplier, self.dtype)
        # a padding token carries nothing (and never meets a real one)
        x = jnp.where((seg > 0)[..., None], x, jnp.zeros((), self.dtype))
        if self.token_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.token_sharding)
        ropes = ()
        if self.rope:
            with jax.named_scope("rope1d"):
                ropes = self._rope(batch["positions"])

        block_kwargs = dict(
            kv_heads=self.kv_heads, head_size=self.head_size,
            window_tokens=self.window_tokens, head_gate=self.head_gate,
            norm_eps=self.norm_eps, ffn_dim=self.ffn_dim,
            expert_dim=self.expert_dim,
            shared_expert_dim=self.shared_expert_dim,
            experts_routed=self.experts_routed,
            experts_held=self.experts_held, expert_first=self.expert_first,
            experts_per_token=self.experts_per_token,
            routed_scale=self.routed_scale, dtype=self.dtype,
            kernels=self.kernels, token_sharding=self.token_sharding,
            attention_scale=self.attention_scale,
            residual_multiplier=self.residual_multiplier, mixer=self.mixer,
            kda=self.kda, latent=self.latent, route=self.route,
            gated_delta=self.gated_delta, norm_after=self.norm_after,
            qk_norm=self.qk_norm, head_norm=self.head_norm,
            gconv_width=self.gconv_width, route_form=self.route_form,
            expert_activation=self.expert_activation,
            route_early=self.route_early)
        for i, (shape, length) in enumerate(self.runs()):
            x = Run(length=length,
                    block_kwargs=tuple({**block_kwargs,
                                        "shape": shape}.items()),
                    scan_blocks=self.scan_blocks,
                    scan_unroll=self.scan_unroll, remat=self.grad_ckpt,
                    policy=run_remat_policy(self, shape[0], length),
                    name=f"run{i}")(x, seg, *ropes)

        x = RMSNorm(self.norm_eps, self.dtype, name="norm")(x)
        with jax.named_scope("lm_head_loss"):
            if self.tie_embeddings:
                logits = jnp.einsum("rtd,vd->rtv", x, table.astype(self.dtype),
                                    preferred_element_type=jnp.float32)
            else:
                head = Table((self.embed_dim, self.vocab_rows),
                             name="lm_head")()
                logits = jnp.einsum("rtd,dv->rtv", x, head.astype(self.dtype),
                                    preferred_element_type=jnp.float32)
            if self.logits_scaling != 1.0:
                logits = logits / self.logits_scaling
            return logits


# --- what per-block remat keeps of the attention kernels --------------------

def _decoder_attention_saveable(prim, *_, **params):
    """`none_saveable` + the attention forward kernel's own outputs (o and
    lse), as vitax/models/vit.py keeps them; by the kernels' names, so that a
    block that gains another `pallas_call` does not keep that one's too. The
    equation carries the name as `name` (tests/decoder_cases.py
    `check_the_policy_keeps_by_the_traced_name` holds that on a traced
    forward); the backward kernels never stand under the remat's forward."""
    return getattr(prim, "name", "") == "pallas_call" and (
        params.get("name") or "").startswith("flash_")


def _decoder_nothing_saveable(*_, **__):
    """What a kept run of ONE layer is given (`run_remat_policy`). An object
    of its own, neither `None` nor jax's `nothing_saveable`: jax stages the
    jitted helpers of runs whose policies are one object as one function,
    and the step compiled from that text is another schedule (LFM2's ran
    0.3% slower, PERF.md section 6, PR 52). With this the one-layer cells
    lower to the text they had."""
    return False


def keeps_attention_residuals(model: Decoder, kind: str) -> bool:
    """PR 30's rule (vitax/models/vit.py: keeps_attention_residuals) by the
    span of a run's layers: a full layer's query meets a whole row, a sliding
    layer's at most `window_tokens` keys. A mamba, kda, linear_attention or
    conv layer has no attention kernel to keep anything of."""
    from vitax.models.vit import keeps_attention_residuals as rule
    return kind not in NO_ATTENTION and rule(model, span=model.span(kind))


def run_remat_policy(model: Decoder, kind: str, length: int):
    """The policy of a run's per-block remat. Where PR 30's rule keeps, the
    kernel's o and lse only in a run of several layers: a scan of one trip is
    inlined, and the chip's compiler merges the remat's forward kernel with
    the first there (`prevent_cse` is off), which keeps o and lse without
    the copy a scan's residual costs (PERF.md section 6, PR 52)."""
    from vitax.models.vit import _REMAT_POLICIES
    if not keeps_attention_residuals(model, kind):
        return _REMAT_POLICIES[model.remat_policy]
    if length > 1:
        return _decoder_attention_saveable
    return _decoder_nothing_saveable


def build_decoder(cfg: Config, kernels=None, token_sharding=None) -> Decoder:
    return Decoder(
        embed_dim=cfg.embed_dim, vocab_rows=cfg.vocab_rows,
        layer_kinds=cfg.layer_kinds, layer_heads=cfg.layer_heads,
        layer_mlps=cfg.layer_mlps, kv_heads=cfg.kv_heads,
        head_size=cfg.head_size, window_tokens=cfg.window_tokens,
        head_gate=cfg.head_gate, norm_eps=cfg.norm_eps, ffn_dim=cfg.ffn_dim,
        expert_dim=cfg.expert_dim, shared_expert_dim=cfg.shared_expert_dim,
        experts_routed=cfg.experts_routed, experts_held=cfg.experts_held,
        expert_first=cfg.expert_first,
        experts_per_token=cfg.experts_per_token,
        routed_scale=cfg.routed_scale,
        rope_full=(cfg.rope_theta_full, cfg.rope_fraction_full,
                   cfg.yarn_factor, cfg.yarn_orig_len, cfg.yarn_beta_fast,
                   cfg.yarn_beta_slow, cfg.yarn_attn_factor),
        rope_window=(cfg.rope_theta_window, cfg.rope_fraction_window),
        pack_tokens=cfg.pack_tokens,
        dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
        scan_blocks=cfg.scan_blocks, scan_unroll=cfg.scan_unroll,
        grad_ckpt=cfg.grad_ckpt, remat_policy=cfg.remat_policy,
        kernels=kernels, token_sharding=token_sharding,
        rope=cfg.position_embedding == "rope",
        tie_embeddings=cfg.tie_embeddings,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_scale=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, mixer=mixer_shape(cfg),
        kda=((cfg.kda_conv_width, cfg.kda_gate_bound)
             if KDA in cfg.layer_kinds else None),
        latent=latent_shape(cfg),
        route=(cfg.route_groups, cfg.groups_per_token, cfg.route_bias,
               cfg.route_weight_eps),
        gated_delta=((cfg.gdn_key_size, cfg.gdn_value_size,
                      cfg.gdn_conv_width)
                     if GATED_DELTA in cfg.layer_kinds else None),
        norm_after=cfg.norm_after, qk_norm=cfg.qk_norm,
        head_norm=cfg.head_norm, gconv_width=cfg.gconv_width,
        route_form=cfg.route_form, expert_activation=cfg.expert_activation,
        route_early=cfg.route_early)


def mixer_shape(cfg: Config) -> Optional[MixerShape]:
    """The shape of the mamba layers' mixer, None in a model that has none."""
    if MAMBA not in cfg.layer_kinds:
        return None
    return MixerShape(
        heads=cfg.ssm_heads, head_size=cfg.ssm_head_size,
        state_size=cfg.ssm_state_size, conv_width=cfg.ssm_conv_width,
        groups=cfg.ssm_groups, chunk=cfg.ssm_chunk)


def delta_shape(cfg: Config, kind: str, heads: int):
    """The shape of a kda or a linear_attention layer's mixer."""
    if kind == KDA:
        return KDAShape(heads, cfg.head_size, cfg.kda_conv_width,
                        cfg.kda_gate_bound)
    return GatedDeltaShape(heads, cfg.gdn_key_size, cfg.gdn_value_size,
                           cfg.gdn_conv_width)


def delta_shapes(cfg: Config) -> list:
    """One `delta_shape` a kind and number of heads the model has, the kda
    layers' first."""
    return [delta_shape(cfg, kind, n) for kind in (KDA, GATED_DELTA)
            for n in sorted({n for k, n in zip(
                cfg.layer_kinds, cfg.layer_heads) if k == kind})]


def latent_shape(cfg: Config) -> Optional[LatentShape]:
    """The shape of the latent_attention layers, None in a model without."""
    if LATENT not in cfg.layer_kinds:
        return None
    return LatentShape(rank=cfg.latent_rank, nope=cfg.qk_nope_size,
                       rope=cfg.qk_rope_size, value=cfg.v_head_size)


def sample_documents(cfg: Config, batch: int):
    """Zeros shaped like the decoder's input, for `model.init`."""
    zeros = jnp.zeros((batch, cfg.pack_tokens), jnp.int32)
    return {"tokens": zeros, "segment_ids": zeros, "positions": zeros}


def expected_param_count(cfg: Config) -> int:
    """Closed-form parameter count of what this chip holds."""
    d, dh = cfg.embed_dim, cfg.head_size
    # embedding, head (the same table when tied), final norm
    total = (1 if cfg.tie_embeddings else 2) * cfg.vocab_rows * d + d
    for kind, heads, mlp in zip(cfg.layer_kinds, cfg.layer_heads,
                                cfg.layer_mlps):
        total += 2 * d                                      # the two norms
        if kind == MAMBA:
            total += mixer_param_count(mixer_shape(cfg), d)
        elif kind == KDA:
            total += kda_param_count(delta_shape(cfg, kind, heads), d)
        elif kind == GATED_DELTA:
            total += gated_delta_param_count(delta_shape(cfg, kind, heads), d)
        elif kind == GATED_CONV:
            total += gated_conv_param_count(d, cfg.gconv_width)
        elif kind == LATENT:
            s = latent_shape(cfg)
            total += (d * heads * (s.nope + s.rope) + d * (s.rank + s.rope)
                      + s.rank + s.rank * heads * (s.nope + s.value)
                      + heads * s.value * d)
            total += d * heads if cfg.head_gate else 0
        else:
            total += 2 * d * heads * dh + 2 * d * cfg.kv_heads * dh
            total += d * heads if cfg.head_gate else 0
            total += (heads + cfg.kv_heads) * dh if cfg.qk_norm else 0
            total += 2 * dh if cfg.head_norm else 0
        if mlp == "dense":
            total += 3 * d * cfg.ffn_dim
        else:
            total += (d * cfg.experts_routed
                      + (cfg.experts_routed if cfg.route_bias else 0)
                      + 3 * d * cfg.expert_dim * cfg.experts_held
                      + 3 * d * cfg.shared_expert_dim)
    return total
