"""Vision Transformer in Flax, designed TPU-first.

Capability parity with the reference model stack (reference run_vit_training.py:99-162
composing timm 0.4.12 PatchEmbed/Block), re-designed for XLA:

- Blocks run under ``jax.lax.scan`` over stacked layer parameters (`nn.scan`):
  one traced/compiled block body regardless of depth, vs the reference's 32
  individually-wrapped modules (compile time + HLO size win).
- Activation checkpointing is `jax.remat` composed *inside* the scan, matching the
  reference's checkpoint_module-inside-FSDP order (reference run_vit_training.py:143-145).
- Computation in bfloat16 (MXU-native), parameters in float32.
- The attention inner product is pluggable: a Pallas flash-attention kernel on TPU
  (vitax.ops.attention) or the dense jnp reference path.

Architecture parity notes (verified against the reference by param-count closed form,
10,077,917,160 at default flags — see tests/test_model.py):
- conv patchify (patch_size stride/kernel) -> (B, N, D)           [timm PatchEmbed]
- learned pos_embed, shape (1, N, D), trunc-normal std 0.02; NO CLS token
  (reference run_vit_training.py:127-128)
- pre-norm blocks: LN -> MHA (fused qkv, qkv_bias=True) -> residual;
  LN -> MLP(GELU, hidden=dim*mlp_ratio) -> residual                [timm Block]
- block LayerNorm eps = 1e-5 (timm Block default when constructed directly,
  as the reference does at run_vit_training.py:134-141); final LayerNorm eps = 1e-6
  (reference run_vit_training.py:151)
- mean-pool over sequence (arXiv:2106.04560), then Linear head
  (reference run_vit_training.py:155-162)
- init: trunc-normal(std=0.02) weights, zero biases, LN ones/zeros (timm
  _init_vit_weights semantics, reference run_vit_training.py:125,142,152,128)

The native-resolution packed model (MoonViT; `pack_tokens > 0`) is the same
`VisionTransformer` / `Block` / scan / remat over another input: rows of
pre-cut patches holding several images of different grids (vitax/data/
packing.py). What differs, all decided by the shape: a linear patch map (the
conv's equal on cut patches), a learned (G, G, D) position table resized
bicubically to each image's grid (`pos_interp`), 2D RoPE on q and k
(`rope2d`), attention within each image only (vitax/ops/flash_blocked.py
packed kernels, or the dense masked fallback below), tanh-GELU, an MLP width
given as a number, LayerNorm eps 1e-5 throughout, and a mean over each
image's tokens (`segment_pool`) into per-image logits (R, S, classes).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from vitax.config import Config

Array = jax.Array
Dtype = Any

# timm _init_vit_weights: trunc_normal_(std=.02) on Linear weights, zero bias.
# jax's truncated_normal truncates at +/-2 sigma without rescaling the stddev —
# the same behavior as torch.nn.init.trunc_normal_ (measured std ~0.0176 for 0.02).
default_init = nn.initializers.truncated_normal(stddev=0.02)


class QuantDense(nn.Module):
    """nn.Dense's quantized-serving twin: kernel stored quantized (int8/fp8)
    with its per-output-channel float32 scale as the sibling `qscale` param.

    The serve engine merges consolidate.py's `__scale__/` arrays into the
    param tree under this name (vitax/serve/quant.py merge_quant_scales), so
    under `nn.scan` the stacked (L, 1, F) scales slice per layer exactly like
    the kernels. `quant_matmul` (vitax/ops/dequant_matmul.make_quant_matmul)
    owns the math — fused Pallas kernel or jnp reference, weight-only or
    int8 x int8 with dynamic activation quant; `act=False` sites (the head)
    stay weight-only always. Never used in training: `_dense` returns the
    byte-identical nn.Dense whenever quant_matmul is None."""

    features: int
    quant_matmul: Callable
    act: bool = True
    use_bias: bool = True
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: Array) -> Array:
        kernel = self.param("kernel", default_init,
                            (x.shape[-1], self.features), jnp.float32)
        qscale = self.param("qscale", nn.initializers.ones,
                            (1, self.features), jnp.float32)
        y = self.quant_matmul(x, kernel, qscale, act=self.act)
        y = y.astype(self.dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


def _dense(quant_matmul: Optional[Callable], act: bool, features: int, *,
           use_bias: bool = True, dtype, name: str):
    """The Dense constructor every matmul site below goes through: plain
    nn.Dense (training and full-precision serving — construction identical
    to the pre-quantization code, so the traced program is unchanged), or
    QuantDense under the SAME name when a quant_matmul is installed (param
    paths stay `<site>/kernel` etc. — no wrapper scope)."""
    if quant_matmul is None:
        return nn.Dense(
            features,
            use_bias=use_bias,
            dtype=dtype,
            param_dtype=jnp.float32,
            kernel_init=default_init,
            bias_init=nn.initializers.zeros,
            name=name,
        )
    return QuantDense(features=features, quant_matmul=quant_matmul, act=act,
                      use_bias=use_bias, dtype=dtype, name=name)


# The sites built with `dtype=self.dtype`: every `_dense` call below except
# `head` (dtype=float32), and PatchEmbed's conv (`proj` too). Flax's
# promote_dtype casts their kernel and bias to the compute dtype before the
# matmul. A new site built that way is added here, beside its constructor.
_COMPUTE_DTYPE_SITES = ("qkv", "proj", "fc1", "fc2")
# MoeMlp's expert weights (vitax/models/moe.py: `w1.astype(self.dtype)` at
# every use); its `router` is a float32 Dense and is not a site above
_COMPUTE_DTYPE_MOE_PARAMS = ("w1", "b1", "w2", "b2")


def cast_before_use(path) -> bool:
    """Whether the forward casts the param leaf at `path` (a jax key path, or
    its names) to the model's compute dtype before its first use — so a
    caller whose weights never change (vitax/serve/engine.py) may hand the
    forward that leaf already cast and get the same values: promote_dtype
    and `.astype` are no-ops on a leaf that has the dtype. False for what the
    forward reads in float32: LayerNorm scale/bias (Flax normalizes and
    scales in f32), `head`, the MoE router. Decided from the leaf's role in
    the tree alone — scan-stacked and unrolled block trees answer alike."""
    names = tuple(getattr(k, "key", k) for k in path)
    leaf = names[-1]
    site = names[-2] if len(names) > 1 else ""
    if leaf == "pos_embed":
        return True
    if site == "moe":
        return leaf in _COMPUTE_DTYPE_MOE_PARAMS
    return leaf in ("kernel", "bias") and site in _COMPUTE_DTYPE_SITES


# --- the packed native-resolution model's pieces (pure functions) ----------

BICUBIC_A = -0.75  # PyTorch's cubic coefficient (jax.image.resize uses -0.5)


def _cubic_taps(index: Array, out_size: Array, in_size: int):
    """PyTorch `F.interpolate(mode="bicubic", align_corners=False)` along one
    axis: for output `index` of an axis resized from `in_size` to `out_size`,
    the four source indices (clamped at the border) and their weights.
    Source coordinate (i + 0.5) * in/out - 0.5, no antialiasing."""
    src = ((index.astype(jnp.float32) + 0.5)
           * (in_size / out_size.astype(jnp.float32)) - 0.5)
    base = jnp.floor(src)
    t = src - base
    a = BICUBIC_A

    def near(x):   # |x| <= 1
        return ((a + 2.0) * x - (a + 3.0)) * x * x + 1.0

    def far(x):    # 1 < |x| < 2
        return ((a * x - 5.0 * a) * x + 8.0 * a) * x - 4.0 * a

    weights = jnp.stack([far(t + 1.0), near(t), near(1.0 - t), far(2.0 - t)],
                        axis=-1)
    taps = base[..., None].astype(jnp.int32) + jnp.arange(-1, 3)
    return jnp.clip(taps, 0, in_size - 1), weights


def pos_interp(table: Array, positions: Array, token_hw: Array,
               dtype) -> Array:
    """The learned (G, G, D) table resized to each token's image grid and
    read at the token's place: per token a 16-tap weighted gather (4 rows x
    4 columns), run on the MXU as one matmul of a (tokens, G*G) weight
    matrix with 16 non-zeros a row against the flat table — no gather, and
    no scatter in the backward. At (h, w) = (G, G) the weights are exactly
    one-hot: the table itself. positions / token_hw: (R, T, 2) int32 as
    (row, column) / (h, w) -> (R, T, D) float32."""
    g = table.shape[0]
    side = jnp.arange(g)

    def axis_weights(index, size):          # -> (R, T, G), 4 non-zeros
        taps, w = _cubic_taps(index, size, g)
        return jnp.sum(w[..., None] * (taps[..., None] == side), axis=-2)

    size = jnp.maximum(token_hw, 1)         # padding tokens: any finite row
    wy = axis_weights(positions[..., 0], size[..., 0])
    wx = axis_weights(positions[..., 1], size[..., 1])
    w = (wy[..., :, None] * wx[..., None, :]).reshape(*wy.shape[:-1], g * g)
    return jnp.einsum("rtk,kd->rtd", w.astype(dtype),
                      table.reshape(g * g, -1).astype(dtype),
                      preferred_element_type=jnp.float32)


def rope2d_tables(positions: Array, head_dim: int, base: float):
    """cos / sin (R, T, head_dim/2) float32 of MoonViT's 2D RoPE: head_dim/4
    frequencies theta_i = base^(-4i/head_dim); the head vector's adjacent
    pair j turns by column * theta_{j/2} for even j, by row *
    theta_{(j-1)/2} for odd j."""
    theta = base ** (-4.0 * jnp.arange(head_dim // 4, dtype=jnp.float32)
                     / head_dim)
    pos = positions.astype(jnp.float32)
    angles = jnp.stack([pos[..., 1:2] * theta, pos[..., 0:1] * theta],
                       axis=-1).reshape(*positions.shape[:-1], head_dim // 2)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope2d(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate the adjacent pairs of (R, T, H, Dh) by the tables, in float32."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.stack([a * c - b * s, a * s + b * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def segment_pool(x: Array, segment_ids: Array, images: int) -> Array:
    """Mean over each image's tokens: (R, T, D), (R, T) -> (R, S, D) float32
    (zeros where a row has no such image)."""
    member = (segment_ids[..., None]
              == jnp.arange(1, images + 1)).astype(x.dtype)       # (R, T, S)
    sums = jnp.einsum("rts,rtd->rsd", member, x,
                      preferred_element_type=jnp.float32)
    count = jnp.sum(member.astype(jnp.float32), axis=1)           # (R, S)
    return sums / jnp.maximum(count, 1.0)[..., None]


def masked_attention(q: Array, k: Array, v: Array, segment_ids: Array,
                     dtype) -> Array:
    """Dense attention within each image of a packed row (the no-kernel
    path, off the TPU): O(T^2) scores, padding rows come back zero."""
    scale = q.shape[-1] ** -0.5
    same = ((segment_ids[:, :, None] == segment_ids[:, None, :])
            & (segment_ids[:, :, None] > 0))[:, None]             # (R,1,T,T)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(same, s, -1e30), axis=-1)
    p = jnp.where(same, p, 0.0).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def sample_input(cfg: Config, batch: int):
    """Zeros shaped like the model's input, for `model.init`: an image
    batch, or the packed model's dict of a packed batch's arrays."""
    if not cfg.packed:
        return jnp.zeros((batch, cfg.image_size, cfg.image_size, 3),
                         jnp.float32)
    t, s = cfg.pack_tokens, cfg.pack_images
    return {"patches": jnp.zeros((batch, t, 3 * cfg.patch_size ** 2),
                                 jnp.float32),
            "segment_ids": jnp.zeros((batch, t), jnp.int32),
            "positions": jnp.zeros((batch, t, 2), jnp.int32),
            "grid_hw": jnp.zeros((batch, s, 2), jnp.int32)}


class PatchEmbed(nn.Module):
    """Conv patchify: (B, H, W, 3) -> (B, N, D). timm PatchEmbed equivalent
    (reference run_vit_training.py:124)."""

    patch_size: int
    embed_dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: Array) -> Array:
        p = self.patch_size
        x = nn.Conv(
            features=self.embed_dim,
            kernel_size=(p, p),
            strides=(p, p),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=default_init,
            bias_init=nn.initializers.zeros,
            name="proj",
        )(x)
        b, h, w, d = x.shape
        return x.reshape(b, h * w, d)


class PatchProj(nn.Module):
    """PatchEmbed on pre-cut patches: the linear map that equals the p x p
    stride-p convolution, (R, T, p*p*3) -> (R, T, D) (its kernel is the
    conv's, reshaped; vitax/data/packing.py:cut_patches gives the order)."""

    embed_dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, patches: Array) -> Array:
        return nn.Dense(
            self.embed_dim, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=default_init, bias_init=nn.initializers.zeros,
            name="proj")(patches)


class Attention(nn.Module):
    """Multi-head self-attention with fused qkv projection (timm Attention parity:
    qkv_bias=True per reference run_vit_training.py:138).

    `attention_impl`, when provided, computes the (softmax(QK^T/sqrt(d))V) core —
    e.g. the Pallas flash-attention kernel — and receives (q, k, v) shaped
    (B, N, H, Dh). The default is the dense jnp path.
    """

    num_heads: int
    qkv_bias: bool = True
    att_dropout: float = 0.0
    proj_dropout: float = 0.0
    dtype: Dtype = jnp.bfloat16
    attention_impl: Optional[Callable[[Array, Array, Array], Array]] = None
    # NamedSharding anchor for the (B, N, 3D) qkv projection output. Without
    # it, a batch spanning 3 mesh axes (dp x fsdp x ep — the MoE meshes)
    # makes GSPMD keep the qkv weight fsdp-sharded instead of all-gathering
    # it (ZeRO-3), and the feature-sharded dot output then triggers
    # "involuntary full rematerialization" at this add (a pre-ledger dry run).
    # Feature axis carries "tp" under tensor parallelism (Megatron layout).
    qkv_sharding: Optional[Any] = None
    quant_matmul: Optional[Callable] = None

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True,
                 segment_ids: Optional[Array] = None,
                 rope: Optional[Any] = None) -> Array:
        """`segment_ids` (R, T) and `rope` (cos, sin): the packed model's
        row context — RoPE on q and k, attention within each image only,
        through `attention_impl(q, k, v, segment_ids)`."""
        b, n, d = x.shape
        head_dim = d // self.num_heads

        qkv = _dense(
            self.quant_matmul, True, 3 * d,
            use_bias=self.qkv_bias,
            dtype=self.dtype,
            name="qkv",
        )(x)
        if self.qkv_sharding is not None:
            qkv = jax.lax.with_sharding_constraint(qkv, self.qkv_sharding)
        fused_qkv = getattr(self.attention_impl, "vitax_fused_qkv", None)
        if (fused_qkv is not None and segment_ids is None
                and (self.att_dropout == 0.0 or deterministic)):
            # the kernel reads q, k and v where the projection wrote them
            # and its backward hands back the qkv cotangent whole: no slice
            # or copy on either side (vitax/ops/attention.py)
            return self._project(fused_qkv(qkv, self.num_heads),
                                 deterministic)
        qkv = qkv.reshape(b, n, 3, self.num_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # each (B, N, H, Dh)

        if segment_ids is not None:
            with jax.named_scope("rope2d"):
                q = apply_rope2d(q, *rope)
                k = apply_rope2d(k, *rope)

        use_kernel = (
            self.attention_impl is not None
            and (self.att_dropout == 0.0 or deterministic)
        )
        drop_impl = getattr(self.attention_impl, "vitax_dropout", None)
        if segment_ids is not None:
            out = (masked_attention(q, k, v, segment_ids, self.dtype)
                   if self.attention_impl is None
                   else self.attention_impl(q, k, v, segment_ids))
        elif use_kernel:
            out = self.attention_impl(q, k, v)  # (B, N, H, Dh)
        elif drop_impl is not None:
            # in-kernel attention dropout (vitax/ops/attention.py): the fused
            # path survives --att_dropout > 0. Flax's per-block rng splitting
            # (scan/pipeline) keys the mask: same (seed, step, layer) -> same
            # mask, matching nn.Dropout's determinism contract
            seed = jax.random.bits(self.make_rng("dropout"), (), jnp.uint32)
            out = drop_impl(q, k, v, seed)
        else:
            scale = head_dim ** -0.5
            # accumulate logits in float32 on the MXU for stable softmax
            attn = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
            attn = jax.nn.softmax(attn, axis=-1).astype(self.dtype)
            attn = nn.Dropout(rate=self.att_dropout)(attn, deterministic=deterministic)
            out = jnp.einsum("bhqk,bkhd->bqhd", attn, v)

        return self._project(out.reshape(b, n, d), deterministic)

    def _project(self, out: Array, deterministic: bool) -> Array:
        out = _dense(
            self.quant_matmul, True, out.shape[-1],
            dtype=self.dtype,
            name="proj",
        )(out)
        out = nn.Dropout(rate=self.proj_dropout)(out, deterministic=deterministic)
        return out


class Mlp(nn.Module):
    """timm Mlp parity: Dense(hidden) -> GELU(exact) -> drop -> Dense(d) -> drop."""

    hidden_dim: int
    out_dim: int
    dropout: float = 0.0
    dtype: Dtype = jnp.bfloat16
    quant_matmul: Optional[Callable] = None
    gelu_tanh: bool = False   # MoonViT's gelu_pytorch_tanh; timm's is exact

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True) -> Array:
        x = _dense(
            self.quant_matmul, True, self.hidden_dim,
            dtype=self.dtype,
            name="fc1",
        )(x)
        x = nn.gelu(x, approximate=self.gelu_tanh)
        x = nn.Dropout(rate=self.dropout)(x, deterministic=deterministic)
        x = _dense(
            self.quant_matmul, True, self.out_dim,
            dtype=self.dtype,
            name="fc2",
        )(x)
        x = nn.Dropout(rate=self.dropout)(x, deterministic=deterministic)
        return x


class Block(nn.Module):
    """Pre-norm transformer block (timm Block parity, reference run_vit_training.py:134-141).

    moe_experts > 0 swaps the dense Mlp for the top-1-routed MoE MLP
    (vitax/models/moe.py) in EVERY block — homogeneous blocks keep the
    lax.scan stacking (and therefore pp partitioning) intact."""

    num_heads: int
    mlp_ratio: float = 4.0
    att_dropout: float = 0.0
    mlp_dropout: float = 0.0
    dtype: Dtype = jnp.bfloat16
    attention_impl: Optional[Callable] = None
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_ep_axis: Optional[str] = None   # manual-ep (pipeline body) only
    moe_ep_size: int = 1
    moe_dispatch_sharding: Optional[Any] = None
    token_sharding: Optional[Any] = None
    quant_matmul: Optional[Callable] = None
    mlp_dim: int = 0          # MLP width as a number; 0 = dim * mlp_ratio
    gelu_tanh: bool = False

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True,
                 segment_ids: Optional[Array] = None,
                 rope: Optional[Any] = None) -> Array:
        d = x.shape[-1]
        if self.token_sharding is not None:
            # re-anchor the carry at every block entry: under the ep mesh the
            # MoE combine einsum hands the next block a partially-sharded
            # layout and the partitioner falls back to involuntary full
            # rematerialization at the qkv projection (a pre-ledger dry run)
            x = jax.lax.with_sharding_constraint(x, self.token_sharding)
        qkv_sharding = None
        if self.token_sharding is not None:
            # qkv output anchor derived from the activation sharding: same
            # batch/token layout, feature over "tp" when tensor parallelism
            # is active (Megatron layout; the proj output returns to full)
            ts = self.token_sharding
            tp_ax = "tp" if ts.mesh.shape.get("tp", 1) > 1 else None
            qkv_sharding = NamedSharding(
                ts.mesh, P(ts.spec[0], ts.spec[1], tp_ax))
        # timm Block default norm_layer is nn.LayerNorm with eps=1e-5 when
        # constructed directly (as the reference does).
        y = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, param_dtype=jnp.float32, name="norm1")(x)
        y = Attention(
            num_heads=self.num_heads,
            att_dropout=self.att_dropout,
            proj_dropout=self.mlp_dropout,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            qkv_sharding=qkv_sharding,
            quant_matmul=self.quant_matmul,
            name="attn",
        )(y, deterministic, segment_ids, rope)
        x = x + y
        y = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, param_dtype=jnp.float32, name="norm2")(x)
        if self.moe_experts > 0:
            from vitax.models.moe import MoeMlp
            y = MoeMlp(
                num_experts=self.moe_experts,
                hidden_dim=int(d * self.mlp_ratio),
                out_dim=d,
                capacity_factor=self.moe_capacity_factor,
                top_k=self.moe_top_k,
                ep_axis=self.moe_ep_axis,
                ep_size=self.moe_ep_size,
                dtype=self.dtype,
                dispatch_sharding=self.moe_dispatch_sharding,
                token_sharding=self.token_sharding,
                name="moe",
            )(y, deterministic=deterministic)
        else:
            y = Mlp(
                hidden_dim=self.mlp_dim or int(d * self.mlp_ratio),
                out_dim=d,
                dropout=self.mlp_dropout,
                dtype=self.dtype,
                quant_matmul=self.quant_matmul,
                gelu_tanh=self.gelu_tanh,
                name="mlp",
            )(y, deterministic=deterministic)
        return x + y


def _dots_and_attn_saveable(prim, *_, **__):
    """dots_saveable + fused-attention outputs: the Pallas attention core is a
    custom_vjp custom-call, NOT a dot_general, so under plain dots_saveable its
    forward kernel re-runs inside the rematted backward (profiled at ~10 ms/step
    on ViT-L/14 v5e — 3 attention call sites in the HLO instead of 2). Saving
    the custom_vjp outputs (o and the lse residual) skips that recompute for
    ~400 MB extra residency at the l14 bench shape."""
    # the fused core appears as `pallas_call` in the remat jaxpr (custom_vjp
    # is transparent there); shard_map-wrapped variants as `shard_map`
    return getattr(prim, "name", "") in (
        "dot_general", "pallas_call", "shard_map",
        "custom_vjp_call", "custom_vjp_call_jaxpr")


_REMAT_POLICIES = {
    # Save nothing per block — recompute everything in backward. This is the
    # reference's checkpoint_module semantics (torch activation checkpointing).
    "none_saveable": None,
    # Save MXU outputs (matmul results), recompute elementwise — often the best
    # HBM/FLOP tradeoff on TPU.
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    # dots + fused-attention (custom_vjp) outputs — skips the attention
    # forward-recompute in the rematted backward; fastest where it fits.
    "dots_attn_saveable": _dots_and_attn_saveable,
}


class VisionTransformer(nn.Module):
    """The full ViT (reference FSDPViTModel parity, run_vit_training.py:99-162),
    with blocks run as a scanned (stacked-parameter) stack."""

    image_size: int = 224
    patch_size: int = 14
    embed_dim: int = 5120
    num_heads: int = 32
    num_blocks: int = 32
    mlp_ratio: float = 4.0
    pos_dropout: float = 0.0
    att_dropout: float = 0.0
    mlp_dropout: float = 0.0
    num_classes: int = 1000
    dtype: Dtype = jnp.bfloat16
    scan_blocks: bool = True
    scan_unroll: int = 1
    grad_ckpt: bool = True
    remat_policy: str = "none_saveable"
    attention_impl: Optional[Callable] = None
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_ep_axis: Optional[str] = None   # manual-ep (pipeline body) only
    moe_ep_size: int = 1
    moe_dispatch_sharding: Optional[Any] = None
    # NamedSharding for (B, N, D) activations — anchors GSPMD batch sharding
    # and shards the token axis over "sp" for sequence parallelism
    token_sharding: Optional[Any] = None
    # serving-only: routes every Dense matmul (QKV/proj/MLP/head) through
    # the quantized path (vitax/ops/dequant_matmul.make_quant_matmul); None
    # keeps the exact nn.Dense program (training, full-precision serving)
    quant_matmul: Optional[Callable] = None
    # the native-resolution packed model's shape (Config's fields of the
    # same names); pack_tokens > 0 selects it
    mlp_dim: int = 0
    pack_tokens: int = 0
    pack_images: int = 0
    pos_grid: int = 0
    rope_base: float = 10000.0

    def block_kwargs(self) -> dict:
        """Constructor kwargs for one transformer Block — shared between the
        scan/loop paths below and the pipeline-parallel stage function
        (vitax/parallel/pipeline.py), which applies detached Blocks against
        slices of the same stacked param tree."""
        return dict(
            num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio,
            att_dropout=self.att_dropout,
            mlp_dropout=self.mlp_dropout,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            moe_experts=self.moe_experts,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_top_k=self.moe_top_k,
            moe_ep_axis=self.moe_ep_axis,
            moe_ep_size=self.moe_ep_size,
            moe_dispatch_sharding=self.moe_dispatch_sharding,
            token_sharding=self.token_sharding,
            quant_matmul=self.quant_matmul,
            mlp_dim=self.mlp_dim,
            gelu_tanh=self.pack_tokens > 0,
        )

    def _embed_packed(self, batch):
        """The packed model's embedding: (x (R, T, D), the row context the
        blocks take: segment ids and the RoPE tables)."""
        seg, positions = batch["segment_ids"], batch["positions"]
        x = PatchProj(embed_dim=self.embed_dim, dtype=self.dtype,
                      name="patch_embed")(batch["patches"].astype(self.dtype))
        table = self.param("pos_embed", default_init,
                           (self.pos_grid, self.pos_grid, self.embed_dim),
                           jnp.float32)
        with jax.named_scope("pos_interp"):
            token_hw = jnp.take_along_axis(
                batch["grid_hw"], jnp.maximum(seg - 1, 0)[..., None], axis=1)
            x = x + pos_interp(table, positions, token_hw,
                               self.dtype).astype(self.dtype)
        # a padding token carries nothing (and never meets a real one)
        x = jnp.where((seg > 0)[..., None], x, jnp.zeros((), self.dtype))
        with jax.named_scope("rope2d"):
            rope = rope2d_tables(positions, self.embed_dim // self.num_heads,
                                 self.rope_base)
        return x, (seg, rope)

    @nn.compact
    def __call__(self, images, deterministic: bool = True) -> Array:
        """images: (B, H, W, 3) float -> logits (B, num_classes) float32.
        Packed model: a dict of a packed batch's `patches` (float),
        `segment_ids`, `positions`, `grid_hw` (vitax/data/packing.py) ->
        per-image logits (R, S, num_classes) float32."""
        packed = self.pack_tokens > 0
        ctx = ()  # what every block takes beside the carry: the row context
        if packed:
            x, ctx = self._embed_packed(images)
        else:
            num_patches = (self.image_size // self.patch_size) ** 2

            x = PatchEmbed(
                patch_size=self.patch_size, embed_dim=self.embed_dim, dtype=self.dtype,
                name="patch_embed",
            )(images.astype(self.dtype))

            pos_embed = self.param(
                "pos_embed", default_init, (1, num_patches, self.embed_dim), jnp.float32)
            x = x + pos_embed.astype(self.dtype)
        x = nn.Dropout(rate=self.pos_dropout)(x, deterministic=deterministic)
        if self.token_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.token_sharding)

        block_kwargs = self.block_kwargs()

        def body(block: Block, carry: Array, det: bool, *ctx):
            return block(carry, det, *ctx), None

        if self.grad_ckpt:
            policy = block_remat_policy(self)  # at the end of this module
            # remat composed inside the scan body — per-block recompute, the
            # reference's checkpoint_module-then-FSDP order (run_vit_training.py:145).
            body = nn.remat(body, policy=policy, prevent_cse=False, static_argnums=(2,))

        if self.scan_blocks:
            # One compiled block body via lax.scan; params stacked with a leading
            # (num_blocks,) axis — uniform FSDP sharding and O(1) compile in depth.
            # unroll > 1 runs that many blocks per scan step: the per-block
            # dynamic-update-slice stacking constrains wgrad fusion layouts
            # (profiled 85-100 TF/s vs 164+ unconstrained on v5e), so giving
            # XLA a multi-block window recovers most of the fully-unrolled
            # throughput while keeping the stacked tree and O(L/unroll) compile.
            scan = nn.scan(
                body,
                # intermediates: per-layer sown values (the MoE aux loss)
                # stack along the layer axis like the params
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True, "dropout": True},
                length=self.num_blocks,
                in_axes=(nn.broadcast,) * (1 + len(ctx)),
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
                unroll=min(self.scan_unroll, self.num_blocks),
            )
            x, _ = scan(Block(name="blocks", **block_kwargs), x, deterministic,
                        *ctx)
        else:
            for i in range(self.num_blocks):
                x, _ = body(Block(name=f"blocks_{i}", **block_kwargs), x,
                            deterministic, *ctx)

        if packed:
            # PyTorch's LayerNorm default, as in the blocks (assumed); then
            # one mean per image, and the float32 head on (R, S, D)
            x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype,
                             param_dtype=jnp.float32, name="norm")(x)
            with jax.named_scope("segment_pool"):
                x = segment_pool(x, ctx[0], self.pack_images)
            return _dense(self.quant_matmul, False, self.num_classes,
                          dtype=jnp.float32, name="head")(x)
        x = nn.LayerNorm(epsilon=1e-6, dtype=self.dtype, param_dtype=jnp.float32, name="norm")(x)
        x = jnp.mean(x, axis=1)  # mean-pool over sequence (arXiv:2106.04560)
        if self.token_sharding is not None:
            # anchor the pooled (B, D) activations batch-sharded; the
            # constraint transposes onto the backward cotangent, where the
            # head-dot otherwise leaves D fsdp-sharded under 3-axis-batch
            # meshes and forces an involuntary full rematerialization
            ts = self.token_sharding
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(ts.mesh, P(ts.spec[0], None)))
        # head + loss in float32; the head site never act-quantizes (its f32
        # logits feed softmax directly — act=False in the quantized path)
        logits = _dense(
            self.quant_matmul, False, self.num_classes,
            dtype=jnp.float32,
            name="head",
        )(x)
        return logits


def apply_embed(p, images, *, patch_size: int, embed_dim: int, dtype):
    """Functional PatchEmbed + pos-embed application against an existing
    param tree — the pipeline paths (vitax/parallel/pipeline*.py) run the
    embed outside their shard_map and must match VisionTransformer.__call__
    exactly; keep in sync with the @nn.compact body above."""
    x = PatchEmbed(
        patch_size=patch_size, embed_dim=embed_dim, dtype=dtype,
    ).apply({"params": p["patch_embed"]}, images.astype(dtype))
    return x + p["pos_embed"].astype(dtype)


def apply_tail(p, x, *, num_classes: int, dtype):
    """Functional final-LayerNorm + mean-pool + head against an existing
    param tree (same keep-in-sync contract as apply_embed)."""
    x = nn.LayerNorm(
        epsilon=1e-6, dtype=dtype, param_dtype=jnp.float32,
    ).apply({"params": p["norm"]}, x)
    x = jnp.mean(x, axis=1)
    return nn.Dense(
        num_classes, dtype=jnp.float32, param_dtype=jnp.float32,
    ).apply({"params": p["head"]}, x)


def make_windowed_forward(cfg: Config, model: "VisionTransformer"):
    """Functional scan forward with remat around GROUPS of --remat_window
    blocks instead of per block.

    The wgrad experiment for the profiled l14 ceiling (ROADMAP A2): the
    per-block scan's saved residuals are written into (L, ...) stacked
    buffers by dynamic-update-slice each iteration, and the backward wgrad
    fusions co-writing those buffers run at 85-100 TF/s vs 164-182
    unconstrained. A group of w blocks saves its residuals ONCE per group
    (L/w stacking events) and gives XLA a w-block window to lay out wgrad
    fusions freely — like --scan_unroll, plus group-level checkpoint
    placement. Consumes the SAME stacked (L, ...) param tree (reshaped in
    the compute graph only — init and checkpoints are unchanged).

    v2 (round 5): composes with dropout (per-layer keys split from the step
    rng ride the scan as xs — same (seed, step) -> same masks, matching
    nn.Dropout's determinism contract) and with MoE (per-layer sown aux
    ingredients become scan ys, combined by aux_from_frac_prob exactly like
    the nn.scan path). pp remains excluded (config.validate; the pipeline
    path owns checkpoint placement there)."""
    w = cfg.remat_window
    groups = cfg.num_blocks // w
    block = Block(**model.block_kwargs())  # keeps the activation anchors
    policy = _REMAT_POLICIES[cfg.remat_policy]
    dtype = model.dtype
    moe = cfg.moe_experts > 0
    has_block_dropout = cfg.att_dropout > 0 or cfg.mlp_dropout > 0

    def forward(params, images, det: bool = True, rng=None,
                with_aux: bool = False):
        assert det or rng is not None, "training under dropout needs rng"
        p = params["params"]
        x = apply_embed(p, images, patch_size=cfg.patch_size,
                        embed_dim=cfg.embed_dim, dtype=dtype)
        if not det and cfg.pos_dropout > 0:
            pos_rng, rng = jax.random.split(rng)
            keep = jax.random.bernoulli(pos_rng, 1.0 - cfg.pos_dropout,
                                        x.shape)
            x = jnp.where(keep, x / (1.0 - cfg.pos_dropout),
                          jnp.zeros((), x.dtype))
        if model.token_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, model.token_sharding)
        grouped = jax.tree.map(
            lambda l: l.reshape(groups, w, *l.shape[1:]), p["blocks"])
        use_keys = not det and has_block_dropout
        keys = (jax.random.split(rng, cfg.num_blocks).reshape(groups, w)
                if use_keys else None)

        def apply_group(carry, gparams, gkeys):
            aux = []
            for i in range(w):
                layer = jax.tree.map(lambda g: g[i], gparams)
                rngs = {"dropout": gkeys[i]} if use_keys else None
                if moe and with_aux:
                    carry, cols = block.apply(
                        {"params": layer}, carry, det, rngs=rngs,
                        mutable=["intermediates"])
                    m = cols["intermediates"]["moe"]
                    aux.append((m["moe_frac_tokens"][0],
                                m["moe_mean_prob"][0]))
                else:
                    carry = block.apply({"params": layer}, carry, det,
                                        rngs=rngs)
            if not aux:
                return carry, None
            return carry, (jnp.stack([a[0] for a in aux]),
                           jnp.stack([a[1] for a in aux]))  # (w, E) each

        body = jax.checkpoint(apply_group, policy=policy, prevent_cse=False,
                              static_argnums=())
        xs = (grouped, keys) if use_keys else (grouped,)
        x, aux_stacks = jax.lax.scan(
            lambda c, gx: body(c, *gx, *(() if use_keys else (None,))),
            x, xs)
        logits = apply_tail(p, x, num_classes=cfg.num_classes, dtype=dtype)
        if not with_aux:
            return logits
        fracs, probs = aux_stacks  # (groups, w, E) each
        if with_aux == "raw":
            # grad-accum microbatching needs the UNCOMBINED ingredients: the
            # load-balance product is taken after averaging them across
            # microbatches (vitax/train/step.py)
            return logits, ((fracs,), (probs,))
        from vitax.train.step import aux_from_frac_prob
        return logits, aux_from_frac_prob([fracs], [probs], cfg)

    return forward


def make_overlap_forward(cfg: Config, model: "VisionTransformer", mesh,
                         block_specs):
    """Functional scan forward with an explicit double-buffered gather
    schedule for the ZeRO-3 block params (--gather_overlap).

    The plain scan leaves each block's fsdp all-gather to GSPMD's use-site
    insertion, and XLA's latency-hiding scheduler cannot hoist a gather
    across a lax.scan iteration boundary — so on a pod the gather for block
    k serializes in front of block k's matmuls. Here the scan carry holds a
    PREFETCH SLOT: at iteration k the body consumes the already-gathered
    params for group k (fetched at k-1 via prefetch_gather, which pins the
    collective on the slot feeding the carry) and issues the gather for
    group k+1, overlapping it with group k's compute; group 0's gather is
    issued once before the scan. Groups are --remat_window blocks when the
    window is active, else single blocks.

    Gradients ride a custom_vjp around the group application, for two
    reasons measured on this exact structure:
    - carrying gathered (unsharded) params through a checkpointed scan body
      makes scan-AD stack them as (L, ...) residuals — the full unsharded
      model on every device, the ZeRO-3 memory bet inverted;
    - the ZeRO-3 backward must RE-gather each group's shards (that is what
      reshard_after_forward means), which plain remat only does as a side
      effect of recomputing through the use sites.
    The custom_vjp forward saves only (x, group index, the sharded stacked
    tree); its backward re-gathers the group explicitly, recomputes the
    group forward (none_saveable semantics — Config.validate pins the
    policy), and scatters the group's grads into a zeros-like stacked
    cotangent. The prefetched carry gets a zero cotangent: grads take the
    direct stacked-tree route, so the carry chain carries no gradient and
    AD never materializes a gathered tree it would have to keep.

    The backward's weight gradients (PR 50): the Blocks are applied under
    `ring_dense_sites`, so each block matrix's gradient is computed and
    reduce-scattered over "fsdp" in one ring of chunked products and
    `ppermute` hops (vitax/parallel/sharding.py:ring_weight_grad) and
    arrives here already in the stacked tree's layout: the window write
    below needs no collective, and the whole product + synchronous
    all-reduce-scatter fusion the TPU compiler made of each of them (a
    seventh of the four-chip cell's busy time, which no compiler option
    overlaps) is gone. Biases and norm scales keep the compiler's small
    reduces. The forward is untouched.

    Dropout keys and the MoE aux ingredients thread through exactly like
    make_windowed_forward (same (seed, step) -> same masks; raw frac/prob
    stacks under with_aux == "raw"). pp is excluded (Config.validate)."""
    from vitax.parallel.sharding import prefetch_gather

    w = cfg.remat_window if cfg.remat_window > 1 else 1
    groups = cfg.num_blocks // w
    block = Block(**model.block_kwargs())  # keeps the activation anchors
    ring_sites = ring_dense_sites(mesh, block_specs)
    policy = _REMAT_POLICIES[cfg.remat_policy]
    dtype = model.dtype
    moe = cfg.moe_experts > 0
    has_block_dropout = cfg.att_dropout > 0 or cfg.mlp_dropout > 0

    def forward(params, images, det: bool = True, rng=None,
                with_aux: bool = False):
        assert det or rng is not None, "training under dropout needs rng"
        p = params["params"]
        x = apply_embed(p, images, patch_size=cfg.patch_size,
                        embed_dim=cfg.embed_dim, dtype=dtype)
        if not det and cfg.pos_dropout > 0:
            pos_rng, rng = jax.random.split(rng)
            keep = jax.random.bernoulli(pos_rng, 1.0 - cfg.pos_dropout,
                                        x.shape)
            x = jnp.where(keep, x / (1.0 - cfg.pos_dropout),
                          jnp.zeros((), x.dtype))
        if model.token_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, model.token_sharding)
        stacked = p["blocks"]
        use_keys = not det and has_block_dropout
        collect_aux = moe and bool(with_aux)
        # raw uint32 key data (not typed key arrays): the keys cross a
        # custom_vjp boundary below, and integer leaves there take a None
        # cotangent cleanly
        key_data = (jax.random.key_data(
                        jax.random.split(rng, cfg.num_blocks)
                    ).reshape(groups, w, -1) if use_keys else None)

        def apply_group(carry, gparams, gkey_data):
            aux = []
            # every Dense of a block runs with the ring for its kernel's
            # gradient (ring_dense_sites, at the end of this module)
            with nn.intercept_methods(ring_sites):
                for i in range(w):
                    layer = jax.tree.map(lambda g: g[i], gparams)
                    rngs = ({"dropout":
                             jax.random.wrap_key_data(gkey_data[i])}
                            if use_keys else None)
                    if collect_aux:
                        carry, cols = block.apply(
                            {"params": layer}, carry, det, rngs=rngs,
                            mutable=["intermediates"])
                        m = cols["intermediates"]["moe"]
                        aux.append((m["moe_frac_tokens"][0],
                                    m["moe_mean_prob"][0]))
                    else:
                        carry = block.apply({"params": layer}, carry, det,
                                            rngs=rngs)
            if not aux:
                return carry, ()
            return carry, (jnp.stack([a[0] for a in aux]),
                           jnp.stack([a[1] for a in aux]))  # (w, E) each

        @jax.custom_vjp
        def run_group(x, gathered, g, gkey_data, stacked):
            del g, stacked  # forward consumes the PREFETCHED params only
            return apply_group(x, gathered, gkey_data)

        def run_group_fwd(x, gathered, g, gkey_data, stacked):
            # consumes the PREFETCHED params; `gathered` is deliberately NOT
            # a residual (a gathered-tree residual would stack to the full
            # unsharded model across scan iterations — see the docstring)
            out = apply_group(x, gathered, gkey_data)
            return out, (x, g, gkey_data, stacked)

        def run_group_bwd(res, ct):
            x, g, gkey_data, stacked = res
            with jax.named_scope("blocks_transpose_regather"):
                # ZeRO-3 backward semantics: re-gather the group's shards
                regathered = prefetch_gather(stacked, g * w, w, mesh,
                                             block_specs)
            # the recompute must run under a remat boundary: jax.checkpoint's
            # transpose wraps the recomputed values in optimization barriers,
            # which keeps XLA from fusing the recompute into its consumers and
            # re-rounding bf16 intermediates differently than the fwd program
            # did — without it the grads drift one bf16 ulp off the nn.scan
            # program's (measured; the fwd itself needs no barrier)
            regroup = jax.checkpoint(
                lambda x_, gp_: apply_group(x_, gp_, gkey_data),
                policy=policy, prevent_cse=False)
            _, vjp = jax.vjp(regroup, x, regathered)
            dx, dgp = vjp(ct)
            d_stacked = jax.tree.map(
                lambda full, d: jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros_like(full), d.astype(full.dtype), g * w,
                    axis=0),
                stacked, dgp)
            # zero cotangent for the prefetched carry: the gradient takes
            # the direct stacked-tree route, cutting the carry grad chain
            return (dx, jax.tree.map(jnp.zeros_like, regathered), None,
                    None, d_stacked)

        run_group.defvjp(run_group_fwd, run_group_bwd)

        def scan_body(carry, xs):
            x, gathered = carry
            g = xs[0]
            gkeys = xs[1] if use_keys else None
            with jax.named_scope("blocks_overlap"):
                x, aux = run_group(x, gathered, g, gkeys, stacked)
            # issue group g+1's gather now, so it overlaps group g+1's wait
            # with THIS group's compute; the final iteration re-fetches the
            # last group (in-bounds, result unused)
            nxt = jnp.minimum(g + 1, groups - 1)
            with jax.named_scope("blocks_prefetch"):
                gathered = prefetch_gather(stacked, nxt * w, w, mesh,
                                           block_specs)
            return (x, gathered), aux

        with jax.named_scope("prefetch_lead"):
            gathered0 = prefetch_gather(stacked, 0, w, mesh, block_specs)
        idx = jnp.arange(groups, dtype=jnp.int32)
        xs = (idx, key_data) if use_keys else (idx,)
        (x, _), aux_stacks = jax.lax.scan(
            scan_body, (x, gathered0), xs,
            unroll=min(cfg.scan_unroll, groups))
        logits = apply_tail(p, x, num_classes=cfg.num_classes, dtype=dtype)
        if not with_aux:
            return logits
        fracs, probs = aux_stacks  # (groups, w, E) each
        if with_aux == "raw":
            return logits, ((fracs,), (probs,))
        from vitax.train.step import aux_from_frac_prob
        return logits, aux_from_frac_prob([fracs], [probs], cfg)

    return forward


def build_model(cfg: Config, attention_impl: Optional[Callable] = None,
                token_sharding=None, moe_dispatch_sharding=None,
                quant_matmul: Optional[Callable] = None) -> VisionTransformer:
    """Construct the model from config (reference build_fsdp_vit_model parity,
    run_vit_training.py:165-200 — minus the wrapping, which in vitax is a sharding
    declaration applied at jit boundaries, not a module transform).

    `quant_matmul` (serving only) swaps every Dense site for QuantDense —
    see vitax/ops/dequant_matmul.make_quant_matmul."""
    return VisionTransformer(
        image_size=cfg.image_size,
        patch_size=cfg.patch_size,
        embed_dim=cfg.embed_dim,
        num_heads=cfg.num_heads,
        num_blocks=cfg.num_blocks,
        mlp_ratio=cfg.mlp_ratio,
        pos_dropout=cfg.pos_dropout,
        att_dropout=cfg.att_dropout,
        mlp_dropout=cfg.mlp_dropout,
        num_classes=cfg.num_classes,
        dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
        scan_blocks=cfg.scan_blocks,
        scan_unroll=cfg.scan_unroll,
        grad_ckpt=cfg.grad_ckpt,
        remat_policy=cfg.remat_policy,
        attention_impl=attention_impl,
        moe_experts=cfg.moe_experts,
        moe_capacity_factor=cfg.moe_capacity_factor,
        moe_top_k=cfg.moe_top_k,
        moe_dispatch_sharding=moe_dispatch_sharding,
        token_sharding=token_sharding,
        quant_matmul=quant_matmul,
        mlp_dim=cfg.mlp_dim,
        pack_tokens=cfg.pack_tokens,
        pack_images=cfg.pack_images,
        pos_grid=cfg.pos_grid,
        rope_base=cfg.rope_base,
    )


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def expected_param_count(cfg: Config) -> int:
    """Closed-form parameter count, matching the reference's 10,077,917,160 at
    default flags (SURVEY.md section 6)."""
    d = cfg.embed_dim
    h = cfg.mlp_hidden_dim
    n = cfg.num_patches
    per_block = (
        d * 3 * d + 3 * d      # qkv
        + d * d + d            # proj
        + d * h + h            # fc1
        + h * d + d            # fc2
        + 2 * (2 * d)          # two LayerNorms
    )
    patch = 3 * cfg.patch_size * cfg.patch_size * d + d
    pos = (cfg.pos_grid ** 2 if cfg.packed else n) * d
    final_ln = 2 * d
    head = d * cfg.num_classes + cfg.num_classes
    return per_block * cfg.num_blocks + patch + pos + final_ln + head


# --- what per-block remat keeps of the attention kernel ---------------------
# (Below everything a kernel's call stack passes through: the Mosaic payloads
# in a lowered step embed those line numbers, and the cells this rule does
# not reach lower to the text they had before it.)

"""From which attention span `none_saveable` keeps the forward kernel's
outputs. Running the kernel again costs 4 * N_kv * Dh FLOPs a query and head;
keeping o costs one more write and one read of 2 * Dh bytes: N_kv FLOPs a
byte kept. A v5e's ridge is 197 TFLOP/s / 819 GB/s = 240 FLOP/B, so at 256
tokens the two cost the same (with everything kept the dense cells read
-0.26% and +0.07%: ledger, PR 27), and from 4x the ridge on the re-run is the
dearer one, on kernels that run well under their roofline at that."""
ATTN_KEEP_MIN_SPAN = 1024


def _attention_kernel_saveable(prim, *_, **__):
    """`none_saveable` + the attention forward kernel's own outputs (o and
    lse, in the layout the backward kernels read): the block keeps them beside
    its input, and the rematted backward runs no second forward kernel. The
    qkv matmul, RoPE, the relayout of q, k, v and everything else are
    recomputed as before. The kernels are a block's only `pallas_call`s; a
    shard_map around one applies the policy to its body."""
    return getattr(prim, "name", "") == "pallas_call"


def attention_span(model: VisionTransformer) -> int:
    """The keys one query can meet, as the model's shape gives them: a packed
    row, or the image's patches."""
    return model.pack_tokens or (model.image_size // model.patch_size) ** 2


def keeps_attention_residuals(model, span: Optional[int] = None) -> bool:
    """Whether `VisionTransformer.__call__`'s per-block remat keeps the
    attention kernel's o and lse: only where `none_saveable` would run the
    kernel twice and the span makes that the dearer choice. Not under
    sequence parallelism: ring attention runs sp block products a layer and
    would keep every one of them. `span`: the keys a query of THESE layers
    can meet, where a model's layers differ (vitax/models/decoder.py)."""
    ts = model.token_sharding
    span = attention_span(model) if span is None else span
    return (model.grad_ckpt and model.remat_policy == "none_saveable"
            and model.attention_impl is not None
            and (ts is None or ts.spec[1] is None)
            and span >= ATTN_KEEP_MIN_SPAN)


def block_remat_policy(model: VisionTransformer):
    """The policy of the per-block remat in `VisionTransformer.__call__`. The
    group forwards above and the pipeline body recompute a group inside their
    own backward and take `_REMAT_POLICIES` as it is."""
    if keeps_attention_residuals(model):
        return _attention_kernel_saveable
    return _REMAT_POLICIES[model.remat_policy]  # KeyError on unknown names


# --- the ring under the overlap schedule's matmul sites ---------------------
# (At the end for the reason above: no line a kernel's call stack passes
# through moves.)


def ring_dense_sites(mesh, block_specs):
    """The Flax method interceptor `make_overlap_forward` applies its Blocks
    under: every `nn.Dense` of a block (qkv, proj, fc1, fc2; an MoE block's
    router) runs as itself with `ring_dot_general` for its `dot_general`, so
    its kernel's gradient is computed and reduce-scattered in one ring
    (vitax/parallel/sharding.py:ring_weight_grad). The spec is the stacked
    tree's at the site's own path, less the layer dimension. Parameter paths
    are the site's own: the stand-in binds to the site's scope. Nothing
    outside that forward sees it: the plain scan, one-chip programs, serving
    and QuantDense build the Dense they always built."""
    from vitax.parallel.sharding import ring_dot_general

    def intercept(next_fun, args, kwargs, context):
        site = context.module
        if (type(site) is not nn.Dense or context.method_name != "__call__"
                or site.dot_general is not None):
            return next_fun(*args, **kwargs)
        spec = block_specs
        for name in site.path:
            spec = spec[name]
        ring = ring_dot_general(mesh, P(*spec["kernel"][1:]))
        return site.clone(parent=site.scope, dot_general=ring)(*args, **kwargs)

    return intercept
