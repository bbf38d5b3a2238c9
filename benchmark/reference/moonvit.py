"""Plain reference MoonViT: float32 `jax.numpy`, one image at a time.

Written from the equations of the published model (Kimi-VL's vision tower,
`moonshotai/Kimi-VL-A3B-Instruct` `modeling_kimi_vl.py`: `MoonVisionPatchEmbed`,
`Learnable2DInterpPosEmb`, `Rope2DPosEmb`, `MoonVitEncoderLayer`), not from
this repository's program:

  patches (14 x 14 x 3 pixels, ImageNet-normalised) -> D: a linear map with
      bias (the 14 x 14 stride-14 convolution)
  + the learned (64, 64, D) position table resized to the image's grid
      (h, w) by `F.interpolate(mode="bicubic")`: align_corners False, cubic
      coefficient A = -0.75, source coordinate (i + 0.5) * 64 / h - 0.5, four
      taps clamped at the border, no antialiasing; (64, 64) is the table.
      Here: two explicit matrices, W_h @ table @ W_w^T
  L pre-LayerNorm blocks:  x += proj(MHA(LN(x)));  x += fc2(gelu_tanh(fc1(LN(x))))
      LN eps 1e-5; qkv one linear map laid out (3, heads, head_dim);
      2D RoPE on q and k, by complex multiplication: head_dim / 4 frequencies
      theta_i = base^(-4 i / head_dim); the complex pair 2i turns by
      column * theta_i, the pair 2i + 1 by row * theta_i;
      softmax(q k^T / sqrt(head_dim)) v over the image's own tokens
  LN (eps 1e-5) -> mean over the image's tokens -> linear head  (assumed
      head: the tower's 2 x 2 merger and projector belong to the language
      model's side)
  loss: softmax cross-entropy, mean over the images

No kernels, no scan, no packing, no segment ids, no mixed precision: an image
is a (h * w, 588) array and its grid, and each is run alone. Every matmul
runs under precision "highest". It reads the program's own seeded parameter
tree (`blocks` stacked on a leading depth axis) so the two can be compared
on the same weights.

Departure that changes no value: the gradient pass wraps each layer in
`jax.checkpoint`, or a 3,100-token image would hold 27 layers' (16, n, n)
attention matrices (16 GB) at once.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.vit import (IMAGENET_MEAN, IMAGENET_STD, PRECISION,
                                     block_params, layer_norm, linear,
                                     split_params)

CUBIC_A = -0.75
LN_EPS = 1e-5

Image = Tuple[jax.Array, Tuple[int, int]]   # (patches uint8 (h*w, 588), (h, w))


def normalize_patches(patches_u8: jax.Array) -> jax.Array:
    """uint8 patch pixels, flattened (row, column, channel), -> normalised
    float32 (ToTensor + Normalize per channel)."""
    n = patches_u8.shape[0]
    x = patches_u8.astype(jnp.float32).reshape(n, -1, 3) / 255.0
    x = (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) \
        / jnp.asarray(IMAGENET_STD, jnp.float32)
    return x.reshape(n, -1)


def cubic_kernel(x: np.ndarray) -> np.ndarray:
    """Keys' cubic convolution kernel with coefficient A, at distance x."""
    x = np.abs(x)
    near = (CUBIC_A + 2) * x ** 3 - (CUBIC_A + 3) * x ** 2 + 1
    far = CUBIC_A * (x ** 3 - 5 * x ** 2 + 8 * x - 4)
    return np.where(x <= 1, near, np.where(x < 2, far, 0.0))


def resize_matrix(out_size: int, in_size: int) -> np.ndarray:
    """(out, in) float32: row o holds the four bicubic weights of output o
    (PyTorch, align_corners False), a weight whose tap falls outside the
    axis added onto the border sample."""
    w = np.zeros((out_size, in_size), np.float64)
    scale = np.float32(in_size) / np.float32(out_size)
    for o in range(out_size):
        src = np.float32(scale * np.float32(o + 0.5) - np.float32(0.5))
        first = int(np.floor(src)) - 1
        for tap in range(first, first + 4):
            w[o, min(max(tap, 0), in_size - 1)] += cubic_kernel(
                np.float64(src) - tap)
    return w.astype(np.float32)


def position_embedding(table: jax.Array, h: int, w: int) -> jax.Array:
    """The (G, G, D) table resized to (h, w), flattened in raster order."""
    g = table.shape[0]
    if (h, w) == (g, g):
        return table.astype(jnp.float32).reshape(h * w, -1)
    rows = jnp.asarray(resize_matrix(h, g))
    cols = jnp.asarray(resize_matrix(w, g))
    out = jnp.einsum("hg,gkd,wk->hwd", rows, table.astype(jnp.float32), cols,
                     precision=PRECISION)
    return out.reshape(h * w, -1)


def rope_cis(h: int, w: int, head_dim: int, base: float) -> jax.Array:
    """(h*w, head_dim/2) complex64: e^{i column theta_0}, e^{i row theta_0},
    e^{i column theta_1}, ... for each token in raster order."""
    theta = 1.0 / base ** (np.arange(0, head_dim, 4)[: head_dim // 4]
                           .astype(np.float32) / head_dim)
    col = np.tile(np.arange(w, dtype=np.float32), h)
    row = np.repeat(np.arange(h, dtype=np.float32), w)
    angles = np.stack([np.outer(col, theta), np.outer(row, theta)], axis=-1)
    angles = jnp.asarray(angles.reshape(h * w, head_dim // 2))
    return jax.lax.complex(jnp.cos(angles), jnp.sin(angles))


def rotate(x: jax.Array, cis: jax.Array) -> jax.Array:
    """(n, heads, head_dim) real, adjacent pairs as complex numbers, times
    `cis` (n, head_dim/2)."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * cis[:, None, :]
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def block(x, cis, p, num_heads: int):
    n, d = x.shape
    dh = d // num_heads
    y = layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], LN_EPS)
    qkv = linear(y, p["attn"]["qkv"]).reshape(n, 3, num_heads, dh)
    q, k, v = rotate(qkv[:, 0], cis), rotate(qkv[:, 1], cis), qkv[:, 2]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=PRECISION) * dh ** -0.5
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=PRECISION)
    x = x + linear(o.reshape(n, d), p["attn"]["proj"])
    y = layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], LN_EPS)
    y = jax.nn.gelu(linear(y, p["mlp"]["fc1"]), approximate=True)
    return x + linear(y, p["mlp"]["fc2"])


def image_logits(rest: Dict, layers: Sequence, image: Image, *,
                 num_heads: int, rope_base: float, checkpoint: bool = False):
    layer_fn = functools.partial(block, num_heads=num_heads)
    if checkpoint:
        layer_fn = jax.checkpoint(layer_fn)
    patches, (h, w) = image
    x = linear(normalize_patches(patches), rest["patch_embed"]["proj"])
    x = x + position_embedding(rest["pos_embed"], h, w)
    cis = rope_cis(h, w, x.shape[-1] // num_heads, rope_base)
    for p in layers:
        x = layer_fn(x, cis, p)
    x = layer_norm(x, rest["norm"]["scale"], rest["norm"]["bias"], LN_EPS)
    return linear(jnp.mean(x, axis=0), rest["head"])


def logits_from(rest, layers, images: Sequence[Image], **shape) -> jax.Array:
    layers = list(layers)
    return jnp.stack([image_logits(rest, layers, im, **shape)
                      for im in images])


def loss_from(rest, layers, images, labels, **shape) -> jax.Array:
    logp = jax.nn.log_softmax(logits_from(rest, layers, images, **shape),
                              axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked)


def logits(variables: Dict, images: Sequence[Image], *, num_heads: int,
           num_blocks: int, rope_base: float) -> jax.Array:
    """Images, each alone -> (images, classes) float32 logits."""
    params = variables["params"]
    return logits_from(params, block_params(params, num_blocks), images,
                       num_heads=num_heads, rope_base=rope_base)


def loss(variables: Dict, images, labels, *, num_heads: int, num_blocks: int,
         rope_base: float) -> jax.Array:
    params = variables["params"]
    return loss_from(params, block_params(params, num_blocks), images, labels,
                     num_heads=num_heads, rope_base=rope_base)


def value_and_grads(variables: Dict, images, labels, *, num_heads: int,
                    num_blocks: int, rope_base: float):
    """(loss, (gradient outside the blocks, a list of per-layer gradients)),
    each layer under `jax.checkpoint` (see the module docstring)."""
    rest, layers = split_params(variables, num_blocks)
    return jax.value_and_grad(loss_from, argnums=(0, 1))(
        rest, layers, images, labels, num_heads=num_heads,
        rope_base=rope_base, checkpoint=True)


def loss_and_grad_norm(variables: Dict, images, labels, **shape):
    """(loss, global L2 norm of its gradient over every parameter)."""
    value, grads = value_and_grads(variables, images, labels, **shape)
    squares = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(squares)


def unpack(batch: Dict[str, np.ndarray]) -> Tuple[List[Image], List[int]]:
    """The images of a packed batch (vitax/data/packing.py arrays, on the
    host), each cut back out of its row, and their labels."""
    images, labels = [], []
    for r in range(batch["segment_ids"].shape[0]):
        at = 0
        for s, (h, w) in enumerate(np.asarray(batch["grid_hw"][r]).tolist()):
            if h * w == 0:
                continue
            images.append((jnp.asarray(batch["patches"][r, at:at + h * w]),
                           (h, w)))
            labels.append(int(batch["label"][r, s]))
            at += h * w
    return images, labels


def shape_of(config: dict) -> dict:
    return {"num_heads": config["num_heads"],
            "num_blocks": config["num_blocks"],
            "rope_base": float(config["native_res"]["rope_base"])}
