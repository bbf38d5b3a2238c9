"""Plain reference Vision Transformer: float32 `jax.numpy`, nothing else.

Written from the published description of the model this repository trains
(Dosovitskiy et al. 2021, in the form of the source repository
`ronghanghu/vit_10b_fsdp_example`, which builds on timm's blocks):

  patchify (p x p x 3 pixels -> D, a linear map with bias)
  + learned position embedding          (no class token)
  L pre-LayerNorm blocks:  x += proj(MHA(LN(x)));  x += fc2(gelu(fc1(LN(x))))
      LN eps 1e-5, qkv one linear map laid out (3, heads, head_dim),
      softmax(q k^T / sqrt(head_dim)) v, exact (erf) GELU
  LN (eps 1e-6) -> mean over tokens (Zhai et al. 2021) -> linear head
  loss: softmax cross-entropy with integer labels, mean over the batch

No kernels, no scan, no remat, no sharding rules, no mixed precision: every
matmul runs under `jax.default_matmul_precision("highest")`, because on a
TPU a float32 matmul otherwise runs in bf16 passes. It consumes the
program's own seeded parameter tree (stacked `blocks` with a leading depth
axis, or `blocks_<i>`), so that the two can be compared on the same weights.

Departure from the issue's sketch: the issue lists a class token; neither the
source repository nor this program has one (mean pooling), so none is here.

The block is one jitted function called from a Python loop: it compiles once
per shape, not once per layer, which keeps the check's set-up short.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, Iterator

import jax
import jax.numpy as jnp

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
PRECISION = "highest"

PyTree = Any


def normalize(images_u8: jax.Array) -> jax.Array:
    """uint8 pixels -> ImageNet-normalised float32 (ToTensor + Normalize)."""
    x = images_u8.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) \
        / jnp.asarray(IMAGENET_STD, jnp.float32)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def linear(x, p):
    return jnp.matmul(x, p["kernel"].astype(jnp.float32),
                      precision=PRECISION) + p["bias"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def block(x, p, num_heads: int):
    b, n, d = x.shape
    dh = d // num_heads
    y = layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], 1e-5)
    qkv = linear(y, p["attn"]["qkv"]).reshape(b, n, 3, num_heads, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=PRECISION) * dh ** -0.5
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=PRECISION)
    x = x + linear(o.reshape(b, n, d), p["attn"]["proj"])
    y = layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], 1e-5)
    y = jax.nn.gelu(linear(y, p["mlp"]["fc1"]), approximate=False)
    return x + linear(y, p["mlp"]["fc2"])


def block_params(params: Dict, num_blocks: int) -> Iterator[PyTree]:
    """The per-layer parameter trees, one at a time, from either layout the
    program uses. A slice of the stacked layout is a copy: a forward pass
    that walks this holds one layer's copy, not the model's."""
    for i in range(num_blocks):
        if "blocks" in params:
            yield jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        else:
            yield params[f"blocks_{i}"]


def embed(params: Dict, images: jax.Array, patch_size: int) -> jax.Array:
    b, h, w, c = images.shape
    p = patch_size
    x = images.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, (h // p) * (w // p), p * p * c)
    proj = params["patch_embed"]["proj"]
    kernel = proj["kernel"].astype(jnp.float32).reshape(p * p * c, -1)
    x = jnp.matmul(x, kernel, precision=PRECISION) + proj["bias"]
    return x + params["pos_embed"].astype(jnp.float32)


def split_params(variables: Dict, num_blocks: int):
    """(everything outside the blocks, the per-layer trees). Gradients are
    taken with respect to these, so that no stacked copy is scattered back
    together: at 10B widths that would not fit beside the train state."""
    params = variables["params"]
    rest = {k: v for k, v in params.items() if not k.startswith("blocks")}
    return rest, list(block_params(params, num_blocks))


def logits_from(rest: Dict, layers: Iterable[PyTree], images_u8: jax.Array, *,
                patch_size: int, num_heads: int) -> jax.Array:
    x = embed(rest, normalize(images_u8), patch_size)
    for p in layers:
        x = block(x, p, num_heads=num_heads)
    x = layer_norm(x, rest["norm"]["scale"], rest["norm"]["bias"], 1e-6)
    return linear(jnp.mean(x, axis=1), rest["head"])


def loss_from(rest, layers, images_u8, labels, **shape) -> jax.Array:
    logp = jax.nn.log_softmax(logits_from(rest, layers, images_u8, **shape),
                              axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(picked)


def logits(variables: Dict, images_u8: jax.Array, *, patch_size: int,
           num_heads: int, num_blocks: int) -> jax.Array:
    """(B, H, W, 3) uint8 -> (B, classes) float32 logits."""
    params = variables["params"]
    return logits_from(params, block_params(params, num_blocks), images_u8,
                       patch_size=patch_size, num_heads=num_heads)


def log_probs(variables: Dict, images_u8, **shape) -> jax.Array:
    return jax.nn.log_softmax(logits(variables, images_u8, **shape), axis=-1)


def loss(variables: Dict, images_u8, labels, *, patch_size: int,
         num_heads: int, num_blocks: int) -> jax.Array:
    params = variables["params"]
    return loss_from(params, block_params(params, num_blocks), images_u8,
                     labels, patch_size=patch_size, num_heads=num_heads)


def loss_and_grad_norm(variables: Dict, images_u8, labels, *,
                       patch_size: int, num_heads: int, num_blocks: int):
    """(loss, global L2 norm of its gradient over every parameter)."""
    rest, layers = split_params(variables, num_blocks)
    value, grads = jax.value_and_grad(loss_from, argnums=(0, 1))(
        rest, layers, images_u8, labels, patch_size=patch_size,
        num_heads=num_heads)
    squares = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(squares)


def shape_of(config: dict) -> dict:
    return {"patch_size": config["patch_size"],
            "num_heads": config["num_heads"],
            "num_blocks": config["num_blocks"]}
