"""A gated short convolution over packed documents: LFM2's `conv` mixer.

The mixer of a `conv` layer of the token decoder (vitax/models/decoder.py),
in place of attention: neither attention nor a recurrence, it has no heads,
no state and no activation. With `u` the normed input of a token and three
parts `B`, `C`, `x` of `embed_dim` channels each, split in this order:

    (B, C, x) = W_in u
    v_t = B_t * x_t
    c_t = sum_{j < taps} w_j v_{t-j}         depthwise, causal, no bias
    out = W_out (C_t * c_t)

A token's convolution sees no token of another document
(vitax/models/ssm.py: `causal_conv`, the recurrent mixers' rules); padding
(`segment_ids` 0) gives zeros and receives nothing.

One form on every platform, plain `jax.numpy`: the two gates and the taps
are float32 between the bf16 projection and the one rounding before `W_out`,
under the scopes `gconv_in` (B * x), `gconv` (the taps) and `gconv_out`
(C * c, the padding's select, the rounding). The kernel pair of
vitax/ops/conv.py has a silu behind its taps and no gate on either side, so
`choose_kernels` gives this kind no kernel (vitax/programs/kernels.py).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from vitax.models.ssm import Leaf, causal_conv, conv_init
from vitax.models.vit import Array, Dtype, default_init


def gated_conv(projected: Array, segment_ids: Array, taps: Array,
               dtype: Dtype) -> Array:
    """C * conv(B * x) of the projection (R, T, 3 * D) = [B; C; x], `taps`
    (taps, D) float32 -> (R, T, D) in `dtype`, zero at padding."""
    f32 = jnp.float32
    b, c, x = jnp.split(projected, 3, axis=-1)
    with jax.named_scope("gconv_in"):
        v = b.astype(f32) * x.astype(f32)
    with jax.named_scope("gconv"):
        y = causal_conv(v, segment_ids, taps, 0.0)
    with jax.named_scope("gconv_out"):
        y = c.astype(f32) * y
        return jnp.where((segment_ids > 0)[..., None], y, 0.0).astype(dtype)


def gated_conv_param_count(embed_dim: int, taps: int) -> int:
    """W_in (D x 3D), the taps, W_out (D x D)."""
    return 4 * embed_dim * embed_dim + taps * embed_dim


class GatedConvMixer(nn.Module):
    taps: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u: Array, segment_ids: Array) -> Array:
        d = u.shape[-1]

        def linear(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, kernel_init=default_init,
                            name=name)

        taps = Leaf((self.taps, d), conv_init, "kernel", name="conv")()
        y = gated_conv(linear(3 * d, "in_proj")(u), segment_ids, taps,
                       self.dtype)
        return linear(d, "out_proj")(y)
