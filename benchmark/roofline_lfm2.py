"""Operations and bytes that the LFM2-MoE decoder's gated short convolution
needs, from shapes and what a step's batch held: the same whatever
implements it (plain `jax.numpy` today, a fused kernel later).

The mixer of a conv layer between its two projections, D channels a token,
L taps: c = C * conv(B * x). Forward it reads the projection's three streams
(B, C, x: 3 D a token) and writes one (D a token), in bf16. Backward it reads
the three again with the cotangent of what it wrote and writes the three
cotangents: 7 D a token (the convolved product is made again from B and x,
never read back). Together 11 D x 2 B a token: the same whatever runs it;
a form that writes B * x, the convolution or a float32 copy to HBM pays for
it in the time. The taps (L x D float32, read forward and backward, their
gradient written) are a few KB a layer and are counted. FLOPs, a token and
channel: forward the gate (1), the taps (2 L - 1) and the gate (1); backward
the two gates' cotangents (2 each), the transposed taps (2 L - 1) and the
taps' own gradient (2 L): 6 L + 5 together; at 3 taps 23 FLOPs against 22
bytes, so the memory bounds it on every chip whose peak FLOP/s is more than
its peak bytes/s.

The attention layer's kernels are `roofline_laguna.attention_need`'s, at
`num_attention_heads` query heads over `num_key_value_heads` key/value heads
of hidden_size / heads, from the step's own `causal_pairs`.
"""

from __future__ import annotations

from typing import Tuple

from benchmark.roofline import ACT_BYTES

TAP_BYTES = 4       # float32 taps and their gradient


def gated_conv_need(tokens: float, channels: int, taps: int,
                    layers: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of `layers` conv layers' mixers between their
    projections, forward and backward, for `tokens` valid tokens."""
    flops = (6.0 * taps + 5.0) * tokens * channels
    nbytes = (4 + 7) * tokens * channels * ACT_BYTES \
        + 3.0 * taps * channels * TAP_BYTES
    return flops * layers, nbytes * layers
