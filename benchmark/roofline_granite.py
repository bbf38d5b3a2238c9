"""Operations and bytes the state-space scan of a Granite 4.0-H mamba layer
needs, from shapes and what a step's batch held: the same whatever
implements it (plain `jax.numpy` today, a fused kernel later).

A layer, forward (benchmark/flops_granite.py: scan_flops_per_layer): C.B and
the masked product over x for every pair of a query and a key not after it
in one chunk and one document, 2 * (G * N + H * P) a pair; the state a chunk
leaves and the state a token reads, 4 * H * P * N a token. The backward is
twice the forward (each product has two operands to differentiate). A
longer chunk has more pairs and fewer states: the need is that of the
configuration's `mamba_chunk_size`, which the step's `ssd_pairs` counts on.

Bytes, once each way: forward reads x (tokens x H x P), B, C (tokens x G x
N), delta (tokens x H, float32) and writes y; backward reads them again with
dy and writes dx, dB, dC, d delta: 3 x (x, B, C, delta) + 2 x y. And the
chunk states (live chunks x H x P x N, float32): written and read forward,
their gradients written and read backward. The (chunk, head, `chunk`,
`chunk`) products themselves never need to reach HBM and are not counted:
an implementation that writes them pays for it in the time.
"""

from __future__ import annotations

from typing import Tuple

from benchmark import flops_granite
from benchmark.roofline import ACT_BYTES

STATE_BYTES = 4     # float32 states and delta


def ssd_need(config: dict, tokens: float, ssd_pairs: float,
             live_chunks: float, layers: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of `layers` mamba layers' scans, forward and
    backward."""
    inner, gn, heads, state = flops_granite.mixer_sizes(config)
    flops = 3.0 * flops_granite.scan_flops_per_layer(config, tokens, ssd_pairs)
    io = tokens * ((3 * (inner + 2 * gn) + 2 * inner) * ACT_BYTES
                   + 3 * heads * STATE_BYTES)
    states = 4.0 * live_chunks * inner * state * STATE_BYTES
    return flops * layers, (io + states) * layers
