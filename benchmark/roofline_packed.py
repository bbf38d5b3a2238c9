"""Operations and bytes that attention within each image of packed rows
needs, from what the rows hold (the arithmetic of `benchmark/roofline.py:
attention_need` with N^2 replaced by the sum over images of n_i^2).

Forward: S = QK^T and O = PV; backward: dV, dP, dQ, dK: (2 + 4) matmuls of
2 * heads * n_i^2 * head_dim per image and layer. Bytes: forward reads Q, K,
V and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV: 12
tensors of valid tokens x heads x head_dim, bf16, per layer. A kernel that
does not skip the block pairs between images does T^2 of work for the same
need, and reads that much lower; padding, the masked part of a boundary
block and the forward that remat runs again are in the time, not the need.
"""

from __future__ import annotations

from typing import Tuple

from benchmark.roofline import ACT_BYTES


def packed_attention_need(token_pairs: float, tokens: float, heads: int,
                          head_dim: int, blocks: int) -> Tuple[float, float]:
    flops = (2 + 4) * 2.0 * heads * token_pairs * head_dim * blocks
    nbytes = 12.0 * tokens * heads * head_dim * ACT_BYTES * blocks
    return flops, nbytes
