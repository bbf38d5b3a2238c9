"""Operations and bytes a kernel's algorithm needs, from shapes, and the
share of the chip's roofline a measured kernel time comes to.

Roofline share = least time the chip could take / measured kernel time,
where the least time is the larger of operations / peak FLOP/s and bytes /
peak bytes/s. `bound` says which of the two it was. Recomputed work is not
needed work: with a remat policy that runs the forward kernel twice, the
second run is in the measured time and not in the need.
"""

from __future__ import annotations

from typing import Tuple

ACT_BYTES = 2   # bf16 activations


def attention_need(batch: int, heads: int, tokens: int, head_dim: int,
                   blocks: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of exact softmax attention, forward and backward,
    over `blocks` layers for `batch` images (per chip: pass the chip's
    share of the batch).

    Forward: S = QK^T and O = PV, 2 matmuls of 2*N*N*Dh each per head.
    Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, 4 matmuls.
    (A flash backward recomputes S; that is the kernel's choice, not the
    algorithm's need.) Bytes: forward reads Q, K, V and writes O; backward
    reads Q, K, V, O, dO and writes dQ, dK, dV: 12 tensors of B*N*H*Dh.
    """
    per_matmul = 2.0 * batch * heads * tokens * tokens * head_dim
    flops = (2 + 4) * per_matmul * blocks
    nbytes = 12.0 * batch * tokens * heads * head_dim * ACT_BYTES * blocks
    return flops, nbytes


def fused_optimizer_need(params_on_chip: float) -> Tuple[float, float]:
    """Clip + AdamW over f32 state: reads gradient, parameter, first and
    second moment (16 B), writes parameter and both moments (12 B): 28 B a
    parameter, and about 12 FLOPs a parameter (never the bound)."""
    return 12.0 * params_on_chip, 28.0 * params_on_chip


def least_seconds(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    by_compute = flops / peaks["bf16_flops"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> Tuple[float, str]:
    least, bound = least_seconds(flops, nbytes, peaks)
    return 100.0 * least / seconds, bound
