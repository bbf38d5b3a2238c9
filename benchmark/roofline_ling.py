"""Operations and bytes that the Ling-3.0-flash decoder's two mixers need,
from shapes and what a step's batch held: the same whatever implements them
(the delta rule is plain `jax.numpy` today, a fused kernel later).

The delta rule of a kda layer, forward (benchmark/flops_ling.py:
delta_rule_flops_per_layer), H heads of K = V = head_dim: 6K + 4V a pair of
a query and a key not after it in one chunk and one document (k.k and q.k
scores, the triangular solve of the corrected keys and values, the
intra-chunk output), 6 K V a token (what the state corrects, what the query
reads, what the chunk leaves). The backward is twice the forward. A longer
chunk has more pairs and fewer states: the need is that of the fixed grid
of `flops_ling.KDA_GRID` = 64 tokens, which the step's `kda_pairs` and
`kda_live_chunks` count on whatever chunk the program runs.
Bytes, once each way: forward reads q, k, v (tokens x H x K, bf16), the
log-decay g (tokens x H x K, float32) and beta (tokens x H, float32) and
writes o; backward reads them again with do and writes their gradients:
3 x (q, k, v, g, beta) + 2 x o. And the chunk states (live chunks x H x K x
V, float32): written and read forward, their gradients written and read
backward. The per-chunk (chunk, chunk) scores, the inverse, the decayed
copies of q and k never need to reach HBM and are not counted: an
implementation that writes them pays for it in the time.

The latent layer's attention kernels: forward S = QK^T contracts the query
and key width (128 + 64) and O = PV the value width (128); backward dV and
dP contract or produce the value width, dQ and dK the key width: 2 x (qk +
v) + 2 x (2 v + 2 qk) a pair the mask leaves and a head held. Bytes: Q, K
(tokens x H x qk) and V, O (tokens x H x v) forward; the same with dO in
place of nothing, and dQ, dK, dV written, backward: 3 x each, bf16. Every
head has a key of its own here (the shared rotated part is laid beside each
head's own part in the kernel's operand), so the operand is what is counted.
"""

from __future__ import annotations

from typing import Tuple

from benchmark import flops_ling
from benchmark.roofline import ACT_BYTES

STATE_BYTES = 4     # float32 states, log-decay and beta


def kda_need(config: dict, tokens: float, kda_pairs: float,
             live_chunks: float, layers: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of `layers` kda layers' delta rules, forward and
    backward."""
    h, k = config["num_attention_heads"], config["head_dim"]
    flops = 3.0 * flops_ling.delta_rule_flops_per_layer(config, tokens,
                                                        kda_pairs)
    io = tokens * h * ((3 * 3 * k + 2 * k) * ACT_BYTES
                       + 3 * (k + 1) * STATE_BYTES)
    states = 4.0 * live_chunks * h * k * k * STATE_BYTES
    return flops * layers, (io + states) * layers


def latent_attention_need(config: dict, pairs: float, tokens: float,
                          layers: int) -> Tuple[float, float]:
    h = config["num_attention_heads"]
    qk, dv = flops_ling.latent_widths(config)
    flops = (2.0 * (qk + dv) + 2.0 * (2 * dv + 2 * qk)) * h * pairs * layers
    nbytes = 3.0 * tokens * h * 2 * (qk + dv) * ACT_BYTES * layers
    return flops, nbytes
