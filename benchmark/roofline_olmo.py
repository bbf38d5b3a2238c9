"""Operations and bytes that the Olmo-Hybrid decoder's delta rule needs, from
shapes and what a step's batch held: the same whatever implements it (plain
`jax.numpy` today, a fused kernel later).

The delta rule of a linear_attention layer (Gated DeltaNet), H heads of K
keys and V values, on the yardstick `roofline_ling.kda_need` uses for Ling's
cell so that the two cells' shares compare: forward
(benchmark/flops_olmo.py: delta_rule_flops_per_layer) 6K + 4V a pair of a
query and a key not after it in one chunk and one document (k.k and q.k
scores, the triangular solve of the corrected keys and values, the
intra-chunk output), 6 K V a token (what the state corrects, what the query
reads, what the chunk leaves). The backward is twice the forward. The need is
that of the fixed grid of `flops_ling.KDA_GRID` = 64 tokens, which the step's
`kda_pairs` and `kda_live_chunks` count on whatever chunk the program runs.
Bytes, once each way: forward reads q, k (tokens x H x K) and v (tokens x H
x V) in bf16, the log-decay g and beta (tokens x H, one float32 a head each:
the decay is a scalar here) and writes o (tokens x H x V); backward reads
them again with do and writes their gradients: 3 x (q, k, v, g, beta) + 2 x
o. And the chunk states (live chunks x H x K x V, float32): written and read
forward, their gradients written and read backward. The per-chunk (chunk,
chunk) scores, the inverse and the decay matrix never need to reach HBM and
are not counted: an implementation that writes them pays for it in the time.

The attention layer's kernels are `roofline_laguna.attention_need`'s, at the
heads held and hidden_size / the published heads a head.
"""

from __future__ import annotations

from typing import Tuple

from benchmark import flops_olmo
from benchmark.roofline import ACT_BYTES

STATE_BYTES = 4     # float32 states, log-decay and beta


def gated_delta_need(config: dict, tokens: float, kda_pairs: float,
                     live_chunks: float, layers: int) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of `layers` linear_attention layers' delta rules,
    forward and backward."""
    h, k, v = flops_olmo.delta_widths(config)
    flops = 3.0 * flops_olmo.delta_rule_flops_per_layer(config, tokens,
                                                        kda_pairs)
    io = tokens * h * ((3 * (2 * k + v) + 2 * v) * ACT_BYTES
                       + 3 * 2 * STATE_BYTES)
    states = 4.0 * live_chunks * h * k * v * STATE_BYTES
    return flops * layers, (io + states) * layers
