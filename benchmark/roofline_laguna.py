"""Operations and bytes the Laguna decoder's kernels need, from what a
step's batch held (the arithmetic of `benchmark/roofline_packed.py` with the
pairs a causal or a windowed mask leaves, grouped key/value heads, and the
grouped expert products).

Attention, a layer: forward S = QK^T and O = PV, backward dV, dP, dQ, dK:
(2 + 4) matmuls of 2 * heads * pairs * head_dim, where `pairs` counts the
(query, key) pairs the mask leaves: sum n (n + 1) / 2 over documents in a
full layer, at most `window` keys a query in a sliding one. Bytes: Q, O
(forward) and Q, O, dO, dQ (backward) of tokens x heads x head_dim; K, V
(forward) and K, V, dK, dV (backward) of tokens x kv_heads x head_dim; bf16.
K and V are never repeated for their query heads, so the repeat is in
neither the need nor the time. The masked part of a boundary block and the
forward that remat runs again are in the time, not the need.

Grouped expert products, a sparse layer: three products of 2 * slots *
hidden * expert width forward, twice that backward (dX and dW). Bytes: every
held expert's three matrices read forward, read again and their gradient
written backward (3 x bf16), and the sorted activations: the rows in (slots
x hidden) and out, and the two hidden products (slots x expert width), x 3
for the backward; rows of the sorted buffer that no held expert owns are not
needed.
"""

from __future__ import annotations

from typing import Tuple

from benchmark.roofline import ACT_BYTES


def attention_need(pairs: float, tokens: float, heads: int, kv_heads: int,
                   head_dim: int, layers: int) -> Tuple[float, float]:
    flops = (2 + 4) * 2.0 * heads * pairs * head_dim * layers
    nbytes = 6.0 * tokens * (heads + kv_heads) * head_dim * ACT_BYTES * layers
    return flops, nbytes


def expert_ffn_need(slots: float, hidden: int, width: int, held: int,
                    layers: int) -> Tuple[float, float]:
    """`slots`: summed over the `layers` sparse layers."""
    flops = 3 * 3 * 2.0 * slots * hidden * width
    weights = 3.0 * held * 3 * hidden * width * ACT_BYTES * layers
    rows = 3.0 * slots * (2 * hidden + 3 * width) * ACT_BYTES
    return flops, weights + rows


def attention_share(run, kernel_mark: str, layer_type: str, counter: str):
    """The roofline share of the kernels named `kernel_mark`* in a traced
    run: the need of the configuration's layers of `layer_type`, from the
    step's own `counter`, over the kernels' summed device time."""
    from benchmark import roofline
    counts = run.records.get("packed_counts")
    if run.trace is None or counts is None or "steps" not in run.records:
        return None
    seconds = run.trace.seconds_matching(kernel_mark)
    if seconds <= 0:
        return None
    c = run.config
    heads = {h for h, kind in zip(c["num_attention_heads_per_layer"],
                                  c["layer_types"]) if kind == layer_type}
    assert len(heads) == 1, heads     # one head count a layer type
    steps = run.records["steps"]
    need = attention_need(
        counts[counter] / run.chips * steps,
        counts["tokens"] / run.chips * steps, heads.pop(),
        c["num_key_value_heads"], c["head_dim"],
        c["layer_types"].count(layer_type))
    share, bound = roofline.roofline_pct(*need, seconds, run.peaks)
    run.records[f"{kernel_mark}bound"] = bound
    run.records[f"{kernel_mark}kernel_s"] = seconds
    return share
