"""Useful matmul FLOPs of a train step of the Laguna decoder, forward and
backward (3x forward), and the parameters a chip holds, from a configuration
file's dict under the SOURCE's names and what a step's batch held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` (PaLM appendix B
convention: recomputation, padding, the masked part of an attention block and
the rows of the sorted buffer no expert owns are not useful and are not
counted), kept here so that no later PR can move the yardstick;
`benchmark/tests` holds the copies equal through `against_program`. The
arithmetic of the traffic kind `train_decoder_packed`.

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` and `window_pairs` ((query, key) pairs a full / a sliding
layer needs), `expert_slots` = (token, choice) slots routed to an expert held
here, summed over the sparse layers.
"""

from __future__ import annotations

from typing import Dict, Sequence

SLIDING = "sliding_attention"


def layout_counts(rows: Sequence[Sequence[int]], window: int) -> Dict[str, int]:
    """What a layout (rows of document lengths) holds."""
    sizes = [n for row in rows for n in row]
    inside = [min(n, window) for n in sizes]
    return {"tokens": sum(sizes), "documents": len(sizes),
            "targets": sum(sizes) - len(sizes),
            "causal_pairs": sum(n * (n + 1) // 2 for n in sizes),
            "window_pairs": sum(w * (w + 1) // 2 + (n - w) * w
                                for n, w in zip(sizes, inside))}


def attention_layers(config: dict):
    """[(query heads, (query, key) pair counter)] a layer."""
    return [(h, "window_pairs" if kind == SLIDING else "causal_pairs")
            for h, kind in zip(config["num_attention_heads_per_layer"],
                               config["layer_types"])]


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, window_pairs: float,
                         expert_slots: float) -> float:
    d, dh = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    routed = config.get("source_values", {}).get("num_experts",
                                                 config["num_experts"])
    pairs = {"causal_pairs": causal_pairs, "window_pairs": window_pairs}
    fwd = 0.0
    for (heads, counter), mlp in zip(attention_layers(config),
                                     config["mlp_layer_types"]):
        per_token = 2 * (2 * d * heads * dh + 2 * d * kv * dh)   # q, o; k, v
        per_token += 2 * d * heads if config["gating"] else 0    # head gate
        if mlp == "dense":
            per_token += 2 * 3 * d * config["intermediate_size"]
        else:
            per_token += 2 * d * routed                          # router
            per_token += 2 * 3 * d * config["shared_expert_intermediate_size"]
        fwd += per_token * tokens
        fwd += 2 * 2 * pairs[counter] * heads * dh               # QK^T, PV
    fwd += 2 * 3 * d * config["moe_intermediate_size"] * expert_slots
    fwd += 2 * d * config["vocab_size"] * targets                # head
    return 3.0 * fwd


def param_count(config: dict) -> int:
    d, dh = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    routed = config.get("source_values", {}).get("num_experts",
                                                 config["num_experts"])
    total = 2 * config["vocab_size"] * d + d
    for heads, mlp in zip(config["num_attention_heads_per_layer"],
                          config["mlp_layer_types"]):
        total += 2 * d + 2 * d * heads * dh + 2 * d * kv * dh
        total += d * heads if config["gating"] else 0
        if mlp == "dense":
            total += 3 * d * config["intermediate_size"]
        else:
            total += (d * routed
                      + 3 * d * config["moe_intermediate_size"]
                      * config["num_experts"]
                      + 3 * d * config["shared_expert_intermediate_size"])
    return total


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout with one slot
    a token and sparse layer."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], config["sliding_window"])
    counts.pop("documents")
    slots = counts["tokens"] * config["mlp_layer_types"].count("sparse")
    return [("FLOPs a step",
             model_flops_per_step(config, **counts, expert_slots=slots),
             decoder_flops_per_step(cfg, **counts, expert_slots=slots)),
            ("parameters", param_count(config), expected_param_count(cfg))]
