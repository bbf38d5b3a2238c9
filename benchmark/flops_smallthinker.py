"""Useful matmul FLOPs of a train step of the SmallThinker decoder (one full
layer that rotates nothing to three sliding layers, grouped key/value heads,
a router before the attention, ReLU-gated experts and no dense feed-forward),
forward and backward (3x forward), and the parameters a chip holds, from a
configuration file's dict under the SOURCE's names and what a step's batch
held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` for this family (PaLM
appendix B convention: recomputation, padding, the masked part of an
attention block and the rows of the sorted buffer no held expert owns are not
useful and are not counted; a hidden unit the ReLU gate leaves at 0 IS
counted, the three products run over it), kept here so that no later PR can
move the yardstick; `benchmark/tests` holds the copies equal through
`against_program`. The arithmetic of the traffic kind
`train_early_router_packed`. `moe_num_primary_experts` and `vocab_size` are
what the chip HOLDS (the file's `reduced`); the router scores the
deployment's experts (`source_values.moe_num_primary_experts`).

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` and `window_pairs` ((query, key) pairs a layer with
`sliding_window_layout` 0 / 1 needs), `expert_slots` ((token, choice) slots
routed to an expert held here, over all the layers).

A layer, forward, a token: W_q and W_o (D x H x Dh), W_k and W_v (D x KV x
Dh), the router (D x experts routed: it costs the same before the attention
as after); scores and values contract Dh a pair; the rotation is no matrix
product. An expert: three products of D x `moe_ffn_hidden_size` a slot. The
head is untied: its product by the targets.
"""

from __future__ import annotations

from typing import Dict, Sequence


def layout_counts(rows: Sequence[Sequence[int]], row_tokens: int,
                  window: int) -> Dict[str, int]:
    """What a layout (rows of `row_tokens` slots holding documents of these
    lengths back to back) holds; a sliding layer's query sees at most
    `window` keys, its own included."""
    sizes = [n for row in rows for n in row]
    inside = [min(n, window) for n in sizes]
    return {"tokens": sum(sizes), "documents": len(sizes),
            "targets": sum(sizes) - len(sizes),
            "causal_pairs": sum(n * (n + 1) // 2 for n in sizes),
            "window_pairs": sum(w * (w + 1) // 2 + (n - w) * w
                                for n, w in zip(sizes, inside)),
            "padding_tokens": len(rows) * row_tokens - sum(sizes)}


def experts_routed(config: dict) -> int:
    return config.get("source_values", {}).get(
        "moe_num_primary_experts", config["moe_num_primary_experts"])


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, window_pairs: float,
                         expert_slots: float) -> float:
    d, h, kv, dh = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    fwd = 0.0
    for slides in config["sliding_window_layout"]:
        per_token = 2 * (2 * d * h * dh + 2 * d * kv * dh)      # q, o; k, v
        per_token += 2 * d * experts_routed(config)             # router
        fwd += per_token * tokens
        pairs = window_pairs if slides else causal_pairs
        fwd += 2 * 2 * pairs * h * dh                           # QK^T, PV
    fwd += 2 * 3 * d * config["moe_ffn_hidden_size"] * expert_slots
    fwd += 2 * d * config["vocab_size"] * targets               # the head
    return 3.0 * fwd


def param_counts_by_part(config: dict) -> Dict[str, int]:
    """Parameters of each part this chip holds (norms with their layer)."""
    d, h, kv, dh = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    return {
        "attention": 2 * d * h * dh + 2 * d * kv * dh,
        "router": d * experts_routed(config),
        "experts_held": 3 * d * config["moe_ffn_hidden_size"]
        * config["moe_num_primary_experts"],
        "layer_norms": 2 * d,
        "embedding_head_and_final_norm": 2 * config["vocab_size"] * d + d}


def param_count(config: dict) -> int:
    part = param_counts_by_part(config)
    return (part["embedding_head_and_final_norm"]
            + config["num_hidden_layers"] * (
                part["attention"] + part["router"] + part["experts_held"]
                + part["layer_norms"]))


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout, at a number
    of routed slots of its own."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], traffic["row_tokens"],
                           config["sliding_window_size"])
    held = {k: counts[k] for k in ("tokens", "targets", "causal_pairs",
                                   "window_pairs")}
    slots = 0.75 * counts["tokens"] * config["num_hidden_layers"]
    return [("FLOPs a step",
             model_flops_per_step(config, expert_slots=slots, **held),
             decoder_flops_per_step(cfg, expert_slots=slots, **held)),
            ("parameters", param_count(config), expected_param_count(cfg))]
