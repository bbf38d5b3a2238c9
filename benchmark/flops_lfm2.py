"""Useful matmul FLOPs of a train step of the LFM2-MoE decoder (gated short
convolutions to one grouped-query attention layer, a dense SwiGLU first and
routed experts with no shared one after), forward and backward (3x forward),
and the parameters a chip holds, from a configuration file's dict under the
SOURCE's names and what a step's batch held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` for this family (PaLM
appendix B convention: recomputation, padding and the masked part of a block
are not useful and are not counted), kept here so that no later PR can move
the yardstick; `benchmark/tests` holds the copies equal through
`against_program`. The arithmetic of the traffic kind
`train_gated_conv_packed`. `num_experts` and `vocab_size` are what the chip
HOLDS (the file's `reduced`); the router scores the deployment's experts
(`source_values.num_experts`).

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` ((query, key) pairs the attention layer needs),
`expert_slots` ((token, choice) slots routed to an expert held here, over
the sparse layers).

A conv layer, forward, a token: W_in (2 * D * 3D) and W_out (2 * D * D); the
two gates and the taps (2 + 2 * L multiply-adds a channel) are no matrix
products. The attention layer: W_q and W_o (D x H x Dh), W_k and W_v (D x KV
x Dh) a token; scores and values contract Dh a pair; the norm a head and the
rotation are no matrix products. The table is tied: one table, the head's
product by the targets.
"""

from __future__ import annotations

from typing import Dict, Sequence

CONV, ATTENTION = "conv", "full_attention"


def layout_counts(rows: Sequence[Sequence[int]], row_tokens: int
                  ) -> Dict[str, int]:
    """What a layout (rows of `row_tokens` slots holding documents of these
    lengths back to back) holds."""
    sizes = [n for row in rows for n in row]
    return {"tokens": sum(sizes), "documents": len(sizes),
            "targets": sum(sizes) - len(sizes),
            "causal_pairs": sum(n * (n + 1) // 2 for n in sizes),
            "padding_tokens": len(rows) * row_tokens - sum(sizes)}


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def experts_routed(config: dict) -> int:
    return config.get("source_values", {}).get("num_experts",
                                               config["num_experts"])


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, expert_slots: float) -> float:
    d, h, kv, dh = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], head_dim(config))
    fwd = 0.0
    for kind, mlp in zip(config["layer_types"], config["mlp_layer_types"]):
        if kind == CONV:
            per_token = 2 * d * 3 * d + 2 * d * d
        else:
            per_token = 2 * (2 * d * h * dh + 2 * d * kv * dh)
            fwd += 2 * 2 * causal_pairs * h * dh                # QK^T, PV
        if mlp == "dense":
            per_token += 2 * 3 * d * config["intermediate_size"]
        else:
            per_token += 2 * d * experts_routed(config)         # router
        fwd += per_token * tokens
    fwd += 2 * 3 * d * config["moe_intermediate_size"] * expert_slots
    fwd += 2 * d * config["vocab_size"] * targets               # the head
    return 3.0 * fwd


def param_counts_by_part(config: dict) -> Dict[str, int]:
    """Parameters of each part this chip holds (norms with their layer)."""
    d, h, kv, dh = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], head_dim(config))
    bias = experts_routed(config) if config["use_expert_bias"] else 0
    return {
        "conv_mixer": 4 * d * d + config["conv_L_cache"] * d,
        "attention_mixer": 2 * d * h * dh + 2 * d * kv * dh + 2 * dh,
        "sparse_ffn": d * experts_routed(config) + bias
        + 3 * d * config["moe_intermediate_size"] * config["num_experts"],
        "dense_mlp": 3 * d * config["intermediate_size"],
        "layer_norms": 2 * d,
        "table_and_final_norm": config["vocab_size"] * d + d}


def param_count(config: dict) -> int:
    part = param_counts_by_part(config)
    total = part["table_and_final_norm"]
    for kind, mlp in zip(config["layer_types"], config["mlp_layer_types"]):
        total += part["layer_norms"]
        total += part["conv_mixer" if kind == CONV else "attention_mixer"]
        total += part["dense_mlp" if mlp == "dense" else "sparse_ffn"]
    return total


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout, at a number
    of routed slots of its own."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], traffic["row_tokens"])
    held = {k: counts[k] for k in ("tokens", "targets", "causal_pairs")}
    slots = 0.5 * counts["tokens"]
    return [("FLOPs a step",
             model_flops_per_step(config, expert_slots=slots, **held),
             decoder_flops_per_step(cfg, window_pairs=0.0,
                                    expert_slots=slots, **held)),
            ("parameters", param_count(config), expected_param_count(cfg))]
