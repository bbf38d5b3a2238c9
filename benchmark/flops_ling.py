"""Useful matmul FLOPs of a train step of the Ling-3.0-flash decoder (Kimi
Delta Attention layers to one latent-attention layer, a dense SwiGLU first
and routed and shared experts after), forward and backward (3x forward), and
the parameters a chip holds, from a configuration file's dict under the
SOURCE's names and what a step's batch held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` for this family (PaLM
appendix B convention: recomputation, padding and the masked part of a block
are not useful and are not counted), kept here so that no later PR can move
the yardstick; `benchmark/tests` holds the copies equal through
`against_program`. The arithmetic of the traffic kind `train_latent_packed`.
`num_attention_heads`, `num_experts` and `vocab_size` are what the chip
HOLDS (the file's `reduced`); the router scores the deployment's experts
(`source_values.num_experts`).

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` ((query, key) pairs the latent layer needs), `kda_pairs`
(pairs of a query and a key not after it in one chunk and one document, on
the grid of `KDA_GRID` tokens: the yardstick's own, fixed, whatever chunk
the program's delta rule runs in), `expert_slots` ((token, choice) slots
routed to an expert held here, over the sparse layers).

A kda layer, forward, H heads of K = V = `head_dim`: five projections of 2 *
D * H * K and two head-wise ones of 2 * D * H a token; the delta rule a
pair: the two score products (k.k and q.k over K channels, 4K), the
triangular solve of the corrected keys and values (2 (K + V)) and the
intra-chunk output (2V): 6K + 4V; and a token: the three products with the
(K, V) state (what the state corrects, what the query reads, what the chunk
leaves), 6 K V. The convolution, the gate, the norms are no matmuls.
The latent layer: W_q (D x H x 192), the down-projection (D x 576), the
up-projection (512 x H x 256), the head gate, W_o (H x 128 x D) a token;
scores contract 192 and values 128 a pair.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence

from benchmark.flops_granite import layout_counts as _grid_counts

# The grid the delta rule's pairs and live chunks are counted on: a constant
# of the yardstick. The step's `kda_pairs` / `kda_live_chunks` are held to
# the layout on THIS grid, so a program that runs another chunk length shows
# it in its time and not in what it is said to need.
KDA_GRID = 64


def layout_counts(rows: Sequence[Sequence[int]], row_tokens: int
                  ) -> Dict[str, int]:
    """What a layout holds, the delta rule's work on the grid of `KDA_GRID`
    tokens (a shorter row's gcd with it)."""
    counts = _grid_counts(rows, row_tokens, math.gcd(row_tokens, KDA_GRID))
    counts["kda_pairs"] = counts.pop("ssd_pairs")
    counts["kda_live_chunks"] = counts.pop("ssd_live_chunks")
    return counts


def kinds(config: dict) -> list:
    period = config["layer_group_size"]
    return ["latent" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def sparse_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def experts_routed(config: dict) -> int:
    return config.get("source_values", {}).get("num_experts",
                                               config["num_experts"])


def delta_rule_flops_per_layer(config: dict, tokens: float,
                               kda_pairs: float) -> float:
    """Forward FLOPs of one kda layer's delta rule (K = V = head_dim)."""
    h, k = config["num_attention_heads"], config["head_dim"]
    return 10.0 * h * k * kda_pairs + 6.0 * h * k * k * tokens


def latent_widths(config: dict):
    """(query/key width, value width) of a latent layer's heads."""
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, kda_pairs: float,
                         expert_slots: float) -> float:
    d, h, k = (config["hidden_size"], config["num_attention_heads"],
               config["head_dim"])
    qk, dv = latent_widths(config)
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    fwd = 0.0
    for i, kind in enumerate(kinds(config)):
        if kind == "kda":
            per_token = 2 * d * h * (5 * k + 2)
            fwd += delta_rule_flops_per_layer(config, tokens, kda_pairs)
        else:
            per_token = 2 * d * (h * qk + rank + rope + h * dv)
            per_token += 2 * rank * h * (config["qk_nope_head_dim"] + dv)
            per_token += 2 * d * h                              # head gate
            fwd += 2 * causal_pairs * h * (qk + dv)             # QK^T, PV
        if i < config["first_k_dense_replace"]:
            per_token += 2 * 3 * d * config["intermediate_size"]
        else:
            per_token += 2 * d * experts_routed(config)         # router
            per_token += 2 * 3 * d * \
                config["moe_shared_expert_intermediate_size"]
        fwd += per_token * tokens
    fwd += 2 * 3 * d * config["moe_intermediate_size"] * expert_slots
    fwd += 2 * d * config["vocab_size"] * targets               # the head
    return 3.0 * fwd


def param_counts_by_part(config: dict) -> Dict[str, int]:
    """Parameters of each part this chip holds (norms with their layer)."""
    d, h, k = (config["hidden_size"], config["num_attention_heads"],
               config["head_dim"])
    qk, dv = latent_widths(config)
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    bias = experts_routed(config) \
        if config["moe_router_enable_expert_bias"] else 0
    return {
        "kda_mixer": 5 * d * h * k
        + 3 * config["short_conv_kernel_size"] * h * k + h + h * k
        + 2 * d * h + k,
        "latent_mixer": d * h * qk + d * (rank + rope) + rank
        + rank * h * (config["qk_nope_head_dim"] + dv) + d * h + h * dv * d,
        "sparse_ffn": d * experts_routed(config) + bias
        + 3 * d * config["moe_intermediate_size"] * config["num_experts"]
        + 3 * d * config["moe_shared_expert_intermediate_size"],
        "dense_mlp": 3 * d * config["intermediate_size"],
        "layer_norms": 2 * d,
        "embedding_head_final_norm": 2 * config["vocab_size"] * d + d}


def param_count(config: dict) -> int:
    part = param_counts_by_part(config)
    total = part["embedding_head_final_norm"]
    for i, kind in enumerate(kinds(config)):
        total += part["layer_norms"] + part[f"{kind}_mixer"]
        total += part["dense_mlp" if i < config["first_k_dense_replace"]
                      else "sparse_ffn"]
    return total


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout, at a number
    of routed slots of its own."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], traffic["row_tokens"])
    held = {k: counts[k] for k in ("tokens", "targets", "causal_pairs",
                                   "kda_pairs")}
    slots = 3.0 * counts["tokens"]
    return [("FLOPs a step",
             model_flops_per_step(config, expert_slots=slots, **held),
             decoder_flops_per_step(cfg, window_pairs=0.0,
                                    expert_slots=slots, **held)),
            ("parameters", param_count(config), expected_param_count(cfg))]
