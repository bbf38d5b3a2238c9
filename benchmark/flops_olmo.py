"""Useful matmul FLOPs of a train step of the Olmo-Hybrid decoder
(Gated-DeltaNet layers to one full-attention layer, a dense SwiGLU in every
layer, an untied head), forward and backward (3x forward), and the
parameters a chip holds, from a configuration file's dict under the SOURCE's
names and what a step's batch held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` for this family (PaLM
appendix B convention: recomputation, padding and the masked part of a block
are not useful and are not counted), kept here so that no later PR can move
the yardstick; `benchmark/tests` holds the copies equal through
`against_program`. The arithmetic of the traffic kind
`train_gated_delta_packed`. The four counts of heads and `vocab_size` are
what the chip HOLDS (the file's `reduced`); a head of the attention layers is
hidden_size / the PUBLISHED num_attention_heads wide.

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` ((query, key) pairs the attention layer needs), `kda_pairs`
(pairs of a query and a key not after it in one chunk and one document, on
the yardstick's fixed grid of `flops_ling.KDA_GRID` = 64 tokens, whatever
chunk the program's delta rule runs in).

A linear_attention layer, forward, H heads of K = `linear_key_head_dim` keys
and V = `linear_value_head_dim` values: W_q, W_k (2 * D * H * K each), W_v,
W_z, W_o (2 * D * H * V each), W_a, W_b (2 * D * H each) a token; the delta
rule on the yardstick `flops_ling.delta_rule_flops_per_layer` uses at K = V:
a pair, the two score products (k.k and q.k over K channels, 4K), the
triangular solve of the corrected keys and values (2 (K + V)) and the
intra-chunk output (2V): 6K + 4V; a token, the three products with the
(K, V) state: 6 K V. The convolution, the gates, the norms are no matmuls.
The attention layer: four projections of 2 * D * H * head_dim a token, QK^T
and PV of 2 * head_dim each a pair and head. The QK-norm and the norms after
are no matmuls.
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops_ling import KDA_GRID, layout_counts  # noqa: F401

LINEAR = "linear_attention"


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config.get("source_values", {}).get(
        "num_attention_heads", config["num_attention_heads"])


def delta_widths(config: dict):
    """(heads held, key width, value width) of a linear_attention layer."""
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    return (config["linear_num_key_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def delta_rule_flops_per_layer(config: dict, tokens: float,
                               kda_pairs: float) -> float:
    """Forward FLOPs of one linear_attention layer's delta rule."""
    h, k, v = delta_widths(config)
    return h * (6.0 * k + 4.0 * v) * kda_pairs + 6.0 * h * k * v * tokens


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, kda_pairs: float) -> float:
    d, dh = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    h, k, v = delta_widths(config)
    fwd = 0.0
    for kind in config["layer_types"]:
        if kind == LINEAR:
            per_token = 2 * d * h * (2 * k + 3 * v + 2)
            fwd += delta_rule_flops_per_layer(config, tokens, kda_pairs)
        else:
            per_token = 2 * (2 * d * heads * dh + 2 * d * kv * dh)
            fwd += 2 * 2 * causal_pairs * heads * dh            # QK^T, PV
        per_token += 2 * 3 * d * config["intermediate_size"]
        fwd += per_token * tokens
    fwd += 2 * d * config["vocab_size"] * targets               # the head
    return 3.0 * fwd


def param_counts_by_part(config: dict) -> Dict[str, int]:
    """Parameters of each part this chip holds."""
    d, dh = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    h, k, v = delta_widths(config)
    inner = h * (2 * k + v)
    return {
        "linear_mixer": d * inner + config["linear_conv_kernel_dim"] * inner
        + 2 * d * h + 2 * h + d * h * v + v + h * v * d,
        "attention_mixer": 2 * d * heads * dh + 2 * d * kv * dh
        + (heads + kv) * dh,
        "mlp": 3 * d * config["intermediate_size"],
        "layer_norms": 2 * d,
        "embedding_head_final_norm": 2 * config["vocab_size"] * d + d}


def param_count(config: dict) -> int:
    assert not config["tie_word_embeddings"]
    part = param_counts_by_part(config)
    return part["embedding_head_final_norm"] + sum(
        part["layer_norms"] + part["mlp"]
        + part["linear_mixer" if kind == LINEAR else "attention_mixer"]
        for kind in config["layer_types"])


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], traffic["row_tokens"])
    held = {k: counts[k] for k in ("tokens", "targets", "causal_pairs",
                                   "kda_pairs")}
    return [("FLOPs a step", model_flops_per_step(config, **held),
             decoder_flops_per_step(cfg, window_pairs=0.0, expert_slots=0.0,
                                    **held)),
            ("parameters", param_count(config), expected_param_count(cfg))]
