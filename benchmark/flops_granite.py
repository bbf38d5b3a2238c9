"""Useful matmul FLOPs of a train step of the Granite 4.0-H hybrid decoder,
forward and backward (3x forward), and the parameters a chip holds, from a
configuration file's dict under the SOURCE's names and what a step's batch
held.

A copy of the arithmetic of `vitax/telemetry/flops.py:decoder_flops_per_step`
and `vitax/models/decoder.py:expected_param_count` for a model whose layers
are `mamba` or `attention` with a dense SwiGLU each (PaLM appendix B
convention: recomputation, padding and the masked part of a block are not
useful and are not counted), kept here so that no later PR can move the
yardstick; `benchmark/tests` holds the copies equal through
`against_program`. The arithmetic of the traffic kind `train_hybrid_packed`.

What a step held (its own counters): `tokens` valid, `targets`,
`causal_pairs` ((query, key) pairs an attention layer needs) and `ssd_pairs`
(pairs of a query and a key not after it in one chunk of `mamba_chunk_size`
tokens and one document: what the chunked scan's masked products need).

A mamba layer, forward: the in-projection 2 * D * (2 * d_inner + 2 * G * N +
H) and the out-projection 2 * d_inner * D a token; the scan 2 * (G * N +
d_inner) a pair (C.B a group, and the masked product over x) and 4 * d_inner
* N a token (the state a chunk leaves, and the state a token reads). The
convolution, the gate and the norms are no matmuls.
"""

from __future__ import annotations

from typing import Dict, Sequence

MAMBA = "mamba"


def layout_counts(rows: Sequence[Sequence[int]], row_tokens: int,
                  chunk: int) -> Dict[str, int]:
    """What a layout (rows of `row_tokens` slots holding documents of these
    lengths back to back) holds, the scan's work on the grid of `chunk`."""
    sizes = [n for row in rows for n in row]
    ssd_pairs, live = 0, 0
    for row in rows:
        at = 0
        for n in row:       # the document's tokens in each chunk it touches
            for c in range(at // chunk, (at + n - 1) // chunk + 1):
                m = min(at + n, (c + 1) * chunk) - max(at, c * chunk)
                ssd_pairs += m * (m + 1) // 2
            at += n
        live += -(-at // chunk)
    return {"tokens": sum(sizes), "documents": len(sizes),
            "targets": sum(sizes) - len(sizes),
            "causal_pairs": sum(n * (n + 1) // 2 for n in sizes),
            "ssd_pairs": ssd_pairs, "ssd_live_chunks": live,
            "padding_tokens": len(rows) * row_tokens - sum(sizes)}


def mixer_sizes(config: dict):
    """(d_inner, groups x state, heads, state) of a mamba layer's mixer."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    assert inner == config["mamba_expand"] * config["hidden_size"]
    return (inner, config["mamba_n_groups"] * config["mamba_d_state"],
            config["mamba_n_heads"], config["mamba_d_state"])


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def scan_flops_per_layer(config: dict, tokens: float,
                         ssd_pairs: float) -> float:
    """Forward FLOPs of one mamba layer's scan."""
    inner, gn, _, state = mixer_sizes(config)
    return 2.0 * (gn + inner) * ssd_pairs + 4.0 * inner * state * tokens


def model_flops_per_step(config: dict, tokens: float, targets: float,
                         causal_pairs: float, ssd_pairs: float) -> float:
    d, dh = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    inner, gn, ssm_heads, _ = mixer_sizes(config)
    fwd = 0.0
    for kind in config["layer_types"]:
        if kind == MAMBA:
            per_token = 2 * d * (2 * inner + 2 * gn + ssm_heads)
            per_token += 2 * inner * d
            fwd += scan_flops_per_layer(config, tokens, ssd_pairs)
        else:
            per_token = 2 * (2 * d * heads * dh + 2 * d * kv * dh)
            fwd += 2 * 2 * causal_pairs * heads * dh            # QK^T, PV
        per_token += 2 * 3 * d * config["shared_intermediate_size"]
        fwd += per_token * tokens
    fwd += 2 * d * config["vocab_size"] * targets                # tied head
    return 3.0 * fwd


def layer_param_counts(config: dict) -> Dict[str, int]:
    """Parameters of one layer of each kind, its two norms and MLP in."""
    d, dh = config["hidden_size"], head_dim(config)
    inner, gn, ssm_heads, _ = mixer_sizes(config)
    channels = inner + 2 * gn
    shared = 2 * d + 3 * d * config["shared_intermediate_size"]
    # the program's convolution always has its bias (vitax/models/ssm.py)
    assert config["mamba_conv_bias"]
    return {
        MAMBA: shared + d * (inner + channels + ssm_heads)
        + channels * (config["mamba_d_conv"] + 1)
        + 3 * ssm_heads + inner + inner * d,
        "attention": shared + 2 * d * config["num_attention_heads"] * dh
        + 2 * d * config["num_key_value_heads"] * dh}


def param_count(config: dict) -> int:
    assert config["tie_word_embeddings"] and config["num_local_experts"] == 0
    per_layer = layer_param_counts(config)
    return (config["vocab_size"] * config["hidden_size"]
            + config["hidden_size"]
            + sum(per_layer[kind] for kind in config["layer_types"]))


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    generator built from `config`, on the traffic's own layout."""
    from vitax.models.decoder import expected_param_count
    from vitax.telemetry.flops import decoder_flops_per_step
    counts = layout_counts(traffic["rows"], traffic["row_tokens"],
                           config["mamba_chunk_size"])
    held = {k: counts[k] for k in ("tokens", "targets", "causal_pairs",
                                   "ssd_pairs")}
    return [("FLOPs a step", model_flops_per_step(config, **held),
             decoder_flops_per_step(cfg, window_pairs=0.0, expert_slots=0.0,
                                    **held)),
            ("parameters", param_count(config), expected_param_count(cfg))]
