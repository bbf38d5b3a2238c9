"""Useful matmul FLOPs of a step of the packed native-resolution model,
forward and backward (3x forward), and its parameter count.

A copy of the arithmetic of `vitax/telemetry/flops.py:packed_flops_per_step`
and `vitax/models/vit.py:expected_param_count` (PaLM appendix B convention:
recomputation, padding, the masked part of an attention block and the
position table's resize are not useful and are not counted), kept here so
that no later PR can move the yardstick; `benchmark/tests` holds the copies
equal through `against_program`. Takes a configuration file's dict and what
a step's batch held. The arithmetic of the traffic kind `train_packed`.
"""

from __future__ import annotations

from typing import Dict, Sequence


def mlp_dim(config: dict) -> int:
    return int(config["native_res"]["mlp_dim"])


def layout_counts(rows: Sequence[Sequence[Sequence[int]]]) -> Dict[str, int]:
    """What a layout (rows of (h, w) grids) holds: `tokens` valid,
    `token_pairs` = the sum over images of n_i^2, `images`."""
    sizes = [h * w for row in rows for h, w in row]
    return {"tokens": sum(sizes), "token_pairs": sum(n * n for n in sizes),
            "images": len(sizes)}


def model_flops_per_step(config: dict, tokens: float, token_pairs: float,
                         images: float) -> float:
    d, depth, h = config["embed_dim"], config["num_blocks"], mlp_dim(config)
    per_token = depth * (2 * (3 * d * d + d * d) + 2 * (d * h + h * d))
    per_token += 2 * (3 * config["patch_size"] ** 2) * d       # patch map
    fwd = per_token * tokens + depth * 2 * 2 * token_pairs * d  # QK^T, AV
    fwd += 2 * d * config["num_classes"] * images               # head
    return 3.0 * fwd


def param_count(config: dict) -> int:
    d, depth, h = config["embed_dim"], config["num_blocks"], mlp_dim(config)
    block = (2 * d) * 2 + (3 * d * d + 3 * d) + (d * d + d) \
        + (d * h + h) + (h * d + d)
    embed = (3 * config["patch_size"] ** 2 * d + d
             + config["native_res"]["pos_grid"] ** 2 * d)
    head = 2 * d + d * config["num_classes"] + config["num_classes"]
    return depth * block + embed + head


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` the
    packed generator built from `config`, on the traffic's own layout."""
    from vitax.models.vit import expected_param_count
    from vitax.telemetry.flops import packed_flops_per_step
    counts = layout_counts(traffic["rows"])
    return [("FLOPs a step", model_flops_per_step(config, **counts),
             packed_flops_per_step(cfg, **counts)),
            ("MLP width", mlp_dim(config), cfg.mlp_hidden_dim),
            ("parameters", param_count(config), expected_param_count(cfg))]
