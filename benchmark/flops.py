"""Useful matmul FLOPs of one image, forward and backward (3x forward).

A copy of the arithmetic of `vitax/telemetry/flops.py:model_flops_per_image`
(PaLM appendix B convention: recomputation, padding and dropped work are not
useful and are not counted), kept here so that no later PR can move the
yardstick; `benchmark/tests` holds the two equal through
`against_program`. Takes a configuration file's dict, not a `Config`. The
arithmetic of the traffic kinds `train_resident` and `serve_closed`: a
generator names its module as `arithmetic`.
"""

from __future__ import annotations


def num_patches(config: dict) -> int:
    return (config["image_size"] // config["patch_size"]) ** 2


def model_flops_per_image(config: dict) -> float:
    d, depth = config["embed_dim"], config["num_blocks"]
    n = num_patches(config)
    h = int(d * config.get("mlp_ratio", 4.0))
    attn_per_token = 2 * (3 * d * d + d * d)                  # qkv, proj
    attn_block = 2 * 2 * n * n * d                            # QK^T and AV
    experts = config.get("moe_experts", 0)
    if experts > 0:
        k = config.get("moe_top_k", 1)
        mlp_per_token = k * 2 * (d * h + h * d) + 2 * d * experts
    else:
        mlp_per_token = 2 * (d * h + h * d)                   # fc1, fc2
    fwd = depth * ((attn_per_token + mlp_per_token) * n + attn_block)
    fwd += 2 * n * (3 * config["patch_size"] ** 2) * d        # patchify
    fwd += 2 * d * config["num_classes"]                      # head
    return 3.0 * fwd


def param_count(config: dict) -> int:
    """Parameters of the dense ViT (no cls token, learned positions,
    mean-pooled head), as `vitax.models.vit.expected_param_count` counts."""
    d, depth = config["embed_dim"], config["num_blocks"]
    h = int(d * config.get("mlp_ratio", 4.0))
    block = (2 * d) * 2 + (3 * d * d + 3 * d) + (d * d + d) \
        + (d * h + h) + (h * d + d)
    embed = 3 * config["patch_size"] ** 2 * d + d + num_patches(config) * d
    head = 2 * d + d * config["num_classes"] + config["num_classes"]
    return depth * block + embed + head


def against_program(config: dict, traffic: dict, cfg) -> list:
    """[(what, this copy's value, the program's)] for the `Config` a
    generator of this arithmetic built from `config`."""
    from vitax.models.vit import expected_param_count
    from vitax.telemetry import flops as programs
    return [("FLOPs an image", model_flops_per_image(config),
             programs.model_flops_per_image(cfg)),
            ("patches", num_patches(config), cfg.num_patches),
            ("parameters", param_count(config), expected_param_count(cfg))]
