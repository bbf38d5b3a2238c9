"""Analytic model-FLOPs accounting -> MFU.

Model FLOPs utilization (MFU, PaLM appendix B convention: Chowdhery et al.
2022) is useful FLOPs per second divided by the chips' peak FLOPs — the
headline efficiency number every perf PR is judged against. "Useful" means
the matmul FLOPs of ONE forward+backward over the batch: remat recompute,
failed experiments and padding are not useful work, so they are NOT counted
(true MFU, not hardware FLOPs utilization).

The FLOPs model is closed-form from `Config` — no tracing, no device work:
patchify conv, per-block qkv/proj + attention einsums + MLP (dense or MoE
top-k experts + router), classifier head, x3 for fwd+bwd (the standard 6ND
convention). Grad accumulation and pipeline microbatching reshape WHERE the
batch's samples flow, not how many matmul FLOPs the optimizer step performs,
so per-step FLOPs are `per_image x batch_size` for every (K, pp_microbatches)
setting — the model is accumulation/pipeline aware by construction.

What the training loop's Recorder reports as MFU. The benchmark keeps its
own copy (benchmark/flops.py); benchmark/tests hold the two equal.
"""

from __future__ import annotations

from typing import Optional

# bf16 peak TFLOP/s per chip by TPU generation (Google Cloud TPU
# documentation, the per-generation system-architecture pages), keyed by a
# substring of the PJRT device_kind. A device that is not here is an error,
# not a default; a CPU has no entry because a CPU run has no MFU.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0, "v5e": 197.0,
    "v5p": 459.0,
    "v6e": 918.0, "v6 lite": 918.0,
}


def detect_peak_tflops(device_kind: str,
                       override: float = 0.0) -> Optional[float]:
    """Per-chip peak TFLOP/s for a PJRT device_kind string, or None for the
    host CPU (its runs report `mfu: null`). `override` > 0 (--peak_tflops)
    wins unconditionally — the way to name new hardware the table has not
    met; without it an unknown accelerator kind raises."""
    if override and override > 0:
        return float(override)
    kind = (device_kind or "").lower()
    if kind == "cpu":
        return None
    for key, val in PEAK_TFLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak TFLOP/s known for device kind {device_kind!r}: add it to "
        f"vitax/telemetry/flops.py PEAK_TFLOPS with its source, or pass "
        f"--peak_tflops")


def model_flops_per_image(cfg) -> float:
    """Useful matmul FLOPs per image, fwd+bwd (3x forward).

    Dense blocks count qkv/proj/fc1/fc2; MoE blocks count the router matmul
    plus top_k expert MLPs per token (capacity-dropped tokens still occupy
    their expert slot in the einsum impl, but dropped work is not useful —
    top_k per token is the honest number)."""
    d, L = cfg.embed_dim, cfg.num_blocks
    n = cfg.num_patches
    h = cfg.mlp_hidden_dim
    attn_per_token = 2 * (3 * d * d + d * d)                   # qkv, proj
    attn_block = 2 * 2 * n * n * d                             # QK^T and AV
    if getattr(cfg, "moe_experts", 0) > 0:
        k = getattr(cfg, "moe_top_k", 1)
        mlp_per_token = (k * 2 * (d * h + h * d)               # top-k experts
                         + 2 * d * cfg.moe_experts)            # router logits
    else:
        mlp_per_token = 2 * (d * h + h * d)                    # fc1, fc2
    fwd = L * ((attn_per_token + mlp_per_token) * n + attn_block)
    fwd += 2 * n * (3 * cfg.patch_size ** 2) * d               # patchify conv
    fwd += 2 * d * cfg.num_classes                             # head
    return 3.0 * fwd


def packed_flops_per_step(cfg, tokens: float, token_pairs: float,
                          images: float) -> float:
    """Useful matmul FLOPs of one step of the packed native-resolution
    model, fwd+bwd (3x forward), from what the step's batch held: `tokens`
    valid tokens (qkv, proj, fc1, fc2 and the patch map), `token_pairs` =
    the sum over images of n_i^2 (QK^T and AV within each image) and
    `images` (the head). Padding, the masked part of a block and the
    position table's resize are not useful work and are not counted."""
    d, L = cfg.embed_dim, cfg.num_blocks
    h = cfg.mlp_hidden_dim
    per_token = L * (2 * (3 * d * d + d * d) + 2 * (d * h + h * d))
    per_token += 2 * (3 * cfg.patch_size ** 2) * d             # patch map
    fwd = per_token * tokens + L * 2 * 2 * token_pairs * d     # QK^T and AV
    fwd += 2 * d * cfg.num_classes * images                    # head
    return 3.0 * fwd


def decoder_flops_per_step(cfg, tokens: float, targets: float,
                           causal_pairs: float, window_pairs: float,
                           expert_slots: float,
                           ssd_pairs: float = 0.0,
                           kda_pairs: float = 0.0) -> float:
    """Useful matmul FLOPs of one step of the token decoder
    (vitax/models/decoder.py), fwd+bwd (3x forward), from the step's own
    counters (vitax/train/step.py: decoder_counts): `tokens` valid (the
    projections, the head gate, a dense layer's MLP, a sparse layer's router
    and shared expert), the (query, key) pairs a full or a sliding layer's
    mask leaves (QK^T and PV), `expert_slots` = the (token, choice) slots
    routed to an expert held here, over all sparse layers (the routed
    experts), and `targets` (the head). A mamba layer (vitax/models/ssm.py):
    its two projections by `tokens`, its scan by `ssd_pairs` (C.B and the
    masked product over x, a pair of one chunk and one document) and by
    `tokens` (the state a chunk leaves and the state a token reads). A kda
    layer (vitax/models/kda.py): its five projections and two head-wise
    ones by `tokens`, its delta rule by `kda_pairs` (the two score products,
    the triangular solve of the corrected keys and values, and the
    intra-chunk output) and by `tokens` (the three products with the
    state). A linear_attention layer (Gated DeltaNet, the same rule at
    gdn_key_size keys and gdn_value_size values): its projections by
    `tokens`; by `kda_pairs` the two score products over the key (4K), the
    solve of the corrected keys and values (2K + 2V) and the intra-chunk
    output (2V); by `tokens` the three products with the state (6KV). A
    latent_attention layer: its projections by `tokens`, QK^T at
    qk_nope_size + qk_rope_size and PV at v_head_size by `causal_pairs`.
    A conv layer (vitax/models/gconv.py): its two projections by `tokens`
    (the two gates and the taps are no matrix products). An expert gated by
    relu (a ReGLU) counts as one gated by silu: the three products run over
    every hidden unit, whatever the gate then leaves at 0; an early router
    (`route_early`) costs what a late one does.
    Padding, the masked part of a block and sorted rows no held expert owns
    are not counted."""
    d, dh = cfg.embed_dim, cfg.head_size
    inner, gn = cfg.ssm_heads * cfg.ssm_head_size, \
        cfg.ssm_groups * cfg.ssm_state_size
    fwd = 0.0
    for kind, heads, mlp in zip(cfg.layer_kinds, cfg.layer_heads,
                                cfg.layer_mlps):
        if kind == "mamba":
            per_token = 2 * d * (2 * inner + 2 * gn + cfg.ssm_heads)
            per_token += 2 * inner * d
            per_token += 4 * inner * cfg.ssm_state_size
            fwd += 2 * (gn + inner) * ssd_pairs
        elif kind == "kda":
            per_token = 2 * d * heads * (5 * dh + 2)
            per_token += 6 * heads * dh * dh
            fwd += 10 * heads * dh * kda_pairs
        elif kind == "linear_attention":
            dk, dv = cfg.gdn_key_size, cfg.gdn_value_size
            per_token = 2 * d * heads * (2 * dk + 3 * dv + 2)
            per_token += 6 * heads * dk * dv
            fwd += heads * (6 * dk + 4 * dv) * kda_pairs
        elif kind == "conv":
            per_token = 2 * d * 3 * d + 2 * d * d
        elif kind == "latent_attention":
            qk, dv = cfg.qk_nope_size + cfg.qk_rope_size, cfg.v_head_size
            per_token = 2 * d * (heads * qk + cfg.latent_rank
                                 + cfg.qk_rope_size + heads * dv)
            per_token += 2 * cfg.latent_rank * heads * (cfg.qk_nope_size + dv)
            per_token += 2 * d * heads if cfg.head_gate else 0
            fwd += 2 * causal_pairs * heads * (qk + dv)
        else:
            per_token = 2 * (2 * d * heads * dh + 2 * d * cfg.kv_heads * dh)
            per_token += 2 * d * heads if cfg.head_gate else 0
            pairs = (window_pairs if kind == "sliding_attention"
                     else causal_pairs)
            fwd += 2 * 2 * pairs * heads * dh
        if mlp == "dense":
            per_token += 2 * 3 * d * cfg.ffn_dim
        else:
            per_token += 2 * d * cfg.experts_routed
            per_token += 2 * 3 * d * cfg.shared_expert_dim
        fwd += per_token * tokens
    fwd += 2 * 3 * d * cfg.expert_dim * expert_slots
    fwd += 2 * d * cfg.vocab_rows * targets
    return 3.0 * fwd


def model_flops_per_step(cfg) -> float:
    """Useful FLOPs of one optimizer step = per-image x global batch.
    Invariant under --grad_accum_steps and --pp_microbatches (see module
    docstring)."""
    return model_flops_per_image(cfg) * cfg.batch_size


def mfu(cfg, sec_per_iter: float, n_devices: int,
        peak_tflops_per_chip: Optional[float],
        flops_per_step: Optional[float] = None) -> Optional[float]:
    """MFU in [0, 1]: achieved useful FLOP/s over aggregate peak FLOP/s.
    None where there is no peak to be a share of (a CPU run).
    `flops_per_step`: the step's own count (a packed batch's, which no
    config gives); default: the closed form of the fixed-size model."""
    if peak_tflops_per_chip is None:
        return None
    if sec_per_iter <= 0 or n_devices <= 0 or peak_tflops_per_chip <= 0:
        return 0.0
    if flops_per_step is None:
        flops_per_step = model_flops_per_step(cfg)
    achieved = flops_per_step / sec_per_iter
    return achieved / (peak_tflops_per_chip * 1e12 * n_devices)
