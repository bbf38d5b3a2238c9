"""Configuration: CLI flags and typed config.

Keeps the reference's exact 26-flag surface (names, defaults, and the ``--no_X`` /
store_false idiom) as a compatibility contract (reference run_vit_training.py:327-363),
plus vitax-specific extensions that default to reference-equivalent behavior.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass
class Config:
    """Typed training configuration.

    The first group mirrors the reference CLI one-to-one
    (reference run_vit_training.py:329-361). The ``vitax:`` group adds
    TPU-native knobs (mesh shape, dtype, kernels) with conservative defaults.
    """

    # --- data / io (reference :329-337) ---
    data_dir: str = "/datasets/imagenet-1k"
    fake_data: bool = False
    num_workers: int = 4
    prefetch_batches: int = 2           # host-prefetch depth of ShardedLoader (queued decoded batches)
    data_format: str = "imagefolder"    # imagefolder = per-file directory scan (reference parity);
    #   stream = .vtxshard streaming containers (vitax/data/stream/ — pack
    #   with tools/make_shards.py, point --data_dir at the shard root)
    stream_prefetch: int = 2            # host-prefetch depth of the streaming loader (>= 1)
    ckpt_dir: str = "/tmp/vit_fsdp"
    resume_epoch: int = 0               # N = resume from epoch N; -1 = auto-resume latest checkpoint
    ckpt_epoch_interval: int = 10
    zero_stall_ckpt: bool = False       # route saves through the zero-stall snapshot pipeline
    #   (vitax/checkpoint/snapshot.py): device->host staging is the only
    #   part on the loop thread; serialization + the Orbax write run on a
    #   background worker, so step N+1 dispatches immediately (ckpt_stall_s
    #   telemetry pins the stall ~0). The step program is bit-identical
    #   with this flag on or off.
    replicate_steps: int = 0            # >0: every N steps, mirror this host's staged state shard
    #   (checksummed, versioned) to its ring-buddy host over the
    #   coordination-service KV — after a lost host, elastic resume
    #   restores from the surviving buddy with ZERO shared-storage reads
    #   (vitax/checkpoint/peer.py). 0 = replication off.
    peer_dir: str = ""                  # local peer-store root (default <ckpt_dir>/peerstore;
    #   VITAX_PEER_DIR env overrides — point it at per-host scratch in
    #   production, NOT shared storage)
    keep_checkpoints: int = 0           # >0: checkpoint GC — prune committed epoch dirs beyond the
    #   newest K after each successful save (torn dirs never touched);
    #   0 = keep all (default)
    test_epoch_interval: int = 10
    log_step_interval: int = 20

    # --- model shape (reference :339-348; defaults = the 10.078B ViT) ---
    image_size: int = 224
    patch_size: int = 14
    embed_dim: int = 5120
    num_heads: int = 32
    num_blocks: int = 32
    mlp_ratio: float = 4.0
    mlp_dim: int = 0                    # MLP width as a number; 0 = int(embed_dim * mlp_ratio).
    #   For a published width that is no ratio of the model's (MoonViT: 4304
    #   at 1152 wide; 1152 * 3.7361 is 4303)
    # Native-resolution packed model (MoonViT; vitax/models/vit.py,
    # vitax/data/packing.py): pack_tokens > 0 selects it. A batch is then
    # `batch_size` ROWS of pack_tokens pre-cut patches, each row holding up to
    # pack_images whole images of different grids back to back, attended
    # within each image only (vitax/ops/flash_blocked.py). These are the
    # model's shape; none chooses a kernel, a block size or a layout.
    pack_tokens: int = 0                # tokens a packed row (T); 0 = the fixed-size dense model
    pack_images: int = 0                # images a packed row can hold (S)
    max_image_tokens: int = 0           # per-image token limit (MoonViT's in_token_limit: 4096)
    pos_grid: int = 0                   # side of the learned position table, resized bicubically
    #   to each image's grid (MoonViT: 64)
    rope_base: float = 10000.0          # base of the 2D rotary embedding on q and k
    # The token decoder family (vitax/models/decoder.py): model_family
    # "decoder" selects it. A batch is `batch_size` rows of pack_tokens token
    # ids holding up to pack_images whole DOCUMENTS back to back
    # (vitax/data/packing.py: document_layout); embed_dim, num_blocks and
    # the fields below are the model's shape under the program's own names.
    # One chip may hold a share of a deployment in which several chips share
    # each layer: `experts_held` of `experts_routed` experts from
    # `expert_first` on, and `vocab_rows` rows of the vocabulary.
    model_family: str = "vit"           # "vit" | "decoder"
    vocab_rows: int = 0                 # rows of the embedding and of the untied head held here
    kv_heads: int = 0                   # key/value heads, each serving layer_heads[i] / kv_heads query heads
    head_size: int = 0                  # width of one head (not embed_dim / heads: q is heads * head_size wide)
    # per layer: "full_attention" (or "attention") | "sliding_attention" |
    # "mamba" | "kda" | "latent_attention" | "linear_attention" | "conv"
    layer_kinds: Tuple[str, ...] = ()
    layer_heads: Tuple[int, ...] = ()   # per layer: query heads, a delta-rule layer's heads (0: mamba, conv)
    layer_mlps: Tuple[str, ...] = ()    # per layer: "dense" | "sparse"
    window_tokens: int = 0              # keys a sliding layer's query sees, its own position included
    ffn_dim: int = 0                    # width of a dense layer's SwiGLU
    expert_dim: int = 0                 # width of one routed expert's SwiGLU
    shared_expert_dim: int = 0          # width of the shared expert every token takes (0 = none)
    experts_routed: int = 0             # experts the router scores (the deployment's count)
    experts_held: int = 0               # experts this chip holds and computes
    expert_first: int = 0               # index of the first held expert among the routed ones
    experts_per_token: int = 0          # experts a token is sent to, chosen over ALL routed ones
    routed_scale: float = 1.0           # factor on the normalised routed weights
    head_gate: bool = False             # sigmoid gate on the attention output, one scalar a head
    norm_eps: float = 1e-6              # RMSNorm epsilon
    rope_theta_full: float = 10000.0    # full layers: RoPE base ...
    rope_fraction_full: float = 1.0     # ... and the leading share of a head it rotates (0: full layers rotate nothing)
    yarn_factor: float = 1.0            # full layers: YaRN context extension (1 = plain RoPE) ...
    yarn_orig_len: int = 0              # ... from this many positions ...
    yarn_beta_fast: float = 32.0        # ... between these two rotation counts ...
    yarn_beta_slow: float = 1.0
    yarn_attn_factor: float = 1.0       # ... with this factor on cos and sin
    rope_theta_window: float = 10000.0  # sliding layers: plain RoPE base ...
    rope_fraction_window: float = 1.0   # ... and rotated share (0: nothing)
    position_embedding: str = "rope"    # "rope" | "nope": nope rotates nothing, in any layer
    tie_embeddings: bool = False        # the head is the embedding table itself (no lm_head leaf)
    embedding_multiplier: float = 1.0   # factor on the embedded tokens
    residual_multiplier: float = 1.0    # factor on what each half of a layer adds to the stream
    attention_multiplier: float = 0.0   # factor on the attention scores; 0 = head_size ** -0.5
    logits_scaling: float = 1.0         # the logits are divided by this
    # A "mamba" layer's mixer (Mamba-2 in its chunked dual form, SSD;
    # vitax/models/ssm.py): ssm_heads heads of ssm_head_size carry a state of
    # ssm_state_size each, B and C shared by the heads of one of ssm_groups
    # groups, behind a depthwise causal convolution of ssm_conv_width taps
    ssm_heads: int = 0
    ssm_head_size: int = 0
    ssm_state_size: int = 0
    ssm_conv_width: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 0                  # tokens a chunk of the scan; pack_tokens is a multiple of it
    # A "kda" layer's mixer (Kimi Delta Attention, a delta rule with a decay
    # per channel, in chunked form; vitax/models/kda.py): layer_heads[i] heads
    # of head_size behind depthwise causal convolutions of kda_conv_width
    # taps, the log-decay held inside (kda_gate_bound, 0)
    kda_conv_width: int = 0
    kda_gate_bound: float = 0.0         # < 0 in a model with kda layers
    # A "linear_attention" layer's mixer (Gated DeltaNet: the same delta rule
    # with ONE unbounded decay a head; vitax/models/kda.py): layer_heads[i]
    # heads of gdn_key_size keys and gdn_value_size values, a state of
    # key x value a head, behind one depthwise causal convolution of
    # gdn_conv_width taps
    gdn_key_size: int = 0
    gdn_value_size: int = 0
    gdn_conv_width: int = 0
    # A "conv" layer's mixer (LFM2's gated short convolution,
    # vitax/models/gconv.py): W_out[C * conv(B * x)] with B, C and x three
    # embed_dim-wide parts of one projection, a depthwise causal convolution
    # of gconv_width taps between the two gates, no activation, no state
    gconv_width: int = 0
    # The block's form (shapes of the model, not knobs): norm_after puts the
    # two RMSNorms on what each half of a layer ADDS (Olmo 2's block; a half
    # reads the raw residual stream); qk_norm RMS-norms q and k of a full or
    # sliding layer over the whole projected width before the heads are
    # split; head_norm RMS-norms them over each head's head_size channels
    # (one weight of head_size for q, one for k) after the split and before
    # the rotation
    norm_after: bool = False
    qk_norm: bool = False
    head_norm: bool = False
    # A "latent_attention" layer (MLA): keys and values come up from a normed
    # latent of latent_rank; a head's query and key are qk_nope_size +
    # qk_rope_size wide, the rotated part of the key one for all heads; its
    # value is v_head_size wide
    latent_rank: int = 0
    qk_nope_size: int = 0
    qk_rope_size: int = 0
    v_head_size: int = 0
    # The router of a sparse layer may choose inside groups: the experts in
    # route_groups equal groups, a group scored by the sum of its two best
    # (biased) scores, groups_per_token groups kept, the experts_per_token
    # best experts inside them; route_bias adds a float32 bias a routed
    # expert to the scores for CHOOSING only (0 groups, no bias: a plain
    # top-K over all experts); route_weight_eps is what the model adds to
    # the sum of the chosen scores that normalises the routed weights
    route_groups: int = 0
    groups_per_token: int = 0
    route_bias: bool = False
    route_weight_eps: float = 0.0
    # ... or take the experts_per_token largest LOGITS and a softmax over
    # those alone (route_form softmax_chosen: no bias, no groups), read the
    # layer's FIRST norm's output, what its mixer reads (route_early; the
    # experts still read the second norm's), and gate its experts by relu
    # in place of silu (expert_activation: a ReGLU). SmallThinker's three
    route_form: str = "sigmoid"         # "sigmoid" | "softmax_chosen"
    route_early: bool = False
    expert_activation: str = "silu"     # "silu" | "relu"
    pos_dropout: float = 0.0
    # NOTE: att_dropout > 0 stays on the fused kernels — every attention path
    # (whole-N, streamed, ring/ulysses sp, and their pipeline bodies at tp=1)
    # carries an in-kernel counter-hash dropout variant (vitax/ops/attention.py
    # dropout_keep_mask). The one remaining dense O(N^2) surface is the
    # pipeline body under tp > 1 (vitax/parallel/pipeline.py asserts on it).
    att_dropout: float = 0.0
    mlp_dropout: float = 0.0
    num_classes: int = 1000

    # --- optimization (reference :351-356) ---
    batch_size: int = 1024
    num_epochs: int = 300
    lr: float = 1e-3
    weight_decay: float = 0.1
    clip_grad_norm: float = 1.0
    warmup_steps: int = 10000

    # --- parallelism toggles (reference :357-361) ---
    grad_ckpt: bool = True              # --no_grad_ckpt clears
    reshard_after_forward: bool = True  # --no_reshard_after_forward clears (ZeRO-3 -> ZeRO-2)
    flatten_parameters: bool = False    # accepted for parity; a no-op under GSPMD (see parallel/sharding.py)
    run_without_fsdp: bool = False      # pure data-parallel baseline (params replicated)
    shard_on_cpu: bool = False          # host-side init + per-shard device_put (10B+ init w/o HBM OOM)

    # --- vitax: TPU-native extensions (all default to reference-equivalent behavior) ---
    seed: int = 0
    grad_accum_steps: int = 1           # K > 1: lax.scan over K microbatches of B/K inside the
    #   jitted step — one clip + AdamW update per loader batch, fp32 grad
    #   accumulators, peak activations ~ one microbatch (vitax/train/step.py)
    dtype: str = "bfloat16"             # compute dtype; params/opt state stay float32
    # Communication precision (vitax/parallel/sharding.py cast_to_compute):
    #   param_gather_dtype: dtype the FSDP collectives move for params. None
    #   resolves to --dtype, so the default bf16 run gathers bf16 (half the
    #   collective bytes) while --dtype float32 runs are untouched. Casting the
    #   *shards* before the gather commutes with the gather, so the forward is
    #   bitwise-identical to gather-then-cast; master params stay f32.
    #   grad_reduce_dtype: dtype the grad reduce-scatter / all-reduce moves.
    #   float32 (default) upcasts each device's bf16 partial before the
    #   reduction — exactly the current numerics; bfloat16 pins the reduction
    #   on bf16 bits for another 2x on grad comm (opt-in precision trade).
    param_gather_dtype: Optional[str] = None  # None -> follow --dtype
    grad_reduce_dtype: str = "float32"
    # Gather/compute overlap (vitax/models/vit.py make_overlap_forward):
    #   an explicit double-buffered gather schedule for the ZeRO-3 block scan.
    #   The scan carry holds the already-gathered params for block k while the
    #   body issues the all-gather (over "fsdp") for block k+1, so the
    #   collective overlaps block k's matmuls instead of serializing in front
    #   of them (XLA's latency-hiding scheduler cannot hoist a gather across a
    #   lax.scan iteration boundary). Its backward computes and
    #   reduce-scatters each block matrix's gradient in one ring over "fsdp"
    #   (vitax/parallel/sharding.py ring_weight_grad). auto = enable when
    #   ZeRO-3 + scanned blocks + per-block remat (none_saveable) are active
    #   (sharding.gather_overlap_active); off = the exact
    #   pre-overlap program; on = require it (validate() rejects configs the
    #   schedule cannot serve: pp, ZeRO-2/DP, unscanned blocks, no-remat).
    gather_overlap: str = "auto"        # auto | off | on
    use_flash_attention: bool = True    # Pallas flash-attention kernel on TPU (jnp fallback elsewhere)
    # Fused clip+AdamW optimizer (vitax/ops/fused_optimizer.py): one Pallas
    #   pass over the sharded state instead of the optax tree-of-ops. auto =
    #   on exactly when the kernels lower to real Mosaic (TPU backend, or
    #   VITAX_FORCE_MOSAIC=1 AOT compiles); on = force it anywhere (Pallas
    #   interpret mode off-TPU — the CI equivalence arms); off = the exact
    #   optax chain.
    fused_optimizer: str = "auto"       # auto | off | on
    # Mesh: (dp, fsdp, tp, sp). -1 on fsdp means "all remaining devices".
    dp_size: int = 1
    fsdp_size: int = -1
    tp_size: int = 1
    sp_size: int = 1
    sp_impl: str = "ring"               # ring (ppermute K/V rotation) | ulysses (all-to-all head<->token)
    pp_size: int = 1                    # pipeline stages (GPipe over the stacked layer axis; composes with dp and fsdp)
    pp_microbatches: int = 0            # GPipe microbatches per step (0 = pp_size; bubble = (S-1)/(M+S-1))
    ep_size: int = 1                    # expert-parallel axis (also carries batch; experts sharded across it)
    moe_experts: int = 0                # 0 = dense reference MLP; >0 = top-1 MoE in every block
    moe_capacity_factor: float = 1.25   # static expert capacity C = ceil(cf * tokens / experts)
    moe_top_k: int = 1                  # 1 = Switch (top-1); 2 = GShard-style top-2 with renormalized gates
    moe_aux_weight: float = 0.01        # load-balance aux loss weight (Switch Transformer)
    scan_blocks: bool = True            # lax.scan over stacked block params (one compile for L blocks)
    scan_unroll: int = 1                # blocks per scan step: >1 frees XLA to fuse across blocks
    #   (the scan's per-block dus-stacking constrains wgrad fusion layouts —
    #   measured l14/v5e: full unroll +29% step throughput; partial unroll
    #   keeps the stacked param tree and O(L/unroll) compile)
    remat_window: int = 0               # >1: remat around GROUPS of this many blocks (functional scan;
    #   saved residuals dus-stack once per group instead of per block — the
    #   wgrad-fusion experiment for the measured 85-100 TF/s stacking ceiling)
    device_normalize: bool = True       # ship uint8 batches; normalize on-device (4x less host->device traffic)
    # none_saveable = the reference's checkpoint_module semantics (recompute
    # everything) and the least HBM — the right default for the 10B+ flagship.
    # Seen on v5e l14 before the ledger (a hand-built program at batch 32):
    # dots_attn_saveable 192.9 > dots_saveable 190.2 > none_saveable ~183
    # img/s/chip. A prior for ROADMAP A2, not a ledger number.
    # From an attention span (patches, or a packed row) of 1,024 tokens on,
    # none_saveable also keeps the attention kernel's o and lse (re-running
    # it costs N_kv FLOPs a byte kept; 1,024 is 4x a v5e's ridge of 240
    # FLOP/B: vitax/models/vit.py ATTN_KEEP_MIN_SPAN).
    remat_policy: str = "none_saveable" # none_saveable | dots_saveable | dots_attn_saveable (only if grad_ckpt)
    profile_dir: str = ""               # if set, capture a jax.profiler trace of a few steps
    profile_start_step: int = 2         # global step the profiler window opens after (with --profile_dir)
    profile_num_steps: int = 5          # steps the profiler window spans (historical default: steps 3-7)
    # --- vitax: telemetry (vitax/telemetry/; all host-side — the compiled
    # step program is identical with telemetry on or off) ---
    metrics_dir: str = ""               # if set, write one JSONL record per log step (schema 1:
    #   loss, lr, sec/iter, images/s, tokens/s, data-wait, MFU, HBM) under
    #   <metrics_dir>/metrics.jsonl; summarize with tools/metrics_report.py
    tensorboard: bool = False           # mirror step records as TB scalars under <metrics_dir>/tb
    #   (no-op with a warning when the tensorboard package is absent)
    peak_tflops: float = 0.0            # per-chip peak TFLOP/s for MFU; 0 = detect from the device
    #   kind (vitax/telemetry/flops.py PEAK_TFLOPS table)
    hang_timeout_s: float = 0.0         # >0: heartbeat watchdog — dump all-thread stacks + device
    #   memory (rank-tagged, job left running) after this many seconds
    #   without a completed step (vitax/telemetry/watchdog.py)
    hang_action: str = "dump"           # dump = stacks only, job left running (PR 4 behavior);
    #   checkpoint_exit = after the dump, emergency-save a committed mid-epoch
    #   checkpoint at the next step boundary and exit with code 42 so a
    #   supervisor (tools/supervise.py) restarts the run; a loop that never
    #   reaches a boundary is hard-exited with the same code after a deadline
    fault_plan: str = ""                # JSON fault-injection plan (vitax/faults.py; or the
    #   VITAX_FAULT_PLAN env var): deterministic crash/hang/write-error/
    #   loader-stall/SIGTERM drills at a chosen step or call site. "" (and
    #   no env var) = every hook is a zero-cost no-op; the compiled step
    #   program is identical either way (all hooks are host-side)
    control_sync_steps: int = 10        # multi-host control-word agreement cadence, in steps
    #   (vitax/train/control.py): SIGTERM/escalation/fault signals agreed
    #   across hosts every N steps (plus every epoch boundary) via one tiny
    #   collective. Hosts must use the same value. Single-host: signals are
    #   checked every step for free and this cadence is moot
    peer_heartbeat_s: float = 0.0       # >0: multi-host peer-liveness heartbeats through the
    #   coordination-service KV store every N seconds; a peer whose beat
    #   stops for peer_grace_s is declared dead and the survivors escalate
    #   to checkpoint_exit (exit 42) instead of blocking in ICI collectives
    #   forever. 0 = liveness off (single-host runs don't need it)
    peer_grace_s: float = 0.0           # silence window before a peer is declared lost, and the
    #   deadline for the survivor's own exit after the verdict; 0 = default
    #   (10 x peer_heartbeat_s)
    arbiter_url: str = ""               # chip-arbiter URL (python -m vitax.arbiter): rank 0 posts
    #   step/progress heartbeats there so borrow policy can gate on
    #   "training is actually progressing". Host-side reporter thread only
    #   (vitax/train/control.py ArbiterReporter) — the compiled step
    #   program is identical with or without it. "" = off
    debug_nans: bool = False            # opt-in jax_debug_nans (SURVEY.md section 5, race-detection analog)
    log_memory: bool = True             # include HBM stats in step log
    steps_per_epoch: int = 0            # override (0 = derive from dataset length // batch_size)
    max_steps: int = 0                  # hard stop after N optimizer steps (0 = no limit; for smoke/bench)
    eval_max_batches: int = 0           # cap val batches per eval (0 = full split, reference behavior)
    # --- vitax: serving (vitax/serve/ — the inference half of the stack) ---
    serve_port: int = 8000              # HTTP port for python -m vitax.serve (0 = ephemeral, tests)
    serve_max_batch: int = 8            # largest micro-batch bucket (power of two); the engine
    #   AOT-compiles every power-of-two bucket 1..serve_max_batch at startup
    #   so steady-state traffic never recompiles (vitax/serve/engine.py)
    max_batch_wait_ms: float = 5.0      # dynamic batcher flush deadline: a queued request waits at
    #   most this long for the bucket to fill (vitax/serve/batcher.py)
    serve_topk: int = 5                 # classes returned per /predict response
    serve_quant_dtype: str = ""         # expected weight quantization of the serve export: "" (full
    #   precision), "int8" or "float8_e4m3" (per-channel weights from
    #   consolidate.py --dtype, dequantized at use inside the jitted
    #   forward — vitax/serve/quant.py). The npz manifest is authoritative;
    #   this flag asserts it, and gates the VTX-R007 invariant arm
    serve_act_quant: str = "off"        # dynamic activation quantization for the serve forward:
    #   "off" or "int8" — per-tensor absmax activation scales computed
    #   inside the jitted forward so eligible matmuls (QKV/proj/MLP in
    #   blocks) run int8 x int8 with a float rescale. Requires
    #   --serve_quant_dtype int8 (int8 weights are the other operand) and
    #   a dense model (MoE dispatch stays float). Gated by the same
    #   quant_gate accuracy event as weight-only int8
    fused_dequant: str = "auto"         # Pallas fused dequant-matmul (vitax/ops/dequant_matmul.py):
    #   fuse weight dequant (+ activation quant when enabled) into the
    #   serve matmul so no dequantized weight block round-trips through
    #   HBM. "auto" = on when serving quantized weights on TPU (dense
    #   model), "on" forces it (interpret mode off-TPU), "off" keeps the
    #   jnp dot path. Pinned by the VTX-R009 invariant
    serve_queue_max: int = 1024         # dynamic batcher queue bound: submit() on a full queue raises
    #   QueueFull, which the single-engine server answers 503 (reason
    #   "queue_full") and the fleet router maps to an admission shed (429)
    #   — the backpressure floor under overload. 0 = unbounded (pre-PR-8)
    serve_request_timeout_s: float = 60.0  # ceiling a /predict handler waits on its batch future before
    #   answering 503: batcher deadline + one engine batch + generous slack
    #   (was the hardcoded REQUEST_TIMEOUT_S); surfaced in /metrics
    serve_brownout_enter_frac: float = 0.75  # brownout trigger: queue depth sustained at or above this
    #   fraction of --serve_queue_max for --serve_brownout_dwell_s enters
    #   degraded mode — topk clamped to 1, batcher deadline shortened to
    #   --serve_brownout_wait_ms, `degraded: true` advertised in /healthz
    #   and /metrics (vitax/serve/server.py BrownoutController). 0 = off
    serve_brownout_exit_frac: float = 0.25  # hysteretic recovery: depth sustained at or below this
    #   fraction for the same dwell exits degraded mode (must be <= the
    #   enter fraction so the two thresholds cannot chatter)
    serve_brownout_dwell_s: float = 2.0 # sustained-pressure window for BOTH brownout transitions:
    #   blips shorter than this never flip the mode
    serve_brownout_wait_ms: float = 1.0 # degraded-mode batcher flush deadline (replaces
    #   --max_batch_wait_ms while browned out; restored on recovery)
    serve_allow_chaos: bool = False     # arm POST /chaos: accepts a fault plan JSON body and
    #   installs it live (vitax/faults.py serve sites) so drills can inject
    #   into running replicas (tools/serve_bench.py --chaos). NEVER enable
    #   on a production replica — the endpoint is deliberately off unless
    #   this flag opts in
    serve_cache_max: int = 0            # router-side content-addressed prediction cache: entries
    #   kept (0 = off). Keyed by SHA-256 of the request bytes + topk;
    #   exact, because AOT-pinned classification is deterministic — a hit
    #   returns the stored bytes verbatim without touching a replica
    serve_cache_ttl_s: float = 300.0    # prediction-cache entry lifetime; expired entries re-dispatch
    #   (bounds staleness across model redeploys that keep the router up)
    serve_batch_window_ms: float = 0.0  # cross-replica continuous batching (fleet router): hold the
    #   first concurrent /predict up to this long to compose a group,
    #   dispatched as ONE /predict_batch to one replica (0 = off).
    #   Counters the least-loaded router spreading co-arrivals so thin
    #   that every replica batcher flushes at batch_size 1
    serve_batch_max: int = 0            # composed-group size cap (0 = use --serve_max_batch, the
    #   largest engine bucket — bigger groups would split anyway)

    # --- scenario registry (vitax/programs/) ---
    task: str = "train"                 # which registered scenario this run executes (train /
    #   finetune / probe / distill); each scenario's validator runs at the
    #   end of validate() (vitax/programs/registry.py)
    init_npz: str = ""                  # finetune warm start: consolidated npz export whose params
    #   overwrite the fresh init leaf-for-leaf (head may re-init)
    teacher_npz: str = ""               # distillation teacher: consolidated npz export served as the
    #   frozen eval-mode tower inside the distill step
    reinit_head: bool = False           # finetune: keep the fresh head init even when the export's
    #   head shapes match (training a new label space of the same size)
    backbone_lr_mult: float = 1.0       # finetune: multiply non-head updates by this after AdamW
    #   (1.0 = off; 0 freezes the backbone — but prefer --task probe, which
    #   also drops the backbone optimizer moments)
    distill_alpha: float = 0.5          # distill loss mix: (1-alpha)*CE(labels) + alpha*KL(teacher)
    distill_temp: float = 2.0           # distill softmax temperature T (KL term scaled by T^2)

    def __post_init__(self) -> None:
        # per-layer lists arrive as lists (a JSON file) or comma-separated
        # strings (a flag); held as tuples, so the dataclass stays hashable
        for name, kind in (("layer_kinds", str), ("layer_heads", int),
                           ("layer_mlps", str)):
            value = getattr(self, name)
            if isinstance(value, str):
                value = [v for v in value.split(",") if v]
            setattr(self, name, tuple(kind(v) for v in value))

    @property
    def resolved_param_gather_dtype(self) -> str:
        """Gather-dtype policy after None -> --dtype resolution."""
        return self.param_gather_dtype or self.dtype

    @property
    def comm_cast_active(self) -> bool:
        """True when params should be downcast (sharded) before FSDP gathers."""
        return self.dtype == "bfloat16" and self.resolved_param_gather_dtype == "bfloat16"

    @property
    def packed(self) -> bool:
        """A model over packed rows (pack_tokens > 0): the native-resolution
        ViT, or the token decoder."""
        return self.pack_tokens > 0

    @property
    def decoder(self) -> bool:
        """The token decoder family (vitax/models/decoder.py)."""
        return self.model_family == "decoder"

    @property
    def num_patches(self) -> int:
        """Tokens a sequence: a packed row's length, or the image's patches."""
        if self.packed:
            return self.pack_tokens
        return (self.image_size // self.patch_size) ** 2

    @property
    def mlp_hidden_dim(self) -> int:
        return self.mlp_dim or int(self.embed_dim * self.mlp_ratio)

    def _validate_packed(self) -> None:
        """Every rule of the packed model's shape, in one place."""
        assert self.pack_images >= 1, (
            f"--pack_tokens {self.pack_tokens} needs --pack_images >= 1 (the "
            f"images a row can hold), got {self.pack_images}")
        assert 0 < self.max_image_tokens <= self.pack_tokens, (
            f"--max_image_tokens must be in (0, pack_tokens={self.pack_tokens}]"
            f", got {self.max_image_tokens}: an image is never split over rows")
        assert self.pos_grid >= 2, (
            f"--pos_grid must be >= 2 (the learned table resized to each "
            f"image's grid), got {self.pos_grid}")
        assert (self.embed_dim // self.num_heads) % 4 == 0, (
            f"2D RoPE rotates pairs by column and row in turn: the head dim "
            f"{self.embed_dim // self.num_heads} must be a multiple of 4")
        assert self.rope_base > 1.0, f"--rope_base must be > 1, got {self.rope_base}"
        assert self.task == "train", (
            f"--pack_tokens trains (--task train); --task {self.task} over "
            f"packed rows is not built")
        assert (self.tp_size == self.sp_size == self.pp_size == self.ep_size
                == 1 and self.moe_experts == 0), (
            "--pack_tokens composes with dp/fsdp only: the packed attention "
            "kernel has no tp/sp/pp path and no MoE block was run packed")
        assert self.pos_dropout == self.att_dropout == self.mlp_dropout == 0.0, (
            "--pack_tokens with dropout is not built (no dropout arm of the "
            "packed kernel)")
        assert self.grad_accum_steps == 1 and self.remat_window <= 1, (
            "--pack_tokens with --grad_accum_steps / --remat_window is not "
            "built: rows hold different numbers of images, so the loss is a "
            "mean over the batch's images, not over microbatches")
        assert self.gather_overlap != "on", (
            "--gather_overlap on is not built for packed rows")

    def _validate_decoder(self) -> None:
        """Every rule of the token decoder's shape, and a sentence for each
        arm of the program it is not built for."""
        n = self.num_blocks
        assert self.pack_tokens > 0 and self.pack_images >= 1, (
            "--model_family decoder trains on packed rows: --pack_tokens (a "
            "row's tokens) and --pack_images (the documents a row can hold) "
            "must be set")
        assert (len(self.layer_kinds) == len(self.layer_heads)
                == len(self.layer_mlps) == n), (
            f"--layer_kinds, --layer_heads and --layer_mlps need one entry "
            f"for each of the {n} layers, got {len(self.layer_kinds)}, "
            f"{len(self.layer_heads)} and {len(self.layer_mlps)}")
        assert set(self.layer_kinds) <= {
            "full_attention", "attention", "sliding_attention",
            "mamba", "kda", "latent_attention",
            "linear_attention", "conv"}, self.layer_kinds
        assert set(self.layer_mlps) <= {"dense", "sparse"}, self.layer_mlps
        assert self.vocab_rows >= 2 and self.head_size >= 2, (
            f"--vocab_rows {self.vocab_rows} and --head_size "
            f"{self.head_size} must be set")
        assert self.kv_heads >= 1 and all(
            h >= self.kv_heads and h % self.kv_heads == 0
            for h, kind in zip(self.layer_heads, self.layer_kinds)
            if kind not in ("mamba", "linear_attention", "conv")), (
            f"every layer's query heads {self.layer_heads} must be a "
            f"multiple of --kv_heads {self.kv_heads}")
        assert self.position_embedding in ("rope", "nope"), (
            f"unknown --position_embedding {self.position_embedding!r} "
            f"(expected 'rope' or 'nope')")
        assert (self.embedding_multiplier > 0 and self.residual_multiplier > 0
                and self.attention_multiplier >= 0
                and self.logits_scaling > 0), (
            "--embedding_multiplier, --residual_multiplier and "
            "--logits_scaling must be > 0, --attention_multiplier >= 0 (0 = "
            "head_size ** -0.5)")
        if "mamba" in self.layer_kinds:
            assert (self.ssm_heads >= 1 and self.ssm_head_size >= 1
                    and self.ssm_state_size >= 1
                    and self.ssm_conv_width >= 1), (
                "a mamba layer needs --ssm_heads, --ssm_head_size, "
                "--ssm_state_size and --ssm_conv_width")
            assert (self.ssm_groups >= 1
                    and self.ssm_heads % self.ssm_groups == 0), (
                f"--ssm_heads {self.ssm_heads} must be a multiple of "
                f"--ssm_groups {self.ssm_groups}")
            assert (self.ssm_chunk >= 1
                    and self.pack_tokens % self.ssm_chunk == 0), (
                f"--pack_tokens {self.pack_tokens} must be a multiple of "
                f"--ssm_chunk {self.ssm_chunk}, the tokens a chunk of the "
                f"scan")
        if "kda" in self.layer_kinds:
            assert self.kda_conv_width >= 1 and self.kda_gate_bound < 0, (
                "a kda layer needs --kda_conv_width >= 1 and "
                "--kda_gate_bound < 0, the lower bound of its log-decay")
        if "linear_attention" in self.layer_kinds:
            assert (self.gdn_key_size >= 1 and self.gdn_value_size >= 1
                    and self.gdn_conv_width >= 1), (
                "a linear_attention layer needs --gdn_key_size, "
                "--gdn_value_size and --gdn_conv_width")
            assert all(h >= 1 for h, kind in zip(
                self.layer_heads, self.layer_kinds)
                if kind == "linear_attention"), (
                f"a linear_attention layer needs heads, got "
                f"{self.layer_heads}")
        if "conv" in self.layer_kinds:
            assert self.gconv_width >= 1, (
                "a conv layer needs --gconv_width, the taps of its "
                "convolution")
        assert not (self.qk_norm and self.head_norm), (
            "--qk_norm (over the whole projected width) and --head_norm (a "
            "head) are two forms of one norm: a model has one")
        assert self.route_weight_eps >= 0, "--route_weight_eps must be >= 0"
        assert (self.route_form in ("sigmoid", "softmax_chosen")
                and self.expert_activation in ("silu", "relu")), (
            f"unknown --route_form {self.route_form!r} (sigmoid | "
            f"softmax_chosen) or --expert_activation "
            f"{self.expert_activation!r} (silu | relu)")
        assert self.route_form == "sigmoid" or not (
            self.route_groups or self.route_bias or self.route_weight_eps), (
            "--route_form softmax_chosen takes the largest logits and a "
            "softmax over them: it has no --route_groups, --route_bias or "
            "--route_weight_eps")
        if "latent_attention" in self.layer_kinds:
            assert (self.latent_rank >= 1 and self.qk_nope_size >= 1
                    and self.qk_rope_size >= 2 and self.v_head_size >= 1), (
                "a latent_attention layer needs --latent_rank, "
                "--qk_nope_size, --qk_rope_size and --v_head_size")
            assert (self.position_embedding == "rope" and self.qk_rope_size
                    == int(self.head_size * self.rope_fraction_full)), (
                f"a latent_attention layer rotates its --qk_rope_size "
                f"{self.qk_rope_size} dimensions with the full layers' "
                f"table: --head_size {self.head_size} x --rope_fraction_full "
                f"{self.rope_fraction_full} must equal it")
        for name in ("rope_fraction_full", "rope_fraction_window"):
            rot = self.head_size * getattr(self, name)
            assert 0 <= rot <= self.head_size and rot == int(rot) \
                and int(rot) % 2 == 0, (
                f"--{name} {getattr(self, name)} must rotate an even number "
                f"of a head's {self.head_size} dimensions (0: the layers of "
                f"that kind rotate nothing, beside a kind that does; "
                f"--position_embedding nope is the model no layer of which "
                f"rotates)")
        assert self.rope_theta_full > 1 and self.rope_theta_window > 1
        assert self.yarn_factor >= 1 and (
            self.yarn_factor == 1 or self.yarn_orig_len > 0), (
            "--yarn_factor > 1 needs --yarn_orig_len, the positions the "
            "model was first trained on")
        if "sliding_attention" in self.layer_kinds:
            assert self.window_tokens >= 1, (
                "a sliding_attention layer needs --window_tokens >= 1")
        if "dense" in self.layer_mlps:
            assert self.ffn_dim >= 1, "a dense layer needs --ffn_dim"
        if "sparse" in self.layer_mlps:
            assert self.expert_dim >= 1, "a sparse layer needs --expert_dim"
            assert 1 <= self.experts_per_token <= self.experts_routed, (
                f"--experts_per_token {self.experts_per_token} must be in "
                f"[1, experts_routed={self.experts_routed}]")
            assert (self.experts_held >= 1 and self.expert_first >= 0
                    and self.expert_first + self.experts_held
                    <= self.experts_routed), (
                f"the held experts [{self.expert_first}, {self.expert_first} "
                f"+ {self.experts_held}) must lie within the "
                f"{self.experts_routed} routed ones")
            if self.route_groups:
                per = self.experts_routed // self.route_groups
                assert (self.experts_routed % self.route_groups == 0
                        and per >= 2
                        and 1 <= self.groups_per_token <= self.route_groups
                        and self.experts_per_token
                        <= self.groups_per_token * per), (
                    f"--route_groups {self.route_groups} must divide "
                    f"--experts_routed {self.experts_routed} into groups of "
                    f"at least 2, and --groups_per_token "
                    f"{self.groups_per_token} of them must hold "
                    f"--experts_per_token {self.experts_per_token} experts")
        assert self.task == "train", (
            f"the decoder trains (--task train); --task {self.task} and "
            f"serving a decoder are not built: the serve path answers images")
        assert self.tp_size == self.sp_size == self.pp_size == 1, (
            "the decoder composes with dp/fsdp only: its attention kernels "
            "have no tensor-, sequence- or pipeline-parallel arm")
        assert self.ep_size == 1 and self.moe_experts == 0, (
            "--ep_size > 1 is not built for the decoder: a chip holds its "
            "share of the experts (--experts_held of --experts_routed) and "
            "runs without the exchange; --moe_experts is the ViT's Switch MLP")
        assert self.pos_dropout == self.att_dropout == self.mlp_dropout == 0.0, (
            "the decoder has no dropout arm")
        assert self.grad_accum_steps == 1 and self.remat_window <= 1, (
            "the decoder with --grad_accum_steps / --remat_window is not "
            "built: rows hold different numbers of targets, so the loss is a "
            "mean over the batch's targets, not over microbatches")
        assert self.gather_overlap != "on", (
            "--gather_overlap on is not built for the decoder")

    def validate(self) -> "Config":
        assert self.image_size % self.patch_size == 0, (
            f"image_size {self.image_size} not divisible by patch_size {self.patch_size}")
        assert self.embed_dim % self.num_heads == 0, (
            f"embed_dim {self.embed_dim} not divisible by num_heads {self.num_heads}")
        assert self.mlp_dim >= 0 and self.pack_tokens >= 0, (
            f"--mlp_dim {self.mlp_dim} and --pack_tokens {self.pack_tokens} "
            f"must be >= 0 (0 = mlp_ratio / the fixed-size model)")
        assert self.model_family in ("vit", "decoder"), (
            f"unknown --model_family {self.model_family!r} (expected 'vit' or "
            f"'decoder')")
        if self.decoder:
            self._validate_decoder()
        elif self.packed:
            self._validate_packed()
        assert self.sp_impl in ("ring", "ulysses"), (
            f"unknown sp_impl {self.sp_impl!r} (expected 'ring' or 'ulysses')")
        for name in ("pos_dropout", "att_dropout", "mlp_dropout"):
            rate = getattr(self, name)
            assert 0.0 <= rate < 1.0, (
                f"--{name} must be in [0, 1), got {rate}: rate >= 1 would "
                f"zero every activation and the kernels' 1/(1-rate) rescale "
                f"turns that into inf/NaN rather than torch's all-zeros")
        assert self.prefetch_batches >= 1, (
            f"--prefetch_batches must be >= 1, got {self.prefetch_batches}: "
            f"the loader needs at least one queued batch to hand the consumer")
        assert self.data_format in ("imagefolder", "stream"), (
            f"unknown data_format {self.data_format!r} "
            f"(expected 'imagefolder' or 'stream')")
        assert self.stream_prefetch >= 1, (
            f"--stream_prefetch must be >= 1, got {self.stream_prefetch}: "
            f"the streaming loader needs at least one queued batch to hand "
            f"the consumer")
        if self.data_format == "stream":
            assert not self.fake_data, (
                "--data_format stream with --fake_data is contradictory: "
                "fake data needs no input pipeline — generate a shard set "
                "from an ImageFolder tree with tools/make_shards.py instead")
            assert self.data_dir, (
                "--data_format stream needs --data_dir pointing at a shard "
                "root (the output of tools/make_shards.py, holding "
                "train/stream_meta.json)")
        assert self.grad_accum_steps >= 1, (
            f"--grad_accum_steps must be >= 1, got {self.grad_accum_steps}")
        assert self.gather_overlap in ("auto", "off", "on"), (
            f"unknown gather_overlap {self.gather_overlap!r} "
            f"(expected 'auto', 'off' or 'on')")
        assert self.fused_optimizer in ("auto", "off", "on"), (
            f"unknown fused_optimizer {self.fused_optimizer!r} "
            f"(expected 'auto', 'off' or 'on')")
        if self.gather_overlap == "on":
            assert self.pp_size == 1, (
                "--gather_overlap on with --pp_size > 1 is rejected: the "
                "pipeline schedules own their gathers (just-in-time in-body "
                "gathers pinned per stage, vitax/parallel/pipeline.py) and a "
                "second prefetch schedule would double-gather every block")
            assert self.scan_blocks, (
                "--gather_overlap on needs the scanned stacked block tree "
                "(drop --no_scan_blocks): the double-buffered prefetch slot "
                "rides the scan carry")
            assert self.reshard_after_forward and not self.run_without_fsdp, (
                "--gather_overlap on needs ZeRO-3 (per-block gathers): under "
                "ZeRO-2 (--no_reshard_after_forward) the whole tree is "
                "gathered once at the step top and under --run_without_fsdp "
                "params are replicated — there is no per-block gather to "
                "overlap")
            assert self.grad_ckpt and self.remat_policy == "none_saveable", (
                "--gather_overlap on requires --grad_ckpt with "
                "remat_policy=none_saveable: the schedule's backward "
                "re-gathers each block's shards and recomputes its forward "
                "(exactly per-block remat); other policies save residuals "
                "the overlap path would silently discard")
        if self.grad_accum_steps > 1:
            assert self.batch_size % self.grad_accum_steps == 0, (
                f"--batch_size {self.batch_size} not divisible by "
                f"--grad_accum_steps {self.grad_accum_steps}: the global "
                f"batch is reshaped to (K, B/K, ...) inside the step")
            assert self.pp_size == 1, (
                "--grad_accum_steps > 1 with --pp_size > 1 is rejected: the "
                "pipeline already microbatches the step (--pp_microbatches) "
                "and nesting a second accumulation scan around it would "
                "double-count the memory/bubble trade — raise "
                "--pp_microbatches instead")
        assert self.scan_unroll >= 1, (
            f"--scan_unroll must be >= 1, got {self.scan_unroll}")
        if self.remat_window > 1:
            assert self.scan_blocks and self.grad_ckpt, (
                "--remat_window needs the scanned stacked tree and remat on")
            assert self.num_blocks % self.remat_window == 0, (
                f"--num_blocks {self.num_blocks} not divisible by "
                f"--remat_window {self.remat_window}")
            assert self.scan_unroll == 1, (
                "--remat_window subsumes --scan_unroll (the window IS the "
                "unrolled group); drop one of the two")
            assert self.pp_size == 1, (
                "--remat_window composes with dropout and MoE (v2) but not "
                "pp: the pipeline path owns checkpoint placement "
                "(vitax/parallel/pipeline.py)")
        if self.pp_size > 1:
            assert self.scan_blocks, "--pp_size needs the stacked block tree (drop --no_scan_blocks)"
            assert self.reshard_after_forward or self.fsdp_size == 1, (
                "--no_reshard_after_forward (ZeRO-2) under --pp_size > 1 "
                "with fsdp sharding is not supported: the pipeline body "
                "gathers each block's shards just-in-time (ZeRO-3 "
                "semantics) and a step-top full gather would defeat that. "
                "With --fsdp_size 1 the flag is a no-op and allowed; "
                "--fsdp_size -1 is treated as sharded here (validate() runs "
                "before the device count is known) — pass an explicit "
                "--fsdp_size 1 if the remaining mesh is a single device")
            assert self.num_blocks % self.pp_size == 0, (
                f"--num_blocks {self.num_blocks} not divisible by --pp_size {self.pp_size}")
            assert self.pp_microbatches >= 0
            if self.moe_experts > 0:
                assert self.tp_size == 1 and self.sp_size == 1, (
                    "--moe_experts under --pp_size > 1 composes with "
                    "dp/fsdp/ep only: the MoE dispatch einsums inside the "
                    "pipeline body are not exercised under auto-tp/sp meshes")
        if self.ep_size > 1:
            assert self.moe_experts > 0, "--ep_size > 1 needs --moe_experts"
            assert self.moe_experts % self.ep_size == 0, (
                f"--moe_experts {self.moe_experts} not divisible by "
                f"--ep_size {self.ep_size}")
        if self.moe_experts > 0:
            assert self.moe_top_k in (1, 2), self.moe_top_k
            assert self.moe_top_k <= self.moe_experts, (
                f"--moe_top_k {self.moe_top_k} > --moe_experts "
                f"{self.moe_experts}: the second choice would be a dead "
                f"branch with gate ~0")
        assert self.profile_start_step >= 0, (
            f"--profile_start_step must be >= 0, got {self.profile_start_step}")
        assert self.profile_num_steps >= 1, (
            f"--profile_num_steps must be >= 1, got {self.profile_num_steps}: "
            f"an empty profiler window would open a trace it never closes "
            f"in-loop")
        assert self.peak_tflops >= 0, (
            f"--peak_tflops must be >= 0 (0 = detect from device kind), "
            f"got {self.peak_tflops}")
        assert self.hang_timeout_s >= 0, (
            f"--hang_timeout_s must be >= 0 (0 = watchdog off), "
            f"got {self.hang_timeout_s}")
        assert self.hang_action in ("dump", "checkpoint_exit"), (
            f"unknown hang_action {self.hang_action!r} (expected 'dump' or "
            f"'checkpoint_exit')")
        if self.fault_plan:
            from vitax import faults
            try:  # fail at startup, not at the step the plan names
                faults.parse_plan(self.fault_plan)
            except ValueError as e:
                raise AssertionError(f"--fault_plan invalid: {e}") from e
        assert self.control_sync_steps >= 1, (
            f"--control_sync_steps must be >= 1 (it is a collective cadence "
            f"every host shares), got {self.control_sync_steps}")
        assert self.peer_heartbeat_s >= 0, (
            f"--peer_heartbeat_s must be >= 0 (0 = liveness off), "
            f"got {self.peer_heartbeat_s}")
        assert self.peer_grace_s >= 0, (
            f"--peer_grace_s must be >= 0 (0 = 10 x peer_heartbeat_s), "
            f"got {self.peer_grace_s}")
        assert not (self.peer_grace_s > 0 and self.peer_heartbeat_s == 0), (
            "--peer_grace_s without --peer_heartbeat_s does nothing: the "
            "grace window bounds heartbeat silence, and no heartbeats are "
            "being sent")
        assert self.replicate_steps >= 0, (
            f"--replicate_steps must be >= 0 (0 = peer replication off), "
            f"got {self.replicate_steps}")
        assert self.keep_checkpoints >= 0, (
            f"--keep_checkpoints must be >= 0 (0 = keep all), "
            f"got {self.keep_checkpoints}")
        assert not (self.peer_dir and self.replicate_steps == 0), (
            "--peer_dir without --replicate_steps does nothing: the peer "
            "store is only written by the replication window")
        if self.tensorboard:
            assert self.metrics_dir, (
                "--tensorboard needs --metrics_dir: the TB event files live "
                "under <metrics_dir>/tb next to the JSONL record they mirror")
        assert self.eval_max_batches >= 0, (
            f"--eval_max_batches must be >= 0 (0 = evaluate the full val "
            f"split), got {self.eval_max_batches}: a negative cap would "
            f"silently skip evaluation entirely")
        assert 0 <= self.serve_port <= 65535, (
            f"--serve_port must be in [0, 65535] (0 = ephemeral port, for "
            f"tests), got {self.serve_port}")
        assert self.serve_max_batch >= 1 and (
            self.serve_max_batch & (self.serve_max_batch - 1)) == 0, (
            f"--serve_max_batch must be a power of two >= 1, got "
            f"{self.serve_max_batch}: the engine pads requests to "
            f"power-of-two buckets (1, 2, 4, ...) and AOT-compiles each one "
            f"at startup — a non-power-of-two cap would leave its own "
            f"bucket uncompiled")
        assert self.max_batch_wait_ms >= 0, (
            f"--max_batch_wait_ms must be >= 0 (0 = flush every request "
            f"immediately), got {self.max_batch_wait_ms}")
        assert self.serve_quant_dtype in ("", "int8", "float8_e4m3"), (
            f"--serve_quant_dtype must be '', 'int8' or 'float8_e4m3', got "
            f"{self.serve_quant_dtype!r}: these are the dtypes the __quant__ "
            f"manifest schema implements (vitax/checkpoint/consolidate.py "
            f"QUANT_DTYPES)")
        assert self.serve_act_quant in ("off", "int8"), (
            f"--serve_act_quant must be 'off' or 'int8', got "
            f"{self.serve_act_quant!r}: int8 is the only activation "
            f"quantization implemented (per-tensor dynamic absmax)")
        if self.serve_act_quant != "off":
            assert self.serve_quant_dtype == "int8", (
                f"--serve_act_quant {self.serve_act_quant} requires "
                f"--serve_quant_dtype int8 (int8 x int8 matmuls need int8 "
                f"weights as the other operand), got serve_quant_dtype="
                f"{self.serve_quant_dtype!r}")
            assert self.moe_experts == 0, (
                f"--serve_act_quant is dense-model only (MoE expert dispatch "
                f"keeps its float einsum path), got --moe_experts "
                f"{self.moe_experts}")
        assert self.fused_dequant in ("auto", "on", "off"), (
            f"--fused_dequant must be 'auto', 'on' or 'off', got "
            f"{self.fused_dequant!r}")
        if self.fused_dequant == "on":
            assert self.serve_quant_dtype, (
                f"--fused_dequant on requires a quantized "
                f"--serve_quant_dtype: there is no weight dequant to fuse "
                f"into a full-precision serve matmul")
            assert self.moe_experts == 0, (
                f"--fused_dequant on is dense-model only (MoE expert "
                f"matmuls keep their einsum path), got --moe_experts "
                f"{self.moe_experts}")
        assert self.serve_topk >= 1, (
            f"--serve_topk must be >= 1, got {self.serve_topk}; values above "
            f"num_classes are clamped by the engine at load time "
            f"(vitax/serve/engine.py)")
        assert self.serve_queue_max >= 0, (
            f"--serve_queue_max must be >= 0 (0 = unbounded), got "
            f"{self.serve_queue_max}: the batcher's pending deque is the "
            f"only queue in the serve path and a negative bound is "
            f"meaningless")
        assert self.serve_request_timeout_s > 0, (
            f"--serve_request_timeout_s must be > 0, got "
            f"{self.serve_request_timeout_s}: a /predict handler that waits "
            f"zero seconds on its batch future would answer 503 before the "
            f"batcher could possibly flush")
        assert 0.0 <= self.serve_brownout_enter_frac <= 1.0, (
            f"--serve_brownout_enter_frac must be in [0, 1] (a fraction of "
            f"--serve_queue_max; 0 = brownout off), got "
            f"{self.serve_brownout_enter_frac}")
        if self.serve_brownout_enter_frac > 0:
            assert (0.0 <= self.serve_brownout_exit_frac
                    <= self.serve_brownout_enter_frac), (
                f"--serve_brownout_exit_frac must be in [0, "
                f"enter_frac={self.serve_brownout_enter_frac}], got "
                f"{self.serve_brownout_exit_frac}: an exit threshold above "
                f"the enter threshold would make the hysteresis chatter")
        assert self.serve_brownout_dwell_s >= 0, (
            f"--serve_brownout_dwell_s must be >= 0, got "
            f"{self.serve_brownout_dwell_s}")
        assert self.serve_cache_max >= 0, (
            f"--serve_cache_max must be >= 0 (0 = prediction cache off), "
            f"got {self.serve_cache_max}")
        assert self.serve_cache_ttl_s > 0, (
            f"--serve_cache_ttl_s must be > 0, got {self.serve_cache_ttl_s}: "
            f"a cache that never expires would replay answers across model "
            f"redeploys; disable the cache with --serve_cache_max 0 instead")
        assert self.serve_batch_window_ms >= 0, (
            f"--serve_batch_window_ms must be >= 0 (0 = cross-replica "
            f"continuous batching off), got {self.serve_batch_window_ms}")
        assert self.serve_batch_max >= 0, (
            f"--serve_batch_max must be >= 0 (0 = use --serve_max_batch), "
            f"got {self.serve_batch_max}")
        assert self.serve_brownout_wait_ms >= 0, (
            f"--serve_brownout_wait_ms must be >= 0 (0 = flush every "
            f"request immediately while degraded), got "
            f"{self.serve_brownout_wait_ms}")
        assert self.resolved_param_gather_dtype in ("bfloat16", "float32"), (
            f"unknown param_gather_dtype {self.param_gather_dtype!r}")
        assert self.grad_reduce_dtype in ("bfloat16", "float32"), (
            f"unknown grad_reduce_dtype {self.grad_reduce_dtype!r}")
        if self.dtype == "float32":
            assert self.param_gather_dtype != "bfloat16", (
                "--param_gather_dtype bfloat16 with --dtype float32 would gather a "
                "downcast tree into an f32 model and silently change compute "
                "precision; use --dtype bfloat16 (f32 master params are kept "
                "either way)")
        if self.grad_reduce_dtype == "bfloat16":
            assert self.comm_cast_active, (
                "--grad_reduce_dtype bfloat16 requires the bf16 comm-cast to be "
                "active (--dtype bfloat16 and param_gather_dtype bfloat16): the "
                "bf16 reduction rides the cast boundary")
        assert 0.0 <= self.distill_alpha <= 1.0, (
            f"--distill_alpha must be in [0, 1] (the CE/KL mix), got "
            f"{self.distill_alpha}")
        assert self.distill_temp > 0, (
            f"--distill_temp must be > 0, got {self.distill_temp}")
        assert self.backbone_lr_mult >= 0, (
            f"--backbone_lr_mult must be >= 0, got {self.backbone_lr_mult}")
        # scenario dispatch: each --task's pairwise flag checks live with its
        # registry entry (vitax/programs/registry.py), not here — this
        # validator stops accreting per-workload blocks
        from vitax.programs.registry import get_scenario
        get_scenario(self.task).validate(self)
        return self


def build_parser() -> argparse.ArgumentParser:
    """Argparse surface: reference flags verbatim + `vitax:`-group extensions."""
    parser = argparse.ArgumentParser(description="vitax: TPU-native large-ViT FSDP training")

    # Reference flag surface (run_vit_training.py:329-361) — names and defaults are a contract.
    parser.add_argument("--data_dir", type=str, default="/datasets/imagenet-1k")
    parser.add_argument("--fake_data", action="store_true", dest="fake_data")
    parser.add_argument("--num_workers", type=int, default=4)
    parser.add_argument("--ckpt_dir", type=str, default="/tmp/vit_fsdp")
    parser.add_argument("--resume_epoch", type=int, default=0)
    parser.add_argument("--ckpt_epoch_interval", type=int, default=10)
    parser.add_argument("--zero_stall_ckpt", action="store_true",
                        dest="zero_stall_ckpt",
                        help="route checkpoint saves through the zero-stall "
                             "snapshot pipeline (vitax/checkpoint/"
                             "snapshot.py): staging on the loop thread, "
                             "serialize + Orbax write on a background "
                             "worker — step N+1 never waits for a "
                             "non-final save")
    parser.add_argument("--replicate_steps", type=int, default=0,
                        help=">0: every N steps, mirror this host's staged "
                             "state shard to its ring-buddy host over the "
                             "coordination-service KV (vitax/checkpoint/"
                             "peer.py) so a lost host restores from the "
                             "surviving buddy without shared storage "
                             "(0 = off)")
    parser.add_argument("--peer_dir", type=str, default="",
                        help="local peer-store root (default <ckpt_dir>/"
                             "peerstore; VITAX_PEER_DIR env overrides) — "
                             "per-host scratch, not shared storage")
    parser.add_argument("--keep_checkpoints", type=int, default=0,
                        help=">0: checkpoint GC — prune committed epoch "
                             "dirs beyond the newest K after each save; "
                             "torn dirs are never touched (0 = keep all)")
    parser.add_argument("--test_epoch_interval", type=int, default=10)
    parser.add_argument("--log_step_interval", type=int, default=20)

    parser.add_argument("--image_size", type=int, default=224)
    parser.add_argument("--patch_size", type=int, default=14)
    parser.add_argument("--embed_dim", type=int, default=5120)
    parser.add_argument("--num_heads", type=int, default=32)
    parser.add_argument("--num_blocks", type=int, default=32)
    parser.add_argument("--mlp_ratio", type=float, default=4.0)
    parser.add_argument("--mlp_dim", type=int, default=0,
                        help="MLP width as a number (0 = embed_dim * "
                             "mlp_ratio)")
    parser.add_argument("--pack_tokens", type=int, default=0,
                        help=">0: the native-resolution packed model "
                             "(MoonViT): --batch_size rows of this many "
                             "pre-cut patches, images of different grids "
                             "back to back, attended within each image")
    parser.add_argument("--pack_images", type=int, default=0,
                        help="images a packed row can hold")
    parser.add_argument("--max_image_tokens", type=int, default=0,
                        help="per-image token limit of the packed model")
    parser.add_argument("--pos_grid", type=int, default=0,
                        help="side of the learned position table the packed "
                             "model resizes to each image's grid")
    parser.add_argument("--rope_base", type=float, default=10000.0,
                        help="base of the packed model's 2D RoPE")
    dec = parser.add_argument_group(
        "decoder", "the token decoder family (vitax/models/decoder.py); "
        "per-layer lists are comma-separated")
    dec.add_argument("--model_family", type=str, default="vit",
                     choices=("vit", "decoder"))
    for name, kind, default, text in (
            ("vocab_rows", int, 0, "vocabulary rows held here"),
            ("kv_heads", int, 0, "key/value heads"),
            ("head_size", int, 0, "width of one head"),
            ("layer_kinds", str, "",
             "full_attention (or attention)|sliding_attention|mamba|kda|"
             "latent_attention|linear_attention|conv a layer"),
            ("layer_heads", str, "",
             "query heads a layer (0 in a mamba or a conv one)"),
            ("layer_mlps", str, "", "dense|sparse a layer"),
            ("window_tokens", int, 0, "keys a sliding layer's query sees"),
            ("ffn_dim", int, 0, "dense SwiGLU width"),
            ("expert_dim", int, 0, "routed expert SwiGLU width"),
            ("shared_expert_dim", int, 0, "shared expert SwiGLU width"),
            ("experts_routed", int, 0, "experts the router scores"),
            ("experts_held", int, 0, "experts this chip holds"),
            ("expert_first", int, 0, "first held expert's index"),
            ("experts_per_token", int, 0, "experts a token is sent to"),
            ("routed_scale", float, 1.0, "factor on the routed weights"),
            ("norm_eps", float, 1e-6, "RMSNorm epsilon"),
            ("rope_theta_full", float, 10000.0, "full layers' RoPE base"),
            ("rope_fraction_full", float, 1.0, "rotated share of a head"),
            ("yarn_factor", float, 1.0, "YaRN factor (1 = plain RoPE)"),
            ("yarn_orig_len", int, 0, "YaRN original positions"),
            ("yarn_beta_fast", float, 32.0, "YaRN fast rotation count"),
            ("yarn_beta_slow", float, 1.0, "YaRN slow rotation count"),
            ("yarn_attn_factor", float, 1.0, "YaRN factor on cos and sin"),
            ("rope_theta_window", float, 10000.0, "sliding layers' RoPE base"),
            ("rope_fraction_window", float, 1.0, "rotated share of a head"),
            ("embedding_multiplier", float, 1.0, "factor on the embedded tokens"),
            ("residual_multiplier", float, 1.0,
             "factor on what each half of a layer adds to the stream"),
            ("attention_multiplier", float, 0.0,
             "factor on the attention scores (0 = head_size ** -0.5)"),
            ("logits_scaling", float, 1.0, "the logits are divided by this"),
            ("ssm_heads", int, 0, "heads of a mamba layer's mixer"),
            ("ssm_head_size", int, 0, "width of one of them"),
            ("ssm_state_size", int, 0, "state a head carries, a channel"),
            ("ssm_conv_width", int, 0, "taps of the mixer's causal convolution"),
            ("ssm_groups", int, 1, "groups of heads that share B and C"),
            ("ssm_chunk", int, 0, "tokens a chunk of the mixer's scan"),
            ("kda_conv_width", int, 0, "taps of a kda layer's convolutions"),
            ("kda_gate_bound", float, 0.0, "lower bound of a kda layer's log-decay"),
            ("gdn_key_size", int, 0, "key width of a linear_attention layer's heads"),
            ("gdn_value_size", int, 0, "value width of its heads"),
            ("gdn_conv_width", int, 0, "taps of its convolution"),
            ("gconv_width", int, 0, "taps of a conv layer's gated convolution"),
            ("latent_rank", int, 0, "width of a latent_attention layer's latent"),
            ("qk_nope_size", int, 0, "unrotated part of its query and key heads"),
            ("qk_rope_size", int, 0, "rotated part (the key's shared by all heads)"),
            ("v_head_size", int, 0, "width of its value heads"),
            ("route_groups", int, 0, "groups the router chooses inside (0 = none)"),
            ("groups_per_token", int, 0, "groups a token's experts come from"),
            ("route_weight_eps", float, 0.0,
             "added to the sum that normalises the routed weights")):
        dec.add_argument(f"--{name}", type=kind, default=default, help=text)
    dec.add_argument("--route_form", type=str, default="sigmoid",
                     choices=("sigmoid", "softmax_chosen"),
                     help="softmax_chosen: the largest logits, and a "
                          "softmax over those alone")
    dec.add_argument("--expert_activation", type=str, default="silu",
                     choices=("silu", "relu"),
                     help="the routed experts' gate: silu (SwiGLU) or relu "
                          "(ReGLU)")
    dec.add_argument("--route_early", action="store_true", dest="route_early",
                     help="the router reads the layer's first norm's output")
    dec.add_argument("--position_embedding", type=str, default="rope",
                     choices=("rope", "nope"),
                     help="nope: attention rotates nothing, in any layer")
    dec.add_argument("--head_gate", action="store_true", dest="head_gate",
                     help="sigmoid gate on the attention output, a head")
    dec.add_argument("--norm_after", action="store_true", dest="norm_after",
                     help="the block norms what each half adds, not its input")
    dec.add_argument("--qk_norm", action="store_true", dest="qk_norm",
                     help="RMSNorm on q and k over the whole projected width")
    dec.add_argument("--head_norm", action="store_true", dest="head_norm",
                     help="RMSNorm on q and k over each head, before the "
                          "rotation")
    dec.add_argument("--route_bias", action="store_true", dest="route_bias",
                     help="a bias a routed expert on the scores that choose")
    dec.add_argument("--tie_embeddings", action="store_true",
                     dest="tie_embeddings",
                     help="the head is the embedding table itself")
    parser.add_argument("--pos_dropout", type=float, default=0.0)
    parser.add_argument("--att_dropout", type=float, default=0.0)
    parser.add_argument("--mlp_dropout", type=float, default=0.0)
    parser.add_argument("--num_classes", type=int, default=1000)

    parser.add_argument("--batch_size", type=int, default=1024)
    parser.add_argument("--num_epochs", type=int, default=300)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--weight_decay", type=float, default=0.1)
    parser.add_argument("--clip_grad_norm", type=float, default=1.0)
    parser.add_argument("--warmup_steps", type=int, default=10000)
    parser.add_argument("--no_grad_ckpt", action="store_false", dest="grad_ckpt")
    parser.add_argument("--no_reshard_after_forward", action="store_false", dest="reshard_after_forward")
    parser.add_argument("--flatten_parameters", action="store_true", dest="flatten_parameters")
    parser.add_argument("--run_without_fsdp", action="store_true", dest="run_without_fsdp")
    parser.add_argument("--shard_on_cpu", action="store_true", dest="shard_on_cpu")

    # vitax extensions
    ext = parser.add_argument_group("vitax")
    ext.add_argument("--seed", type=int, default=0)
    ext.add_argument("--prefetch_batches", type=int, default=2,
                     help="host-prefetch depth: decoded batches the loader "
                          "keeps queued ahead of the training loop (>= 1)")
    ext.add_argument("--data_format", type=str, default="imagefolder",
                     choices=["imagefolder", "stream"],
                     help="input pipeline: imagefolder = per-file directory "
                          "scan (reference parity); stream = .vtxshard "
                          "streaming containers (vitax/data/stream/) — pack "
                          "an ImageFolder tree with tools/make_shards.py "
                          "and point --data_dir at the shard root")
    ext.add_argument("--stream_prefetch", type=int, default=2,
                     help="host-prefetch depth of the streaming loader: "
                          "decoded batches kept queued ahead of the "
                          "training loop (>= 1; --data_format stream)")
    ext.add_argument("--gather_overlap", type=str, default="auto",
                     choices=["auto", "off", "on"],
                     help="double-buffered ZeRO-3 block-param gathers: the "
                          "scan body consumes the already-gathered params for "
                          "block k and issues the all-gather for block k+1, "
                          "overlapping the collective with block k's compute. "
                          "auto (default) = enable under zero3 + scanned "
                          "blocks + none_saveable remat; off = the exact "
                          "pre-overlap program; on = require it (rejected "
                          "under pp / ZeRO-2 / DP / --no_scan_blocks).")
    ext.add_argument("--fused_optimizer", type=str, default="auto",
                     choices=["auto", "off", "on"],
                     help="fused clip+AdamW Pallas kernel over the sharded "
                          "state (vitax/ops/fused_optimizer.py): one launch "
                          "per leaf group writing (param, mu, nu) in place. "
                          "auto (default) = on when the kernels lower to real "
                          "Mosaic (TPU / VITAX_FORCE_MOSAIC); on = force it "
                          "anywhere (interpret mode off-TPU); off = the "
                          "exact optax chain.")
    ext.add_argument("--grad_accum_steps", type=int, default=1)
    ext.add_argument("--dtype", type=str, default="bfloat16", choices=["bfloat16", "float32"])
    ext.add_argument("--param_gather_dtype", type=str, default=None,
                     choices=["bfloat16", "float32"],
                     help="dtype the FSDP param collectives (ZeRO-3 per-block "
                          "all-gathers, the ZeRO-2 step-top gather, pipeline "
                          "in-body gathers) move on the wire. Default: follow "
                          "--dtype, i.e. bf16 runs gather bf16 (2x fewer bytes, "
                          "bitwise-identical forward: casting shards commutes "
                          "with the gather); float32 forces the pre-PR f32 "
                          "gathers. Rejected with --dtype float32.")
    ext.add_argument("--grad_reduce_dtype", type=str, default="float32",
                     choices=["float32", "bfloat16"],
                     help="dtype the gradient reduce-scatter / all-reduce moves. "
                          "float32 (default) upcasts bf16 wgrad partials before "
                          "the cross-device reduction — exact current numerics; "
                          "bfloat16 reduces on bf16 bits for another 2x on grad "
                          "comm (~1e-2 step agreement; needs the bf16 gather "
                          "policy active).")
    ext.add_argument("--no_flash_attention", action="store_false", dest="use_flash_attention")
    ext.add_argument("--dp_size", type=int, default=1)
    ext.add_argument("--fsdp_size", type=int, default=-1)
    ext.add_argument("--tp_size", type=int, default=1)
    ext.add_argument("--sp_size", type=int, default=1)
    ext.add_argument("--sp_impl", type=str, default="ring",
                     choices=["ring", "ulysses"])
    ext.add_argument("--pp_size", type=int, default=1)
    ext.add_argument("--pp_microbatches", type=int, default=0)
    ext.add_argument("--ep_size", type=int, default=1)
    ext.add_argument("--moe_experts", type=int, default=0)
    ext.add_argument("--moe_capacity_factor", type=float, default=1.25)
    ext.add_argument("--moe_top_k", type=int, default=1, choices=[1, 2])
    ext.add_argument("--moe_aux_weight", type=float, default=0.01)
    ext.add_argument("--no_scan_blocks", action="store_false", dest="scan_blocks")
    ext.add_argument("--scan_unroll", type=int, default=1)
    ext.add_argument("--remat_window", type=int, default=0)
    ext.add_argument("--host_normalize", action="store_false", dest="device_normalize")
    ext.add_argument("--remat_policy", type=str, default=Config.remat_policy,
                     choices=["none_saveable", "dots_saveable", "dots_attn_saveable"])
    ext.add_argument("--profile_dir", type=str, default="")
    ext.add_argument("--profile_start_step", type=int, default=2,
                     help="global step count after which the jax.profiler "
                          "trace window opens (with --profile_dir; default 2 "
                          "skips the compile step)")
    ext.add_argument("--profile_num_steps", type=int, default=5,
                     help="how many steps the profiler window spans "
                          "(default 5 = the historical steps-3..7 window)")
    ext.add_argument("--metrics_dir", type=str, default="",
                     help="write one JSONL telemetry record per log step "
                          "(schema 1: loss, lr, sec/iter, tokens/s, "
                          "data-wait, MFU, HBM) under "
                          "<metrics_dir>/metrics.jsonl; summarize with "
                          "tools/metrics_report.py")
    ext.add_argument("--tensorboard", action="store_true", dest="tensorboard",
                     help="mirror telemetry records as TensorBoard scalars "
                          "under <metrics_dir>/tb (warns and degrades to a "
                          "no-op when tensorboard is not installed)")
    ext.add_argument("--peak_tflops", type=float, default=0.0,
                     help="per-chip peak TFLOP/s for MFU accounting "
                          "(0 = detect from the device kind via the "
                          "vitax/telemetry/flops.py table)")
    ext.add_argument("--hang_timeout_s", type=float, default=0.0,
                     help=">0: watchdog dumps all-thread Python stacks + "
                          "device memory stats (rank-tagged, without killing "
                          "the job) after this many seconds with no "
                          "completed step")
    ext.add_argument("--hang_action", type=str, default="dump",
                     choices=["dump", "checkpoint_exit"],
                     help="what the watchdog does after its dump: dump = "
                          "leave the job running (default); checkpoint_exit "
                          "= emergency-save a committed checkpoint at the "
                          "next step boundary and exit 42 for a supervisor "
                          "(tools/supervise.py) to restart")
    ext.add_argument("--control_sync_steps", type=int, default=10,
                     help="multi-host failure-signal agreement cadence in "
                          "steps (vitax/train/control.py; one tiny "
                          "collective per cadence, plus every epoch "
                          "boundary) — hosts must share the same value")
    ext.add_argument("--peer_heartbeat_s", type=float, default=0.0,
                     help=">0: heartbeat peers through the coordination "
                          "service every N seconds; a peer silent for "
                          "--peer_grace_s is declared dead and survivors "
                          "escalate to checkpoint_exit (exit 42) instead "
                          "of blocking in collectives (0 = off)")
    ext.add_argument("--peer_grace_s", type=float, default=0.0,
                     help="heartbeat-silence window before a peer is "
                          "declared lost, and the survivor's own exit "
                          "deadline after the verdict (0 = 10 x "
                          "--peer_heartbeat_s)")
    ext.add_argument("--arbiter_url", type=str, default="",
                     help="chip-arbiter URL (python -m vitax.arbiter): "
                          "rank 0 posts step/progress heartbeats there so "
                          "the arbiter's borrow policy sees live training "
                          "telemetry (host-side thread; the compiled step "
                          "program is unchanged). \"\" = off")
    ext.add_argument("--fault_plan", type=str, default="",
                     help="JSON fault-injection plan (vitax/faults.py), e.g. "
                          "'{\"site\": \"step\", \"at\": 6, \"action\": "
                          "\"crash\"}' — deterministic crash/hang/"
                          "write-error/loader-stall/SIGTERM drills for the "
                          "failure-reaction machinery (VITAX_FAULT_PLAN env "
                          "var is the flagless equivalent)")
    ext.add_argument("--debug_nans", action="store_true", dest="debug_nans")
    ext.add_argument("--no_log_memory", action="store_false", dest="log_memory")
    ext.add_argument("--steps_per_epoch", type=int, default=0)
    ext.add_argument("--max_steps", type=int, default=0)
    ext.add_argument("--eval_max_batches", type=int, default=0)
    serve = parser.add_argument_group("vitax serving (vitax/serve/)")
    serve.add_argument("--serve_port", type=int, default=8000,
                       help="HTTP port for python -m vitax.serve "
                            "(0 = ephemeral, for tests)")
    serve.add_argument("--serve_max_batch", type=int, default=8,
                       help="largest micro-batch bucket (power of two); "
                            "every power-of-two bucket up to it is "
                            "AOT-compiled at startup so steady-state "
                            "traffic never recompiles")
    serve.add_argument("--max_batch_wait_ms", type=float, default=5.0,
                       help="dynamic batcher deadline: a queued request "
                            "waits at most this long for the largest "
                            "bucket to fill before the batch is flushed")
    serve.add_argument("--serve_topk", type=int, default=5,
                       help="classes returned per /predict response")
    serve.add_argument("--serve_quant_dtype", type=str, default="",
                       choices=["", "int8", "float8_e4m3"],
                       help="expected weight quantization of the serve "
                            "export ('' = full precision); asserts the npz "
                            "__quant__ manifest matches at load")
    serve.add_argument("--serve_act_quant", type=str, default="off",
                       choices=["off", "int8"],
                       help="dynamic activation quantization for the serve "
                            "forward: int8 computes per-tensor absmax "
                            "activation scales inside the jitted forward so "
                            "eligible matmuls run int8 x int8 (requires "
                            "--serve_quant_dtype int8, dense model)")
    serve.add_argument("--fused_dequant", type=str, default="auto",
                       choices=["auto", "on", "off"],
                       help="Pallas fused dequant-matmul for quantized "
                            "serving: auto = on-TPU dense quantized serving "
                            "only; on forces it (interpret mode off-TPU); "
                            "off keeps the jnp dot path (VTX-R009 pins the "
                            "fused program)")
    serve.add_argument("--serve_queue_max", type=int, default=1024,
                       help="dynamic batcher queue bound: a submit against "
                            "a full queue raises QueueFull, answered 503 "
                            "(reason queue_full) by the single-engine "
                            "server and shed as 429 by the fleet router "
                            "(0 = unbounded)")
    serve.add_argument("--serve_request_timeout_s", type=float, default=60.0,
                       help="seconds a /predict handler waits on its batch "
                            "future before answering 503 (> 0; surfaced in "
                            "/metrics)")
    serve.add_argument("--serve_brownout_enter_frac", type=float,
                       default=0.75,
                       help="brownout trigger: queue depth sustained at or "
                            "above this fraction of --serve_queue_max for "
                            "--serve_brownout_dwell_s enters degraded mode "
                            "(topk clamped to 1, batcher deadline shortened, "
                            "degraded: true in /healthz; 0 = off)")
    serve.add_argument("--serve_brownout_exit_frac", type=float, default=0.25,
                       help="hysteretic brownout recovery: depth sustained "
                            "at or below this fraction for the dwell exits "
                            "degraded mode (must be <= the enter fraction)")
    serve.add_argument("--serve_brownout_dwell_s", type=float, default=2.0,
                       help="sustained-pressure window for both brownout "
                            "transitions — blips shorter than this never "
                            "flip the mode")
    serve.add_argument("--serve_brownout_wait_ms", type=float, default=1.0,
                       help="degraded-mode batcher flush deadline, replacing "
                            "--max_batch_wait_ms while browned out")
    serve.add_argument("--serve_allow_chaos", action="store_true",
                       dest="serve_allow_chaos",
                       help="arm POST /chaos (accepts a vitax/faults.py "
                            "plan JSON body, installed live) for chaos "
                            "drills — never enable in production")
    serve.add_argument("--serve_cache_max", type=int, default=0,
                       help="fleet router prediction-cache entries "
                            "(0 = off); exact content-addressed hits "
                            "bypass dispatch entirely")
    serve.add_argument("--serve_cache_ttl_s", type=float, default=300.0,
                       help="prediction-cache entry lifetime in seconds")
    serve.add_argument("--serve_batch_window_ms", type=float, default=0.0,
                       help="fleet router cross-replica continuous "
                            "batching window (0 = off): concurrent "
                            "/predict bodies compose into one "
                            "/predict_batch per group")
    serve.add_argument("--serve_batch_max", type=int, default=0,
                       help="composed-group size cap "
                            "(0 = --serve_max_batch)")

    # scenario registry (vitax/programs/registry.py)
    scen = parser.add_argument_group("vitax scenarios (vitax/programs/)")
    scen.add_argument("--task", type=str, default="train",
                      choices=["train", "finetune", "probe", "distill"],
                      help="which registered scenario to run: "
                           "train = reference pretraining (CE over labels); "
                           "finetune = warm start from --init_npz with the "
                           "head re-initialized for a new --num_classes "
                           "(--reinit_head / shape mismatch) and optional "
                           "--backbone_lr_mult; "
                           "probe = linear probe — backbone frozen via "
                           "optax masking, optimizer moments exist for the "
                           "head only; "
                           "distill = knowledge distillation — frozen "
                           "teacher (--teacher_npz) and student in ONE "
                           "jitted program, loss (1-alpha)*CE + alpha*KL "
                           "at --distill_temp")
    scen.add_argument("--init_npz", type=str, default="",
                      help="finetune/probe warm start: consolidated npz "
                           "export (vitax.checkpoint.consolidate) loaded "
                           "into the fresh sharded state")
    scen.add_argument("--teacher_npz", type=str, default="",
                      help="distillation teacher: consolidated npz export "
                           "(quantized exports dequantize to f32 for the "
                           "teacher forward)")
    scen.add_argument("--reinit_head", action="store_true",
                      dest="reinit_head",
                      help="finetune: keep the fresh head init even when "
                           "the export's head shapes match")
    scen.add_argument("--backbone_lr_mult", type=float, default=1.0,
                      help="finetune: scale non-head updates by this after "
                           "AdamW (1.0 = off)")
    scen.add_argument("--distill_alpha", type=float, default=0.5,
                      help="distill loss mix: (1-alpha)*CE + alpha*KL")
    scen.add_argument("--distill_temp", type=float, default=2.0,
                      help="distill softmax temperature (KL scaled by T^2)")
    return parser


def config_fields_from_namespace(ns: argparse.Namespace) -> dict:
    """Config kwargs from a parsed namespace — tolerant of extra attributes,
    so tools may extend build_parser() with their own flags and still build a
    Config from the shared surface (tools/comm_audit.py does)."""
    return {f.name: getattr(ns, f.name) for f in dataclasses.fields(Config)}


def parse_config(argv: Optional[Tuple[str, ...]] = None) -> Config:
    """The validated `Config` of a command line (default: sys.argv)."""
    ns = build_parser().parse_args(argv)
    return Config(**config_fields_from_namespace(ns)).validate()
