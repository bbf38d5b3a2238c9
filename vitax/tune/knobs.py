"""The shared perf-knob surface: one dataclass, one argparse group, one
resolved-knob payload.

Historically bench.py, tools/profile_step.py and tools/aot_topology.py each
carried their own copy of the knob flags, and the round-4 `knobs` payload
predates the gather-overlap / fused-optimizer / comm-dtype knobs entirely —
so a trajectory entry could not say what actually ran. This module is the
single definition all of them (and tools/autotune.py) import:

  - ``Knobs``: the CLI-level knob set with bench's exact sentinel defaults
    (0 / -1 / None = "resolve per preset"), serializable via ``to_json``.
  - ``add_knob_args``: the argparse group, flag names and defaults verbatim
    from the historical bench.py surface (they are a contract: ladder rows
    in LADDER_*.jsonl replay these flags).
  - ``knob_payload``: the RESOLVED knob set a measured number records —
    ground truth for tools/apply_ladder.py and tools/perf_gate.py. Batch is
    PER-CHIP: img/s/chip only compares at equal per-chip batch.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

REMAT_POLICIES = ("none_saveable", "dots_saveable", "dots_attn_saveable")

# the payload contract: every measured BENCH number and every autotune trial
# records exactly these keys (tools/apply_ladder.py reads a subset; the
# telemetry schema validator requires the full set)
KNOB_PAYLOAD_KEYS = (
    "batch_per_chip", "remat_policy", "scan_blocks", "scan_unroll",
    "remat_window", "grad_ckpt", "use_flash_attention", "grad_accum_steps",
    "param_gather_dtype", "grad_reduce_dtype", "gather_overlap",
    "fused_optimizer",
)


@dataclasses.dataclass
class Knobs:
    """CLI-level knob values, sentinel defaults = "resolve per preset"."""

    batch_size: int = 0                 # GLOBAL batch; 0 = preset default
    remat_policy: Optional[str] = None
    grad_ckpt: bool = True
    scan_blocks: Optional[bool] = None  # None = per-preset default
    scan_unroll: int = 0                # 0 = per-preset default
    remat_window: int = -1              # -1 = per-preset default
    use_flash_attention: bool = True
    moe_impl: Optional[str] = None
    att_dropout: Optional[float] = None
    grad_accum_steps: int = 1
    param_gather_dtype: Optional[str] = None  # None = follow --dtype
    grad_reduce_dtype: str = "float32"
    gather_overlap: str = "auto"
    fused_optimizer: str = "auto"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Knobs":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def other_explicit(self) -> bool:
        """Whether any non-scan A/B lever was given explicitly — the
        resolve_bench_knobs() purity rule: tuned defaults must not leak
        into a run that differs from its reference by an explicit knob."""
        return (not self.grad_ckpt or not self.use_flash_attention
                or bool(self.batch_size)
                or self.moe_impl is not None
                or self.att_dropout is not None
                or self.grad_accum_steps > 1
                or self.param_gather_dtype is not None
                or self.grad_reduce_dtype != "float32"
                or self.gather_overlap != "auto"
                or self.fused_optimizer != "auto")

    def apply_to_preset_kw(self, kw: dict) -> dict:
        """Overlay the explicit (non-sentinel) knobs onto a train_presets()
        kwargs dict — the exact historical bench.py merge order."""
        if self.batch_size:
            kw["batch_size"] = self.batch_size
        if self.moe_impl:
            kw["moe_impl"] = self.moe_impl
        if self.att_dropout is not None:
            kw["att_dropout"] = self.att_dropout
        if self.grad_accum_steps > 1:
            kw["grad_accum_steps"] = self.grad_accum_steps
        if self.param_gather_dtype:
            kw["param_gather_dtype"] = self.param_gather_dtype
        if self.grad_reduce_dtype != "float32":
            kw["grad_reduce_dtype"] = self.grad_reduce_dtype
        if self.gather_overlap != "auto":
            kw["gather_overlap"] = self.gather_overlap
        if self.fused_optimizer != "auto":
            kw["fused_optimizer"] = self.fused_optimizer
        return kw


def knobs_from_args(ns: argparse.Namespace) -> Knobs:
    """Knobs from a namespace parsed with add_knob_args (tolerant of flags a
    tool chose not to add — missing attrs keep the dataclass default)."""
    kw = {}
    for f in dataclasses.fields(Knobs):
        if hasattr(ns, f.name):
            kw[f.name] = getattr(ns, f.name)
    return Knobs(**kw)


def add_knob_args(p: argparse.ArgumentParser,
                  preset_file: bool = True) -> argparse.ArgumentParser:
    """The shared knob-flag group. Names, defaults and choices are a
    contract (historical bench.py surface; LADDER rows replay them)."""
    p.add_argument("--batch_size", type=int, default=0)
    # default resolved per preset (bench.default_remat_policy):
    # dots_attn_saveable measured fastest on v5e where activations fit
    # (192.9 > dots_saveable 190.2 on l14); the 10B flagship keeps
    # none_saveable (minimal HBM residency is what makes it fit)
    p.add_argument("--remat_policy", default=None,
                   choices=list(REMAT_POLICIES))
    p.add_argument("--no_grad_ckpt", action="store_false", dest="grad_ckpt")
    p.add_argument("--no_scan_blocks", action="store_false",
                   dest="scan_blocks", default=None,
                   help="unroll blocks instead of lax.scan (the scan's "
                        "dus-stacking constrains wgrad fusion layouts; "
                        "default resolves per preset — see "
                        "default_scan_blocks; --scan_unroll forces the scan)")
    p.add_argument("--scan_unroll", type=int, default=0,
                   help="blocks per scan step (0 = preset default); keeps "
                        "the stacked param tree, frees cross-block fusion")
    p.add_argument("--remat_window", type=int, default=-1,
                   help=">1: remat around groups of this many blocks "
                        "(functional scan; residuals dus-stack once per "
                        "group — the wgrad stacking experiment); 0 = "
                        "explicit per-block remat; -1 = tuned/preset default")
    p.add_argument("--moe_impl", default=None, choices=["gather", "einsum"],
                   help="MoE dispatch/combine A/B (vitax/models/moe.py): "
                        "einsum (GShard one-hot, default — measured fastest "
                        "on v5e) vs gather (slot-index scatter+gathers)")
    p.add_argument("--grad_accum_steps", type=int, default=1,
                   help="K > 1: accumulate grads over K microbatches inside "
                        "the jitted step (images/sec vs K trade on the train "
                        "presets; an explicit A/B knob like --batch_size)")
    p.add_argument("--att_dropout", type=float, default=None,
                   help="attention-dropout A/B arm (in-kernel dropout path)")
    p.add_argument("--param_gather_dtype", default=None,
                   choices=["bfloat16", "float32"],
                   help="comm-precision A/B arm: dtype the FSDP param "
                        "collectives move (None = Config default: follow "
                        "--dtype, i.e. bf16 gathers on the bf16 presets)")
    p.add_argument("--grad_reduce_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="comm-precision A/B arm: dtype the grad "
                        "reduce-scatter/all-reduce moves (float32 = exact "
                        "pre-policy numerics)")
    p.add_argument("--gather_overlap", default="auto",
                   choices=["auto", "off", "on"],
                   help="overlap A/B arm: double-buffered ZeRO-3 block-param "
                        "gathers prefetched through the layer-scan carry "
                        "(off = exact pre-overlap schedule; auto = on "
                        "whenever ZeRO-3 + scanned blocks + per-block remat "
                        "are active)")
    p.add_argument("--fused_optimizer", default="auto",
                   choices=["auto", "off", "on"],
                   help="optimizer A/B arm: one-pass Pallas fused clip+AdamW "
                        "update over the sharded state (off = exact optax "
                        "chain; auto = on where the kernels lower to real "
                        "Mosaic, i.e. TPU)")
    p.add_argument("--no_flash_attention", action="store_false",
                   dest="use_flash_attention")
    if preset_file:
        p.add_argument("--preset_file", default="",
                       help="load a committed autotune preset JSON "
                            "(presets/<model>_<topology>.json); its knobs "
                            "fill every knob still at its default — "
                            "explicit flags on the command line win")
    return p


def knob_payload(cfg, n_dev: int) -> dict:
    """The RESOLVED knob set a measured number was taken under — the
    `knobs` object in the bench JSON result line and in every autotune
    trial record. Keys are KNOB_PAYLOAD_KEYS exactly."""
    return {
        "batch_per_chip": cfg.batch_size // max(n_dev, 1),
        "remat_policy": cfg.remat_policy,
        "scan_blocks": cfg.scan_blocks,
        "scan_unroll": cfg.scan_unroll,
        "remat_window": cfg.remat_window,
        "grad_ckpt": cfg.grad_ckpt,
        "use_flash_attention": cfg.use_flash_attention,
        "grad_accum_steps": cfg.grad_accum_steps,
        "param_gather_dtype": cfg.resolved_param_gather_dtype,
        "grad_reduce_dtype": cfg.grad_reduce_dtype,
        "gather_overlap": cfg.gather_overlap,
        "fused_optimizer": cfg.fused_optimizer,
    }
