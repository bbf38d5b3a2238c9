#!/usr/bin/env python3
"""Audit the collectives the compiled train step moves on the wire.

AOT-compiles the train step for the given config (any trainer flag works —
the CLI is the full vitax flag surface plus the audit flags below), dumps the
HLO right after SPMD partitioning, and tabulates every collective op
(all-gather / reduce-scatter / all-reduce / all-to-all / collective-permute):
op count, element type, shape, and bytes per step. This is the artifact that
proves the `--param_gather_dtype bfloat16` policy halves FSDP gather traffic
and guards against precision regressions (tests/test_comm_precision.py).

Why the *post-partitioning* dump and not the final executable HLO: backend
simplification passes may rewrite collective element types after SPMD
partitioning. XLA:CPU's float normalization in particular rewrites every bf16
collective as an f32 collective wrapped in converts, so the final CPU HLO can
never show a bf16 gather no matter what the program asked for. The
post-`spmd-partitioning` module is the backend-independent ground truth for
what dtype each collective moves.

Known result worth recording: under ZeRO-3 (reshard_after_forward) GSPMD sinks
the compute-dtype convert below the per-use gathers, so per-block all-gathers
are bf16 even under the f32 policy — the byte delta of the bf16 policy shows
at the ZeRO-2 step-top gather of the whole param tree (~2x total gather
bytes), plus once-per-step casting and bf16 scan carries instead of per-slice
converts.

The HLO/while-body parser lives in vitax.analysis.hlo (it started here and
was generalized for the rule registry in vitax.analysis.rules); this tool is
now a thin CLI over it. The re-exports below keep the historical module-level
API (`from tools.comm_audit import audit_config`, `comm_audit.gather_bytes`)
stable for the tier-1 tests.

Usage:
    python tools/comm_audit.py --embed_dim 1024 --num_blocks 24 [vitax flags]
    python tools/comm_audit.py ... --json          # machine-readable report
    python tools/comm_audit.py ... --compare       # vs the f32 gather policy
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from vitax.analysis.hlo import (  # noqa: E402  (sys.path fix must precede)
    COLLECTIVE_RE,
    DTYPE_BYTES,
    INSTR_RE as _INSTR_RE,
    TRIVIAL_OPS as _TRIVIAL_OPS,
    collect_collectives,
    gather_bytes,
    overlap_verdict,
    partitioned_hlo_text,
    split_computations as _split_computations,
    summarize,
)
from vitax.parallel.mesh import MESH_AXES, resolve_mesh_shape  # noqa: E402

__all__ = [
    "COLLECTIVE_RE", "DTYPE_BYTES", "collect_collectives", "summarize",
    "gather_bytes", "overlap_verdict", "partitioned_hlo_text",
    "audit_config", "format_report", "main",
]


def audit_config(cfg):
    """Full audit report for one config: collective rows + per-op totals +
    the block-param gather facts the tier-1 test asserts on."""
    hlo_text = partitioned_hlo_text(cfg)
    rows = collect_collectives(hlo_text)
    block_numel = cfg.embed_dim * cfg.embed_dim  # smallest block matmul param
    return {
        "config": {
            "dtype": cfg.dtype,
            "param_gather_dtype": cfg.resolved_param_gather_dtype,
            "grad_reduce_dtype": cfg.grad_reduce_dtype,
            "reshard_after_forward": cfg.reshard_after_forward,
            "run_without_fsdp": cfg.run_without_fsdp,
            "grad_accum_steps": cfg.grad_accum_steps,
            "pp_size": cfg.pp_size,
            "gather_overlap": cfg.gather_overlap,
        },
        "collectives": rows,
        "totals": summarize(rows),
        "all_gather_bytes": gather_bytes(rows),
        "f32_block_param_gathers": [
            r for r in rows
            if r["op"] == "all-gather" and r["dtype"] == "f32"
            and r["numel"] >= block_numel],
        "overlap": overlap_verdict(
            hlo_text, min_reduce_numel=block_numel // max(
                resolve_mesh_shape(cfg)[MESH_AXES.index("fsdp")], 1)),
    }


def format_report(report):
    lines = []
    c = report["config"]
    lines.append(f"comm_audit: dtype={c['dtype']} "
                 f"param_gather_dtype={c['param_gather_dtype']} "
                 f"grad_reduce_dtype={c['grad_reduce_dtype']}")
    lines.append(f"{'count':>6} {'op':<20} {'dtype':<6} {'bytes':>12}  shape")
    for r in report["collectives"]:
        lines.append(f"{r['count']:>6} {r['op']:<20} {r['dtype']:<6} "
                     f"{r['bytes']:>12,}  {r['shape']}")
    lines.append("-- totals --")
    for op, t in sorted(report["totals"].items()):
        split = ", ".join(f"{d}: {v['bytes']:,}B x{v['count']}"
                          for d, v in sorted(t["by_dtype"].items()))
        lines.append(f"  {op:<20} {t['bytes']:>12,} B/step  ({split})")
    bad = report["f32_block_param_gathers"]
    lines.append(f"  f32 block-param all-gathers: "
                 f"{len(bad)}{' <- POLICY NOT APPLIED' if bad else ''}")
    ov = report.get("overlap")
    if ov is not None:
        lines.append(
            f"  overlap ({c.get('gather_overlap', '?')}): "
            f"{ov['gathers_in_scan_body']} gathers in scan bodies, "
            f"{ov['prefetch_slot_gathers']} on the prefetch slot; "
            f"{ov['sync_block_reduces']} block-sized synchronous reduces, "
            f"{ov['ring_permutes']} ring permutes")
    return "\n".join(lines)


def main(argv=None):
    from vitax.config import build_parser, config_fields_from_namespace

    parser = build_parser()
    aud = parser.add_argument_group("comm_audit")
    aud.add_argument("--json", action="store_true", dest="audit_json",
                     help="emit the audit report as JSON on stdout")
    aud.add_argument("--compare", action="store_true", dest="audit_compare",
                     help="also audit the same config under the f32 gather "
                          "policy and report the gather-byte ratio")
    # audit runs standalone on dev boxes: small default geometry instead of
    # the 10B trainer defaults so `python tools/comm_audit.py` just works
    parser.set_defaults(image_size=224, patch_size=14, embed_dim=1024,
                        num_heads=16, num_blocks=4, num_classes=1000,
                        batch_size=64, warmup_steps=2)
    ns = parser.parse_args(argv)

    from vitax.config import Config
    cfg = Config(**config_fields_from_namespace(ns)).validate()
    report = audit_config(cfg)

    if ns.audit_compare:
        alt = {**config_fields_from_namespace(ns),
               "param_gather_dtype": "float32"}
        f32_report = audit_config(Config(**alt).validate())
        num = f32_report["all_gather_bytes"]
        den = report["all_gather_bytes"]
        report["compare"] = {
            "f32_policy_all_gather_bytes": num,
            "all_gather_bytes_ratio": round(num / den, 3) if den else None,
        }

    if ns.audit_json:
        print(json.dumps(report, indent=2))
    else:
        print(format_report(report))
        if "compare" in report:
            cmp_ = report["compare"]
            print(f"-- vs f32 gather policy --\n"
                  f"  f32-policy all-gather bytes: "
                  f"{cmp_['f32_policy_all_gather_bytes']:,}\n"
                  f"  gather-byte reduction: "
                  f"{cmp_['all_gather_bytes_ratio']}x")
    return report


if __name__ == "__main__":
    main()
