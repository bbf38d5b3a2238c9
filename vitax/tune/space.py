"""Deterministic candidate enumeration over the knob space.

The train-side grid is the cross product the ISSUE names — remat policy x
batch per chip x scan unroll / remat window x --gather_overlap x
--fused_optimizer x comm dtypes — filtered through Config.validate() so the
driver never compiles a combination the trainer would reject (rejected
combinations are counted, not silently dropped: the driver records one
pruned_by:"invalid" trial per filtered candidate when asked).

Enumeration order is fixed (nested loops over tuples declared here), so the
ranked shortlist is bit-reproducible run to run — the acceptance contract
for the off-TPU degradation path.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from vitax.tune.knobs import REMAT_POLICIES

# (scan_blocks, scan_unroll, remat_window) arms: the scan-geometry lattice.
# window > 1 subsumes unroll (Config.validate); unrolled path has no window.
SCAN_ARMS = (
    (True, 1, 0),      # scanned, per-block remat
    (True, 2, 0),      # partial unroll
    (False, 1, 0),     # fully unrolled
    (True, 1, 2),      # window-2 group remat
)

# (param_gather_dtype, grad_reduce_dtype) comm-precision arms
COMM_ARMS = (
    (None, "float32"),            # Config defaults (gather follows --dtype)
    ("bfloat16", "bfloat16"),     # full bf16 comm
)

BATCH_LADDER_PER_CHIP = {
    "tiny": (32, 64, 128),
    "b16": (32, 64, 128),
    "b16_moe": (32, 64),
    "l14": (16, 32, 64),
    "10b": (4, 8),
    "10b_slice": (32, 64),
}

GATHER_OVERLAP_ARMS = ("auto", "off")
FUSED_OPTIMIZER_ARMS = ("auto", "off")

# serve bucket geometry: (serve_max_batch, max_batch_wait_ms)
SERVE_GEOMETRY_ARMS = (
    (4, 2.0), (8, 2.0), (8, 5.0), (16, 5.0), (16, 10.0), (32, 10.0),
)


def candidate_space(model_preset: str, n_dev: int, preset_kw: dict,
                    max_candidates: int = 0,
                    batches: Optional[Tuple[int, ...]] = None,
                    ) -> Tuple[List[dict], int]:
    """Enumerate valid train-knob candidates for (model preset, topology).

    Returns (candidates, n_invalid). Each candidate is a Config-kwargs
    dict (preset shape + knobs, validated); n_invalid counts combinations
    Config.validate() rejected. `max_candidates` > 0 truncates the
    deterministic enumeration (the cap is logged by the driver — silent
    truncation must not read as full coverage)."""
    from vitax.config import Config

    batches = batches or BATCH_LADDER_PER_CHIP.get(model_preset, (32, 64))
    out, n_invalid = [], 0
    for bpc in batches:
        for policy in REMAT_POLICIES:
            for scan_blocks, unroll, window in SCAN_ARMS:
                for overlap in GATHER_OVERLAP_ARMS:
                    for fused in FUSED_OPTIMIZER_ARMS:
                        for gather_dt, reduce_dt in COMM_ARMS:
                            kw = dict(preset_kw)
                            kw.update(
                                num_classes=1000, warmup_steps=0,
                                batch_size=bpc * n_dev,
                                remat_policy=policy,
                                scan_blocks=scan_blocks,
                                scan_unroll=unroll,
                                remat_window=window,
                                gather_overlap=overlap,
                                fused_optimizer=fused,
                                param_gather_dtype=gather_dt,
                                grad_reduce_dtype=reduce_dt)
                            try:
                                Config(**kw).validate()
                            except AssertionError:
                                n_invalid += 1
                                continue
                            out.append(kw)
                            if max_candidates and len(out) >= max_candidates:
                                return out, n_invalid
    return out, n_invalid


def serve_space() -> Tuple[Tuple[int, float], ...]:
    """Serve bucket-geometry candidates (validated power-of-two buckets)."""
    return SERVE_GEOMETRY_ARMS


def serve_geometry_cost(serve_max_batch: int, max_batch_wait_ms: float,
                        target_rps: float = 200.0,
                        image_s: float = 0.004) -> float:
    """Analytic serve score (lower = better) at an assumed arrival rate:
    expected per-request latency = batching wait (a request waits ~half the
    window unless the bucket fills first) + padded-bucket compute, where
    padding waste falls as the expected batch approaches the bucket size.
    Deterministic — used only to RANK geometries off-TPU; serve numbers
    on the chip: not measured."""
    expected_batch = min(max(target_rps * max_batch_wait_ms / 1e3, 1.0),
                         float(serve_max_batch))
    # padded power-of-two bucket the expected batch lands in
    bucket = 1
    while bucket < expected_batch:
        bucket *= 2
    bucket = min(bucket, serve_max_batch)
    fill = expected_batch / bucket
    wait_s = (max_batch_wait_ms / 1e3) * 0.5 * \
        (1.0 - min(expected_batch / serve_max_batch, 1.0))
    compute_s = image_s * bucket / expected_batch  # per-request share
    return wait_s + compute_s + (1.0 - fill) * image_s


def rank_serve_geometries() -> List[dict]:
    """Serve geometries ranked by the analytic score, deterministic."""
    scored = [{"serve_max_batch": b, "max_batch_wait_ms": w,
               "score": serve_geometry_cost(b, w)}
              for b, w in serve_space()]
    scored.sort(key=lambda r: (r["score"], r["serve_max_batch"],
                               r["max_batch_wait_ms"]))
    return scored
