"""The autotune search driver: analytic rank -> compile prune -> (on TPU)
successive-halving measured windows.

Every trial — including the ones a stage prunes — is one schema'd JSONL
record (kind:"autotune_trial", vitax/telemetry/schema.py) with monotone
trial ids, so the whole search replays from its log. Budget allocation for
the measured stage follows successive halving (Jamieson & Talwalkar,
AISTATS 2016 — see PAPERS.md): every survivor gets the same step budget per
round, the better half advances, and the per-candidate window doubles as
the field halves, so the budget concentrates on contenders while every
candidate gets at least a short fenced window.

Measured windows reuse bench.py's fenced-timing idiom exactly: sync via
``float(jax.device_get(metrics["loss"]))``.
"""

from __future__ import annotations

import json
import math
import time
from typing import List, Optional

from vitax.telemetry.flops import model_flops_per_image
from vitax.tune import cost as cost_mod
from vitax.tune.knobs import knob_payload
from vitax.tune.preset import make_preset
from vitax.tune.space import candidate_space, rank_serve_geometries

TRIAL_KIND = "autotune_trial"
TRIAL_SCHEMA = 1


class TrialLog:
    """Append-only JSONL trial log with monotone trial ids."""

    def __init__(self, path: str):
        self.path = path
        self._next_id = 0
        self._f = open(path, "a", encoding="utf-8")

    def write(self, model_preset: str, topology: str, phase: str,
              knobs: dict, pruned_by: Optional[str] = None,
              **payload) -> dict:
        rec = {
            "schema": TRIAL_SCHEMA,
            "kind": TRIAL_KIND,
            "trial_id": self._next_id,
            "time": time.time(),
            "model_preset": model_preset,
            "topology": topology,
            "phase": phase,
            "knobs": knobs,
            "pruned_by": pruned_by,
            **payload,
        }
        self._next_id += 1
        self._f.write(json.dumps(rec, sort_keys=True) + "\n")
        self._f.flush()
        return rec

    def close(self) -> None:
        self._f.close()


def plan_successive_halving(n_candidates: int, total_steps: int,
                            min_steps: int = 10, eta: int = 2) -> List[tuple]:
    """Budget plan [(survivors_i, steps_each_i), ...]: R = floor(log_eta n)+1
    rounds, equal per-round budget, field divided by eta each round. When
    min_steps does not bind, total usage is <= total_steps exactly."""
    assert n_candidates >= 1 and total_steps >= 1 and eta >= 2
    rounds = int(math.floor(math.log(n_candidates, eta))) + 1
    per_round = total_steps // rounds
    plan, n = [], n_candidates
    for _ in range(rounds):
        steps = max(min_steps, per_round // n)
        plan.append((n, steps))
        if n == 1:
            break
        n = max(1, n // eta)
    return plan


class _Runner:
    """One candidate's compiled program + device-resident batch, reusable
    across halving rounds (no recompile between rounds)."""

    def __init__(self, cfg, devices=None):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding

        from vitax.models import build_model
        from vitax.ops.attention import make_attention_impl
        from vitax.parallel.mesh import batch_pspec, build_mesh
        from vitax.train.state import build_optimizer, make_train_state
        from vitax.train.step import make_train_step

        self.cfg = cfg
        mesh = build_mesh(cfg, devices=devices)
        self.n_dev = int(mesh.devices.size)
        model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh))
        tx, schedule = build_optimizer(cfg, max_iteration=10_000)
        self.state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                                 jax.random.key(0))
        self.step_fn = make_train_step(cfg, model, tx, mesh, sspecs,
                                       schedule=schedule)
        sh = NamedSharding(mesh, batch_pspec())
        rng = np.random.default_rng(0)
        self.batch = {
            "image": jax.device_put(jnp.asarray(rng.normal(
                size=(cfg.batch_size, cfg.image_size, cfg.image_size, 3)),
                jnp.float32), sh),
            "label": jax.device_put(jnp.asarray(rng.integers(
                0, cfg.num_classes, size=(cfg.batch_size,)), jnp.int32), sh),
        }
        self.rng_key = jax.random.key(1)
        self._warm = False

    def measure(self, steps: int, warmup: int) -> dict:
        """bench.py's fenced window: device_get is the fence (see module
        docstring), warmup covers compile on the first round only."""
        import jax
        import numpy as np

        from vitax.telemetry.record import memory_stats_bytes

        n_warm = max(warmup, 1) if not self._warm else 1
        for _ in range(n_warm):
            self.state, metrics = self.step_fn(self.state, self.batch,
                                               self.rng_key)
        float(jax.device_get(metrics["loss"]))
        self._warm = True

        t0 = time.perf_counter()
        for _ in range(steps):
            self.state, metrics = self.step_fn(self.state, self.batch,
                                               self.rng_key)
        final_loss = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
        step_time = dt / steps
        return {
            "step_time_s": step_time,
            "images_per_sec_chip": self.cfg.batch_size / step_time
            / self.n_dev,
            "mem": memory_stats_bytes(),
        }


def _rank_key(scored: dict) -> tuple:
    """Deterministic order: analytic score, then the knob payload text —
    never a wall-clock measurement (compile_s varies run to run)."""
    return (round(scored["cost"]["sec_per_image_chip"], 12),
            json.dumps(scored["knobs"], sort_keys=True))


def run_search(model_preset: str, topology: str, preset_kw: dict,
               n_dev: int, log: TrialLog, *, peak_tflops: float,
               devices=None, hbm_bound_bytes: float = 0.0,
               max_candidates: int = 0, shortlist: int = 8,
               compile_top: int = 0, measure: bool = False,
               budget_steps: int = 240, min_steps: int = 10,
               warmup: int = 3, log_fn=print) -> dict:
    """One (model preset, topology) search. Returns {ranked, winner,
    n_candidates, n_invalid, serve} where `ranked` is the surviving
    shortlist best-first and `winner` a committable preset dict."""
    from vitax.config import Config

    candidates, n_invalid = candidate_space(model_preset, n_dev, preset_kw,
                                            max_candidates=max_candidates)
    log_fn(f"[autotune] {model_preset}@{topology}: {len(candidates)} valid "
           f"candidates ({n_invalid} rejected by Config.validate"
           + (f", enumeration capped at {max_candidates}"
              if max_candidates else "") + ")")

    # stage 1: analytic cost over the whole space (deterministic)
    scored = []
    for kw in candidates:
        cfg = Config(**kw).validate()
        c = cost_mod.analytic_cost(cfg, n_dev, peak_tflops)
        entry = {"cfg": cfg, "kw": kw, "knobs": knob_payload(cfg, n_dev),
                 "cost": c}
        if hbm_bound_bytes and c["live_bytes_estimate"] > hbm_bound_bytes:
            entry["pruned_by"] = "hbm_estimate"
        scored.append(entry)
    scored.sort(key=_rank_key)

    survivors = []
    for rank, entry in enumerate(scored):
        pruned = entry.get("pruned_by")
        if pruned is None and len(survivors) >= shortlist:
            pruned = "cost_rank"
        trial_cost = {k: v for k, v in entry["cost"].items()
                      if k != "params"}
        log.write(model_preset, topology, "analytic", entry["knobs"],
                  pruned_by=pruned, rank=rank, cost=trial_cost)
        if pruned is None:
            survivors.append(entry)

    # stage 2: AOT compile probe on the shortlist head (cost-model ground
    # truth: collective bytes from the partitioned HLO + compiler live
    # bytes); compile failures and HBM overflows drop out here
    if compile_top > 0:
        kept = []
        for entry in survivors:
            if len(kept) >= compile_top:
                kept.append(entry)  # beyond the probe budget: keep unprobed
                continue
            try:
                probe = cost_mod.compile_probe(
                    entry["cfg"], devices=devices,
                    hbm_bound_bytes=hbm_bound_bytes)
            except Exception as e:  # noqa: BLE001 — a failed compile is a pruned trial
                log.write(model_preset, topology, "compile", entry["knobs"],
                          pruned_by="compile_error",
                          error=f"{type(e).__name__}: {e}")
                continue
            pruned = "hbm" if probe.get("fits_hbm") is False else None
            entry["compile"] = probe
            log.write(model_preset, topology, "compile", entry["knobs"],
                      pruned_by=pruned, compile_s=probe["compile_s"],
                      compile=probe)
            if pruned is None:
                kept.append(entry)
        survivors = kept

    # stage 3: measured successive halving (real backend only)
    if measure and survivors:
        plan = plan_successive_halving(len(survivors), budget_steps,
                                       min_steps=min_steps)
        log_fn(f"[autotune] halving plan {plan} "
               f"(budget {budget_steps} steps)")
        field = survivors
        runners = {}
        for rnd, (n_keep, steps) in enumerate(plan):
            field = field[:n_keep]
            results = []
            for entry in field:
                key = id(entry)
                try:
                    if key not in runners:
                        runners[key] = _Runner(entry["cfg"], devices=devices)
                    m = runners[key].measure(steps, warmup)
                except Exception as e:  # noqa: BLE001 — a crashed window is a pruned trial
                    log.write(model_preset, topology, "measure",
                              entry["knobs"], pruned_by="run_error",
                              round=rnd, error=f"{type(e).__name__}: {e}")
                    continue
                mfu = (m["images_per_sec_chip"]
                       * model_flops_per_image(entry["cfg"])
                       / (peak_tflops * 1e12))
                entry["measured"] = {**m, "mfu": mfu}
                log.write(model_preset, topology, "measure", entry["knobs"],
                          round=rnd, steps=steps,
                          step_time_s=m["step_time_s"],
                          images_per_sec_chip=m["images_per_sec_chip"],
                          mfu=mfu, mem=m["mem"])
                results.append(entry)
            # best measured first; losers of this round are recorded pruned
            results.sort(
                key=lambda e: e["measured"]["images_per_sec_chip"],
                reverse=True)
            if rnd + 1 < len(plan):
                for entry in results[plan[rnd + 1][0]:]:
                    log.write(model_preset, topology, "measure",
                              entry["knobs"], pruned_by="halving", round=rnd)
            field = results
        survivors = field or survivors

    serve_ranked = rank_serve_geometries()
    winner = None
    if survivors:
        best = survivors[0]
        src = {
            "mode": "measured" if best.get("measured") else "compile_only",
            "cost_step_s": best["cost"]["step_s"],
            "images_per_sec_chip": (best.get("measured") or {}).get(
                "images_per_sec_chip"),
            "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        }
        winner = make_preset(model_preset, topology, best["knobs"],
                             serve={k: serve_ranked[0][k] for k in
                                    ("serve_max_batch", "max_batch_wait_ms")},
                             source=src)
    return {
        "ranked": [{"knobs": e["knobs"],
                    "sec_per_image_chip": e["cost"]["sec_per_image_chip"],
                    "measured": e.get("measured")}
                   for e in survivors],
        "winner": winner,
        "n_candidates": len(candidates),
        "n_invalid": n_invalid,
        "serve": serve_ranked,
    }
