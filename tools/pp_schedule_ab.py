#!/usr/bin/env python3
"""GPipe vs 1F1B A/B on the virtual CPU mesh: live-buffer (temp) memory and
step time as the microbatch count M grows (VERDICT r3 item 5 done-condition).

The point being measured: GPipe's autodiff backward keeps O(M) microbatch
activations live (every in-flight tick's carry is a saved residual), so the
M knob that shrinks the (S-1)/(M+S-1) bubble buys memory pain; 1F1B's
interleaved schedule bounds live activations at O(S) regardless of M.
XLA's buffer assignment (compiled.memory_analysis().temp_size_in_bytes) is
the ground truth for "live", no chip needed.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/pp_schedule_ab.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def build(schedule: str, microbatches: int):
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.parallel.mesh import build_mesh, batch_pspec
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step
    from jax.sharding import NamedSharding

    cfg = Config(image_size=32, patch_size=8, embed_dim=256, num_heads=4,
                 num_blocks=4, num_classes=16, batch_size=64, dtype="float32",
                 pp_size=2, dp_size=4, fsdp_size=1, warmup_steps=0,
                 pp_schedule=schedule, pp_microbatches=microbatches,
                 grad_ckpt=True).validate()
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, schedule = build_optimizer(cfg, max_iteration=100)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    step_fn = make_train_step(cfg, model, tx, mesh, sspecs, schedule=schedule)
    sh = NamedSharding(mesh, batch_pspec())
    rng = np.random.default_rng(0)
    batch = {
        "image": jax.device_put(jnp.asarray(rng.normal(
            size=(cfg.batch_size, 32, 32, 3)), jnp.float32), sh),
        "label": jax.device_put(jnp.asarray(rng.integers(
            0, 16, size=(cfg.batch_size,)), jnp.int32), sh),
    }
    return cfg, state, step_fn, batch


def measure(schedule: str, microbatches: int, steps: int = 5):
    cfg, state, step_fn, batch = build(schedule, microbatches)
    rng = jax.random.key(1)
    lowered = step_fn.lower(state, batch, rng)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    temp_mb = getattr(mem, "temp_size_in_bytes", 0) / 2**20
    state, metrics = step_fn(state, batch, rng)  # warm (donated state reuse)
    loss0 = float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch, rng)
    loss = float(jax.device_get(metrics["loss"]))
    dt = (time.perf_counter() - t0) / steps
    return {"schedule": schedule, "M": microbatches,
            "temp_mb": round(temp_mb, 2), "step_ms": round(dt * 1e3, 1),
            "loss0": round(loss0, 6), "loss_end": round(loss, 6)}


def main():
    rows = []
    for m in (2, 8, 16):
        for sched in ("gpipe", "1f1b"):
            r = measure(sched, m)
            rows.append(r)
            print(f"{sched:>6} M={m:<3} temp={r['temp_mb']:>8.2f} MB "
                  f"step={r['step_ms']:>7.1f} ms loss0={r['loss0']}",
                  flush=True)
    # loss trajectories must agree per M (same math, different schedule)
    by_m = {}
    for r in rows:
        by_m.setdefault(r["M"], []).append(r)
    for m, pair in by_m.items():
        a, b = pair
        assert abs(a["loss0"] - b["loss0"]) < 2e-4 * abs(a["loss0"]), (m, pair)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PP_AB.json")
    device = jax.devices()[0].device_kind  # vtx: ignore[VTX104] CLI entry: labels the benchmarked backend
    with open(out, "w") as f:
        json.dump({"device": device,
                   "config": "embed256 L4 pp2 x dp4 batch64 f32 remat",
                   "rows": rows}, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
