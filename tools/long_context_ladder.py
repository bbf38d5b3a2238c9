"""Streaming-kernel block-size ladder + long-N frontier, on chip.

Round-4 measured the streaming kernel (vitax/ops/flash_blocked.py) only at
its untuned DEFAULT_BLOCK_Q/K = 512.
This ladder sweeps (block_q, block_k) over {256, 512, 1024}^2 at N = 4,096
and N = 9,216, then pushes the max trainable N at ViT-L width with the
winning blocks (16k+). Same end-to-end train-step methodology as round 4:
ViT-L width (1024d/16h), 4 blocks, batch 2, none_saveable remat, N set by
the image size (N = (image/14)^2), single v5e chip.

Usage:
    python tools/long_context_ladder.py [--steps 10] [--out LADDER_LONGCTX.jsonl]

Each row: {"n": N, "block_q": bq, "block_k": bk, "ms_per_step": t | null,
           "error": ...}. The dense arm at N=4,096 re-verifies the round-4
comparison point. tools/apply_ladder.py is NOT involved — the winner is
applied by editing DEFAULT_BLOCK_Q/K with a PERF.md finding.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(n_tokens: int, block_q, block_k, steps: int, dense: bool = False):
    """ms/step for one config in a FRESH subprocess (an OOM must not poison
    the parent or the remaining rows)."""
    code = f"""
import sys, time, json
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from vitax.platform import setup_compile_cache
setup_compile_cache()
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from vitax.config import Config
from vitax.models import build_model
from vitax.parallel.mesh import build_mesh, batch_pspec
from vitax.train.state import build_optimizer, make_train_state
from vitax.train.step import make_train_step

side = 14 * int(round({n_tokens} ** 0.5))
cfg = Config(image_size=side, patch_size=14, embed_dim=1024, num_heads=16,
             num_blocks=4, num_classes=1000, batch_size=2, warmup_steps=0,
             grad_ckpt=True, remat_policy="none_saveable").validate()
assert cfg.num_patches == {n_tokens}, cfg.num_patches
if {dense!r}:
    impl = None
else:
    from vitax.ops.flash_blocked import blocked_flash_attention
    import functools
    impl = functools.partial(blocked_flash_attention,
                             block_q={block_q}, block_k={block_k})
mesh = build_mesh(cfg)
model = build_model(cfg, attention_impl=impl)
tx, schedule = build_optimizer(cfg, max_iteration=100)
state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
step = make_train_step(cfg, model, tx, mesh, sspecs, schedule=schedule)
sh = NamedSharding(mesh, batch_pspec())
rng = np.random.default_rng(0)
batch = {{
    "image": jax.device_put(jnp.asarray(rng.normal(
        size=(cfg.batch_size, side, side, 3)), jnp.float32), sh),
    "label": jax.device_put(jnp.asarray(rng.integers(
        0, 1000, size=(cfg.batch_size,)), jnp.int32), sh),
}}
key = jax.random.key(1)
for _ in range(3):
    state, metrics = step(state, batch, key)
float(jax.device_get(metrics["loss"]))
t0 = time.perf_counter()
for _ in range({steps}):
    state, metrics = step(state, batch, key)
loss = float(jax.device_get(metrics["loss"]))
dt = time.perf_counter() - t0
assert np.isfinite(loss), loss
print("RESULT " + json.dumps({{"ms_per_step": dt / {steps} * 1e3}}))
"""
    import subprocess
    # the chip belongs to one process at a time: this parent never imports
    # JAX, and the children run one after another
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1200)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])["ms_per_step"], None
    err = (r.stderr or "")[-400:]
    return None, err.replace("\n", " ")[-400:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--blocks", type=int, nargs="+", default=[256, 512, 1024])
    ap.add_argument("--ns", type=int, nargs="+", default=[4096, 9216])
    ap.add_argument("--frontier", type=int, nargs="+", default=[16384, 25600])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "LADDER_LONGCTX.jsonl"))
    args = ap.parse_args()

    rows = []

    def record(n, bq, bk, dense=False):
        ms, err = measure(n, bq, bk, args.steps, dense=dense)
        row = {"n": n, "block_q": bq, "block_k": bk, "dense": dense,
               "ms_per_step": None if ms is None else round(ms, 1),
               "error": err}
        print(json.dumps(row), flush=True)
        rows.append(row)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        return ms

    # dense comparison arm (round-4 point: 224.5 ms at N=4096)
    record(4096, 0, 0, dense=True)
    for n in args.ns:
        for bq in args.blocks:
            for bk in args.blocks:
                record(n, bq, bk)

    done = [r for r in rows if not r["dense"] and r["ms_per_step"]]
    if done:
        best = min(done, key=lambda r: r["ms_per_step"])
        print(f"[ladder] winner at N={best['n']}: "
              f"bq={best['block_q']} bk={best['block_k']} "
              f"{best['ms_per_step']} ms", flush=True)
        # long-N frontier with the winning blocks
        for n in args.frontier:
            side = 14 * int(round(n ** 0.5))
            if (side // 14) ** 2 != n:
                continue
            record(n, best["block_q"], best["block_k"])


if __name__ == "__main__":
    main()
