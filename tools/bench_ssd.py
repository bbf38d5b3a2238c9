"""Time the state-space scan alone, on the chip: the plain `ssd`
(vitax/models/ssm.py) against the fused kernels (vitax/ops/ssd.py) over
`HEADS_PER_STEP` and `ROW_BLOCK`, at the hybrid cell's shape and layout
(benchmark/traffic/packed_1x4096_chatmix.json: one row of 4,096 tokens, 64
heads of 64, one group, state 128, chunk 256). Where `HEADS_PER_STEP` and
`ROW_BLOCK` of vitax/ops/ssd.py come from.

    chiprun --timeout 900 -- python3 tools/bench_ssd.py

A line a variant goes to `chiprun_out/bench_ssd.jsonl`: milliseconds a call
of the forward and of forward + backward (host clock over `--reps` calls
queued back to back, best of three), the Mosaic compile's seconds, and the
variant's largest relative distance from the plain form's y and gradients
(norm of the difference over the norm).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/bench_ssd.jsonl"
HEADS, HEAD_SIZE, GROUPS, STATE, CHUNK = 64, 64, 1, 128, 256


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--variants", nargs="*",
                    default=["16:128", "8:128", "32:128", "16:256"],
                    help="HEADS_PER_STEP:ROW_BLOCK")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vitax.data.packing import document_layout
    from vitax.models.ssm import ssd
    from vitax.ops import ssd as fused

    with open("benchmark/traffic/packed_1x4096_chatmix.json") as f:
        traffic = json.load(f)
    seg = jnp.asarray(document_layout(
        traffic["rows"], traffic["row_tokens"],
        traffic["docs_per_row"])["segment_ids"])
    r, t = seg.shape
    dtype = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 7)
    valid = (seg > 0)[..., None, None]
    x = jax.random.normal(ks[0], (r, t, HEADS, HEAD_SIZE)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (r, t, HEADS)) - 3.0)
    a_head = -jnp.exp(jax.random.uniform(ks[2], (HEADS,), maxval=2.7))
    b = (0.3 * jax.random.normal(ks[3], (r, t, GROUPS, STATE))).astype(dtype)
    c = (0.3 * jax.random.normal(ks[4], (r, t, GROUPS, STATE))).astype(dtype)
    d_skip = jax.random.normal(ks[5], (HEADS,))
    w = jax.random.normal(ks[6], (r, t, HEADS, HEAD_SIZE))
    operands = (x, delta, a_head, b, c, d_skip)

    def programs(scan):
        def forward(x, *ops):      # x is zero at padding, as the mixer's
            return scan(jnp.where(valid, x, jnp.zeros((), dtype)), *ops, seg,
                        CHUNK, dtype)

        def both(*ops):
            return jax.value_and_grad(
                lambda *o: jnp.sum(forward(*o) * w),
                argnums=tuple(range(6)))(*ops)
        return jax.jit(forward), jax.jit(both)

    def ms(fn):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = None
            for _ in range(args.reps):
                out = fn(*operands)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return 1e3 * best

    def gap(got, want):
        got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                     for a in (got, want))
        return float(np.linalg.norm(got - want)
                     / max(np.linalg.norm(want), 1e-30))

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    want = None
    variants = [("plain", None, None)] + [
        ("fused", *map(int, v.split(":"))) for v in args.variants]
    for name, hb, row_block in variants:
        if hb is not None:
            fused.HEADS_PER_STEP, fused.ROW_BLOCK = hb, row_block
        forward, both = programs(ssd if hb is None else fused.ssd_fused)
        line = {"variant": name, "heads_per_step": hb,
                "row_block": row_block, "device": jax.devices()[0].device_kind}
        try:
            t0 = time.perf_counter()
            y = jax.block_until_ready(forward(*operands))
            (_, grads) = jax.block_until_ready(both(*operands))
            line.update(compile_s=round(time.perf_counter() - t0, 2),
                        fwd_ms=round(ms(forward), 4),
                        fwd_bwd_ms=round(ms(both), 4))
            if want is None:
                want = (y, grads)
            else:
                line["gap_y"] = gap(y, want[0])
                line["gap_grads"] = dict(zip(
                    ("x", "delta", "A", "B", "C", "D"),
                    (gap(g, h) for g, h in zip(grads, want[1]))))
        except Exception as e:  # noqa: BLE001 — a variant Mosaic refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
