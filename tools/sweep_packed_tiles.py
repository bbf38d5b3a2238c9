"""Time the packed attention kernels alone, on the chip, over block and
sub-tile shapes: where `PACKED_TILES`, `WINDOW_TILES`, `CAUSAL_BLOCKS` and
`WINDOW_BLOCKS` of vitax/ops/flash_blocked.py come from.

    chiprun --timeout 2400 -- python3 tools/sweep_packed_tiles.py

Three layouts, the two packed cells' (benchmark/traffic): `moonvit` (2 rows
of 8,192, 16 heads of 72, segment mask), `causal` (1 row, 48 query heads
over 8 key/value heads of 128) and `window` (64 over 8, window 512). For
each (layout, blocks) every sub-tile shape of {128, 256, 512} x {128, 256,
512, 1024} that divides the block into at most 32 tiles, and the whole block
(one sub-tile a pair: no second level of liveness), one kernel at a time:
the forward through `_packed_fwd`, dK/dV and dQ through `_packed_bwd` with
the other's outputs unused, which XLA then removes. A line a variant goes
to `chiprun_out/sweep_packed_tiles.jsonl`: milliseconds
a call (host clock over `--reps` calls queued back to back, best of three),
the score pairs a head the variant computes over the pairs needed, and its
largest difference from the whole-block variant's result.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/sweep_packed_tiles.jsonl"
KERNELS = ("fwd", "dkv", "dq")
# (layout, blocks, "all" sub-tile shapes or a short list): the blocks the
# constants name in full, the blocks they were chosen over in short
SHORT = [(256, 256), (256, 512), (512, 512), (128, 256)]
JOBS = [
    ("moonvit", (512, 1024), "all"), ("causal", (512, 1024), "all"),
    ("window", (512, 1024), "all"),
    ("window", (512, 512), SHORT), ("causal", (512, 512), SHORT),
    ("moonvit", (1024, 1024), SHORT),
]


def layouts():
    from vitax.data.packing import document_layout

    def traffic(name):
        with open(os.path.join("benchmark", "traffic", name + ".json")) as f:
            return json.load(f)

    moon = traffic("packed_2x8192_docmix")
    seg = document_layout([[h * w for h, w in row] for row in moon["rows"]],
                          moon["row_tokens"],
                          moon["images_per_row"])["segment_ids"]
    code = traffic("packed_1x8192_codemix")
    docs = document_layout(code["rows"], code["row_tokens"],
                           code["docs_per_row"])["segment_ids"]
    need = code["layout"]
    return {
        "moonvit": dict(seg=seg, heads=16, kv=16, dh=72, window=0,
                        causal=False, need=moon["layout"]["token_pairs"]),
        "causal": dict(seg=docs, heads=48, kv=8, dh=128, window=0,
                       causal=True, need=need["causal_pairs"]),
        "window": dict(seg=docs, heads=64, kv=8, dh=128, window=512,
                       causal=True, need=need["window_pairs"]),
    }


def sub_shapes(bq, bk, which):
    every = [(sq, sk) for sq, sk in itertools.product(
        (128, 256, 512), (128, 256, 512, 1024))
        if bq % sq == 0 and bk % sk == 0
        and (bq // sq) * (bk // sk) <= 32 and (sq, sk) != (bq, bk)]
    if which != "all":
        every = [s for s in every if s in which]
    return [(bq, bk)] + every


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--deadline_s", type=float, default=2000.0,
                    help="start no variant after this many seconds")
    ap.add_argument("--only", default="", help="layout names, comma separated")
    args = ap.parse_args()
    t_start = time.time()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from vitax.ops import flash_blocked as fb

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    out = open(OUT, "a")
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)

    def timed(fn, *a):
        result = jax.block_until_ready(fn(*a))          # compile, warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                last = fn(*a)
            jax.block_until_ready(last)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return result, best * 1e3

    def gap(a, b):
        return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                         - y.astype(jnp.float32))))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    cases = layouts()
    for name, (bq, bk), which in JOBS:
        if args.only and name not in args.only.split(","):
            continue
        lay = cases[name]
        seg = jnp.asarray(lay["seg"])
        r, t = seg.shape
        grouped = lay["kv"] != lay["heads"]
        hb = (lay["heads"] // lay["kv"] if grouped
              else fb.PACKED_HEADS_PER_STEP)
        keys = jax.random.split(jax.random.key(0), 4)
        q, do = (jax.random.normal(k, (r * lay["heads"], t, lay["dh"]),
                                   jnp.bfloat16) for k in keys[:2])
        k_, v = (jax.random.normal(k, (r * lay["kv"], t, lay["dh"]),
                                   jnp.bfloat16) for k in keys[2:])
        common = dict(causal=lay["causal"], window=lay["window"],
                      grouped=grouped)
        scale = lay["dh"] ** -0.5

        def run(kernel, sub):
            tiles = fb.Tiles(*(sub if kk == kernel else (bq, bk)
                               for kk in KERNELS))
            if kernel == "fwd":
                return jax.jit(lambda q, k, v: fb._packed_fwd(
                    q, k, v, seg, scale, bq, bk, hb, lay["heads"], True,
                    tiles=tiles, **common))
            pick = (lambda g: g[1:]) if kernel == "dkv" else (lambda g: g[0])
            return jax.jit(lambda q, k, v, o, lse, do: pick(fb._packed_bwd(
                q, k, v, o, lse, do, seg, scale, bq, bk, hb, lay["heads"],
                True, tiles=tiles, **common)))

        o, lse = jax.block_until_ready(run("fwd", (bq, bk))(q, k_, v))
        for kernel in KERNELS:
            base = None
            for sub in sub_shapes(bq, bk, which):
                if time.time() - t_start > args.deadline_s:
                    print("deadline: stopping", flush=True)
                    return
                a = (q, k_, v) if kernel == "fwd" else (q, k_, v, o, lse, do)
                try:
                    result, ms = timed(run(kernel, sub), *a)
                except Exception as e:  # a shape Mosaic refuses: say so, go on
                    print(name, (bq, bk), kernel, sub, "FAILED",
                          str(e).splitlines()[0][:200], flush=True)
                    continue
                base = result if base is None else base
                bits = fb.packed_block_tables(
                    seg, bq, bk, True, lay["causal"], lay["window"], sub)[0]
                area = (int(np.sum(np.asarray(jax.lax.population_count(bits))))
                        * sub[0] * sub[1])
                line = dict(layout=name, blocks=[bq, bk], kernel=kernel,
                            sub=list(sub), ms=round(ms, 4),
                            computed_over_needed=round(area / lay["need"], 4),
                            max_gap=gap(result, base),
                            device=device.device_kind)
                out.write(json.dumps(line) + "\n")
                out.flush()
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
