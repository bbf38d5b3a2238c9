"""Time the recurrent mixers' short convolution alone, on the chip: the plain
`conv_silu` (vitax/models/ssm.py: `causal_conv`, silu and, a delta mixer, the
L2 norms of q and k a head) against the kernel pair of vitax/ops/conv.py, at
the three recurrent cells' shapes and layouts (one row of 4,096 tokens each):

    ling     6,144 channels, 16 heads of 128 normed, no bias, tracemix
    granite  4,352 channels, a bias, no norm, chatmix
    olmo     5,760 channels, 30 heads of 96 normed, no bias, webmix

over `LANE_BLOCK` and `ROW_BLOCK`. Where those two constants come from.

    chiprun --timeout 900 -- python3 tools/bench_conv.py

A line a shape and variant goes to `chiprun_out/bench_conv.jsonl`:
milliseconds a call of the forward and of forward + backward (host clock over
`--reps` calls queued back to back, best of three), the seconds of both
programs' trace, lowering and compile apart (the first two are the host's
Python and paid by a run that finds its programs in the compile cache too),
the bytes one pass needs each way over the chip's 819 GB/s as
`roofline_fwd_ms` / `roofline_fwd_bwd_ms`, the variant's largest relative
distance from the plain form's y and gradients (norm of the difference over
the norm; both compiled on the chip in bfloat16), and every variant's
distance (`off_float32`) from the plain form with a float32 output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/bench_conv.jsonl"
HBM_BYTES_PER_S = 819e9
NAMES = ("x", "kernel", "bias")
# channels, taps, a bias, (head, normed, scaled), the cell's traffic
SHAPES = {
    "ling": (6144, 4, False, (128, 4096, 2048), "packed_1x4096_tracemix"),
    "granite": (4352, 4, True, None, "packed_1x4096_chatmix"),
    "olmo": (5760, 4, False, (96, 2880, 1440), "packed_1x4096_webmix"),
}


def operands(shape: str, seed: int = 0):
    """(segment ids, (x, kernel, bias or None), a cotangent, norm) as the
    mixer of the cell hands them over."""
    import jax
    import jax.numpy as jnp

    from vitax.data.packing import document_layout
    from vitax.models.ssm import conv_init
    channels, taps, bias, norm, traffic = SHAPES[shape]
    with open(f"benchmark/traffic/{traffic}.json") as f:
        traffic = json.load(f)
    seg = jnp.asarray(document_layout(
        traffic["rows"], traffic["row_tokens"],
        traffic["docs_per_row"])["segment_ids"])
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], seg.shape + (channels,)).astype(jnp.bfloat16)
    kernel = conv_init(ks[1], (taps, channels))
    b = 0.1 * jax.random.normal(ks[2], (channels,)) if bias else None
    w = jax.random.normal(ks[3], x.shape)
    return seg, (x, kernel, b), w, norm


def gap(got, want) -> float:
    import jax.numpy as jnp
    import numpy as np
    got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--variants", nargs="*",
                    default=["512:128", "256:128", "512:64", "512:256"],
                    help="LANE_BLOCK:ROW_BLOCK")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from vitax.models.ssm import conv_silu
    from vitax.ops import conv as fused
    from vitax.platform import device_kind

    # compile_s is the compiler's time, not a read of the machine's cache
    jax.config.update("jax_enable_compilation_cache", False)
    dtype = jnp.bfloat16
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def report(line):
        print(json.dumps(line), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")

    def ms(fn, ops):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = None
            for _ in range(args.reps):
                out = fn(*ops)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return 1e3 * best

    for shape in args.shapes:
        seg, (x, kernel, b), w, norm = operands(shape)
        ops = (x, kernel) + (() if b is None else (b,))
        one_way = x.size * 2
        def programs(conv, out_dtype=dtype):
            def forward(x, kernel, *bias):
                return conv(x, seg, kernel, *(bias or (None,)), out_dtype,
                            norm)

            def both(*o):
                return jax.value_and_grad(
                    lambda *o: jnp.sum(forward(*o).astype(jnp.float32) * w),
                    argnums=tuple(range(len(o))))(*o)
            return jax.jit(forward), jax.jit(both)

        truth = jax.block_until_ready(
            programs(conv_silu, jnp.float32)[1](*ops))[1]
        want = None
        for variant in [None] + list(args.variants):
            if variant is not None:
                fused.LANE_BLOCK, fused.ROW_BLOCK = map(int,
                                                        variant.split(":"))
            forward, both = programs(
                conv_silu if variant is None else fused.conv_silu)
            line = {"shape": shape,
                    "variant": "plain" if variant is None else "fused",
                    "lane_block:row_block": variant,
                    "roofline_fwd_ms": round(
                        2e3 * one_way / HBM_BYTES_PER_S, 4),
                    "roofline_fwd_bwd_ms": round(
                        5e3 * one_way / HBM_BYTES_PER_S, 4),
                    "device": device_kind()}
            try:
                stages = [time.perf_counter()]
                traced = [f.trace(*ops) for f in (forward, both)]
                stages.append(time.perf_counter())
                lowered = [t.lower() for t in traced]
                stages.append(time.perf_counter())
                forward, both = (low.compile() for low in lowered)
                stages.append(time.perf_counter())
                trace_s, lower_s, compile_s = (
                    round(b - a, 2) for a, b in zip(stages, stages[1:]))
                y = jax.block_until_ready(forward(*ops))
                (_, grads) = jax.block_until_ready(both(*ops))
                line.update(trace_s=trace_s, lower_s=lower_s,
                            compile_s=compile_s,
                            fwd_ms=round(ms(forward, ops), 4),
                            fwd_bwd_ms=round(ms(both, ops), 4))
                line["off_float32"] = dict(zip(NAMES, (
                    gap(a, c) for a, c in zip(grads, truth))))
                if want is None:
                    want = (y, grads)
                else:
                    line["gap_y"] = gap(y, want[0])
                    line["gap_grads"] = dict(zip(NAMES, (
                        gap(a, c) for a, c in zip(grads, want[1]))))
            except Exception as e:  # noqa: BLE001 — a variant Mosaic refuses
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            report(line)
            # a variant's tiling is part of the jitted calls' static
            # arguments, so the next one traces anew
            jax.clear_caches()


if __name__ == "__main__":
    main()
