"""Time the gated short convolution alone, on the chip: the plain
`gated_conv` of vitax/models/gconv.py (C * conv(B * x), no activation: what
a `conv` layer runs between its two projections) at the LFM2 cell's shape and
layout (2 rows of 8,192 tokens, 2,048 channels, 3 taps,
`packed_rows8192_tunemix`), forward and forward + backward, in bfloat16. The
parent's number of the kernel PR that fuses it.

    chiprun --timeout 900 -- python3 tools/bench_gconv.py

One line goes to `chiprun_out/bench_gconv.jsonl`: milliseconds a call of the
forward and of forward + backward (host clock over `--reps` calls queued back
to back, best of three), the bytes each needs over the chip's 819 GB/s as
`roofline_fwd_ms` / `roofline_fwd_bwd_ms` (benchmark/roofline_lfm2.py:
gated_conv_need's streams: four a token forward, eleven with the backward),
the compile seconds, and the largest relative distance of the bfloat16 y and
gradients from the same function with float32 operands. `--rehearse` runs a
tiny shape on the CPU (control flow only, its times mean nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/bench_gconv.jsonl"
HBM_BYTES_PER_S = 819e9
TRAFFIC = "benchmark/traffic/packed_rows8192_tunemix.json"
CHANNELS, TAPS = 2048, 3


def operands(rehearse: bool, seed: int = 0):
    """(segment ids, the projection [B; C; x], the taps, a cotangent) as the
    mixer of the cell hands them over."""
    import jax
    import jax.numpy as jnp

    from vitax.data.packing import document_layout
    from vitax.models.ssm import conv_init
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    if rehearse:
        traffic.update(traffic["rehearse"])
    channels = 128 if rehearse else CHANNELS
    seg = jnp.asarray(document_layout(
        traffic["rows"], traffic["row_tokens"],
        traffic["docs_per_row"])["segment_ids"])
    keys = jax.random.split(jax.random.key(seed), 3)
    projected = jax.random.normal(keys[0], (*seg.shape, 3 * channels))
    taps = conv_init(keys[1], (TAPS, channels))
    cotangent = jax.random.normal(keys[2], (*seg.shape, channels))
    return seg, projected, taps, cotangent


def gap(got, want) -> float:
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark.roofline_lfm2 import gated_conv_need
    from vitax.models.gconv import gated_conv
    from vitax.platform import setup_compile_cache
    setup_compile_cache()
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("bench_gconv: no TPU (--rehearse runs the control flow)")

    seg, projected, taps, cotangent = operands(args.rehearse)
    valid = int(jnp.sum(seg > 0))

    def programs(dtype):
        def forward(projected, taps):
            return gated_conv(projected.astype(dtype), seg, taps, dtype)

        def both(projected, taps):
            y, pull = jax.vjp(forward, projected, taps)
            return (y, *pull(cotangent.astype(dtype)))
        return jax.jit(forward), jax.jit(both)

    def ms(fn, *ops):
        best = float("inf")
        for _ in range(3):
            jax.block_until_ready(fn(*ops))
            t0 = time.perf_counter()
            out = [fn(*ops) for _ in range(args.reps)]
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return 1e3 * best

    low = projected.astype(jnp.bfloat16)
    forward, both = programs(jnp.bfloat16)
    t0 = time.perf_counter()
    forward.lower(low, taps).compile()
    both.lower(low, taps).compile()
    compile_s = time.perf_counter() - t0
    exact = programs(jnp.float32)[1](low.astype(jnp.float32), taps)
    got = both(low, taps)
    _, need = gated_conv_need(valid, projected.shape[-1] // 3, TAPS, 1)
    line = {
        "shape": list(projected.shape), "taps": TAPS, "valid_tokens": valid,
        "device": jax.devices()[0].device_kind, "rehearsal": args.rehearse,
        "compile_s": round(compile_s, 2),
        "fwd_ms": ms(forward, low, taps),
        "fwd_bwd_ms": ms(both, low, taps),
        "roofline_fwd_ms": 1e3 * need * 4 / 11 / HBM_BYTES_PER_S,
        "roofline_fwd_bwd_ms": 1e3 * need / HBM_BYTES_PER_S,
        "off_float32": max(gap(a, b) for a, b in zip(got, exact))}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
