"""Time the delta rule alone, on the chip: the plain `kda`
(vitax/models/kda.py) against the fused kernels (vitax/ops/kda.py) over
`HEADS_PER_STEP`, at the Ling cell's shape and layout
(benchmark/traffic/packed_1x4096_tracemix.json: one row of 4,096 tokens, 16
heads of 128, chunks of 64 in sub-chunks of 16, gate bound -5). Where
`HEADS_PER_STEP` of vitax/ops/kda.py comes from.

    chiprun --timeout 900 -- python3 tools/bench_kda.py

A line a variant goes to `chiprun_out/bench_kda.jsonl`: milliseconds a call
of the forward and of forward + backward (host clock over `--reps` calls
queued back to back, best of three), the seconds of both programs' trace,
lowering and compile apart (the first two are the host's Python and paid by a
run that finds its programs in the compile cache too; the forward + backward
program finds the forward's jitted kernel already traced, as a step's second
site does), and the variant's largest relative distance from the plain form's o and gradients (norm of the
difference over the norm), both compiled on the chip in bfloat16, and every
variant's distance (`off_float32`) from the plain form in float32 with
full-precision products, compiled on the chip too. A last line
holds the inverse alone: max |X (I + A) - I| of the kernel's float32 products
on the chip, which a single bf16 pass would leave at 1e-3.

`--scalar` adds the rule with ONE decay a head (Gated DeltaNet; the plain form
only: no kernel tiles it, `vitax/programs/kernels.py`) at the Olmo-Hybrid
cell's shape and layout (benchmark/traffic/packed_1x4096_webmix.json: 15
heads, a 96 x 192 state, chunks of 64, beta in (0, 2), an unbounded decay),
over `INVERSE_BASE` of vitax/models/kda.py (the blocks the triangular inverse
is merged up from): the number a later kernel for that shape starts from,
beside the per-channel form's `plain` line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = "chiprun_out/bench_kda.jsonl"
HEADS, HEAD_SIZE, GATE_BOUND = 16, 128, -5.0
NAMES = ("q", "k", "v", "g", "beta")


SCALAR = dict(traffic="packed_1x4096_webmix", heads=15, key_size=96,
              value_size=192)


def operands(seed: int = 0, traffic: str = "packed_1x4096_tracemix",
             heads: int = HEADS, key_size: int = 0, value_size: int = 0):
    """(segment ids, (q, k, v, g, beta), a cotangent) as the mixer hands them
    over at the cell's shape: q and k unit length a head, zero at padding.
    With `key_size`: one decay a head, softplus-sized and unbounded, beta in
    (0, 2), keys of `key_size` and values of `value_size` (`SCALAR`)."""
    import jax
    import jax.numpy as jnp

    from vitax.data.packing import document_layout
    with open(f"benchmark/traffic/{traffic}.json") as f:
        traffic = json.load(f)
    seg = jnp.asarray(document_layout(
        traffic["rows"], traffic["row_tokens"],
        traffic["docs_per_row"])["segment_ids"])
    r, t = seg.shape
    ks = jax.random.split(jax.random.key(seed), 6)
    shape = (r, t, heads, key_size or HEAD_SIZE)
    valid = (seg > 0)[..., None]

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], shape)) * shape[-1] ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    wide = shape[:3] + (value_size or HEAD_SIZE,)
    v = jax.nn.silu(jax.random.normal(ks[2], wide))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    if key_size:    # -exp(A_log) * softplus(.): a few tenths, some tokens -16
        g = -16.0 * jax.nn.sigmoid(jax.random.normal(ks[3], shape[:3]) - 4.0)
        g, beta = jnp.where(valid, g, 0.0), 2.0 * beta
    else:
        g = GATE_BOUND * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], shape))
        g = jnp.where(valid[..., None], g, 0.0)
    beta = jnp.where(valid, beta, 0.0)
    q, k, v = (jnp.where(valid[..., None], x, 0.0).astype(jnp.bfloat16)
               for x in (q, k, v))
    return seg, (q, k, v, g, beta), jax.random.normal(ks[5], wide)


def gap(got, want) -> float:
    import jax.numpy as jnp
    import numpy as np
    got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def inverse_residual(seed: int = 0) -> float:
    """max |X (I + A) - I| over 64 strictly lower (64, 64) matrices of the
    size beta (k . k) has, X by the kernel's own doublings inside a Mosaic
    kernel; the check itself in float64 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from vitax.ops import kda as fused
    a = jnp.tril(jax.random.normal(jax.random.key(seed), (64, 64, 64)),
                 -1) * 0.25

    def kernel(a_ref, x_ref):
        x_ref[0] = fused.unit_lower_inverse(a_ref[0])

    x = pl.pallas_call(
        kernel, grid=(64,),
        in_specs=[pl.BlockSpec((1, 64, 64), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 64, 64), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=fused._interpret())(a)
    x, a = np.asarray(x, np.float64), np.asarray(a, np.float64)
    eye = np.eye(64)
    return float(np.max(np.abs(x @ (eye + a) - eye)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--variants", nargs="*", type=int, default=[16, 8, 4, 1],
                    help="HEADS_PER_STEP")
    ap.add_argument("--scalar", nargs="*", type=int, default=None,
                    metavar="INVERSE_BASE",
                    help="also the scalar-decay plain rule at the "
                         "Olmo-Hybrid cell's shape, over INVERSE_BASE "
                         "(default: the program's)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from vitax.models.kda import kda
    from vitax.ops import kda as fused

    # compile_s is the compiler's time, not a read of the machine's cache
    jax.config.update("jax_enable_compilation_cache", False)
    seg, ops, w = operands()
    chunk, sub = fused.chunk_tiling(seg.shape[1], GATE_BOUND)
    dtype = jnp.bfloat16

    def programs(rule, seg=seg, w=w):
        def forward(*o):
            return rule(*o, seg, chunk, sub, dtype)

        def both(*o):
            return jax.value_and_grad(lambda *o: jnp.sum(forward(*o) * w),
                                      argnums=tuple(range(5)))(*o)
        return jax.jit(forward), jax.jit(both)

    def ms(fn, ops=ops):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = None
            for _ in range(args.reps):
                out = fn(*ops)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return 1e3 * best

    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def report(line):
        print(json.dumps(line), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")

    exact = tuple(a.astype(jnp.float32) for a in ops)
    with jax.default_matmul_precision("highest"):
        truth = jax.block_until_ready(jax.jit(jax.value_and_grad(
            lambda *o: jnp.sum(kda(*o, seg, chunk, sub, jnp.float32) * w),
            argnums=tuple(range(5))))(*exact))[1]
    want = None
    for hb in [None] + list(args.variants):
        if hb is not None:
            fused.HEADS_PER_STEP = hb
        forward, both = programs(kda if hb is None else fused.kda_fused)
        line = {"variant": "plain" if hb is None else "fused",
                "heads_per_step": hb, "chunk": chunk, "sub": sub,
                "device": jax.devices()[0].device_kind}
        try:
            # the host's part (Python runs the bodies; jaxpr to Mosaic and
            # StableHLO) apart from the compiler's: a run that finds its
            # programs in the compile cache still pays the first two
            stages = [time.perf_counter()]
            traced = [f.trace(*ops) for f in (forward, both)]
            stages.append(time.perf_counter())
            lowered = [t.lower() for t in traced]
            stages.append(time.perf_counter())
            forward, both = (low.compile() for low in lowered)
            stages.append(time.perf_counter())
            trace_s, lower_s, compile_s = (
                round(b - a, 2) for a, b in zip(stages, stages[1:]))
            o = jax.block_until_ready(forward(*ops))
            (_, grads) = jax.block_until_ready(both(*ops))
            line.update(trace_s=trace_s, lower_s=lower_s, compile_s=compile_s,
                        fwd_ms=round(ms(forward), 4),
                        fwd_bwd_ms=round(ms(both), 4))
            line["off_float32"] = dict(zip(NAMES, (
                gap(a, b) for a, b in zip(grads, truth))))
            if want is None:
                want = (o, grads)
            else:
                line["gap_o"] = gap(o, want[0])
                line["gap_grads"] = dict(zip(NAMES, (
                    gap(a, b) for a, b in zip(grads, want[1]))))
        except Exception as e:  # noqa: BLE001 — a variant Mosaic refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        report(line)
    report({"inverse_max_residual": inverse_residual(),
            "device": jax.devices()[0].device_kind})
    if args.scalar is not None:
        scalar_rule(args.scalar, programs, ms, report)


def scalar_rule(bases, programs, ms, report) -> None:
    """The plain rule with one decay a head at `SCALAR`'s shape, a line an
    `INVERSE_BASE`: milliseconds and the distance from float32."""
    import jax
    import jax.numpy as jnp

    from vitax.models import kda as plain
    seg, ops, w = operands(**SCALAR)
    chunk = plain.chunk_tiling(seg.shape[1], GATE_BOUND)[0]
    exact = tuple(a.astype(jnp.float32) for a in ops)
    with jax.default_matmul_precision("highest"):
        truth = jax.block_until_ready(jax.jit(jax.value_and_grad(
            lambda *o: jnp.sum(plain.kda(*o, seg, chunk, chunk,
                                         jnp.float32) * w),
            argnums=tuple(range(5))))(*exact))[1]
    for base in bases or [plain.INVERSE_BASE]:
        plain.INVERSE_BASE = base
        forward, both = programs(plain.kda, seg, w)
        t0 = time.perf_counter()
        forward, both = (f.lower(*ops).compile() for f in (forward, both))
        line = {"variant": "plain_scalar", "inverse_base": base,
                "chunk": chunk, **SCALAR,
                "trace_lower_compile_s": round(time.perf_counter() - t0, 2),
                "device": jax.devices()[0].device_kind}
        (_, grads) = jax.block_until_ready(both(*ops))
        line.update(fwd_ms=round(ms(forward, ops), 4),
                    fwd_bwd_ms=round(ms(both, ops), 4))
        line["off_float32"] = dict(zip(NAMES, (
            gap(a, b) for a, b in zip(grads, truth))))
        report(line)


if __name__ == "__main__":
    main()
