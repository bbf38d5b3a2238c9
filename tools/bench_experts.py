"""Time the expert layer alone, on the chip: `SharedRoutedExperts` of
vitax/models/experts.py at the three expert cells' shapes (tokens, K, held of
routed, hidden, expert width, shared width), forward and forward + backward
in bfloat16, over sizes of the block of sorted rows its loops work on and of
the chunk whose rows meet the kernels' gradients in one product. How
`block_rows` and `BLOCKS_A_CHUNK` were chosen (PERF.md section 6, PR 49).

    chiprun --timeout 1500 -- python3 tools/bench_experts.py \
        --blocks 0 1024 2048 4096 --chunks 1 4

Block 0 is the layer's own rule. `--tree <dir>` times the layer of another
checkout of the package (the parent's whole-buffer form has no block to
set: one line a cell). A line a variant goes to
`chiprun_out/bench_experts.jsonl`: milliseconds a call (host clock over
`--reps` calls queued back to back, best of three), the slots the chip holds
and the rows the loops worked on. `--rehearse` runs tiny shapes on the CPU
(control flow only, its times mean nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

OUT = "chiprun_out/bench_experts.jsonl"
# cell: (tokens, K, held, routed, hidden, expert width, shared width,
#        route groups, groups a token, bias)
CELLS = {
    "lfm2": (16384, 4, 8, 64, 2048, 1536, 0, 0, 0, True),
    "laguna": (8192, 8, 32, 256, 2048, 512, 512, 0, 0, False),
    "ling": (4096, 8, 8, 512, 2560, 768, 768, 8, 4, True),
}
TINY = (256, 4, 2, 8, 64, 32, 32, 0, 0, False)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="+", default=list(CELLS))
    ap.add_argument("--blocks", nargs="+", type=int, default=[0])
    ap.add_argument("--chunks", nargs="+", type=int, default=[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp

    from vitax.models import experts
    from vitax.platform import setup_compile_cache
    setup_compile_cache()
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("bench_experts: no TPU (--rehearse runs the control flow)")
    blocked = hasattr(experts, "block_rows")
    rule, chunk_rule = (experts.block_rows, experts.BLOCKS_A_CHUNK) \
        if blocked else (None, 0)

    def ms(fn, *ops):
        best = float("inf")
        for _ in range(3):
            jax.block_until_ready(fn(*ops))
            t0 = time.perf_counter()
            out = [fn(*ops) for _ in range(args.reps)]
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.reps)
        return 1e3 * best

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for cell in args.cells:
        (tokens, k, held, routed, d, f, shared, groups, kept,
         bias) = TINY if args.rehearse else CELLS[cell]
        layer = experts.SharedRoutedExperts(
            routed, held, 0, k, f, shared, 2.5, jnp.bfloat16,
            route_groups=groups, groups_per_token=kept, route_bias=bias)
        keys = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(keys[0], (1, tokens, d), jnp.bfloat16)
        valid = jnp.ones((1, tokens), bool)
        push = jax.random.normal(keys[1], x.shape, jnp.float32)
        params = jax.jit(layer.init)(keys[2], x, valid)

        def forward(params, x):
            y, cols = layer.apply(params, x, valid, mutable=["intermediates"])
            return y, cols["intermediates"]

        def both(params, x):
            def loss(params, x):
                y, sown = forward(params, x)
                return jnp.sum(y.astype(jnp.float32) * push), sown
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
                params, x)

        variants = [(b, c) for b in args.blocks for c in args.chunks] \
            if blocked else [(0, 0)]
        for block, chunk in variants:
            if blocked:
                experts.block_rows = (
                    (lambda *a, b=block: b) if block else rule)
                experts.BLOCKS_A_CHUNK = chunk or chunk_rule
            jax.clear_caches()      # the sizes are no argument of the jit
            t0 = time.perf_counter()
            fwd, bwd = jax.jit(forward), jax.jit(both)
            (_, sown), grads = bwd(params, x)
            jax.block_until_ready(grads)
            line = {
                "cell": cell, "tree": args.tree, "block": block,
                "chunk": chunk,
                "rule": ([rule(tokens * k, held, routed), chunk_rule]
                         if blocked else None),
                "device": jax.devices()[0].device_kind,
                "rehearsal": args.rehearse,
                "slots_here": int(jnp.sum(sown["expert_load"][0])),
                "rows_computed": (int(sown["expert_rows_computed"][0])
                                  if "expert_rows_computed" in sown
                                  else tokens * k),
                "finite": bool(all(jnp.isfinite(g.astype(jnp.float32)).all()
                                   for g in jax.tree.leaves(grads))),
                "first_call_s": round(time.perf_counter() - t0, 2),
                "fwd_ms": ms(fwd, params, x),
                "fwd_bwd_ms": ms(bwd, params, x)}
            with open(OUT, "a") as out:
                out.write(json.dumps(line) + "\n")
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
