#!/usr/bin/env python3
"""On-hardware numerics check for the Pallas attention kernels.

The CPU test suite runs every kernel in Pallas interpret mode, which
faithfully emulates the math but NOT Mosaic's lowering: real-TPU-only
failure modes (tiling legality, layout padding, sublane rules — e.g. the
hb=4 lse block the round-3 10b_slice compile rejected) and real-dtype MXU
behavior are invisible there. This tool compiles and runs each kernel
family on the actual attached TPU against the dense jnp reference, fwd and
backward, in bf16, and fails loudly on divergence.

Usage: python tools/check_kernels_on_chip.py   (needs a TPU; ~1 min)

Shapes cover the three dispatch paths of vitax/ops/attention.py:
- 4D whole-N kernel, full-array head blocks (l14/b16 geometry)
- 4D whole-N kernel, grouped-padded lse (10B-family geometry, hb=4)
- the fused-qkv entry of the 4D kernel at both geometries
- BH relayout kernel (forced)
plus the streaming blocked kernel (vitax/ops/flash_blocked.py) at a
sequence length past MAX_SEQ_IN_VMEM's block sizes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# bf16 has ~3 decimal digits; the fused kernels do softmax/accum in f32 so
# outputs agree to bf16 resolution against the (also f32-accumulating) dense
# reference
REL_TOL = 0.06


def check(name, fn, ref, shape, dtype=jnp.bfloat16, seed=0):
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)
    ct = jax.random.normal(kg, shape, dtype)

    def run(f):
        o, vjp = jax.vjp(lambda a, b, c: f(a, b, c), q, k, v)
        return [np.asarray(x, np.float32) for x in (o, *vjp(ct))]

    got, want = run(fn), run(ref)
    worst = 0.0
    for tag, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        err = float(np.max(np.abs(g - w)) / max(1e-6, np.max(np.abs(w))))
        worst = max(worst, err)
        status = "ok" if err < REL_TOL else "FAIL"
        print(f"  {name:34s} {tag:3s} rel-max-err {err:.4f} {status}")
        if err >= REL_TOL:
            return False
    return True


def main():
    from vitax.platform import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]  # vtx: ignore[VTX104] CLI entry point: probes whatever backend the user launched on
    if dev.platform != "tpu":
        print(f"no TPU attached (found {dev.platform}); this tool checks "
              f"real-hardware lowering — run it on a chip", file=sys.stderr)
        return 2

    from vitax.ops.attention import (_heads_per_program, flash_attention,
                                     flash_attention_4d, flash_attention_qkv,
                                     reference_attention)
    from vitax.ops.flash_blocked import blocked_flash_attention

    print(f"device: {dev.device_kind}")
    ok = True
    # dispatch-path preconditions: if head-grouping selection changed, the
    # labels below would describe the wrong kernel geometry — report, don't
    # assert (python -O must not skip these)
    for shape_args, want_hb, label in [((256, 16, 64, 2), 16, "l14"),
                                       ((256, 32, 160, 2), 4, "10B")]:
        got_hb = _heads_per_program(*shape_args)
        if got_hb != want_hb:
            print(f"  precondition FAIL: {label} geometry picks hb={got_hb}, "
                  f"expected {want_hb} — selection logic changed; update the "
                  f"path labels/shapes in this tool")
            ok = False
    # l14 geometry: full-array head blocks (hb == h)
    ok &= check("4D full-array (l14: h16 dh64)", flash_attention_4d,
                reference_attention, (4, 256, 16, 64))
    # 10B-family geometry: grouped-padded lse (hb=4, P=8)
    ok &= check("4D padded-lse (10B: h32 dh160)", flash_attention_4d,
                reference_attention, (8, 256, 32, 160))
    # the same two geometries through the fused-qkv entry: one (B, N, 3D)
    # operand read through three block windows, one (B, N, 3D) cotangent —
    # a (1, N, 3D) block out at l14 (one head group), written by the
    # kernel's own DMAs from two VMEM slots at the 10B widths (8 groups)

    def fused_qkv(q, k, v):
        b, n, h, dh = q.shape
        qkv = jnp.concatenate(
            [x.reshape(b, n, h * dh) for x in (q, k, v)], axis=-1)
        return flash_attention_qkv(qkv, h).reshape(q.shape)

    ok &= check("4D fused qkv (l14: 1 head group)", fused_qkv,
                reference_attention, (4, 256, 16, 64))
    ok &= check("4D fused qkv (10B: 8 head groups)", fused_qkv,
                reference_attention, (8, 256, 32, 160))
    # BH relayout kernel, forced (the fallback dispatch path)
    ok &= check("BH relayout (h8 dh64)", flash_attention,
                reference_attention, (2, 256, 8, 64))
    # streaming blocked kernel (long-sequence path)
    ok &= check("streaming blocked (n4096)", blocked_flash_attention,
                reference_attention, (1, 4096, 4, 64))

    # dropout variants (round 5): the dense comparator shares the
    # counter-hash mask code, so these check Mosaic's lowering of the
    # uint32 hash + masked-softmax math on real hardware, fwd and bwd
    from vitax.ops.attention import (dropout_keep_mask, flash4_dropout,
                                     flash_bh_dropout, _to_bh, _from_bh)
    from vitax.ops.flash_blocked import blocked_dropout_attention
    seed32, rate = jnp.uint32(2024), 0.2

    def dense_masked(q, k, v):
        b, n, h, dh = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        probs = jax.nn.softmax(s, axis=-1)
        mask = jnp.stack([jnp.stack([
            dropout_keep_mask(seed32, jnp.uint32(bi * h + hi), n, n, rate)
            for hi in range(h)]) for bi in range(b)])
        return jnp.einsum("bhqk,bkhd->bqhd",
                          (probs * mask / (1 - rate)).astype(q.dtype), v)

    ok &= check("4D dropout (l14 geometry)",
                lambda q, k, v: flash4_dropout(
                    q, k, v, seed32, q.shape[-1] ** -0.5, rate),
                dense_masked, (4, 256, 16, 64))
    ok &= check("BH dropout (h8 dh64)",
                lambda q, k, v: _from_bh(flash_bh_dropout(
                    _to_bh(q), _to_bh(k), _to_bh(v), seed32,
                    q.shape[-1] ** -0.5, rate), q.shape),
                dense_masked, (2, 256, 8, 64))
    ok &= check("streaming dropout (n4096)",
                lambda q, k, v: blocked_dropout_attention(
                    q, k, v, seed32, rate),
                dense_masked, (1, 4096, 4, 64))

    ok &= check_fused_optimizer()
    ok &= check_dequant_matmul()
    ok &= check_delta_rule()
    ok &= check_mixer_convolution()
    print("ON-CHIP KERNEL NUMERICS:", "OK" if ok else "FAILED")
    return 0 if ok else 1


def check_fused_optimizer() -> bool:
    """Mosaic-lowered fused clip+AdamW vs the closed-form jnp update.

    The optimizer kernel is f32 elementwise (no MXU, no softmax rescaling),
    so on-chip agreement is tight — 1e-5 relative, not the bf16 attention
    tolerance. States compare directly (no vjp: the optimizer sits outside
    autodiff). Shapes cover a ragged grid row count, a >1-block leaf, a
    vector leaf, a scalar leaf, and a leaf wider than 8,192 (the 10B-width
    fc1's last dimension: the lane-tiled grid)."""
    from vitax.ops.fused_optimizer import fused_clip_adamw
    from vitax.train.state import ADAMW_HPARAMS
    b1, b2, eps = (ADAMW_HPARAMS[k] for k in ("b1", "b2", "eps"))
    wd, clip, lr = 0.05, 1.0, 3e-4
    shapes = [(2, 37, 96), (70_000, 8), (128,), (), (40, 20480)]
    keys = jax.random.split(jax.random.key(7), 3 * len(shapes))
    params = {f"leaf{i}": jax.random.normal(keys[3 * i], s, jnp.float32)
              for i, s in enumerate(shapes)}
    grads = {f"leaf{i}": 4.0 * jax.random.normal(keys[3 * i + 1], s,
                                                 jnp.float32)
             for i, s in enumerate(shapes)}  # norm > clip: clip branch live
    mu = {f"leaf{i}": 0.1 * jax.random.normal(keys[3 * i + 2], s, jnp.float32)
          for i, s in enumerate(shapes)}
    nu = {k: v * v for k, v in mu.items()}
    import optax
    opt_state = (optax.ScaleByAdamState(count=jnp.int32(3), mu=mu, nu=nu),)
    gnorm = optax.global_norm(grads)

    got_p, got_s = jax.jit(lambda g, s, p, n: fused_clip_adamw(
        g, s, p, grad_norm=n, schedule=lambda c: lr, clip_norm=clip,
        weight_decay=wd, b1=b1, b2=b2, eps=eps))(grads, opt_state, params,
                                                 gnorm)

    def closed_form(g, p, m, v):
        g = g * jnp.minimum(1.0, clip / gnorm)
        m2 = (1 - b1) * g + b1 * m
        v2 = (1 - b2) * g * g + b2 * v
        upd = (m2 / (1 - b1 ** 4)) / (jnp.sqrt(v2 / (1 - b2 ** 4)) + eps)
        return p - lr * (upd + wd * p), m2, v2

    ok = True
    for name in params:
        want = closed_form(grads[name], params[name], mu[name], nu[name])
        got = (got_p[name], got_s[0].mu[name], got_s[0].nu[name])
        for tag, g, w in zip(("p", "mu", "nu"), got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            err = float(np.max(np.abs(g - w)) / max(1e-6,
                                                    np.max(np.abs(w))))
            status = "ok" if err < 1e-5 else "FAIL"
            print(f"  fused adamw {name:24s} {tag:3s} rel-max-err "
                  f"{err:.2e} {status}")
            if err >= 1e-5:
                ok = False
    if int(got_s[0].count) != 4:
        print(f"  fused adamw count FAIL: {int(got_s[0].count)} != 4")
        ok = False
    return ok


def check_dequant_matmul() -> bool:
    """Mosaic-lowered fused dequant-matmul vs the closed-form numpy math.

    Three modes per the serve paths (vitax/ops/dequant_matmul.py): int8
    weight-only, int8 weights + int8 activations (the MXU i8xi8->i32 path),
    and fp8 weight-only. The kernel's k-loop accumulates in i32 (act) or
    f32 (weight-only) with the scales applied once after. The act-quant
    path is integer arithmetic and must reproduce the closed form to 1e-5.
    The weight-only path multiplies f32 operands, which the MXU does in bf16
    passes at default precision — in the kernel exactly as in the XLA dot it
    replaces (measured on a v5e, PR 21: 1.3e-3..2.3e-3 of the largest output;
    1e-5 holds only in interpret mode). It is held to the serve path's 1e-2
    acceptance bound, and the unfused XLA path's error is printed beside it.
    Shapes cover ragged m/k/n (block padding) and an aligned case."""
    import ml_dtypes

    from vitax.ops.dequant_matmul import dequant_matmul, quantize_activations

    rng = np.random.default_rng(11)
    ok = True
    for (m, k, n) in [(64, 128, 256), (130, 257, 96)]:
        x = rng.standard_normal((m, k)).astype(np.float32)
        w = rng.standard_normal((k, n)).astype(np.float32) * 3.0
        scale = (np.abs(w).max(axis=0, keepdims=True) / 127.0).astype(
            np.float32)
        w_i8 = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        s_fp8 = (np.abs(w).max(axis=0, keepdims=True) / 240.0).astype(
            np.float32)
        w_fp8 = (w / s_fp8).astype(ml_dtypes.float8_e4m3)

        cases = {
            "int8 weight-only": (
                dequant_matmul(x, jnp.asarray(w_i8), jnp.asarray(scale),
                               act=False, fused=True, interpret=False),
                x @ (w_i8.astype(np.float32) * scale)),
            "int8 unfused (XLA)": (
                dequant_matmul(x, jnp.asarray(w_i8), jnp.asarray(scale),
                               act=False, fused=False),
                x @ (w_i8.astype(np.float32) * scale)),
            "fp8 weight-only": (
                dequant_matmul(x, jnp.asarray(w_fp8), jnp.asarray(s_fp8),
                               act=False, fused=True, interpret=False),
                x @ (w_fp8.astype(np.float32) * s_fp8)),
            "fp8 unfused (XLA)": (
                dequant_matmul(x, jnp.asarray(w_fp8), jnp.asarray(s_fp8),
                               act=False, fused=False),
                x @ (w_fp8.astype(np.float32) * s_fp8)),
        }
        xq, sx = jax.device_get(quantize_activations(jnp.asarray(x)))
        cases["int8 act-quant"] = (
            dequant_matmul(x, jnp.asarray(w_i8), jnp.asarray(scale),
                           act=True, fused=True, interpret=False),
            (xq.astype(np.int32) @ w_i8.astype(np.int32)).astype(np.float32)
            * float(sx) * scale)

        for name, (got, want) in cases.items():
            got = np.asarray(jax.device_get(got), np.float32)
            err = float(np.max(np.abs(got - want))
                        / max(1e-6, float(np.max(np.abs(want)))))
            tol = 1e-5 if name == "int8 act-quant" else 1e-2
            status = "ok" if err < tol else "FAIL"
            print(f"  dequant matmul {name:18s} ({m}x{k}x{n}) rel-max-err "
                  f"{err:.2e} {status}")
            if err >= tol:
                ok = False
    return ok


def check_delta_rule() -> bool:
    """The fused delta rule (`kda_fwd` / `kda_bwd`, vitax/ops/kda.py) at the
    Ling cell's shape and layout (one row of 4,096 tokens, 16 heads of 128,
    `packed_1x4096_tracemix`: every later document starts inside a chunk)
    against the plain `kda` compiled on the chip, o and the five gradients in
    bfloat16; and the inverse alone, whose float32 products a single bf16
    pass would leave 1e-3 from X (I + A) = I. Operands, distances and the
    inverse's kernel are tools/bench_kda.py's."""
    from tools import bench_kda as bench
    from vitax.models.kda import kda
    from vitax.ops.kda import chunk_tiling, kda_fused

    seg, ops, weight = bench.operands()
    chunk, sub = chunk_tiling(seg.shape[1], bench.GATE_BOUND)

    def run(rule):
        def total(*a):
            o = rule(*a, seg, chunk, sub, jnp.bfloat16)
            return jnp.sum(o * weight), o

        (_, o), grads = jax.jit(jax.value_and_grad(
            total, argnums=tuple(range(5)), has_aux=True))(*ops)
        return (o, *grads)

    ok = True
    # the same roundings in the forward: o to float32's last bits; gradients
    # to bf16's, g's through a cumsum of terms that cancel
    for tag, got, want, tol in zip(("o",) + bench.NAMES, run(kda_fused),
                                   run(kda), (1e-5, 1e-2, 1e-2, 1e-2, 5e-2,
                                              1e-2)):
        err = bench.gap(got, want)
        print(f"  delta rule 1x4096x16x128 {tag:5s} rel-norm-err {err:.2e} "
              f"{'ok' if err < tol else 'FAIL'}")
        ok &= err < tol
    residual = bench.inverse_residual()
    print(f"  delta rule inverse max |X (I + A) - I| {residual:.2e} "
          f"{'ok' if residual < 1e-5 else 'FAIL: not float32 products'}")
    return ok and residual < 1e-5


def check_mixer_convolution() -> bool:
    """The mixers' one-pass convolution (`conv_silu_fwd` / `conv_silu_bwd`,
    vitax/ops/conv.py) at the three recurrent cells' shapes and layouts
    against the plain `conv_silu` compiled on the chip, y and the gradients
    of x, the taps and the bias in bfloat16: the same float32 sums rounded at
    the same place, so y to a unit in the last place here and there and the
    gradients to bfloat16's. Operands and distances are tools/bench_conv.py's."""
    from tools import bench_conv as bench
    from vitax.models.ssm import conv_silu
    from vitax.ops import conv as fused

    ok = True
    for shape in bench.SHAPES:
        seg, (x, kernel, b), weight, norm = bench.operands(shape)
        ops = (x, kernel) + (() if b is None else (b,))

        def run(conv):
            def total(x, kernel, *bias):
                y = conv(x, seg, kernel, *(bias or (None,)), jnp.bfloat16,
                         norm)
                return jnp.sum(y.astype(jnp.float32) * weight), y

            (_, y), grads = jax.jit(jax.value_and_grad(
                total, argnums=tuple(range(len(ops))), has_aux=True))(*ops)
            return (y, *grads)

        for tag, got, want in zip(("y",) + bench.NAMES,
                                  run(fused.conv_silu), run(conv_silu)):
            err = bench.gap(got, want)
            print(f"  mixer convolution {shape:8s} {tag:7s} rel-norm-err "
                  f"{err:.2e} {'ok' if err < 5e-3 else 'FAIL'}")
            ok &= err < 5e-3
    return ok


if __name__ == "__main__":
    sys.exit(main())
