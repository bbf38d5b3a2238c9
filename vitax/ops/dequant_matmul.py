"""Fused dequant-matmul: quantized serve matmuls in one Pallas pass.

The PR-14 weight-only serve path dequantizes in-jit (`w_q.astype(f32) *
scale`, then a float dot): correct, but XLA materializes the dequantized
f32 weight as a real HBM tensor per matmul — at serve geometry that round
trip is the whole point of quantizing lost. This module is the serve twin
of vitax/ops/fused_optimizer.py: ONE blocked kernel per matmul that

- streams int8/fp8 weight blocks into VMEM and dequantizes them in
  registers (weight-only mode: f32 accumulation, per-output-channel scale
  applied AFTER the k-loop — exact, because the scale is constant along
  the contraction axis: ``(x @ (w*s))[i,j] == s[j] * (x @ w)[i,j]``);
- or, with dynamic activation quantization on, takes int8 activations
  (per-tensor absmax scale computed in-jit by `quantize_activations`) and
  runs the MXU's int8 x int8 path with an int32 accumulator, rescaling by
  ``act_scale * weight_scale`` once at the end.

No dequantized weight block ever exists outside VMEM — the VTX-R009
invariant (vitax/analysis/rules.py) pins both halves: the serve jaxpr must
launch `DEQUANT_KERNEL_NAME` and must not convert any weight-sized
quantized tensor to float outside a pallas_call.

Off-TPU the kernel runs in Pallas interpret mode, exactly like
vitax/ops/attention.py; `--fused_dequant {auto,on,off}` resolves through
`fused_dequant_active` (auto = real-Mosaic backends only). The unfused
fallbacks here are the reference semantics the kernel is pinned against
(tests/test_dequant_matmul.py, tools/check_kernels_on_chip.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vitax.ops.common import interpret as _interpret

# the pallas_call `name=`: the jaxpr marker VTX-R009 greps for (one
# occurrence per launch) and the custom call's op_name in compiled HLO
DEQUANT_KERNEL_NAME = "dequant_matmul_kernel"

# block caps: x (bm, bk) + w (bk, bn) + acc/out (bm, bn) stay well under
# ~0.5 MB of VMEM per grid step at int8 operand widths
_BM_CAP = 128
_BK_CAP = 512
_BN_CAP = 256


def fused_dequant_active(cfg) -> bool:
    """Resolve --fused_dequant {auto,on,off} for this process.

    `auto` engages the fused kernel exactly when serving quantized weights
    of a dense model on a real-Mosaic backend (TPU, or VITAX_FORCE_MOSAIC=1
    — the attention kernels' `_interpret()` policy), so CPU CI stays on the
    jnp reference path unless a test forces it. `on` forces the kernel
    anywhere (interpret mode off-TPU — the CI equivalence arms); MoE expert
    einsums are never routed through it."""
    mode = getattr(cfg, "fused_dequant", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return (bool(getattr(cfg, "serve_quant_dtype", ""))
            and getattr(cfg, "moe_experts", 0) == 0
            and not _interpret())


def quantize_activations(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor dynamic absmax quantization of activations to int8.

    Computed INSIDE the jitted forward (per batch — "dynamic"): one scalar
    scale per tensor keeps the rescale a cheap epilogue multiply, and the
    absmax guard maps all-zero tensors to scale 1.0 (they quantize and
    dequantize to 0). Returns (int8 values, float32 scalar scale)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    sx = jnp.where(absmax == 0.0, jnp.float32(1.0),
                   absmax / jnp.float32(127.0))
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx),
                  -127, 127).astype(jnp.int8)
    return xq, sx


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _make_kernel(act: bool, nk: int):
    def dequant_matmul_kernel(sx_ref, x_ref, w_ref, s_ref, o_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if act:
            # int8 x int8 on the MXU, int32 accumulator; both scales are
            # constant along k, so they factor out of the whole k-loop
            acc_ref[...] += jax.lax.dot_general(
                x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            # dequantize the weight block in registers: int8/fp8 -> f32
            # never leaves VMEM (the channel scale is still the epilogue)
            acc_ref[...] += jax.lax.dot_general(
                x_ref[...], w_ref[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _write():
            o_ref[...] = (acc_ref[...].astype(jnp.float32)
                          * sx_ref[0, 0] * s_ref[...])
    return dequant_matmul_kernel


@functools.lru_cache(maxsize=None)
def _pallas_matmul_call(m: int, k: int, n: int, act: bool, w_dtype: str,
                        interpret: bool):
    """One pallas_call per (padded geometry, mode, weight dtype) — the serve
    engine's fixed buckets mean a handful of cache entries per model."""
    # quantized operands tile at (32, 128) on TPU, f32 at (8, 128); the
    # caller pads every dim to these multiples so blocks divide evenly
    bm = min(_BM_CAP, _round_up(m, 32 if act else 8))
    bk = min(_BK_CAP, _round_up(k, 128))
    bn = min(_BN_CAP, _round_up(n, 128))
    grid = (m // bm, n // bn, k // bk)  # k innermost: sequential on TPU
    acc_dtype = jnp.int32 if act else jnp.float32
    return pl.pallas_call(
        _make_kernel(act, grid[2]),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),     # sx (1, 1)
                  pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        name=DEQUANT_KERNEL_NAME,
        interpret=interpret,
    )


def _fused_2d(x2d, w, scale, sx, act: bool, interpret: bool):
    """Pad to tile multiples (zero padding is exact: padded k contributes
    x*0, padded m/n rows are sliced off) and launch the kernel."""
    m, k = x2d.shape
    n = w.shape[1]
    if w.dtype == jnp.float8_e4m3:
        # The export's fp8 is ml_dtypes' IEEE-style float8_e4m3, which Mosaic
        # cannot load as a vector ("Invalid vector type for load" on a v5e);
        # float8_e4m3fn it can. Every FINITE e4m3 value has the same bits in
        # e4m3fn (same bias and subnormals; they differ only where the
        # exponent is all ones: inf/NaN there, 256..448 here), and a
        # quantized export holds finite values only (absmax maps to 240, the
        # format's largest). So reinterpret the bits; nothing is converted.
        w = jax.lax.bitcast_convert_type(w, jnp.float8_e4m3fn)
    mp = _round_up(m, min(_BM_CAP, _round_up(m, 32 if act else 8)))
    kp = _round_up(k, min(_BK_CAP, _round_up(k, 128)))
    np_ = _round_up(n, min(_BN_CAP, _round_up(n, 128)))
    x2d = jnp.pad(x2d, ((0, mp - m), (0, kp - k)))
    w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    scale = jnp.pad(scale.reshape(1, n).astype(jnp.float32),
                    ((0, 0), (0, np_ - n)))
    call = _pallas_matmul_call(mp, kp, np_, act, str(w.dtype), interpret)
    out = call(sx.reshape(1, 1), x2d, w, scale)
    return out[:m, :n]


def dequant_matmul(x: jax.Array, w: jax.Array, scale: jax.Array, *,
                   act: bool = False, fused: bool = True,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``x @ (w * scale)`` for a quantized weight, without materializing the
    dequantized weight.

    `w` is an int8 or fp8 (K, F) kernel with per-output-channel float32
    `scale` broadcastable to (1, F); `x` keeps any leading batch dims.
    `act=True` additionally quantizes `x` per tensor and runs the matmul
    int8 x int8 (int8 weights only). `fused=False` is the jnp reference
    path — for act mode that is a PLAIN int8 dot_general, the lowering the
    activation-quant acceptance test pins via lower_bucket_mlir."""
    if interpret is None:
        interpret = _interpret()
    assert w.ndim == 2, f"dequant_matmul wants a 2-D kernel, got {w.shape}"
    if act:
        assert w.dtype == jnp.int8, (
            f"act-quant needs int8 weights (the other int8 operand), got "
            f"{w.dtype}")
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    if act:
        xq, sx = quantize_activations(x2d)
        if fused:
            out = _fused_2d(xq, w, scale, sx, True, bool(interpret))
        else:
            out = jax.lax.dot_general(
                xq, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
            out = out * sx * scale.reshape(1, -1)
    elif fused:
        out = _fused_2d(x2d.astype(jnp.float32), w, scale,
                        jnp.float32(1.0), False, bool(interpret))
    else:
        out = x2d.astype(jnp.float32) @ (w.astype(jnp.float32)
                                         * scale.reshape(1, -1))
    return out.reshape(*lead, w.shape[1])


def make_quant_matmul(cfg):
    """The quant_matmul closure vitax/models/vit.py QuantDense calls:
    resolves the act-quant and fused flags from cfg ONCE so the traced
    forward is static in both. `act=False` callers (the head — its f32
    output feeds softmax directly) stay weight-only even with act-quant
    on; eligibility lives at the call site."""
    act_mode = getattr(cfg, "serve_act_quant", "off") == "int8"
    fused = fused_dequant_active(cfg)

    def quant_matmul(x, w, scale, act=True):
        return dequant_matmul(x, w, scale, act=act_mode and act, fused=fused)
    return quant_matmul
