"""Pallas fused dequant-matmul (vitax/ops/dequant_matmul.py) numerics.

Everything here runs in interpret mode on CPU (the `interpret=True` flag),
which emulates the kernel math faithfully — Mosaic lowering legality is the
on-chip tool's job (tools/check_kernels_on_chip.py check_dequant_matmul).
The oracle is the closed-form quantized math, NOT the float matmul: the
kernel's contract is "same integer sums, scales applied once after the
k-loop", so agreement with the closed form is tight (1e-5 relative) while
agreement with the float matmul is bounded only by quantization error.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ml_dtypes

from vitax.ops.dequant_matmul import (
    DEQUANT_KERNEL_NAME,
    dequant_matmul,
    fused_dequant_active,
    quantize_activations,
)

# shapes cover: block-aligned, ragged in every dim (padding correctness),
# sub-block tiny, and a >1-block k so the k-loop accumulates across steps
SHAPES = [(64, 128, 256), (5, 33, 17), (130, 257, 96), (1, 8, 4)]


def _quantize_w(w, qmax, qdtype):
    scale = (np.abs(w).max(axis=0, keepdims=True) / qmax).astype(np.float32)
    scale[scale == 0] = 1.0
    if qdtype == np.int8:
        return np.clip(np.round(w / scale), -127, 127).astype(np.int8), scale
    return (w / scale).astype(qdtype), scale


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(1e-6, float(np.max(np.abs(want)))))


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_weight_only_int8_matches_closed_form(m, k, n):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32) * 2.0
    w_q, scale = _quantize_w(w, 127.0, np.int8)
    want = x @ (w_q.astype(np.float32) * scale)
    fused = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                           act=False, fused=True, interpret=True)
    unfused = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                             act=False, fused=False)
    assert _rel_err(fused, want) < 1e-5
    assert _rel_err(unfused, want) < 1e-5


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_weight_only_fp8_matches_closed_form(m, k, n):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32) * 2.0
    w_q, scale = _quantize_w(w, 240.0, ml_dtypes.float8_e4m3)
    want = x @ (w_q.astype(np.float32) * scale)
    fused = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                           act=False, fused=True, interpret=True)
    assert _rel_err(fused, want) < 1e-5


def test_fp8_export_bits_mean_the_same_as_e4m3fn():
    """The fused path hands Mosaic the export's float8_e4m3 weights
    reinterpreted as float8_e4m3fn (the chip's compiler loads only the
    latter): every finite e4m3 bit pattern must mean the same number there,
    and the quantizer's largest value must be finite."""
    bits = np.arange(256, dtype=np.uint8)
    ieee = bits.view(ml_dtypes.float8_e4m3).astype(np.float32)
    fn = bits.view(ml_dtypes.float8_e4m3fn).astype(np.float32)
    finite = np.isfinite(ieee)
    assert finite.sum() == 256 - 16  # exponent all ones: inf / NaN
    np.testing.assert_array_equal(ieee[finite], fn[finite])
    assert np.abs(ieee[finite]).max() == 240.0 == float(
        ml_dtypes.finfo(ml_dtypes.float8_e4m3).max)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_act_quant_fused_matches_unfused_bitwise(m, k, n):
    """Fused and unfused act-quant paths compute the SAME int32 sums and
    apply the same scales, so they agree bit-for-bit — the strongest form
    of the <= 1e-2 acceptance bound."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32)
    w_q, scale = _quantize_w(w, 127.0, np.int8)
    fused = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                           act=True, fused=True, interpret=True)
    unfused = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                             act=True, fused=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))
    # and both match the closed-form quantized oracle exactly
    xq, sx = jax.device_get(quantize_activations(jnp.asarray(x)))
    want = ((xq.astype(np.int32) @ w_q.astype(np.int32)).astype(np.float32)
            * float(sx) * scale)
    assert _rel_err(fused, want) < 1e-5


def test_leading_dims_reshape():
    """(B, N, K) inputs flatten through the 2-D kernel and reshape back."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 7, 33)).astype(np.float32)
    w = rng.standard_normal((33, 12)).astype(np.float32)
    w_q, scale = _quantize_w(w, 127.0, np.int8)
    out = dequant_matmul(x, jnp.asarray(w_q), jnp.asarray(scale),
                         act=False, fused=True, interpret=True)
    assert out.shape == (2, 7, 12)
    want = x.reshape(14, 33) @ (w_q.astype(np.float32) * scale)
    assert _rel_err(np.asarray(out).reshape(14, 12), want) < 1e-5


def test_quantize_activations_zeros_and_range():
    # all-zero input: scale clamps to 1.0, no division by zero
    xq, sx = jax.device_get(quantize_activations(jnp.zeros((4, 8))))
    assert float(sx) == 1.0 and np.all(xq == 0)
    # range: symmetric round-to-nearest within the +-127 grid
    x = np.linspace(-3.0, 3.0, 64, dtype=np.float32).reshape(8, 8)
    xq, sx = jax.device_get(quantize_activations(jnp.asarray(x)))
    assert xq.dtype == np.int8 and np.abs(xq).max() <= 127
    np.testing.assert_allclose(xq.astype(np.float32) * float(sx), x,
                               atol=float(sx) / 2 + 1e-7)


def test_kernel_launch_visible_in_jaxpr():
    """The pallas_call carries DEQUANT_KERNEL_NAME — the marker VTX-R009
    greps for in the traced serve program."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((8, 32)).astype(np.float32)
    w_q, scale = _quantize_w(
        rng.standard_normal((32, 16)).astype(np.float32), 127.0, np.int8)
    jaxpr = str(jax.make_jaxpr(
        lambda a: dequant_matmul(a, jnp.asarray(w_q), jnp.asarray(scale),
                                 act=True, fused=True, interpret=True))(x))
    assert DEQUANT_KERNEL_NAME in jaxpr
    # the unfused path must NOT launch it (that's what the negative arm of
    # the rule distinguishes)
    jaxpr_u = str(jax.make_jaxpr(
        lambda a: dequant_matmul(a, jnp.asarray(w_q), jnp.asarray(scale),
                                 act=True, fused=False))(x))
    assert DEQUANT_KERNEL_NAME not in jaxpr_u


def test_fused_dequant_active_policy():
    """auto = quantized dense model on TPU; on forces; off kills."""
    from vitax.config import Config
    base = dict(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                num_blocks=2, num_classes=4, batch_size=16, dtype="float32",
                warmup_steps=2, serve_max_batch=4)
    cfg = Config(**base, serve_quant_dtype="int8").validate()
    # auto on CPU (interpret mode): stays off — the XLA fallback is faster
    # than an emulated kernel
    assert fused_dequant_active(cfg) is False
    cfg_on = Config(**base, serve_quant_dtype="int8",
                    fused_dequant="on").validate()
    assert fused_dequant_active(cfg_on) is True
    cfg_off = Config(**base, serve_quant_dtype="int8",
                     fused_dequant="off").validate()
    assert fused_dequant_active(cfg_off) is False
    # no quantized weights -> nothing to fuse, auto resolves False
    assert fused_dequant_active(Config(**base).validate()) is False
