"""The gated short convolution of a `conv` layer (vitax/models/gconv.py:
C * conv(B * x) between two projections, no activation, no state) at small
sizes on the CPU against a loop that walks each row token by token:
documents ending anywhere, a row that is all padding, values and gradients,
float32 and bf16. Every case calls compiled programs (tests/decoder_cases.py:
the rule of the test tree)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.models.gconv import (GatedConvMixer, gated_conv,
                                gated_conv_param_count)

D, TAPS, T = 16, 3, 24
# documents ending anywhere: back to back, one token long, a row's tail of
# padding, a row that is all padding, a row one document fills
SEGMENTS = np.array([
    [1] * 7 + [2] * 1 + [3] * 9 + [4] * 5 + [0] * 2,
    [0] * T,
    [1] * T,
    [1] * 2 + [2] * 2 + [3] * 20,
], np.int32)


def by_token(projected, segment_ids, taps):
    """The definition, one token at a time in numpy float64: the taps reach
    back inside the token's own document only; padding gives zeros."""
    projected = np.asarray(projected, np.float64)
    taps = np.asarray(taps, np.float64)
    r, t, d3 = projected.shape
    d = d3 // 3
    b, c, x = projected[..., :d], projected[..., d:2 * d], projected[..., 2 * d:]
    out = np.zeros((r, t, d))
    for row in range(r):
        for at in range(t):
            if segment_ids[row, at] == 0:
                continue
            acc = np.zeros(d)
            for j in range(taps.shape[0]):      # w_j on the token j back
                back = at - j
                if back >= 0 and segment_ids[row, back] == segment_ids[row, at]:
                    acc += taps[taps.shape[0] - 1 - j] * b[row, back] * x[row, back]
            out[row, at] = c[row, at] * acc
    return out


@functools.cache
def inputs():
    keys = jax.random.split(jax.random.key(0), 3)
    projected = jax.random.normal(keys[0], (len(SEGMENTS), T, 3 * D))
    taps = jax.random.uniform(keys[1], (TAPS, D), minval=-0.6, maxval=0.6)
    weight = jax.random.normal(keys[2], (len(SEGMENTS), T, D))
    return projected, taps, weight


def test_the_mixer_matches_the_loop_document_by_document():
    projected, taps, _ = inputs()
    got = jax.jit(lambda p, w: gated_conv(p, SEGMENTS, w, jnp.float32))(
        projected, taps)
    want = by_token(projected, SEGMENTS, taps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.abs(want).max() > 1.0
    # padding gives zeros; a one-token document sees its own token only
    assert not np.asarray(got)[SEGMENTS == 0].any()
    one = projected[0, 7]
    np.testing.assert_allclose(
        got[0, 7], one[D:2 * D] * taps[TAPS - 1] * one[:D] * one[2 * D:],
        rtol=1e-5)


def test_padding_receives_nothing_and_documents_do_not_meet():
    """Whatever stands at padding, or in another document, moves no real
    token's output."""
    projected, taps, _ = inputs()
    run = jax.jit(lambda p: gated_conv(p, SEGMENTS, taps, jnp.float32))
    base = run(projected)
    noisy = jnp.where((SEGMENTS == 0)[..., None], 1e3, projected)
    np.testing.assert_array_equal(run(noisy), base)
    other = projected.at[0, :7].add(5.0)       # document 1 of row 0 alone
    moved = np.asarray(run(other) - base)
    assert np.abs(moved[0, :7]).max() > 0.1
    assert not moved[0, 7:].any() and not moved[1:].any()


def test_gradients_match_the_loops_by_finite_differences_of_its_own():
    """d/d(projection) and d/d(taps) of a weighted sum of the outputs against
    the token-by-token definition's closed form: the loop is linear in the
    taps and in C, and quadratic in (B, x), so its gradients are loops too."""
    projected, taps, weight = inputs()

    def loss(p, w):
        return jnp.sum(gated_conv(p, SEGMENTS, w, jnp.float32) * weight)

    dp, dw = jax.jit(jax.grad(loss, argnums=(0, 1)))(projected, taps)
    p64, w64 = np.asarray(projected, np.float64), np.asarray(taps, np.float64)
    g64 = np.asarray(weight, np.float64)
    # taps: the loop with a one-hot tap in turn
    want_dw = np.zeros_like(w64)
    for j in range(TAPS):
        hot = np.zeros_like(w64)
        hot[j] = 1.0
        want_dw[j] = np.sum(by_token(p64, SEGMENTS, hot) * g64, axis=(0, 1))
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-5)
    # C: the loop with C = 1 is the convolution itself
    ones = p64.copy()
    ones[..., D:2 * D] = 1.0
    np.testing.assert_allclose(dp[..., D:2 * D],
                               by_token(ones, SEGMENTS, w64) * g64,
                               rtol=1e-4, atol=1e-5)
    # B and x: central differences of the float64 loop on a few entries
    draw = np.random.default_rng(0)
    for _ in range(12):
        r = int(draw.choice([0, 2, 3]))
        t = int(draw.integers(0, T))
        ch = int(draw.choice([*range(D), *range(2 * D, 3 * D)]))
        step = np.zeros_like(p64)
        step[r, t, ch] = 1e-4
        want = np.sum((by_token(p64 + step, SEGMENTS, w64)
                       - by_token(p64 - step, SEGMENTS, w64)) * g64) / 2e-4
        np.testing.assert_allclose(dp[r, t, ch], want, rtol=2e-3, atol=1e-5)
    assert not np.asarray(dp)[SEGMENTS == 0].any()


def test_bfloat16_reads_the_projection_once_and_rounds_once():
    """The bf16 path: float32 between the bf16 projection and the one
    rounding of what it hands W_out."""
    projected, taps, _ = inputs()
    low = projected.astype(jnp.bfloat16)
    got = jax.jit(lambda p: gated_conv(p, SEGMENTS, taps, jnp.bfloat16))(low)
    assert got.dtype == jnp.bfloat16
    want = by_token(low.astype(jnp.float32), SEGMENTS, taps)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=8e-3,
                               atol=1e-3)


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_module_its_leaves_and_its_count(taps):
    mixer = GatedConvMixer(taps, jnp.float32)
    u = jax.random.normal(jax.random.key(1), (2, T, D))
    seg = jnp.asarray(SEGMENTS[[0, 3]])
    variables = jax.jit(mixer.init)(jax.random.key(0), u, seg)
    shapes = jax.tree.map(lambda a: a.shape, variables["params"])
    assert shapes == {"in_proj": {"kernel": (D, 3 * D)},
                      "conv": {"kernel": (taps, D)},
                      "out_proj": {"kernel": (D, D)}}
    assert sum(a.size for a in jax.tree.leaves(variables)) \
        == gated_conv_param_count(D, taps)
    # the taps start uniform inside +-1 / sqrt(taps), not at zero
    kernel = np.asarray(variables["params"]["conv"]["kernel"])
    assert np.abs(kernel).max() <= taps ** -0.5 and np.abs(kernel).max() > 0
    out = jax.jit(mixer.apply)(variables, u, seg)
    p = variables["params"]
    want = by_token(u @ p["in_proj"]["kernel"], np.asarray(seg),
                    p["conv"]["kernel"]) @ np.asarray(
                        p["out_proj"]["kernel"], np.float64)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-6)


def test_the_three_scopes_are_in_the_lowered_program():
    projected, taps, _ = inputs()
    text = jax.jit(jax.grad(lambda p: jnp.sum(gated_conv(
        p, SEGMENTS, taps, jnp.float32)))).lower(projected).as_text(
            debug_info=True)
    for scope in ("gconv_in", "gconv", "gconv_out"):    # as benchmark/scopes.py
        assert re.search(rf"[/(]{scope}[/)]", text), scope      # splits a path
