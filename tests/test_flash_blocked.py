"""Blocked (streaming) flash attention vs the dense reference core.

Runs in Pallas interpret mode on the CPU test mesh; covers non-divisible
sequence lengths (padding + masking path) and all three gradients through the
custom VJP. Long-sequence capability beyond the reference (SURVEY.md section 5:
the reference's sequence length is fixed at 256 tokens, dense O(N^2) timm
attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.ops.attention import reference_attention
from vitax.ops.flash_blocked import blocked_flash_attention


@pytest.mark.parametrize("b,n,h,dh,blk", [
    (2, 256, 4, 64, 128),    # multiple blocks, divisible
    (1, 300, 2, 64, 128),    # padding: 300 -> 384
    (1, 1024, 2, 128, 512),  # larger head dim
    (1, 130, 1, 64, 256),    # N smaller than the block
])
def test_blocked_fwd_matches_reference(devices8, b, n, h, dh, blk):
    _check_fwd(b, n, h, dh, blk, blk)


def test_blocked_unequal_blocks(devices8):
    # unequal block_q/block_k must pad to their lcm so both grids tile evenly
    _check_fwd(1, 500, 2, 64, 512, 384)


def _check_fwd(b, n, h, dh, bq, bk):
    rng = np.random.default_rng(n)
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, h, dh)), jnp.float32)
               for _ in range(3))
    ref = reference_attention(q, k, v)
    out = blocked_flash_attention(q, k, v, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n,blk", [(256, 128), (300, 128)])
def test_blocked_grads_match_reference(devices8, n, blk):
    rng = np.random.default_rng(n)
    q, k, v = (jnp.asarray(rng.normal(size=(1, n, 2, 64)), jnp.float32)
               for _ in range(3))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) ** 2).sum()

    got = jax.jit(jax.grad(loss(lambda q, k, v: blocked_flash_attention(
        q, k, v, block_q=blk, block_k=blk)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(reference_attention),
                            argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-5 * scale, rtol=2e-4)


def test_blocked_bf16_activations(devices8):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
               for _ in range(3))
    out = blocked_flash_attention(q, k, v, block_q=128, block_k=128)
    ref = reference_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2)


@pytest.mark.parametrize("n,bq,bk", [(256, 128, 128), (300, 128, 256)])
def test_blocked_dropout_matches_masked_dense(devices8, n, bq, bk):
    """Streaming in-kernel dropout (round 5) == dense attention with the
    identical global-coordinate mask, outputs AND grads, including a padded
    N and unequal blocks — the fwd's kv-streaming tiles and the two
    backward kernels' differently-shaped tiles must regenerate the same
    keep decisions."""
    from vitax.ops.attention import dropout_keep_mask
    from vitax.ops.flash_blocked import blocked_dropout_attention

    b, h, dh, rate = 1, 2, 64, 0.3
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, h, dh)), jnp.float32)
               for _ in range(3))
    seed = jnp.uint32(99)

    def dense_masked(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        probs = jax.nn.softmax(s, axis=-1)
        mask = jnp.stack([jnp.stack([
            dropout_keep_mask(seed, jnp.uint32(bi * h + hi), n, n, rate)
            for hi in range(h)]) for bi in range(b)])
        return jnp.einsum("bhqk,bkhd->bqhd", probs * mask / (1 - rate), v)

    def stream(q, k, v):
        return blocked_dropout_attention(q, k, v, seed, rate,
                                         block_q=bq, block_k=bk)

    out_s = stream(q, k, v)
    out_d = dense_masked(q, k, v)
    assert not np.allclose(np.asarray(out_s),
                           np.asarray(reference_attention(q, k, v)),
                           atol=1e-3)  # the mask actually bit
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
    # same (seed, inputs) -> identical output (determinism)
    np.testing.assert_array_equal(np.asarray(stream(q, k, v)),
                                  np.asarray(out_s))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) ** 2).sum()

    got = jax.jit(jax.grad(loss(stream), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(dense_masked), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-5 * scale, rtol=2e-4)
