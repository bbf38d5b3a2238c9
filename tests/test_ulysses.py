"""Ulysses (all-to-all) sequence parallelism on the 8-virtual-device CPU mesh:
numerics + gradients vs dense attention, selector routing, and a full
sequence-parallel train step matching the FSDP-only trajectory — mirrors the
ring-attention suite (tests/test_ring_attention.py) for --sp_impl ulysses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.config import Config
from vitax.ops.attention import make_attention_impl, reference_attention
from vitax.parallel.mesh import build_mesh
from vitax.parallel.ulysses import make_ulysses_attention


def sp_cfg(**kw):
    base = dict(image_size=32, patch_size=8, embed_dim=32, num_heads=4,
                num_blocks=2, num_classes=4, batch_size=8, dtype="float32",
                sp_size=4, fsdp_size=2, sp_impl="ulysses", warmup_steps=0)
    base.update(kw)
    return Config(**base).validate()


def _inner_impls():
    from vitax.ops.attention import flash_attention
    # None = dense reference inner; flash = the production TPU composition
    # (Pallas kernel inside the ulysses shard_map), interpret mode on CPU
    return [pytest.param(None, id="dense"),
            pytest.param(flash_attention, id="flash")]


@pytest.mark.parametrize("inner", _inner_impls())
def test_ulysses_matches_dense(devices8, inner):
    mesh = build_mesh(sp_cfg())  # dp1 x fsdp2 x tp1 x sp4
    ulysses = make_ulysses_attention(mesh, inner=inner)
    b, n, h, dh = 4, 16, 4, 8  # h % sp == 0
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (b, n, h, dh), jnp.float32)
    k = jax.random.normal(kk, (b, n, h, dh), jnp.float32)
    v = jax.random.normal(kv, (b, n, h, dh), jnp.float32)
    out = jax.jit(ulysses)(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("inner", _inner_impls())
def test_ulysses_grad_matches_dense(devices8, inner):
    mesh = build_mesh(sp_cfg())
    ulysses = make_ulysses_attention(mesh, inner=inner)
    shape = (2, 16, 4, 8)
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    got = jax.jit(jax.grad(loss(ulysses), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(reference_attention),
                            argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_selector_routes_by_sp_impl(devices8):
    mesh = build_mesh(sp_cfg())
    impl = make_attention_impl(sp_cfg(), mesh)
    assert getattr(impl, "vitax_name", "") == "ulysses all-to-all (sp)"
    impl = make_attention_impl(sp_cfg(sp_impl="ring"), mesh)
    assert getattr(impl, "vitax_name", "") == "ring attention (sp)"
    # heads not divisible by sp*tp -> falls back to ring
    impl = make_attention_impl(sp_cfg(num_heads=2, embed_dim=32), mesh)
    assert getattr(impl, "vitax_name", "") == "ring attention (sp)"


def test_ulysses_train_step_equivalence(devices8):
    """Full train step with sp=4 (ulysses) must match the sp=1 FSDP
    trajectory — the resharding must not change the math."""
    from tests.test_train_smoke import run_steps

    cfg_sp = sp_cfg()
    cfg_base = sp_cfg(sp_size=1, fsdp_size=-1, sp_impl="ring")
    _, losses_sp = run_steps(cfg_sp, n_steps=4)
    _, losses_base = run_steps(cfg_base, n_steps=4)
    assert all(np.isfinite(losses_sp))
    np.testing.assert_allclose(losses_sp, losses_base, rtol=2e-4)


def test_ulysses_dropout_matches_masked_dense(devices8):
    """Ulysses in-kernel dropout (round 5): the resharded inner kernel drops
    with the shared counter-hash on its full-sequence head slice, seeded per
    shard. The oracle reconstructs the exact per-(shard, local-block) masks
    from the a2a layout (shard s holds heads [s*H/sp, (s+1)*H/sp)), so this
    also pins the head-slice ordering the seed-fold assumes."""
    from vitax.ops.attention import (_GOLD_BH, _fmix32, dropout_keep_mask,
                                     make_attention_impl)

    cfg = sp_cfg(sp_size=2, fsdp_size=1, att_dropout=0.25)
    mesh = build_mesh(cfg, devices=jax.devices()[:2])  # sp2 only
    impl = make_attention_impl(cfg, mesh, force_tpu_kernels=True)
    drop = getattr(impl, "vitax_dropout", None)
    assert drop is not None

    b, n, h, dh = 4, cfg.num_patches, cfg.num_heads, 8
    h_loc = h // 2
    rng_k = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (b, n, h, dh), jnp.float32)
               for kk in rng_k)
    seed, rate = jnp.uint32(17), cfg.att_dropout

    out = jax.jit(lambda q, k, v: drop(q, k, v, seed))(q, k, v)

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    probs = jax.nn.softmax(s, axis=-1)
    masks = []
    for g in range(h):
        shard, hl = g // h_loc, g % h_loc
        seed_s = seed ^ _fmix32(jnp.uint32(shard) * jnp.uint32(_GOLD_BH))
        masks.append(jnp.stack([
            dropout_keep_mask(seed_s, jnp.uint32(bi * h_loc + hl), n, n,
                              rate) for bi in range(b)]))
    mask = jnp.stack(masks, axis=1)                      # (B, H, N, N)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs * mask / (1 - rate), v)

    assert not np.allclose(np.asarray(out),
                           np.asarray(reference_attention(q, k, v)),
                           atol=1e-3)  # dropout actually bit
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # determinism given the seed
    out2 = jax.jit(lambda q, k, v: drop(q, k, v, seed))(q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_ulysses_dropout_dense_inner_off_tpu(devices8):
    """Off-TPU without forced kernels the ulysses flavor now carries a DENSE
    dropout inner (PR 1 satellite) — the two sp flavors behave
    consistently anywhere ring's _dense_block_drop runs, including the
    pipeline body at tp=1. The dense inner makes the same counter-hash mask
    decisions at the same local coordinates as the kernel inner, so its
    output must match the forced-kernel path."""
    cfg = sp_cfg(sp_size=2, fsdp_size=1, att_dropout=0.25)
    mesh = build_mesh(cfg, devices=jax.devices()[:2])
    impl = make_attention_impl(cfg, mesh)  # no force: dense dropout inner
    drop = getattr(impl, "vitax_dropout", None)
    assert drop is not None
    assert getattr(impl.vitax_pp_impl, "vitax_dropout", None) is not None

    b, n, h, dh = 2, cfg.num_patches, cfg.num_heads, 8
    q, k, v = (jax.random.normal(kk, (b, n, h, dh), jnp.float32)
               for kk in jax.random.split(jax.random.key(5), 3))
    seed = jnp.uint32(29)
    out = jax.jit(lambda q, k, v: drop(q, k, v, seed))(q, k, v)

    impl_k = make_attention_impl(cfg, mesh, force_tpu_kernels=True)
    want = jax.jit(
        lambda q, k, v: impl_k.vitax_dropout(q, k, v, seed))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
