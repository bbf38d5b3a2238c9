"""Kernel numerics: Pallas fused attention (interpret mode on CPU) vs the dense
reference path, forward and gradients; data-pipeline transform parity checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.ops.attention import flash_attention, reference_attention


@pytest.mark.parametrize("shape", [(2, 64, 2, 32), (1, 128, 3, 16)])
def test_flash_matches_reference_fwd(devices8, shape):
    b, n, h, dh = shape
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    out_f = flash_attention(q, k, v)
    out_r = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r), rtol=2e-4, atol=2e-4)


def test_flash_matches_reference_grad(devices8):
    shape = (2, 64, 2, 32)
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(reference_attention), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_model_with_flash_attention_matches_dense(devices8):
    """The full model with the kernel plugged in must match the dense path."""
    from vitax.config import Config
    from vitax.models import build_model

    cfg = Config(image_size=32, patch_size=8, embed_dim=32, num_heads=2,
                 num_blocks=2, num_classes=4, batch_size=8, dtype="float32").validate()
    model_d = build_model(cfg, attention_impl=None)
    model_f = build_model(cfg, attention_impl=flash_attention)
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3), jnp.float32)
    params = jax.jit(model_d.init, static_argnums=2)(jax.random.key(0), x,
                                                     True)
    out_d, out_f = (jax.jit(m.apply, static_argnums=2)(params, x, True)
                    for m in (model_d, model_f))
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d), rtol=2e-3, atol=2e-3)


class TestTransforms:
    def test_val_transform_shapes_and_normalization(self):
        from PIL import Image
        from vitax.data.transforms import ValTransform, IMAGENET_MEAN, IMAGENET_STD
        t = ValTransform(64)
        img = Image.new("RGB", (300, 200), (124, 116, 104))  # ~ImageNet mean*255
        out = t(img)
        assert out.shape == (64, 64, 3)
        # uniform mean-colored image normalizes to ~0
        assert np.abs(out).max() < 0.1

    def test_train_transform_deterministic_per_index_epoch(self):
        from PIL import Image
        from vitax.data.transforms import TrainTransform
        rng = np.random.default_rng(0)
        arr = rng.integers(0, 255, size=(80, 100, 3), dtype=np.uint8)
        img = Image.fromarray(arr)
        t = TrainTransform(32, seed=1)
        t.set_epoch(1)
        a = t(img, index=7)
        b = t(img, index=7)
        np.testing.assert_array_equal(a, b)  # same epoch+index -> same crop
        t.set_epoch(2)
        c = t(img, index=7)
        assert not np.array_equal(a, c)  # new epoch -> new randomness
        assert a.shape == (32, 32, 3)

    def test_imagefolder_scan(self, tmp_path):
        from PIL import Image
        from vitax.data.imagefolder import ImageFolderDataset
        for cls in ["n01", "n02"]:
            d = tmp_path / "train" / cls
            d.mkdir(parents=True)
            for i in range(3):
                Image.new("RGB", (40, 40), (i * 40, 0, 0)).save(d / f"img{i}.jpg")
        from vitax.data.transforms import val_transform
        ds = ImageFolderDataset(str(tmp_path / "train"), val_transform(32))
        assert len(ds) == 6
        assert ds.classes == ["n01", "n02"]
        img, label = ds[0]
        assert img.shape == (32, 32, 3) and label == 0
        _, label5 = ds[5]
        assert label5 == 1

    def test_imagefolder_missing_dir_raises(self, tmp_path):
        from vitax.data.imagefolder import ImageFolderDataset
        with pytest.raises(FileNotFoundError):
            ImageFolderDataset(str(tmp_path / "nope"))


def test_real_data_end_to_end(devices8, tmp_path):
    """Tiny ImageFolder -> full train() epoch: the non-fake-data path works."""
    from PIL import Image
    from vitax.config import Config
    from vitax.train.loop import train

    rng = np.random.default_rng(0)
    for split, n in [("train", 4), ("val", 2)]:
        for cls in ["a", "b"]:
            d = tmp_path / "data" / split / cls
            d.mkdir(parents=True)
            for i in range(n):
                arr = rng.integers(0, 255, size=(48, 48, 3), dtype=np.uint8)
                Image.fromarray(arr).save(d / f"{i}.jpg")

    cfg = Config(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=2, batch_size=8, dtype="float32", warmup_steps=0,
        data_dir=str(tmp_path / "data"), num_epochs=1, log_step_interval=1,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=1,
        test_epoch_interval=99, num_workers=2,
    ).validate()
    state = train(cfg)
    assert int(jax.device_get(state.step)) == 1  # 8 images // batch 8


def test_att_dropout_kernel_bypass_warning(devices8, capsys):
    """--att_dropout runs fused on the whole-N AND streaming kernels (round
    5); only sp and pp-under-tp still bypass to dense under dropout, and
    make_attention_impl must warn loudly for exactly those cases — and NOT
    where the cliff is gone."""
    from vitax.config import Config
    from vitax.ops.attention import make_attention_impl

    # whole-N shape with dropout: fused dropout variant, no warning
    cfg = Config(image_size=32, patch_size=16, embed_dim=32, num_heads=2,
                 num_blocks=1, att_dropout=0.1).validate()
    impl = make_attention_impl(cfg, mesh=None, force_tpu_kernels=True)
    assert getattr(impl, "vitax_dropout", None) is not None
    assert "WARNING" not in capsys.readouterr().out

    # streaming shape (4096 tokens > MAX_SEQ_IN_VMEM): fused too (round 5)
    cfg_s = Config(image_size=1024, patch_size=16, embed_dim=32, num_heads=2,
                   num_blocks=1, att_dropout=0.1).validate()
    impl_s = make_attention_impl(cfg_s, mesh=None, force_tpu_kernels=True)
    assert getattr(impl_s, "vitax_dropout", None) is not None
    assert "WARNING" not in capsys.readouterr().out

    # pipeline body under tp has no dropout kernel (vitax_pp_impl is None
    # there — dense einsum path): pp x tp with dropout must warn
    from vitax.parallel.mesh import build_mesh
    cfg_pp = Config(image_size=32, patch_size=16, embed_dim=32, num_heads=2,
                    num_blocks=2, pp_size=2, tp_size=2, dp_size=2,
                    att_dropout=0.1).validate()
    make_attention_impl(cfg_pp, build_mesh(cfg_pp),
                        force_tpu_kernels=True)
    out = capsys.readouterr().out
    assert "WARNING" in out and "pipeline" in out

    # no warning at the reference default (att_dropout == 0)
    cfg0 = Config(image_size=32, patch_size=16, embed_dim=32, num_heads=2,
                  num_blocks=1, att_dropout=0.0).validate()
    make_attention_impl(cfg0, mesh=None)
    assert "WARNING" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# in-kernel attention dropout (vitax/ops/attention.py dropout variants)
# ---------------------------------------------------------------------------

def _dropout_oracle(q, k, v, seed, rate):
    """Dense attention with the EXACT mask the kernels generate (the
    counter-hash RNG is pure jnp, so the oracle shares its code path)."""
    from vitax.ops.attention import dropout_keep_mask
    b, n, h, dh = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    probs = jax.nn.softmax(s, axis=-1)
    mask = jnp.stack([jnp.stack([
        dropout_keep_mask(seed, jnp.uint32(bi * h + hi), n, n, rate)
        for hi in range(h)]) for bi in range(b)])    # (B, H, N, N)
    a = (probs * mask / (1.0 - rate)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


@pytest.mark.parametrize("family", ["4d", "bh"])
def test_flash_dropout_matches_masked_dense(devices8, family):
    """Kernel-path dropout == dense attention with the identical mask, for
    outputs AND grads — both kernel families, real drops in play."""
    from vitax.ops.attention import flash4_dropout, flash_bh_dropout, _to_bh, _from_bh

    shape, rate = (2, 64, 2, 32), 0.35
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    seed = jnp.uint32(1234)
    scale = shape[-1] ** -0.5

    if family == "4d":
        fn = lambda q, k, v: flash4_dropout(q, k, v, seed, scale, rate)  # noqa: E731
    else:
        fn = lambda q, k, v: _from_bh(flash_bh_dropout(  # noqa: E731
            _to_bh(q), _to_bh(k), _to_bh(v), seed, scale, rate), q.shape)

    out_k = jax.jit(fn)(q, k, v)
    out_d = jax.jit(_dropout_oracle, static_argnums=4)(q, k, v, seed, rate)
    # sanity: the mask actually dropped something (kernel != no-dropout path)
    assert not np.allclose(np.asarray(out_k),
                           np.asarray(reference_attention(q, k, v)), atol=1e-3)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gk = jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(
        loss(lambda q, k, v: _dropout_oracle(q, k, v, seed, rate)),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_dropout_mask_statistics_and_determinism():
    """Empirical drop rate ~ rate; same (seed, block) -> identical mask;
    different seed or block index -> different mask; 4D's transposed layout
    holds the same element decisions."""
    from vitax.ops.attention import dropout_keep_mask

    n, rate = 256, 0.3
    seed = jnp.uint32(77)
    m = dropout_keep_mask(seed, jnp.uint32(5), n, n, rate)
    drop_frac = 1.0 - float(jnp.mean(m))
    # binomial std at n^2 = 65536 draws: ~0.0018; allow 5 sigma
    assert abs(drop_frac - rate) < 0.01, drop_frac
    m2 = dropout_keep_mask(seed, jnp.uint32(5), n, n, rate)
    assert np.array_equal(np.asarray(m), np.asarray(m2))
    m3 = dropout_keep_mask(jnp.uint32(78), jnp.uint32(5), n, n, rate)
    m4 = dropout_keep_mask(seed, jnp.uint32(6), n, n, rate)
    assert not np.array_equal(np.asarray(m), np.asarray(m3))
    assert not np.array_equal(np.asarray(m), np.asarray(m4))
    mt = dropout_keep_mask(seed, jnp.uint32(5), n, n, rate, transposed=True)
    assert np.array_equal(np.asarray(m), np.asarray(mt).T)


def test_model_train_att_dropout_keeps_kernel_and_is_deterministic(devices8):
    """Full model: --att_dropout > 0 training routes through the in-kernel
    dropout variant (impl.vitax_dropout) and is reproducible given the same
    dropout rng — nn.Dropout's determinism contract, now on the fused path."""
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl

    cfg = Config(image_size=32, patch_size=8, embed_dim=32, num_heads=2,
                 num_blocks=2, num_classes=4, batch_size=8, dtype="float32",
                 att_dropout=0.2).validate()
    impl = make_attention_impl(cfg, mesh=None, force_tpu_kernels=True)
    assert getattr(impl, "vitax_dropout", None) is not None
    model = build_model(cfg, attention_impl=impl)
    x = jax.random.normal(jax.random.key(4), (4, 32, 32, 3), jnp.float32)
    params = jax.jit(model.init, static_argnums=2)(jax.random.key(0), x, True)
    train = jax.jit(lambda p, rngs: model.apply(p, x, False, rngs=rngs))

    rngs = {"dropout": jax.random.key(9)}
    out1 = train(params, rngs)
    out2 = train(params, rngs)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    out3 = train(params, {"dropout": jax.random.key(10)})
    assert not np.array_equal(np.asarray(out1), np.asarray(out3))
    # eval path (deterministic) unaffected by the dropout hook
    out_eval = jax.jit(model.apply, static_argnums=2)(params, x, True)
    assert np.all(np.isfinite(np.asarray(out_eval)))

    def loss_fn(p):
        return jnp.sum(model.apply(p, x, False, rngs=rngs) ** 2)

    grads = jax.jit(jax.grad(loss_fn))(params)
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("shape", [(2, 64, 2, 32), (1, 128, 4, 16)])
def test_flash4d_matches_reference_fwd(devices8, shape):
    from vitax.ops.attention import flash_attention_4d
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flash_attention_4d(q, k, v)),
        np.asarray(reference_attention(q, k, v)), rtol=2e-4, atol=2e-4)


def test_flash4d_matches_reference_grad(devices8):
    from vitax.ops.attention import flash_attention_4d
    shape = (2, 64, 2, 32)
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.jit(jax.grad(loss(flash_attention_4d), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(reference_attention), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash4d_odd_head_count(devices8):
    """Head counts with no nice divisors still work (per-head lane slicing)."""
    from vitax.ops.attention import flash_attention_4d
    shape = (1, 64, 6, 16)  # h=6, dh=16: narrow odd-count lane slices
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flash_attention_4d(q, k, v)),
        np.asarray(reference_attention(q, k, v)), rtol=2e-4, atol=2e-4)


def _check_flash4d_matches_reference(shape, seed):
    from vitax.ops.attention import flash_attention_4d
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    np.testing.assert_allclose(
        np.asarray(flash_attention_4d(q, k, v)),
        np.asarray(reference_attention(q, k, v)), rtol=2e-4, atol=2e-4)
    gf = jax.jit(jax.grad(loss(flash_attention_4d), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(reference_attention), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash4d_head_grouping(devices8):
    """Shapes whose full head set busts the VMEM budget split into head
    groups; numerics must be identical to the dense reference. Groupings
    whose sublane count is legal (hb % 8 == 0) use the plain (B, H, N) lse
    layout; no padding involved."""
    from vitax.ops.attention import _heads_per_program, _lse_pad_rows
    shape = (1, 256, 16, 64)  # f32: full set needs ~21 MB -> splits to hb=8
    assert _heads_per_program(256, 16, 64, 4) == 8
    assert _lse_pad_rows(8, 16) == 0
    _check_flash4d_matches_reference(shape, seed=6)


def test_flash4d_padded_lse_grouping(devices8):
    """Groupings with hb % 8 != 0 (the 10B family: h=32, dh=160 -> hb=4)
    store lse in the grouped-padded (B, H/hb, 8, N) layout so every block
    satisfies Mosaic's sublane rule — the layout that keeps the 4D kernel
    (640-lane blocks, no (8,128)-tile padding) on the flagship shapes where
    the BH kernel's Dh=160 operands pad 1.6x in HBM. Numerics must match
    the dense reference through fwd AND the padded-lse backward."""
    from vitax.ops.attention import _heads_per_program, _lse_pad_rows
    assert _heads_per_program(256, 32, 160, 2) == 4   # flagship, bf16
    assert _lse_pad_rows(4, 32) == 8
    # f32 version of the same head geometry at n=128 picks hb=4 too
    assert _heads_per_program(128, 32, 160, 4) == 4
    _check_flash4d_matches_reference((1, 128, 32, 160), seed=7)


def test_tpu_kernel_selection_uses_local_heads(devices8):
    """Under tp, the shard_map'd kernel sees num_heads/tp heads — 4D-kernel
    support must be judged on the LOCAL count, falling back to the BH kernel
    when the local grouping has no VMEM fit (review finding, round 3)."""
    from vitax.config import Config
    from vitax.ops.attention import (_tpu_kernel, flash4_supported,
                                     flash_attention, flash_attention_4d)

    # n=324, dh=80, bf16: global h=24 has a legal grouping (hb=8: lane
    # 8*80=640 % 128 == 0, fits the VMEM budget), local h=12 has none
    # (hb=12 full-array busts the budget; every proper divisor's lane dim
    # hb*80 is not a multiple of 128)
    assert flash4_supported(324, 24, 80, 2)
    assert not flash4_supported(324, 12, 80, 2)
    cfg = Config(image_size=144, patch_size=8, embed_dim=1920, num_heads=24,
                 num_blocks=1, dtype="bfloat16").validate()
    k_global, _ = _tpu_kernel(cfg, cfg.num_patches, force=True)
    k_local, name = _tpu_kernel(cfg, cfg.num_patches, force=True,
                                local_heads=12)
    assert k_global is flash_attention_4d
    assert k_local is flash_attention and "BH relayout" in name


# --- the fused-qkv entry of the 4D kernels (PR 36) --------------------------

def _split_qkv(qkv, heads):
    b, n, d3 = qkv.shape
    x = qkv.reshape(b, n, 3, heads, d3 // (3 * heads))
    return x[:, :, 0], x[:, :, 1], x[:, :, 2]


@pytest.mark.parametrize("shape,hb", [
    ((2, 64, 16, 64), 16),    # ViT-L/14's heads: one head group, a
                              # (1, N, 3D) block out
    ((1, 128, 32, 160), 4),   # the 10B widths: 8 groups, the grouped-padded
                              # lse, the cotangent written by the kernel
    ((2, 64, 3, 128), 3),     # an odd head count
    ((1, 256, 16, 64), 8),    # two groups on the plain (B, H, N) lse
], ids=["l14_one_group", "tenb_hb4_padded_lse", "odd_heads", "two_groups"])
def test_flash_qkv_matches_reference_fwd_and_grad(devices8, shape, hb):
    """`flash_attention_qkv` against `reference_attention` on the split q, k,
    v: the output, and the gradient compared on the (B, N, 3D) cotangent
    itself, under a cotangent that differs by position and column."""
    from vitax.ops.attention import (_heads_per_program, flash4_qkv_supported,
                                     flash_attention_qkv)
    b, n, h, dh = shape
    assert _heads_per_program(n, h, dh, 4) == hb
    assert flash4_qkv_supported(n, h, dh, 4)
    qkv = jax.random.normal(jax.random.key(11), (b, n, 3 * h * dh),
                            jnp.float32)
    weight = jax.random.normal(jax.random.key(12), (b, n, h * dh),
                               jnp.float32)

    def reference(x):
        return reference_attention(*_split_qkv(x, h)).reshape(b, n, h * dh)

    def fused(x):
        return flash_attention_qkv(x, h)

    np.testing.assert_allclose(np.asarray(fused(qkv)),
                               np.asarray(reference(qkv)),
                               rtol=2e-4, atol=2e-4)
    got, want = (jax.jit(jax.grad(lambda x, f=f: jnp.sum(f(x) * weight)))(qkv)
                 for f in (fused, reference))
    assert got.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_flash_qkv_refuses_a_window_off_the_lane_tile(devices8):
    """h=6, dh=16: the 4D kernel's grouping (hb = h, 96 lanes) is legal only
    as a full-array block, which a window into a 3D-wide buffer never is:
    the entry is not offered and says so when called anyway."""
    from vitax.ops.attention import (flash4_qkv_supported, flash4_supported,
                                     flash_attention_qkv)
    assert flash4_supported(64, 6, 16, 4)
    assert not flash4_qkv_supported(64, 6, 16, 4)
    with pytest.raises(AssertionError, match="flash4_qkv_supported"):
        flash_attention_qkv(jnp.zeros((1, 64, 3 * 6 * 16), jnp.float32), 6)


def _attention_config(**over):
    from vitax.config import Config
    base = dict(image_size=64, patch_size=8, embed_dim=256, num_heads=4,
                num_blocks=1, dtype="float32")
    return Config(**{**base, **over}).validate()


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs its equations hold, but
    not the kernels' bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def _activation_relayouts(jaxpr, b, n):
    """Names of the slice / squeeze / transpose / pad / concatenate
    equations that read or write a (b, n, ...) activation of three or more
    dimensions."""
    relayout = {"slice", "squeeze", "transpose", "pad", "concatenate",
                "dynamic_slice", "gather"}
    return [eqn.primitive.name for eqn in _equations(jaxpr)
            if eqn.primitive.name in relayout and any(
                len(getattr(v.aval, "shape", ())) >= 3
                and v.aval.shape[:2] == (b, n)
                for v in (*eqn.invars, *eqn.outvars))]


def test_attention_with_the_fused_entry_moves_no_activation(devices8):
    """Jaxpr equations (not compiled text): with the fused entry nothing
    slices, squeezes, transposes, pads or concatenates a (B, N, .) activation
    between the qkv `dot_general` and the `pallas_call`, forward or backward;
    two kernel calls in all. The same walk over the split entry finds the
    slices and the pads, so it can see them."""
    from vitax.models.vit import Attention
    from vitax.ops.attention import flash_attention_4d, make_attention_impl
    cfg = _attention_config()
    b, n, d = 2, cfg.num_patches, cfg.embed_dim
    x = jax.random.normal(jax.random.key(0), (b, n, d), jnp.float32)
    impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
    assert impl.vitax_fused_qkv is not None

    def jaxpr_of(attention_impl):
        model = Attention(num_heads=cfg.num_heads, dtype=jnp.float32,
                          attention_impl=attention_impl)
        params = model.init(jax.random.key(1), x)

        def loss(params, x):
            return jnp.sum(model.apply(params, x) ** 2)
        return params, model, jax.make_jaxpr(
            jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr

    params, fused_model, fused = jaxpr_of(impl)
    assert _activation_relayouts(fused, b, n) == []
    assert sum(eqn.primitive.name == "pallas_call"
               for eqn in _equations(fused)) == 2
    _, split_model, split = jaxpr_of(flash_attention_4d)
    moved = _activation_relayouts(split, b, n)
    assert moved.count("slice") >= 3 and moved.count("pad") >= 3, moved
    # one set of weights, one function
    np.testing.assert_allclose(
        np.asarray(fused_model.apply(params, x)),
        np.asarray(split_model.apply(params, x)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name,over,fused", [
    ("l14", dict(image_size=224, patch_size=14, embed_dim=1024,
                 num_heads=16, dtype="bfloat16"), True),
    ("tenb_one_chip", dict(image_size=224, patch_size=14, embed_dim=5120,
                           num_heads=32, dtype="bfloat16"), True),
    ("att_dropout_eval_only", dict(att_dropout=0.1), True),
    ("bh_fallback", dict(image_size=144, patch_size=8, embed_dim=960,
                         num_heads=12, dtype="bfloat16"), False),
    ("lane_tile_off", dict(embed_dim=96, num_heads=6), False),
    ("streaming", dict(image_size=384, patch_size=8, embed_dim=256), False),
])
def test_fused_entry_is_offered_by_what_the_shape_allows(devices8, name,
                                                         over, fused):
    """One chip: the entry is advertised where `_select_path` says 4d and the
    head group's lanes tile; the impl's name says so; the BH fallback, a
    96-lane group and the streaming kernel keep today's entry."""
    from vitax.ops.attention import make_attention_impl
    impl = make_attention_impl(_attention_config(**over), None,
                               force_tpu_kernels=True)
    assert (getattr(impl, "vitax_fused_qkv", None) is not None) == fused
    assert ("fused qkv" in impl.vitax_name) == fused


@pytest.mark.parametrize("name,over,fused", [
    ("fsdp8", dict(fsdp_size=8), True),
    ("tenb_fsdp4_dp2", dict(image_size=224, patch_size=14, embed_dim=5120,
                            num_heads=32, dtype="bfloat16", fsdp_size=4,
                            dp_size=2), True),
    ("tp2", dict(fsdp_size=4, tp_size=2), False),
    ("sp2_ring", dict(fsdp_size=4, sp_size=2), False),
    ("sp2_ulysses", dict(fsdp_size=4, sp_size=2, sp_impl="ulysses"), False),
])
def test_fused_entry_on_a_mesh_needs_the_head_axis_whole(devices8, name,
                                                         over, fused):
    """On a mesh the entry rides the same shard_map over the batch axes;
    tp > 1 and sp > 1 keep today's entry, and the pipeline body's impls
    never carry it."""
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh
    cfg = _attention_config(**over)
    impl = make_attention_impl(cfg, build_mesh(cfg), force_tpu_kernels=True)
    assert (getattr(impl, "vitax_fused_qkv", None) is not None) == fused
    assert ("fused qkv" in impl.vitax_name) == fused
    for body in ("vitax_local_impl", "vitax_pp_impl"):
        assert getattr(getattr(impl, body, None), "vitax_fused_qkv",
                       None) is None


def test_fused_entry_under_shard_map_matches_the_split_entry(devices8):
    """fsdp = 8 on the virtual mesh: same values and gradients through the
    (B, N, 3D) spec as through today's three (B, N, H, Dh) operands."""
    from vitax.models.vit import Attention
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh
    cfg = _attention_config(fsdp_size=8)
    mesh = build_mesh(cfg)
    impl = make_attention_impl(cfg, mesh, force_tpu_kernels=True)
    x = jax.random.normal(jax.random.key(0), (8, cfg.num_patches, 256),
                          jnp.float32)

    def split(q, k, v):   # the impl without what it advertises
        return impl(q, k, v)
    fused_model = Attention(num_heads=4, dtype=jnp.float32,
                            attention_impl=impl)
    split_model = Attention(num_heads=4, dtype=jnp.float32,
                            attention_impl=split)
    params = fused_model.init(jax.random.key(1), x)

    def grads(model):
        return jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(model.apply(p, x) ** 2)))(params)
    with mesh:
        (lf, gf), (ls, gs) = grads(fused_model), grads(split_model)
    np.testing.assert_allclose(float(lf), float(ls), rtol=1e-5)
    for a, c in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("case", ["train_dropout", "packed"])
def test_calls_that_put_something_before_the_kernel_keep_the_split_entry(
        devices8, case):
    """Active attention dropout and the packed model (RoPE, `segment_ids`)
    keep q, k and v apart: the fused entry is there (or not offered at all)
    and is not called."""
    from vitax.models.vit import Attention
    from vitax.ops.attention import make_attention_impl
    called = []
    if case == "train_dropout":
        cfg = _attention_config(att_dropout=0.1)
        impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
        assert impl.vitax_fused_qkv is not None
        real = impl.vitax_fused_qkv
        impl.vitax_fused_qkv = lambda *a: called.append(1) or real(*a)
        model = Attention(num_heads=4, dtype=jnp.float32, att_dropout=0.1,
                          attention_impl=impl)
        x = jnp.ones((2, cfg.num_patches, 256), jnp.float32)
        params = model.init(jax.random.key(0), x)
        called.clear()               # init is an evaluation call
        model.apply(params, x, False, rngs={"dropout": jax.random.key(1)})
        assert called == []          # training: the in-kernel dropout entry
        model.apply(params, x, True)
        assert called == [1]         # evaluation: nothing in the way
    else:
        from vitax.config import Config
        cfg = Config(pack_tokens=128, pack_images=4, max_image_tokens=64,
                     pos_grid=8, patch_size=4, embed_dim=64, num_heads=4,
                     num_blocks=1, mlp_dim=100, num_classes=10,
                     dtype="float32").validate()
        impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
        assert getattr(impl, "vitax_fused_qkv", None) is None
        assert "fused qkv" not in impl.vitax_name
