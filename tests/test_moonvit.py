"""The native-resolution packed model (MoonViT shape) against its plain
reference, at a small shape on the CPU: 64 wide, 4 heads of 16, 2 blocks, a
position table of 8 x 8, rows of 128 tokens holding images such as 4x6, 8x8,
2x10 and padding. Seeded random weights, every leaf perturbed (biases and
LayerNorm parameters are otherwise 0 and 1 and would hide a dropped term).

Tolerances, and why. The float32 program and the float32 reference compute
the same mathematics in another order (packed rows, a scan, a one-hot
position matmul and real-valued rotations against one image at a time,
interpolation matrices and complex multiplication): they agree to a few
float32 roundings through 2 blocks. F32_RTOL = 2e-4 of the largest value
holds that with room. The same program in bfloat16 is off by 1e-2 and more,
and must fail (`test_lower_precision_fails`), as must a program without the
RoPE or one that attends across images.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import moonvit as reference
from vitax.config import Config, parse_config
from vitax.data import packing
from vitax.models import vit
from vitax.models.vit import build_model, sample_input
from vitax.train.step import packed_inputs, packed_loss

F32_RTOL = 2e-4
ROWS = [[(4, 6), (8, 8), (2, 10)], [(6, 6), (2, 2), (4, 4)]]
SHAPE = dict(num_heads=4, num_blocks=2, rope_base=10000.0)


def small_cfg(**kw):
    base = dict(embed_dim=64, num_heads=4, num_blocks=2, mlp_dim=100,
                patch_size=4, num_classes=10, pack_tokens=128, pack_images=4,
                max_image_tokens=64, pos_grid=8, batch_size=2, fsdp_size=1,
                dtype="float32", fake_data=True, warmup_steps=2,
                fused_optimizer="off")
    base.update(kw)
    return Config(**base).validate()


def make_batch(cfg, rows, seed=1):
    """A packed batch through the trainer's packer: random pixels, labels."""
    rng = np.random.default_rng(seed)
    grids = [g for row in rows for g in row]
    dim = 3 * cfg.patch_size ** 2
    pixels = [rng.integers(0, 256, (h * w, dim), dtype=np.uint8)
              for h, w in grids]
    labels = rng.integers(0, cfg.num_classes, len(grids)).tolist()
    lay = packing.row_layout(rows, cfg.pack_tokens, cfg.pack_images)
    batch = dict(lay, patches=np.zeros((len(rows), cfg.pack_tokens, dim),
                                       np.uint8),
                 label=np.zeros((len(rows), cfg.pack_images), np.int32))
    i = 0
    for r, row in enumerate(rows):
        at = 0
        for s, (h, w) in enumerate(row):
            batch["patches"][r, at:at + h * w] = pixels[i]
            batch["label"][r, s] = labels[i]
            at += h * w
            i += 1
    return batch


def init_params(cfg, model, seed=0):
    @jax.jit
    def seeded():
        params = model.init(jax.random.key(seed), sample_input(cfg, 2), True)
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
        params = jax.tree.unflatten(tree, [
            leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
            for leaf, k in zip(leaves, keys)])
        # peaked attention: with near-uniform softmax rows neither the RoPE
        # nor the mask between images would move a logit by much
        qkv = params["params"]["blocks"]["attn"]["qkv"]
        qkv["kernel"] = qkv["kernel"] * 6.0
        return params
    return seeded()


def program_logits(model, params, batch):
    """(images, classes): the program's per-image logits in packing order."""
    out = jax.jit(lambda p, b: model.apply(p, packed_inputs(b), True))(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    return np.asarray(out)[np.asarray(batch["label_mask"]) > 0]


def rel_gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want)))
                 / np.max(np.abs(np.asarray(want))))


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    model = build_model(cfg)
    params = init_params(cfg, model)
    batch = make_batch(cfg, ROWS)
    images, labels = reference.unpack(batch)
    return cfg, model, params, batch, images, labels


@pytest.fixture(scope="module")
def want(setup):
    """The reference's logits, once for the cases held to them."""
    _, _, params, _, images, _ = setup
    return jax.jit(lambda p: reference.logits(p, images, **SHAPE))(params)


def test_logits_match_the_reference(setup, want):
    cfg, model, params, batch, images, _ = setup
    assert rel_gap(program_logits(model, params, batch), want) < F32_RTOL


def test_loss_and_every_gradient_leaf_match_the_reference(setup):
    cfg, model, params, batch, images, labels = setup
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    loss, grads = jax.jit(jax.value_and_grad(lambda p: packed_loss(
        model.apply(p, packed_inputs(jb), True), jb)))(params)
    ref_loss, (ref_rest, ref_layers) = jax.jit(
        lambda p: reference.value_and_grads(p, images, labels, **SHAPE))(
            params)
    assert abs(float(loss) - float(ref_loss)) < F32_RTOL * float(ref_loss)
    got = grads["params"]
    want = dict(ref_rest, blocks=jax.tree.map(
        lambda *xs: jnp.stack(xs), *ref_layers))
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 19
    # a leaf's gradient is held to the scale of the whole gradient's largest
    # entry on that leaf: an all-but-zero leaf has no relative scale of its own
    for path, g in flat_got.items():
        assert rel_gap(g, flat_want[path]) < 5 * F32_RTOL, path


def test_train_step_loss_grad_norm_and_counters(setup):
    """The compiled step: its loss and gradient norm are the reference's,
    and its `tokens` / `padding_tokens` / `images` are the packer's."""
    from vitax.parallel.mesh import build_mesh
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step
    cfg, model, params, batch, images, labels = setup
    mesh = build_mesh(cfg, devices=jax.devices()[:1])
    tx, schedule = build_optimizer(cfg, 10)
    state, specs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    state = state.replace(params=params)
    step = make_train_step(cfg, model, tx, mesh, specs, donate=False,
                           schedule=schedule)
    _, metrics = step(state, {k: jnp.asarray(v) for k, v in batch.items()},
                      jax.random.key(1))
    ref_loss, ref_norm = jax.jit(lambda p: reference.loss_and_grad_norm(
        p, images, labels, **SHAPE))(params)
    assert abs(float(metrics["loss"]) - float(ref_loss)) \
        < F32_RTOL * float(ref_loss)
    assert abs(float(metrics["grad_norm"]) - float(ref_norm)) \
        < 5 * F32_RTOL * float(ref_norm)
    grids = [g for row in ROWS for g in row]
    _, info = packing.pack_batch(
        grids, labels, None, rows=2, row_tokens=cfg.pack_tokens,
        images_per_row=cfg.pack_images, patch_dim=3 * cfg.patch_size ** 2)
    assert info["left"] == []
    for key in ("tokens", "padding_tokens", "images"):
        assert int(metrics[key]) == info[key], key
    assert int(metrics["token_pairs"]) == sum((h * w) ** 2 for h, w in grids)
    # what the kernels compute for them: the live sub-tiles of the table the
    # kernels read (here one tile of 128 x 128 a row)
    from vitax.ops.flash_blocked import packed_block_tables
    live = np.asarray(packed_block_tables(
        jnp.asarray(batch["segment_ids"]), 128, 128)[0])
    assert float(metrics["computed_pairs"]) == live.sum() * 128 * 128 == 32768


def test_lower_precision_fails(setup, want):
    """The tolerance is tight enough that bfloat16 compute does not pass."""
    cfg, _, params, batch, images, _ = setup
    low = build_model(dataclasses.replace(cfg, dtype="bfloat16"))
    assert rel_gap(program_logits(low, params, batch), want) > 10 * F32_RTOL


def test_dropping_the_rope_fails(setup, want):
    cfg, model, params, batch, images, _ = setup
    flat = dict(batch, positions=np.zeros_like(batch["positions"]))
    # positions also place the position table: keep that part right, so that
    # only the rotation is missing
    unrotated = build_model(dataclasses.replace(cfg, rope_base=1e30))
    assert rel_gap(program_logits(unrotated, params, batch), want) \
        > 10 * F32_RTOL
    assert rel_gap(program_logits(model, params, flat), want) > 10 * F32_RTOL


def test_attending_across_images_fails(setup, want, monkeypatch):
    cfg, model, params, batch, images, _ = setup

    def across(q, k, v, segment_ids, dtype):
        merged = (segment_ids > 0).astype(segment_ids.dtype)
        return real(q, k, v, merged, dtype)

    real = vit.masked_attention
    monkeypatch.setattr(vit, "masked_attention", across)
    assert rel_gap(program_logits(model, params, batch), want) > 10 * F32_RTOL


def test_packing_invariance(setup):
    """An image's logits are the same alone in a row, last in a full row,
    and in another order: nothing crosses from one image to another."""
    cfg, model, params, _, _, _ = setup
    rng = np.random.default_rng(7)
    dim = 3 * cfg.patch_size ** 2
    target = (4, 6)
    pixels = rng.integers(0, 256, (24, dim), dtype=np.uint8)
    others = [(8, 8), (2, 10), (4, 4)]
    apply = jax.jit(lambda p, b: model.apply(p, packed_inputs(b), True))

    def logits_of_target(row):
        lay = packing.row_layout([row, []], cfg.pack_tokens, cfg.pack_images)
        patches = rng.integers(0, 256, (2, cfg.pack_tokens, dim),
                               dtype=np.uint8)
        at = sum(h * w for h, w in row[:row.index(target)])
        patches[0, at:at + 24] = pixels
        patches *= (lay["segment_ids"] > 0)[..., None].astype(np.uint8)
        batch = dict(lay, patches=patches,
                     label=np.zeros((2, cfg.pack_images), np.int32))
        out = apply(params, {k: jnp.asarray(v) for k, v in batch.items()})
        return np.asarray(out)[0, row.index(target)]

    alone = logits_of_target([target])
    last = logits_of_target(others + [target])
    first = logits_of_target([target] + others[::-1])
    # float32 sums over the same values in the same order inside the image;
    # only the row-level matmuls' tiling can differ: a few ulps
    for other in (last, first):
        np.testing.assert_allclose(other, alone, rtol=0, atol=2e-6)


# --- the position table -----------------------------------------------------

@pytest.mark.parametrize("hw", [(4, 6), (8, 8), (2, 10), (16, 12), (3, 5)])
def test_interpolated_table_against_the_two_matrix_form(hw):
    h, w = hw
    table = jax.random.normal(jax.random.key(0), (8, 8, 16), jnp.float32)
    lay = packing.row_layout([[hw]], h * w + 8, 2)
    token_hw = np.repeat(np.asarray([[hw]]), h * w + 8, axis=1)
    got = jax.jit(vit.pos_interp, static_argnums=3)(
        table, jnp.asarray(lay["positions"]), jnp.asarray(token_hw),
        jnp.float32)[0, :h * w]
    want = jax.jit(reference.position_embedding, static_argnums=(1, 2))(
        table, h, w)
    if hw == (8, 8):    # the table's own grid: the table itself, exactly
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table).reshape(64, 16))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    else:               # two roundings of float32 weights and their sums
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-5)


def test_resize_matrix_rows_sum_to_one_and_differ_from_half_kernel():
    m = reference.resize_matrix(6, 8)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-6)
    # A = -0.75 (PyTorch), not -0.5 (jax.image.resize): the outer taps differ
    assert abs(m[2].min()) > 0.04


# --- 2D RoPE ----------------------------------------------------------------

def test_rope_scores_depend_on_the_offset_only():
    keys = jax.random.split(jax.random.key(3), 2)
    q = jax.random.normal(keys[0], (1, 1, 2, 16))
    k = jax.random.normal(keys[1], (1, 1, 2, 16))

    @jax.jit
    def rotated(pos):                                         # (1, 2, 2)
        cos, sin = vit.rope2d_tables(pos, 16, 10000.0)
        rq = vit.apply_rope2d(q, cos[:, :1], sin[:, :1])
        rk = vit.apply_rope2d(k, cos[:, 1:], sin[:, 1:])
        return jnp.einsum("bqhd,bkhd->h", rq, rk)

    def score(pq, pk):
        return np.asarray(rotated(np.asarray([[pq, pk]], np.int32)))

    base = score((3, 5), (1, 2))
    np.testing.assert_allclose(score((13, 25), (11, 22)), base, atol=1e-5)
    np.testing.assert_allclose(score((2, 3), (0, 0)), base, atol=1e-5)
    assert np.abs(score((3, 5), (2, 2)) - base).max() > 1e-3   # a row apart
    assert np.abs(score((3, 5), (1, 3)) - base).max() > 1e-3   # a column


def test_rope_matches_the_complex_form():
    x = jax.random.normal(jax.random.key(4), (1, 24, 2, 16))
    lay = packing.row_layout([[(4, 6)]], 24, 1)
    got = jax.jit(lambda x, pos: vit.apply_rope2d(
        x, *vit.rope2d_tables(pos, 16, 10000.0)))(
            x, jnp.asarray(lay["positions"]))[0]
    want = jax.jit(lambda x: reference.rotate(
        x, reference.rope_cis(4, 6, 16, 10000.0)))(x[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# --- the packed kernels (interpret mode) ------------------------------------

def kernel_case(dh=16, t=512):
    rows = [[(6, 10), (16, 12), (3, 8)], [(20, 20), (5, 5)], []]
    lay = packing.row_layout(rows, t, 4)
    seg = jnp.asarray(lay["segment_ids"])
    keys = jax.random.split(jax.random.key(11), 4)
    q, k, v, w = (jax.random.normal(kk, (3, t, 4, dh)) for kk in keys)
    return seg, q, k, v, w


@pytest.mark.parametrize("dh", [16, 72])
@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128)])
def test_packed_kernel_matches_dense_masked_attention(dh, blocks):
    """Forward and all three gradients, on rows that span several blocks,
    with block skipping on and forced off giving equal values; padding rows
    (and an all-padding row) come back zero, never NaN."""
    from vitax.ops.flash_blocked import packed_flash_attention
    seg, q, k, v, w = kernel_case(dh)

    def dense(q, k, v):
        return vit.masked_attention(q, k, v, seg, jnp.float32)

    def run(fn):         # (output, its three gradients): one program
        def weighted(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighted, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    want_o, want_g = run(dense)
    outs = {}
    for skip in (True, False):
        o, g = run(lambda q, k, v: packed_flash_attention(
            q, k, v, seg, blocks[0], blocks[1], skip))
        outs[skip] = (o, g)
        # float32 online softmax against a one-pass softmax: roundings only
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                                   atol=2e-5)
        for a, b in zip(g, want_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        pad = np.asarray(seg) == 0
        assert not np.asarray(o)[pad].any()
        for a in g:
            assert np.isfinite(np.asarray(a)).all()
            assert not np.asarray(a)[pad].any()
    for a, b in zip(jax.tree.leaves(outs[True]), jax.tree.leaves(outs[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_block_tables_skip_exactly_the_pairs_between_images():
    from vitax.ops.flash_blocked import packed_block_tables
    seg = np.zeros((1, 1024), np.int32)
    seg[0, :300] = 1          # blocks 0-2 of 128
    seg[0, 300:640] = 2       # blocks 2-4
    seg[0, 640:700] = 3       # block 5
    live, kidx, live_kq, qidx = (np.asarray(x) for x in packed_block_tables(
        jnp.asarray(seg), 128, 128))
    live = live.reshape(8, 8)
    want = np.zeros((8, 8), bool)
    for lo, hi in ((0, 300), (300, 640), (640, 700)):
        blocks = range(lo // 128, (hi - 1) // 128 + 1)
        for i in blocks:
            for j in blocks:
                want[i, j] = True
    np.testing.assert_array_equal(live.astype(bool), want)
    np.testing.assert_array_equal(live_kq.reshape(8, 8), live.T)
    kidx = kidx.reshape(8, 8)
    # a dead step names the block already held: the latest live one, or the
    # first live one before any (so it moves no bytes)
    np.testing.assert_array_equal(kidx[0], [0, 1, 2, 2, 2, 2, 2, 2])
    np.testing.assert_array_equal(kidx[2], [0, 1, 2, 3, 4, 4, 4, 4])
    np.testing.assert_array_equal(kidx[5], [5] * 8)
    assert not live[6].any() and not live[7].any()      # padding blocks
    every = np.asarray(packed_block_tables(jnp.asarray(seg), 128, 128,
                                           skip=False)[0])
    assert every.all()


def test_model_through_the_kernel_equals_the_dense_path(setup):
    """`make_attention_impl` hands a packed config the segment-masked
    kernel; the model through it (interpret mode) equals the model through
    the dense masked path."""
    from vitax.ops.attention import make_attention_impl
    cfg, model, params, batch, _, _ = setup
    assert make_attention_impl(cfg, None) is None      # off the TPU: dense
    impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
    assert "segment-masked" in impl.vitax_name
    through = build_model(cfg, attention_impl=impl)
    np.testing.assert_allclose(program_logits(through, params, batch),
                               program_logits(model, params, batch),
                               atol=2e-5)


# --- what per-block remat keeps of the kernel --------------------------------

def kernel_model(tokens):
    """The small model through the segment-masked kernels (interpret mode) at
    rows of `tokens`, with the batch of ROWS and perturbed parameters."""
    from vitax.ops.attention import make_attention_impl
    cfg = small_cfg(pack_tokens=tokens)
    model = build_model(cfg, attention_impl=make_attention_impl(
        cfg, None, force_tpu_kernels=True))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, ROWS).items()}
    return cfg, model, init_params(cfg, model), batch


def loss_and_grads(model):
    def loss(params, batch):
        logits = model.apply(params, packed_inputs(batch), True)
        return packed_loss(logits, batch)
    return jax.value_and_grad(loss)


def kernel_calls(jaxpr, name):
    """`pallas_call`s named `name` anywhere in a jaxpr: its call sites."""
    count = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += kernel_calls(sub, name)
    return count


@pytest.mark.parametrize("tokens,keeps,forward_sites", [
    (vit.ATTN_KEEP_MIN_SPAN // 2, False, 2),   # the backward scan re-runs it
    (vit.ATTN_KEEP_MIN_SPAN, True, 1),         # the forward scan's, alone
])
def test_forward_kernel_call_sites_follow_the_span(tokens, keeps,
                                                   forward_sites):
    _, model, params, batch = kernel_model(tokens)
    assert vit.keeps_attention_residuals(model) is keeps
    jaxpr = jax.make_jaxpr(loss_and_grads(model))(params, batch).jaxpr
    assert kernel_calls(jaxpr, "flash_packed_fwd") == forward_sites
    assert kernel_calls(jaxpr, "flash_packed_dkv") == 1
    assert kernel_calls(jaxpr, "flash_packed_dq") == 1


def test_keeping_program_equals_the_recomputing_one_bit_for_bit(monkeypatch):
    """What is kept is what the kernel would have produced again: loss and
    every gradient leaf of the two programs are the same bits."""
    _, model, params, batch = kernel_model(vit.ATTN_KEEP_MIN_SPAN)
    kept = jax.jit(loss_and_grads(model))(params, batch)
    monkeypatch.setattr(vit, "ATTN_KEEP_MIN_SPAN", 1 << 30)
    assert not vit.keeps_attention_residuals(model)
    again = jax.jit(loss_and_grads(model))(params, batch)
    assert float(kept[0]) > 0.0
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("keeps", [True, False])
def test_a_layer_keeps_its_input_o_and_lse_and_nothing_else(keeps):
    """The residuals of one rematted block, beside its own arguments (the
    block input, the layer's parameters, the row context): the kernel's o in
    the BH layout and its lse as the backward kernels take them; nothing
    under the span."""
    from jax._src.ad_checkpoint import saved_residuals
    tokens = vit.ATTN_KEEP_MIN_SPAN // (1 if keeps else 2)
    cfg, model, params, batch = kernel_model(tokens)
    block = vit.Block(**model.block_kwargs())
    layer = jax.tree.map(lambda l: l[0], params["params"]["blocks"])
    seg = batch["segment_ids"]
    rope = vit.rope2d_tables(batch["positions"], 16, cfg.rope_base)
    x = jnp.ones((2, tokens, cfg.embed_dim), jnp.float32)
    body = jax.checkpoint(
        lambda layer, x, seg, rope: block.apply({"params": layer}, x, True,
                                                seg, rope),
        policy=vit.block_remat_policy(model), prevent_cse=False)
    kept = [aval for aval, why in saved_residuals(body, layer, x, seg, rope)
            if "from the argument" not in why]
    bh = 2 * cfg.num_heads
    want = [((bh, tokens, 16), jnp.float32),
            ((bh, 1, tokens), jnp.float32)] if keeps else []
    assert sorted((a.shape, a.dtype) for a in kept) == sorted(want)


# --- the packer -------------------------------------------------------------

def test_packer_places_every_token_once_and_splits_no_image():
    rng = np.random.default_rng(0)
    grids = [tuple((2 * rng.integers(1, 5, 2)).tolist()) for _ in range(14)]
    labels = list(range(14))
    dim = 12
    pixels = [np.full((h * w, dim), i + 1, np.uint8)
              for i, (h, w) in enumerate(grids)]
    batch, info = packing.pack_batch(grids, labels, pixels, rows=3,
                                     row_tokens=128, images_per_row=5,
                                     patch_dim=dim)
    seg = batch["segment_ids"]
    placed = [i for i in range(14) if i not in info["left"]]
    assert info["images"] == len(placed) == int(batch["label_mask"].sum())
    assert info["tokens"] == sum(grids[i][0] * grids[i][1] for i in placed)
    assert info["tokens"] + info["padding_tokens"] == 3 * 128
    assert (batch["patches"][seg == 0] == 0).all()        # padding: segment 0
    seen = []
    for r in range(3):
        for s in range(5):
            h, w = batch["grid_hw"][r, s]
            where = np.flatnonzero(seg[r] == s + 1)
            if h * w == 0:
                assert where.size == 0 and batch["label_mask"][r, s] == 0
                continue
            i = int(batch["label"][r, s])
            seen.append(i)
            assert grids[i] == (h, w) and where.size == h * w
            assert (np.diff(where) == 1).all()            # whole, contiguous
            assert (batch["patches"][r, where] == i + 1).all()
            np.testing.assert_array_equal(
                batch["positions"][r, where, 0] * w
                + batch["positions"][r, where, 1], np.arange(h * w))
    assert sorted(seen) == placed
    # first fit: an image left over fitted no row at its turn
    rows, left = packing.first_fit(grids, 3, 128, 5)
    assert left == info["left"] and sum(map(len, rows)) == len(placed)


def test_cut_patches_is_the_convolutions_order():
    """A linear map on cut patches with the conv's kernel reshaped equals
    the p x p stride-p convolution."""
    img = jax.random.normal(jax.random.key(0), (8, 12, 3))
    kernel = jax.random.normal(jax.random.key(1), (4, 4, 3, 5))
    conv = jax.lax.conv_general_dilated(
        img[None], kernel, (4, 4), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0].reshape(6, 5)
    cut = packing.cut_patches(np.asarray(img), 4)
    np.testing.assert_allclose(cut @ np.asarray(kernel).reshape(48, 5),
                               np.asarray(conv), atol=1e-5)


def test_config_rules_of_the_packed_shape():
    assert small_cfg().packed and small_cfg().num_patches == 128
    assert small_cfg().mlp_hidden_dim == 100
    assert Config(embed_dim=1152, mlp_dim=4304).mlp_hidden_dim == 4304
    assert Config(embed_dim=1152, mlp_ratio=3.7361).mlp_hidden_dim == 4303
    for bad in (dict(pack_images=0), dict(max_image_tokens=0),
                dict(max_image_tokens=256), dict(pos_grid=1),
                dict(att_dropout=0.1), dict(tp_size=2), dict(moe_experts=4),
                dict(num_heads=32), dict(grad_accum_steps=2)):
        with pytest.raises(AssertionError):
            small_cfg(**bad)
    assert vit.expected_param_count(small_cfg()) == vit.count_params(
        jax.jit(lambda: build_model(small_cfg()).init(
            jax.random.key(0), sample_input(small_cfg(), 2), True))())


# --- telemetry and the trainer ----------------------------------------------

def test_step_record_reads_a_packed_steps_counters(tmp_path):
    from vitax.telemetry.flops import packed_flops_per_step
    from vitax.telemetry.record import build_recorder
    cfg = small_cfg(metrics_dir=str(tmp_path), peak_tflops=100.0)
    rec = build_recorder(cfg, 1, "cpu")
    counts = {"tokens": 196.0, "padding_tokens": 60.0, "images": 6.0,
              "token_pairs": 5000.0}
    r = rec.record_step(step=1, epoch=1, step_in_epoch=1, loss=1.0, lr=1e-3,
                        sec_per_iter=0.5, data_wait_s=0.0,
                        packed_counts=counts)
    rec.close()
    assert r["tokens_per_sec"] == pytest.approx(196 / 0.5)
    assert r["images_per_sec"] == pytest.approx(6 / 0.5)
    assert r["padding_frac"] == pytest.approx(60 / 256)
    flops = packed_flops_per_step(cfg, 196.0, 5000.0, 6.0)
    assert r["mfu"] == pytest.approx(flops / 0.5 / 100e12)
    d, h = 64, 100
    assert flops == 3.0 * (
        (2 * (2 * 4 * d * d + 2 * 2 * d * h) + 2 * 48 * d) * 196
        + 2 * 2 * 2 * 5000 * d + 2 * d * 10 * 6)


def test_training_through_the_cli_path(tmp_path):
    """`python -m vitax.train --fake_data` at the small packed shape (its
    flags through `parse_config`, then the loop the entry point calls):
    packed fake batches (8 rows over the 8 virtual devices' fsdp mesh),
    `build_program("train")`, a checkpoint save, and a finite, falling loss
    on the step records."""
    from vitax.train.loop import train
    cfg = parse_config((
        "--fake_data", "--pack_tokens", "128", "--pack_images", "4",
        "--max_image_tokens", "64", "--pos_grid", "8", "--patch_size", "4",
        "--embed_dim", "64", "--num_heads", "4", "--num_blocks", "2",
        "--mlp_dim", "100", "--num_classes", "10", "--batch_size", "8",
        "--num_epochs", "1", "--steps_per_epoch", "4", "--lr", "3e-3",
        "--log_step_interval", "1", "--warmup_steps", "1",
        "--ckpt_dir", str(tmp_path / "ckpt"),
        "--metrics_dir", str(tmp_path / "metrics")))
    train(cfg)
    with open(tmp_path / "metrics" / "metrics.jsonl") as f:
        steps = [r for r in map(json.loads, f) if "kind" not in r]
    losses = [r["loss"] for r in steps]
    assert len(losses) == 4 and np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert all(0.0 <= r["padding_frac"] < 1.0 for r in steps)
    assert (tmp_path / "ckpt" / "epoch_1").exists()
