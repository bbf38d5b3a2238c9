"""Model math tests: parameter-count parity with the reference's closed forms,
init statistics, forward shapes, scan-vs-loop equivalence, remat gradient parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.config import Config
from vitax.models.vit import VisionTransformer, build_model, count_params, expected_param_count


def tiny_cfg(**kw):
    base = dict(
        image_size=32, patch_size=16, embed_dim=64, num_heads=2, num_blocks=2,
        mlp_ratio=4.0, num_classes=10, batch_size=8, dtype="float32",
    )
    base.update(kw)
    return Config(**base).validate()


def init_params(cfg, rng=0):
    model = build_model(cfg)
    x = jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.float32)
    return model, jax.jit(model.init, static_argnums=2)(
        jax.random.key(rng), x, True)


def test_param_count_closed_form_10b():
    """The flagship config must hit the reference's exact 10,077,917,160
    (SURVEY.md section 6; reference README.md:3 '10 billion')."""
    cfg = Config()  # defaults = the 10B config
    assert expected_param_count(cfg) == 10_077_917_160


def test_param_count_tiny_matches_closed_form():
    cfg = tiny_cfg()
    _, params = init_params(cfg)
    assert count_params(params) == expected_param_count(cfg)


def test_param_count_vit_tiny_16():
    """BASELINE.json config 1: ViT-Tiny/16 (192 dim, 3 heads, 12 blocks)."""
    cfg = Config(image_size=224, patch_size=16, embed_dim=192, num_heads=3,
                 num_blocks=12, num_classes=1000, dtype="float32").validate()
    _, params = init_params(cfg)
    n = count_params(params)
    assert n == expected_param_count(cfg)
    # ViT-Tiny/16 is ~5.7M params
    assert 5_000_000 < n < 6_500_000


def test_forward_shape_and_dtype():
    cfg = tiny_cfg()
    model, params = init_params(cfg)
    x = jnp.ones((4, 32, 32, 3), jnp.float32)
    logits = jax.jit(model.apply, static_argnums=2)(params, x, True)
    assert logits.shape == (4, 10)
    assert logits.dtype == jnp.float32


def test_scan_and_unrolled_blocks_agree():
    """lax.scan over stacked params must compute the same function as an
    unrolled per-block loop (same per-layer weights)."""
    cfg_scan = tiny_cfg(scan_blocks=True, grad_ckpt=False)
    cfg_loop = tiny_cfg(scan_blocks=False, grad_ckpt=False)
    model_s, params_s = init_params(cfg_scan)
    model_l = build_model(cfg_loop)

    # Rebuild loop params from the stacked scan params.
    stacked = params_s["params"]["blocks"]
    loop_params = {k: v for k, v in params_s["params"].items() if k != "blocks"}
    for i in range(cfg_loop.num_blocks):
        loop_params[f"blocks_{i}"] = jax.tree.map(lambda a: a[i], stacked)

    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3), jnp.float32)
    out_s = jax.jit(model_s.apply, static_argnums=2)(params_s, x, True)
    out_l = jax.jit(model_l.apply, static_argnums=2)(
        {"params": loop_params}, x, True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_l), rtol=1e-5, atol=1e-5)


def test_scan_unroll_matches_unroll1():
    """--scan_unroll > 1 (multi-block windows inside lax.scan, the wgrad-
    fusion lever) must not change values or the stacked param tree; a
    non-divisor unroll exercises lax.scan's remainder handling."""
    cfg1 = tiny_cfg(grad_ckpt=True, num_blocks=5)
    model1, params = init_params(cfg1)
    x = jax.random.normal(jax.random.key(3), (2, 32, 32, 3), jnp.float32)

    def loss(model):
        return lambda p: jnp.sum(model.apply(p, x, True) ** 2)

    l1, g1 = jax.jit(jax.value_and_grad(loss(model1)))(params)
    for unroll in (3, 64):  # non-divisor of num_blocks; > num_blocks clamps
        cfgu = tiny_cfg(grad_ckpt=True, num_blocks=5, scan_unroll=unroll)
        modelu = build_model(cfgu)
        assert jax.tree.structure(jax.eval_shape(
            lambda: modelu.init(jax.random.key(0), x[:1], True))) \
            == jax.tree.structure(params), \
            "scan_unroll must keep the stacked param tree"
        lu, gu = jax.jit(jax.value_and_grad(loss(modelu)))(params)
        np.testing.assert_allclose(float(l1), float(lu), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(gu)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_remat_matches_no_remat():
    """Activation checkpointing must not change forward or gradient values."""
    cfg_a = tiny_cfg(grad_ckpt=True)
    cfg_b = tiny_cfg(grad_ckpt=False)
    model_a, params = init_params(cfg_a)
    model_b = build_model(cfg_b)
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3), jnp.float32)

    def loss_fn(model):
        def f(p):
            return jnp.sum(model.apply(p, x, True) ** 2)
        return f

    la, ga = jax.jit(jax.value_and_grad(loss_fn(model_a)))(params)
    lb, gb = jax.jit(jax.value_and_grad(loss_fn(model_b)))(params)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_init_statistics():
    """trunc-normal(0.02) weights, zero biases, LN ones/zeros
    (timm _init_vit_weights semantics, reference run_vit_training.py:125-152)."""
    cfg = tiny_cfg(embed_dim=128, num_blocks=2)
    _, params = init_params(cfg)
    p = params["params"]

    qkv_kernel = p["blocks"]["attn"]["qkv"]["kernel"]
    std = float(jnp.std(qkv_kernel))
    assert 0.015 < std < 0.025, f"qkv kernel std {std} not ~0.02"
    # truncated at 2 sigma (bound leaves headroom for rescaling jax versions)
    assert float(jnp.max(jnp.abs(qkv_kernel))) < 0.046

    assert float(jnp.max(jnp.abs(p["blocks"]["attn"]["qkv"]["bias"]))) == 0.0
    np.testing.assert_array_equal(np.asarray(p["norm"]["scale"]), 1.0)
    np.testing.assert_array_equal(np.asarray(p["norm"]["bias"]), 0.0)

    pos = p["pos_embed"]
    assert pos.shape == (1, cfg.num_patches, cfg.embed_dim)
    std = float(jnp.std(pos))
    assert 0.015 < std < 0.025


def test_dropout_active_in_train_mode():
    cfg = tiny_cfg(pos_dropout=0.5, mlp_dropout=0.5)
    model, params = init_params(cfg)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    train = jax.jit(lambda p, key: model.apply(p, x, False,
                                               rngs={"dropout": key}))
    out1 = train(params, jax.random.key(1))
    out2 = train(params, jax.random.key(2))
    assert not np.allclose(np.asarray(out1), np.asarray(out2))
    # deterministic mode is rng-independent
    out3 = jax.jit(model.apply, static_argnums=2)(params, x, True)
    out4 = jax.jit(model.apply, static_argnums=2)(params, x, True)
    np.testing.assert_array_equal(np.asarray(out3), np.asarray(out4))


def test_mean_pool_not_cls():
    """No CLS token: sequence length stays (image/patch)^2 and the head sees the
    mean-pooled sequence (reference run_vit_training.py:127,159-161)."""
    cfg = tiny_cfg()
    _, params = init_params(cfg)
    assert params["params"]["pos_embed"].shape[1] == (32 // 16) ** 2


def test_windowed_remat_matches_scan_path(devices8):
    """--remat_window w: the functional group-remat scan (make_windowed_forward)
    consumes the SAME stacked param tree and must reproduce the per-block
    scan path exactly — forward, grads, and a short training trajectory (the
    wgrad dus-stacking experiment must not change the math)."""
    import numpy as np
    from tests.test_train_smoke import run_steps
    from vitax.config import Config
    from vitax.models.vit import make_windowed_forward

    kw = dict(image_size=32, patch_size=8, embed_dim=32, num_heads=4,
              num_blocks=4, num_classes=4, batch_size=16, dtype="float32",
              fsdp_size=-1, warmup_steps=0, grad_ckpt=True)
    cfg_w = Config(remat_window=2, **kw).validate()
    cfg_ref = Config(**kw).validate()

    model = build_model(cfg_ref)
    x = jax.random.normal(jax.random.key(1),
                          (16, 32, 32, 3), jnp.float32)
    params = jax.jit(lambda k: model.init(k, x[:1], True))(jax.random.key(0))
    fwd_w = make_windowed_forward(cfg_w, model)

    ref = jax.jit(model.apply, static_argnums=2)(params, x, True)
    got = jax.jit(fwd_w)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.jit(jax.grad(
        lambda p: jnp.sum(model.apply(p, x, True) ** 2)))(params)
    g_w = jax.jit(jax.grad(lambda p: jnp.sum(fwd_w(p, x) ** 2)))(params)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_ref)[0],
            jax.tree_util.tree_flatten_with_path(g_w)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(ka)}")

    _, losses_w = run_steps(cfg_w, n_steps=3)
    _, losses_ref = run_steps(cfg_ref, n_steps=3)
    np.testing.assert_allclose(losses_w, losses_ref, rtol=2e-4)


@pytest.mark.parametrize("variant", ["moe", "dropout", "sp"])
def test_windowed_remat_v2_moe_and_dropout(devices8, variant):
    """--remat_window v2 (VERDICT r4 weak #3): the 10B family's measured
    winner must compose with the flagship's own flags. MoE is deterministic
    -> exact trajectory parity with the nn.scan path (incl. the aux loss
    riding the functional scan as ys). Dropout is keyed differently than
    flax's lifted split, so the assertable properties are nn.Dropout's
    contract: same (seed, step) -> identical trajectory, and the masks
    actually bite."""
    import numpy as np
    from tests.test_train_smoke import run_steps
    from vitax.config import Config

    kw = dict(image_size=32, patch_size=8, embed_dim=32, num_heads=4,
              num_blocks=4, num_classes=4, batch_size=16, dtype="float32",
              fsdp_size=-1, warmup_steps=0, grad_ckpt=True)
    if variant == "moe":
        kw.update(moe_experts=4, moe_top_k=2)
        _, losses_w = run_steps(Config(remat_window=2, **kw).validate(),
                                n_steps=3)
        _, losses_ref = run_steps(Config(**kw).validate(), n_steps=3)
        assert all(np.isfinite(losses_w))
        np.testing.assert_allclose(losses_w, losses_ref, rtol=2e-4)
        # and on the expert-sharded mesh: the windowed functional scan's
        # block.apply carries the same dispatch/token anchors, so ep
        # sharding must not change the trajectory either
        kw_ep = {**kw, "fsdp_size": 2, "dp_size": 2}
        _, losses_ep = run_steps(
            Config(remat_window=2, ep_size=2, **kw_ep).validate(), n_steps=3)
        np.testing.assert_allclose(losses_ep, losses_ref, rtol=2e-4)
    elif variant == "sp":
        # ring sequence parallelism: the windowed functional scan applies
        # the same shard_map'd attention impl the nn.scan path uses — the
        # sp trajectory must match the scan path's exactly
        kw_sp = {**kw, "fsdp_size": 2, "dp_size": 2, "sp_size": 2}
        _, losses_w = run_steps(Config(remat_window=2, **kw_sp).validate(),
                                n_steps=3)
        _, losses_ref = run_steps(Config(**kw_sp).validate(), n_steps=3)
        assert all(np.isfinite(losses_w))
        np.testing.assert_allclose(losses_w, losses_ref, rtol=2e-4)
    else:
        drop = dict(att_dropout=0.2, mlp_dropout=0.1, pos_dropout=0.1)
        from tests.test_train_smoke import build_train_objects, fresh
        cfg_w = Config(remat_window=2, **kw, **drop).validate()
        mesh, state, step_fn, eval_fn = build_train_objects(cfg_w)
        _, l1 = run_steps(cfg_w, n_steps=3,
                          built=(mesh, fresh(state), step_fn, eval_fn))
        _, l2 = run_steps(cfg_w, n_steps=3,
                          built=(mesh, state, step_fn, eval_fn))
        assert all(np.isfinite(l1))
        np.testing.assert_array_equal(l1, l2)  # deterministic given seed
        _, l0 = run_steps(Config(remat_window=2, **kw).validate(), n_steps=3)
        assert l1 != l0, "dropout had no effect under the windowed scan"


# --- what per-block remat keeps of the attention kernel ----------------------

def kernel_model(**kw):
    """A tiny dense model through the Pallas kernels (interpret mode), its
    parameters and a batch of two images."""
    from vitax.ops.attention import make_attention_impl
    cfg = tiny_cfg(embed_dim=32, num_classes=4, batch_size=2, **kw)
    impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
    model = build_model(cfg, attention_impl=impl)
    x = jax.random.normal(jax.random.key(1),
                          (2, cfg.image_size, cfg.image_size, 3), jnp.float32)
    params = jax.jit(model.init, static_argnums=2)(jax.random.key(0), x, True)
    return model, params, x, impl.vitax_name


def loss_and_grads(model):
    return jax.value_and_grad(
        lambda p, x: jnp.sum(model.apply(p, x, True) ** 2))


@pytest.mark.parametrize("image,patch,family", [
    (128, 4, "whole-N"),         # 1,024 tokens: the span where keeping starts
    (192, 4, "streaming"),       # 2,304 tokens: past the whole-N kernels
])
def test_dense_keeping_program_equals_the_recomputing_one(monkeypatch, image,
                                                          patch, family):
    """A dense ViT at a long sequence gets what the packed model gets: the
    kernel's o and lse are kept, and loss and every gradient leaf equal the
    recomputing program's bit for bit."""
    from vitax.models import vit
    model, params, x, name = kernel_model(image_size=image, patch_size=patch)
    assert family in name
    assert vit.attention_span(model) >= vit.ATTN_KEEP_MIN_SPAN
    assert vit.block_remat_policy(model) is vit._attention_kernel_saveable
    kept = jax.jit(loss_and_grads(model))(params, x)
    monkeypatch.setattr(vit, "ATTN_KEEP_MIN_SPAN", 1 << 30)
    assert vit.block_remat_policy(model) is None
    again = jax.jit(loss_and_grads(model))(params, x)
    assert np.isfinite(float(kept[0])) and float(kept[0]) > 0.0
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rule_does_not_engage_at_256_tokens(monkeypatch):
    """The benchmark's dense cells (256 tokens): the model takes
    `_REMAT_POLICIES` as it always did, and its lowered value_and_grad is the
    same text with this PR's policy function in place and without it."""
    from vitax.models import vit
    model, params, x, _ = kernel_model(image_size=64, patch_size=4)
    assert vit.attention_span(model) == 256
    assert not vit.keeps_attention_residuals(model)
    assert vit.block_remat_policy(model) is vit._REMAT_POLICIES["none_saveable"]
    from vitax.parallel.mesh import build_mesh
    from vitax.train.loop import _attention_remat_note
    cfg = tiny_cfg(image_size=64, patch_size=4, fsdp_size=1, dp_size=8)
    assert "runs its forward again (span 256 < 1024" in _attention_remat_note(
        cfg, model, build_mesh(cfg))
    with_rule = jax.jit(loss_and_grads(model)).lower(params, x).as_text()
    monkeypatch.setattr(vit, "block_remat_policy",
                        lambda m: vit._REMAT_POLICIES[m.remat_policy])
    without = jax.jit(loss_and_grads(model)).lower(params, x).as_text()
    assert with_rule == without


@pytest.mark.parametrize("policy,grad_ckpt,keeps", [
    ("none_saveable", True, True),
    ("dots_saveable", True, False), ("dots_attn_saveable", True, False),
    ("none_saveable", False, False),
])
def test_rule_leaves_the_other_policies_alone(policy, grad_ckpt, keeps):
    from vitax.models import vit
    cfg = tiny_cfg(image_size=128, patch_size=4, remat_policy=policy,
                   grad_ckpt=grad_ckpt)
    model = build_model(cfg, attention_impl=lambda q, k, v: q)
    assert vit.keeps_attention_residuals(model) is keeps
    if not keeps:
        assert vit.block_remat_policy(model) is vit._REMAT_POLICIES[policy]
    # no kernel, nothing to keep: the dense jnp core
    assert not vit.keeps_attention_residuals(build_model(cfg))


@pytest.mark.parametrize("arm", ["model", "overlap", "windowed", "pipeline"])
def test_group_forwards_keep_their_own_policy(devices8, monkeypatch, arm):
    """`make_overlap_forward`, `make_windowed_forward` and the pipeline body
    recompute a group inside their own backward: at a span where the model's
    own remat keeps the kernel's outputs they still consult
    `_REMAT_POLICIES[cfg.remat_policy]` and never this PR's policy; and a
    ZeRO-3 config at that span still arms `gather_overlap auto`."""
    from vitax.models import vit
    from vitax.parallel.sharding import gather_overlap_active
    from vitax.programs.builder import Geometry
    from vitax.train.loop import _attention_remat_note
    from vitax.train.step import _forward_fn

    asked = {"table": 0, "rule": 0}

    def table_policy(prim, *_, **__):
        asked["table"] += 1
        return False

    def rule_policy(prim, *_, **__):
        asked["rule"] += 1
        return False

    monkeypatch.setitem(vit._REMAT_POLICIES, "none_saveable", table_policy)
    monkeypatch.setattr(vit, "_attention_kernel_saveable", rule_policy)
    kw = {"model": dict(fsdp_size=1, dp_size=8),        # plain data parallel
          "overlap": dict(fsdp_size=-1),                # ZeRO-3, overlap auto
          "windowed": dict(fsdp_size=1, dp_size=8, remat_window=2),
          "pipeline": dict(pp_size=2, dp_size=4, fsdp_size=1)}[arm]
    cfg = tiny_cfg(image_size=128, patch_size=4, embed_dim=32, num_classes=4,
                   num_blocks=4, **kw)
    geom = Geometry.assemble(cfg, force_tpu_kernels=True)
    assert vit.keeps_attention_residuals(geom.model)
    assert gather_overlap_active(cfg, geom.mesh) is (arm == "overlap")
    forward = _forward_fn(cfg, geom.model, geom.mesh, geom.state_specs)
    images = jax.ShapeDtypeStruct((8, 128, 128, 3), jnp.float32)
    asked.update(table=0, rule=0)   # `model.init` went through the model's own
    jax.eval_shape(jax.grad(lambda p, x: jnp.sum(forward(p, x) ** 2)),
                   geom.abstract_state.params, images)
    if arm == "model":
        assert asked["rule"] > 0 and asked["table"] == 0
    else:
        assert asked["table"] > 0 and asked["rule"] == 0
    # the trainer's start-up line says which
    note = _attention_remat_note(cfg, geom.model, geom.mesh)
    assert ("keeps its o and lse (span 1024 >= 1024" in note) is (arm == "model")
    assert ("checkpoints groups of blocks itself" in note) is (arm != "model")
