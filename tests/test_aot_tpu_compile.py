"""The main path's kernels, compiled for a DESCRIBED v5e chip with real Mosaic
lowering — the third rehearsal of the `on-chip-measurement` guide (section
2), kept as tests so every later PR is held to what the chip's compiler
accepts at no chip time.

Interpret mode (every other kernel test here) emulates the math but not the
lowering: tiling legality, block shapes, VMEM budgets. This file caught the
fused clip+AdamW kernel refusing every leaf wider than 8,192 (the 10B-width
fc1 and qkv), which no interpret-mode test could see.

A compile that passes is a compile: nothing runs, so nothing here says a
result is right or a kernel is fast. Skipped where the topology cannot be
described (no libtpu). The persistent compile cache is turned off around the
file: a described-topology entry cannot be read back without a chip.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep the compiler's logs out of /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

L14_ATTN = (32, 256, 16, 64)     # (B, N, H, Dh): ViT-L/14 at batch 32
TENB_ATTN = (8, 256, 32, 160)    # the 10B widths at the per-chip batch 8
LONG_ATTN = (1, 4096, 16, 64)    # past MAX_SEQ_IN_VMEM: the streaming kernel
PACKED_ATTN = (2, 8192, 16, 72)  # MoonViT-SO400M: 2 packed rows, head dim 72


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on one chip of a described v5e:2x2, plus the
    topology (its four devices build the fsdp=4 mesh)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile for
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return SingleDeviceSharding(topo.devices[0]), topo


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """The `_interpret()` seam (vitax/ops/attention.py): real Mosaic lowering
    although the host backend is the CPU."""
    monkeypatch.setenv("VITAX_FORCE_MOSAIC", "1")


def _kernel_names(compiled):
    """op_name of every tpu_custom_call in the compiled text."""
    return [ln.split('op_name="', 1)[1].split('"', 1)[0]
            for ln in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _attention(family):
    from vitax.ops.attention import flash_attention, flash_attention_4d
    from vitax.ops.flash_blocked import blocked_flash_attention
    return {"4d": (flash_attention_4d, "flash_4d"),
            "4d_qkv": (None, "flash_4d"),
            "bh": (flash_attention, "flash_bh"),
            "streaming": (blocked_flash_attention, "flash_blocked")}[family]


@pytest.mark.parametrize("family,shape", [
    ("4d", L14_ATTN), ("4d", TENB_ATTN),
    ("bh", L14_ATTN), ("bh", TENB_ATTN),
    ("streaming", LONG_ATTN),
    ("4d_qkv", L14_ATTN), ("4d_qkv", TENB_ATTN),
], ids=lambda v: v if isinstance(v, str) else "x".join(map(str, v)))
def test_attention_forward_and_vjp_compile(chip, mosaic, family, shape):
    """`4d_qkv` is the fused-qkv entry of the 4D kernels: one (B, N, 3D)
    operand, one (B, N, 3D) cotangent; at L14_ATTN one head group (a
    (1, N, 3D) block out), at TENB_ATTN eight (the cotangent written from
    the kernel's own VMEM slots)."""
    one_chip, _ = chip
    fn, name = _attention(family)
    b, n, h, dh = shape

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(fn, q, k, v)
        return o, vjp(o)

    def fwd_bwd_qkv(qkv):
        from vitax.ops.attention import flash_attention_qkv
        o, vjp = jax.vjp(lambda x: flash_attention_qkv(x, h), qkv)
        return o, vjp(o)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    if family == "4d_qkv":
        qkv = jax.ShapeDtypeStruct((b, n, 3 * h * dh), jnp.bfloat16,
                                   sharding=one_chip)
        compiled = jax.jit(fwd_bwd_qkv).lower(qkv).compile()
        # nothing but the two kernels touches an activation
        assert " copy(" not in compiled.as_text()
    else:
        compiled = jax.jit(fwd_bwd).lower(x, x, x).compile()
    kernels = _kernel_names(compiled)
    assert any(f"{name}_fwd" in k for k in kernels), kernels
    assert len(kernels) >= 2, kernels  # forward and at least one backward


def test_packed_attention_forward_and_vjp_compile(chip, mosaic):
    """The segment-masked streaming kernels at the MoonViT cell's shape: a
    72-wide block, scalar-prefetched block tables, 8 heads a grid step."""
    from vitax.ops.flash_blocked import packed_flash_attention
    one_chip, _ = chip

    def fwd_bwd(q, k, v, segment_ids):
        o, vjp = jax.vjp(
            lambda q, k, v: packed_flash_attention(q, k, v, segment_ids),
            q, k, v)
        return o, vjp(o)

    x = jax.ShapeDtypeStruct(PACKED_ATTN, jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct(PACKED_ATTN[:2], jnp.int32, sharding=one_chip)
    compiled = jax.jit(fwd_bwd).lower(x, x, x, seg).compile()
    kernels = _kernel_names(compiled)
    for name in ("flash_packed_fwd", "flash_packed_dkv", "flash_packed_dq"):
        assert any(name in k for k in kernels), kernels


@pytest.mark.parametrize(
    "tokens,heads,head_size,window,scale",
    [(8192, 48, 128, 0, 0.0), (8192, 64, 128, 512, 0.0),
     (4096, 32, 64, 0, 0.015625)],
    ids=["full_48q_8kv", "window512_64q_8kv", "nope_32q_8kv_dh64_scale"])
def test_document_attention_forward_and_vjp_compile(chip, mosaic, tokens,
                                                    heads, head_size, window,
                                                    scale):
    """The decoder's kernels at the Laguna-XS.2 cell's shapes (one row of
    8,192 tokens, head dim 128, 6 or 8 query heads a key/value head read
    inside the kernel, causal and window terms in mask and block table) and
    at the hybrid cell's (4,096 tokens, 4 query heads a key/value head of
    64, a score scale of its own)."""
    from vitax.ops.flash_blocked import document_flash_attention
    one_chip, _ = chip

    def fwd_bwd(q, k, v, segment_ids):
        o, vjp = jax.vjp(
            lambda q, k, v: document_flash_attention(
                q, k, v, segment_ids, window, scale=scale), q, k, v)
        return o, vjp(o)

    q = jax.ShapeDtypeStruct((1, tokens, heads, head_size), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, tokens, 8, head_size), jnp.bfloat16,
                              sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fwd_bwd).lower(q, kv, kv, seg).compile()
    kernels = _kernel_names(compiled)
    name = "flash_window" if window else "flash_causal"
    for part in ("fwd", "dkv", "dq"):
        assert any(f"{name}_{part}" in k for k in kernels), kernels
    assert not any("flash_packed" in k for k in kernels), kernels


@pytest.mark.parametrize("mode", ["int8", "fp8", "int8_act"])
@pytest.mark.parametrize("mkn", [
    (8 * 257, 1024, 4096),       # ViT-L/14 fc1 at the largest serve bucket
    (8 * 257, 5120, 20480),      # the 10B widths' fc1
], ids=["l14_fc1", "10b_fc1"])
def test_dequant_matmul_compiles(chip, mode, mkn):
    from vitax.ops.dequant_matmul import DEQUANT_KERNEL_NAME, dequant_matmul
    one_chip, _ = chip
    m, k, n = mkn
    # the export's own fp8: ml_dtypes' IEEE-style float8_e4m3, which Mosaic
    # refused to load until the kernel reinterpreted it as e4m3fn
    w_dtype = jnp.float8_e4m3 if mode == "fp8" else jnp.int8
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), w_dtype, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip)
    fn = functools.partial(dequant_matmul, act=mode == "int8_act",
                           fused=True, interpret=False)
    compiled = jax.jit(fn).lower(x, w, s).compile()
    assert any(DEQUANT_KERNEL_NAME in k for k in _kernel_names(compiled))


def _compile_leaf_update(shape, one_chip):
    from vitax.ops.fused_optimizer import (FUSED_KERNEL_NAME,
                                           _local_leaf_update)
    from vitax.train.state import ADAMW_HPARAMS
    hparams = (ADAMW_HPARAMS["b1"], ADAMW_HPARAMS["b2"],
               ADAMW_HPARAMS["eps"], 0.1)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    scal = jax.ShapeDtypeStruct((1, 4), jnp.float32, sharding=one_chip)
    fn = functools.partial(_local_leaf_update, hparams=hparams,
                           interpret=False)
    compiled = jax.jit(fn, donate_argnums=(0, 2, 3)).lower(
        leaf, leaf, leaf, leaf, scal).compile()
    assert any(FUSED_KERNEL_NAME in k for k in _kernel_names(compiled))


@pytest.mark.parametrize("shape", [
    (1024, 4096), (24, 1024, 3072), (1024,), (1000,), (1, 257, 1024),
    # last dimension over 8,192: the row block fell to 3 and 4 rows, which
    # the TPU lowering refuses — the trainer at --embed_dim 5120 died in
    # compilation before the fix
    (5120, 20480), (2, 5120, 15360),
    # the shapes tools/check_kernels_on_chip.py runs on the chip
    (2, 37, 96), (70_000, 8), (),
], ids=lambda s: "x".join(map(str, s)) or "scalar")
def test_fused_adamw_leaf_update_compiles(chip, shape):
    _compile_leaf_update(shape, chip[0])


def _state_leaf_shapes(topo):
    """Every distinct leaf shape of the ViT-L/14 and 10B-width parameter
    trees — stacked (scan_blocks) and unstacked, whole and as the local shard
    of an fsdp=4 mesh."""
    from chip_smoke import MODELS
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import param_specs

    shapes = set()
    for model in ("l14", "10b_width"):
        for scan in (True, False):
            cfg = Config(batch_size=4, scan_blocks=scan,
                         **MODELS[model]).validate()
            mesh = build_mesh(cfg, devices=list(topo.devices))
            net = build_model(cfg)
            x = jax.ShapeDtypeStruct(
                (1, cfg.image_size, cfg.image_size, 3), jnp.float32)
            params = jax.eval_shape(
                lambda k, im: net.init(k, im, True), jax.random.key(0), x)
            specs = param_specs(params, cfg, mesh)
            for leaf, spec in zip(
                    jax.tree.leaves(params),
                    jax.tree.leaves(specs, is_leaf=lambda s: isinstance(
                        s, jax.sharding.PartitionSpec))):
                shapes.add(tuple(leaf.shape))
                shapes.add(tuple(
                    d // (mesh.shape[ax] if isinstance(ax, str) else 1)
                    for d, ax in zip(leaf.shape,
                                     tuple(spec) + (None,) * leaf.ndim)))
    return sorted(shapes)


def test_fused_adamw_every_state_leaf_compiles(chip):
    """`--fused_optimizer auto` is on for every leaf on a TPU, so every leaf
    must get a block the lowering accepts: checked on the block shape for
    all of them, and by compiling each distinct 2-D view."""
    from vitax.ops.fused_optimizer import _as_2d, _block_shape
    one_chip, topo = chip
    shapes = _state_leaf_shapes(topo)
    # the wide leaves, stacked and unstacked, and an fsdp=4 shard of one
    assert {(5120, 20480), (2, 5120, 15360), (2, 5120, 3840)} <= set(shapes)
    views = sorted({_as_2d(s) for s in shapes})
    for m, n in views:
        bm, bn = _block_shape(m, n)
        assert bm == m or bm % 8 == 0, (m, n, bm, bn)
        assert bn == n or bn % 128 == 0, (m, n, bm, bn)
        assert bm * (-(-bn // 128) * 128) <= 64 * 1024, (m, n, bm, bn)
    for view in views:
        _compile_leaf_update(view, one_chip)


@pytest.mark.parametrize("tokens,forward_sites", [(512, 2), (1024, 1)])
def test_packed_step_forward_kernel_call_sites(chip, mosaic, tokens,
                                               forward_sites):
    """The lowered value_and_grad of a small packed model, kernels as real
    Mosaic custom calls: from ATTN_KEEP_MIN_SPAN tokens of row on the per-block
    remat keeps the forward kernel's outputs and the whole program holds one
    `flash_packed_fwd` call site (the forward scan's); below it the backward
    scan's body holds a second. The chip's compiler takes both."""
    from vitax.config import Config
    from vitax.models.vit import (ATTN_KEEP_MIN_SPAN, build_model,
                                  sample_input)
    from vitax.ops.attention import make_attention_impl
    one_chip, _ = chip
    assert 512 < ATTN_KEEP_MIN_SPAN <= 1024
    cfg = Config(embed_dim=64, num_heads=4, num_blocks=2, mlp_dim=100,
                 patch_size=4, num_classes=10, pack_tokens=tokens,
                 pack_images=4, max_image_tokens=64, pos_grid=8, batch_size=2,
                 fsdp_size=1, fake_data=True).validate()
    model = build_model(cfg, attention_impl=make_attention_impl(
        cfg, None, force_tpu_kernels=True))
    x = sample_input(cfg, 2)
    params = jax.eval_shape(lambda k: model.init(k, x, True),
                            jax.random.key(0))
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        tree)
    lowered = jax.jit(jax.value_and_grad(
        lambda p, b: jnp.sum(model.apply(p, b, True) ** 2))).lower(
            on_chip(params), on_chip(x))
    text = lowered.as_text()
    assert text.count('kernel_name = "flash_packed_fwd"') == forward_sites
    assert text.count('kernel_name = "flash_packed_dkv"') == 1
    assert text.count('kernel_name = "flash_packed_dq"') == 1
    kernels = _kernel_names(lowered.compile())
    assert sum("flash_packed_fwd" in k for k in kernels) == forward_sites


def test_state_space_scan_compiles_and_lies_under_its_scopes(chip, mosaic):
    """The fused scan (vitax/ops/ssd.py) through the mixer, at the hybrid
    cell's shape (1 x 4,096 tokens, 64 heads of 64, one group, state 128,
    chunk 256): real Mosaic lowering and compile of forward and backward, and
    every `ssd_*` custom call of the compiled text has `ssd_chunk` or
    `ssd_state` in its `op_name` path: the join `benchmark/scopes.py:index`
    makes for `ssd_roofline` and `ssm_mixer_busy_pct`."""
    import re

    from vitax.config import Config
    from vitax.models.ssm import MixerShape, SSDMixer
    from vitax.programs.kernels import choose_kernels
    one_chip, _ = chip
    cfg = Config(
        model_family="decoder", embed_dim=2048, num_blocks=1, vocab_rows=128,
        kv_heads=8, head_size=64, layer_kinds=["mamba"], layer_heads=[0],
        layer_mlps=["dense"], ffn_dim=128, ssm_heads=64, ssm_head_size=64,
        ssm_state_size=128, ssm_conv_width=4, ssm_groups=1, ssm_chunk=256,
        pack_tokens=4096, pack_images=4, batch_size=1).validate()
    scan = choose_kernels(cfg, None, force_tpu_kernels=True).scan
    assert scan.vitax_name == "fused kernel (chunk 256, 16 heads a grid step)"
    mixer = SSDMixer(MixerShape(64, 64, 128, 4, 1, 256), 1e-5, jnp.bfloat16,
                     scan=scan)
    u = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), u, seg))
    compiled = jax.jit(jax.grad(lambda p, u, seg: jnp.sum(
        mixer.apply(p, u, seg).astype(jnp.float32)), argnums=(0, 1))).lower(
            params, u, seg).compile()
    kernels = [k for k in _kernel_names(compiled) if "/ssd_" in k]
    assert sorted(k.rsplit("/", 2)[-2] for k in kernels) == \
        ["ssd_bwd", "ssd_fwd"], kernels
    from benchmark import scopes
    text = compiled.as_text()
    found = scopes.index(text, ("ssd_chunk", "ssd_state"))
    calls = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", ln).group(1)
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln and "/ssd_" in ln]
    assert len(calls) == 2 and all(c in found for c in calls), (calls, kernels)


def test_delta_rule_compiles_and_lies_under_its_scope(chip, mosaic):
    """The fused delta rule (vitax/ops/kda.py) through the mixer, at the Ling
    cell's shape (1 x 4,096 tokens, 16 heads of 128, chunks of 64 in
    sub-chunks of 16): real Mosaic lowering and compile of forward and
    backward, and every `kda_*` custom call of the compiled text has
    `kda_chunk` in its `op_name` path: the join `benchmark/scopes.py:index`
    makes for `kda_roofline` and `kda_mixer_busy_pct`."""
    import re

    from vitax.config import Config
    from vitax.models.kda import KDAMixer, KDAShape
    from vitax.ops.kda import HEADS_PER_STEP
    from vitax.programs.kernels import choose_kernels
    one_chip, _ = chip
    cfg = Config(
        model_family="decoder", embed_dim=2048, num_blocks=1, vocab_rows=128,
        kv_heads=8, head_size=128, layer_kinds=["kda"], layer_heads=[16],
        layer_mlps=["dense"], ffn_dim=128, kda_conv_width=4,
        kda_gate_bound=-5.0, pack_tokens=4096, pack_images=4,
        batch_size=1).validate()
    rule = choose_kernels(cfg, None, force_tpu_kernels=True).rule
    assert rule.vitax_name == (f"fused kernel (chunk 64, sub-chunks of 16, "
                               f"{HEADS_PER_STEP} heads a grid step)")
    mixer = KDAMixer(KDAShape(16, 128, 4, -5.0), 1e-6, jnp.bfloat16,
                     rule=rule)
    u = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), u, seg))
    compiled = jax.jit(jax.grad(lambda p, u, seg: jnp.sum(
        mixer.apply(p, u, seg).astype(jnp.float32)), argnums=(0, 1))).lower(
            params, u, seg).compile()
    kernels = [k for k in _kernel_names(compiled) if "/kda_" in k]
    assert sorted(k.rsplit("/", 2)[-2] for k in kernels) == \
        ["kda_bwd", "kda_fwd"], kernels
    from benchmark import scopes
    text = compiled.as_text()
    found = scopes.index(text, ("kda_chunk", "kda_state"))
    calls = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", ln).group(1)
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln and "/kda_" in ln]
    assert len(calls) == 2 and all(found.get(c) == "kda_chunk"
                                   for c in calls), (calls, kernels)


def test_mixer_convolution_compiles_and_lies_under_its_scope(chip, mosaic):
    """The one-pass convolution (vitax/ops/conv.py) through the state-space
    mixer at the hybrid cell's shape (1 x 4,096 tokens, 4,352 channels with a
    bias, no norm): real Mosaic lowering and compile of forward and backward,
    and both `conv_silu_*` custom calls of the compiled text have `ssm_conv`
    in their `op_name` path: the join `benchmark/scopes.py:index` makes for
    `ssm_mixer_busy_pct`. The delta mixers' shapes (6,144 channels with 32
    heads of 128 normed, 5,760 with 30 heads of 96 normed four to three lane
    tiles) compile in the Ling and Olmo cells' whole steps below, under
    `kda_conv`."""
    import re

    from vitax.config import Config
    from vitax.models.ssm import MixerShape, SSDMixer
    from vitax.programs.kernels import choose_kernels
    one_chip, _ = chip
    cfg = Config(
        model_family="decoder", embed_dim=2048, num_blocks=1, vocab_rows=128,
        kv_heads=8, head_size=64, layer_kinds=["mamba"], layer_heads=[0],
        layer_mlps=["dense"], ffn_dim=128, ssm_heads=64, ssm_head_size=64,
        ssm_state_size=128, ssm_conv_width=4, ssm_groups=1, ssm_chunk=256,
        pack_tokens=4096, pack_images=4, batch_size=1).validate()
    conv = choose_kernels(cfg, None, force_tpu_kernels=True).conv
    assert conv.vitax_name == ("fused kernel (256 channels a grid step in "
                               "blocks of 128 tokens)")
    mixer = SSDMixer(MixerShape(64, 64, 128, 4, 1, 256), 1e-5, jnp.bfloat16,
                     conv=conv)
    u = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), u, seg))
    compiled = jax.jit(jax.grad(lambda p, u, seg: jnp.sum(
        mixer.apply(p, u, seg).astype(jnp.float32)), argnums=(0, 1))).lower(
            params, u, seg).compile()
    kernels = [k for k in _kernel_names(compiled) if "/conv_silu_" in k]
    assert sorted(k.rsplit("/", 2)[-2] for k in kernels) == \
        ["conv_silu_bwd", "conv_silu_fwd"], kernels
    from benchmark import scopes
    text = compiled.as_text()
    found = scopes.index(text, ("ssm_conv", "kda_conv"))
    calls = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", ln).group(1)
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "/conv_silu_" in ln]
    assert len(calls) == 2 and all(found.get(c) == "ssm_conv"
                                   for c in calls), (calls, kernels)


def test_latent_attention_forward_and_vjp_compile(chip, mosaic):
    """The packed causal kernels at the Ling cell's latent layer: one row of
    4,096 tokens, 16 heads each with a key of its own, q and k 192 wide
    (128 + the shared rotated 64) and v 128: real Mosaic lowering of
    `flash_latent_*`, the output and dV at v's width."""
    from vitax.ops.flash_blocked import document_flash_attention
    one_chip, _ = chip

    def fwd_bwd(q, k, v, segment_ids):
        o, vjp = jax.vjp(lambda q, k, v: document_flash_attention(
            q, k, v, segment_ids), q, k, v)
        return o, vjp(o)

    qk = jax.ShapeDtypeStruct((1, 4096, 16, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    compiled = jax.jit(fwd_bwd).lower(qk, qk, v, seg).compile()
    kernels = _kernel_names(compiled)
    for part in ("fwd", "dkv", "dq"):
        assert any(f"flash_latent_{part}" in k for k in kernels), kernels
    o, (dq, dk, dv) = jax.eval_shape(fwd_bwd, qk, qk, v, seg)
    assert o.shape == dv.shape == v.shape and dq.shape == dk.shape == qk.shape


def test_the_ling_cells_step_compiles_and_fits(chip, mosaic):
    """The whole train step of `ling3_flash_vl_ep64tp2_train_packed4k` at the
    published widths (seven layers, 648.9M parameters with Adam's state, one
    row of 4,096 tokens), through the cell's own `lower_described`: the
    chip's compiler takes it, it fits the 15.75 GB the compiler allows, the
    latent layer's three kernels, the delta rule's two (a forward a layer's
    forward and its remat, a backward), the mixers' convolution's two under
    `kda_conv` and the fused optimizer are in it, and
    the delta rule lies under the scope `kda_roofline` reads (`kda_chunk`:
    the fused form has no `kda_state` of its own)."""
    import re

    from benchmark import harness, scopes
    from benchmark import manifest as mf
    _, topo = chip
    man = mf.Manifest()
    cell = man.cell("ling3_flash_vl_ep64tp2_train_packed4k")
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    gen = mf.generator(traffic["kind"])
    lowered, what = gen.lower_described(man.config_kwargs(config), traffic,
                                        list(topo.devices)[:1])
    assert what == "decoder train step, 1 rows of 4096 tokens"
    # three runs of kda layers, each with a forward, a remat's forward and a
    # backward: nine sites, and the module holds the backward kernel once and
    # the forward once a set of outputs (with the states the backward reads,
    # and without), each behind a `func.call`: vitax/ops/kda.py's `jax.jit`s
    text = lowered.as_text()
    assert sorted(re.findall(r'kernel_name = "(kda_\w+)"', text)) == [
        "kda_bwd", "kda_fwd", "kda_fwd"]
    assert len(re.findall(r"call @_backward\w*\(", text)) == 3
    del text
    compiled = lowered.compile()
    step_bytes = harness.program_facts(compiled)["step_bytes"]
    assert 0.25 * 16.909e9 < step_bytes <= 15.75e9, step_bytes
    kernels = _kernel_names(compiled)
    for part in ("fwd", "dkv", "dq"):
        assert any(f"flash_latent_{part}" in k for k in kernels), kernels
    assert any("fused_adamw" in k for k in kernels)
    for part in ("kda_fwd", "kda_bwd"):
        assert any("kda_chunk" in k and f"/{part}/" in k
                   for k in kernels), kernels
    for part in ("conv_silu_fwd", "conv_silu_bwd"):     # vitax/ops/conv.py
        assert any("kda_conv" in k and f"/{part}/" in k
                   for k in kernels), kernels
    found = set(scopes.index(compiled.as_text(), gen.SCOPES).values())
    assert {"kda_conv", "kda_gate", "kda_chunk", "kda_out_norm",
            "mla_latent", "moe_route", "moe_dispatch", "expert_ffn",
            "moe_combine", "shared_expert"} <= found, found


def test_the_olmo_hybrid_cells_step_compiles_and_fits(chip, mosaic):
    """The whole train step of `olmo_hybrid_7b_tp2vp8_train_packed4k` at the
    published widths (four layers, 766.2M parameters with Adam's state, one
    row of 4,096 tokens), through the cell's own `lower_described`: the
    chip's compiler takes it, it fits the 15.75 GB the compiler allows, the
    attention layer's three `flash_causal_*` kernels and the fused optimizer
    are in it and NO `kda_fwd` (a 96 x 192 state under one decay a head is
    none the delta rule's kernels tile: the plain form runs), and the delta
    rule, the norms after and the QK-norm lie under the scopes the cell's
    readers read."""
    from benchmark import harness, scopes
    from benchmark import manifest as mf
    _, topo = chip
    man = mf.Manifest()
    cell = man.cell("olmo_hybrid_7b_tp2vp8_train_packed4k")
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    gen = mf.generator(traffic["kind"])
    lowered, what = gen.lower_described(man.config_kwargs(config), traffic,
                                        list(topo.devices)[:1])
    assert what == "decoder train step, 1 rows of 4096 tokens"
    compiled = lowered.compile()
    step_bytes = harness.program_facts(compiled)["step_bytes"]
    assert 0.25 * 16.909e9 < step_bytes <= 15.75e9, step_bytes
    kernels = _kernel_names(compiled)
    # one attention layer, a run of one, where the compiler merges the
    # remat's forward kernel with the first: each kernel once
    import re
    assert sorted(re.search(r"flash_causal_\w+", k).group() for k in kernels
                  if "flash_" in k) == [
        "flash_causal_dkv", "flash_causal_dq", "flash_causal_fwd"], kernels
    assert any("fused_adamw" in k for k in kernels)
    assert not any("kda_fwd" in k or "kda_bwd" in k for k in kernels)
    for part in ("conv_silu_fwd", "conv_silu_bwd"):     # vitax/ops/conv.py
        assert any("kda_conv" in k and f"/{part}/" in k
                   for k in kernels), kernels
    found = set(scopes.index(compiled.as_text(), gen.SCOPES).values())
    assert {"kda_conv", "kda_gate", "kda_chunk", "kda_state", "kda_out_norm",
            "post_norm", "qk_norm", "lm_head_loss"} <= found, found


def test_the_lfm2_moe_cells_step_compiles_and_fits(chip, mosaic):
    """The whole train step of `lfm2_24b_a2b_ep8_train_packed8k` at the
    published widths (five layers, 469.3M parameters with Adam's state, two
    rows of 8,192 tokens), through the cell's own `lower_described`: the
    chip's compiler takes it, it fits the 15.75 GiB the compiler allows and
    fills over 70% of it, the attention layer's three `flash_causal_*`
    kernels and the fused optimizer are in it and NO `conv_silu_*` (the gated
    convolution has no activation: the plain form runs), and the mixer, the
    norm a head and the expert layer lie under the scopes the cell's readers
    read."""
    import re

    from benchmark import harness, scopes
    from benchmark import manifest as mf
    _, topo = chip
    man = mf.Manifest()
    cell = man.cell("lfm2_24b_a2b_ep8_train_packed8k")
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    gen = mf.generator(traffic["kind"])
    lowered, what = gen.lower_described(man.config_kwargs(config), traffic,
                                        list(topo.devices)[:1])
    assert what == "decoder train step, 2 rows of 8192 tokens"
    compiled = lowered.compile()
    step_bytes = harness.program_facts(compiled)["step_bytes"]
    assert 0.7 * 16.909e9 < step_bytes <= 16.909e9, step_bytes
    kernels = _kernel_names(compiled)
    # one attention layer, a run of one, where the compiler merges the
    # remat's forward kernel with the first: each kernel once
    assert sorted(re.search(r"flash_causal_\w+", k).group() for k in kernels
                  if "flash_" in k) == [
        "flash_causal_dkv", "flash_causal_dq", "flash_causal_fwd"], kernels
    assert any("fused_adamw" in k for k in kernels)
    assert not any("conv_silu" in k for k in kernels)
    found = set(scopes.index(compiled.as_text(), gen.SCOPES).values())
    assert {"gconv_in", "gconv", "gconv_out", "qk_norm", "rope1d",
            "moe_route", "moe_dispatch", "expert_ffn", "moe_combine",
            "lm_head_loss"} <= found, found


def test_the_smallthinker_cells_step_compiles_and_fits(chip, mosaic):
    """The whole train step of `smallthinker_21b_a3b_ep8_train_longrow` at
    the published widths (four layers, 370.5M parameters with Adam's state,
    one row of 16,384 tokens), through the cell's own `lower_described`: the
    chip's compiler takes both attention kernels at 7 query heads a key/value
    head (28 over 4 of 128) and a window of 4,096, the step fits the 15.75
    GiB the compiler allows and fills over half of it, the fused optimizer is
    in it, and the early router, the ReGLU loops and the sliding kind's
    rotation lie under the scopes the cell's readers read. The full layer's
    run holds ONE `flash_causal_fwd` and the sliding run of three ONE
    `flash_window_fwd`, so no backward runs a forward kernel again: the
    remat of the run of three keeps the kernel's o and lse (span 4,096), and
    in the run of one the compiler merges the second forward with the first
    (`run_remat_policy`; tests/test_smallthinker_decoder.py holds the trace's
    side; PR 52, ROADMAP A24)."""
    import re

    from benchmark import harness, scopes
    from benchmark import manifest as mf
    _, topo = chip
    man = mf.Manifest()
    cell = man.cell("smallthinker_21b_a3b_ep8_train_longrow")
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    gen = mf.generator(traffic["kind"])
    lowered, what = gen.lower_described(man.config_kwargs(config), traffic,
                                        list(topo.devices)[:1])
    assert what == "decoder train step, 1 rows of 16384 tokens"
    compiled = lowered.compile()
    step_bytes = harness.program_facts(compiled)["step_bytes"]
    assert 0.5 * 16.909e9 < step_bytes <= 16.909e9, step_bytes
    kernels = _kernel_names(compiled)
    attention = [re.search(r"flash_(causal|window)_\w+", k).group()
                 for k in kernels if "flash_" in k]
    assert sorted(set(attention)) == [
        "flash_causal_dkv", "flash_causal_dq", "flash_causal_fwd",
        "flash_window_dkv", "flash_window_dq", "flash_window_fwd"], kernels
    assert [attention.count(f"flash_{k}_{part}")
            for k in ("causal", "window") for part in ("fwd", "dq")] \
        == [1, 1, 1, 1], kernels
    assert any("fused_adamw" in k for k in kernels)
    # 7 query heads a grid step: the kernels' q block holds a key/value
    # head's whole group
    text = compiled.as_text()
    assert "bf16[1,16384,28,128]" in text and "bf16[1,16384,4,128]" in text
    found = set(scopes.index(text, gen.SCOPES).values())
    assert {"rope1d", "moe_route", "moe_dispatch", "expert_ffn",
            "moe_combine", "lm_head_loss"} <= found, found


def test_the_four_chip_cells_gradients_leave_through_the_ring(chip, mosaic):
    """The whole train step of `vit10b_fsdp4_train_b8` (ZeRO-3 over the four
    described chips, the 10B widths), through the cell's own
    `lower_described`: the backward scan's body holds NO block-sized
    synchronous reduce (the parent's four `fusion kind=kCustom
    calls=%all-reduce-scatter`, which no option of this compiler runs
    asynchronously: PERF.md, PR 50) and the ring's `fsdp - 1` = 3
    `collective-permute-start`s for each of the four block matrices and
    each of the two directions, on a bfloat16 wire; the forward body holds
    neither; the program fits the
    15.75 GiB the compiler allows and stays under the 12.6 GB ISSUE 50
    holds `step_hbm_gb` to."""
    from benchmark import harness
    from benchmark import manifest as mf
    from vitax.analysis import hlo
    _, topo = chip
    man = mf.Manifest()
    cell = man.cell("vit10b_fsdp4_train_b8")
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    lowered, _ = mf.generator(traffic["kind"]).lower_described(
        man.config_kwargs(config), traffic, list(topo.devices)[:cell["chips"]])
    compiled = lowered.compile()
    text = compiled.as_text()
    width, fsdp = config["embed_dim"], cell["chips"]
    verdict = hlo.overlap_verdict(text, min_reduce_numel=width * width // fsdp)
    forward, backward = list(verdict["ring_permutes_by_body"])
    assert verdict["sync_block_reduces"] == 0, verdict
    assert verdict["ring_permutes_by_body"] == {
        forward: 0, backward: 2 * 4 * (fsdp - 1)}, verdict
    wires = [ln.split(" = ", 1)[1].split(" collective-permute-start(")[0]
             for ln in hlo.split_computations(text)[backward]
             if " collective-permute-start(" in ln]
    assert len(wires) == 2 * 4 * (fsdp - 1), wires
    assert all(w.lstrip("(").startswith("bf16[") for w in wires), wires
    step_bytes = harness.program_facts(compiled)["step_bytes"]
    assert step_bytes <= 12.6e9 < 16.909e9, step_bytes
