"""The recurrent mixers' one-pass convolution (vitax/ops/conv.py) in interpret
mode at small shapes that tile (channels a multiple of 128, rows of 64
tokens): against the plain `conv_silu` of vitax/models/ssm.py (`causal_conv`,
silu, the padding's select and `l2norm` a head), which stays the oracle: y
and the gradients of x, the taps and the bias over rows of two documents and
padding, with and without a bias and the norm a head, a head of 128 lanes and
one of 96 (four to three lane tiles); bfloat16 rounded where the plain form
rounds it; which form `choose_kernels` chooses, that a program traces each
kernel body once, and that the kernels are found by name under the scopes
the mixers' metrics read."""

import collections
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_kda import segment_ids
from tests.test_kda_kernel import LATENT
from tests.test_ssd_kernel import _every_equation, _kernel_name, gap
from vitax.config import Config
from vitax.models import ssm as plain
from vitax.ops import conv as fused
from vitax.programs import kernels as programs
from vitax.programs.kernels import Kernels, choose_kernels, kernel_lines

T = 64
# two rows, each of two documents and padding (segment 0): 9 and 0 tokens
SEG = segment_ids([[30, 25], [17, 47]], T)
NAMES = ("x", "kernel", "bias")
# a head of 128 lanes is a lane tile; heads of 96 are normed four to three
# lane tiles, one a q, one a k, two halves of a v
NORMS = {"no_norm": None, "head_128": 128, "head_96": 96}

CASES = [(c, taps, bias, norm)
         for c, taps, bias, norm in itertools.product(
             (256, 384), (4, 2), (True, False), ("no_norm", "head_128"))
         # a third of 256 channels is no head of 128
         if not (c == 256 and norm == "head_128")] + [
    (384, 4, False, "head_96"), (384, 2, True, "head_96")]


def norm_of(channels, name):
    """q, k and v a third of the channels each (a head of 96 in 384: one
    head of q, one of k and v of 192, Olmo's proportions)."""
    head = NORMS[name]
    if head is None:
        return None
    third = channels // 3 if head == 128 else head
    return head, 2 * third, third


@functools.partial(jax.jit, static_argnames=(
    "channels", "taps", "bias", "dtype", "seed"))
def operands(channels, taps, bias, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed + channels + taps), 4)
    x = jax.random.normal(ks[0], SEG.shape + (channels,)).astype(dtype)
    kernel = plain.conv_init(ks[1], (taps, channels))
    b = 0.3 * jax.random.normal(ks[2], (channels,)) if bias else None
    return (x, kernel) + ((b,) if bias else ()), jax.random.normal(
        ks[3], x.shape)


def program(conv, operands, dtype, norm, seg=SEG):
    """The compiled `(weight, *ops) -> (y, the gradients of sum(y * weight)
    by each of the `operands` ops)`."""
    def total(weight, x, kernel, *bias):
        y = conv(x, seg, kernel, *(bias or (None,)), dtype, norm)
        return jnp.sum(y.astype(jnp.float32) * weight), y

    both = jax.jit(jax.value_and_grad(
        total, argnums=tuple(range(1, 1 + operands)), has_aux=True))

    def y_and_grads(weight, *ops):
        (_, y), grads = both(weight, *ops)
        return y, grads
    return y_and_grads


def value_and_grads(conv, ops, weight, dtype, norm, seg=SEG):
    return program(conv, len(ops), dtype, norm, seg)(weight, *ops)


@pytest.mark.parametrize("channels,taps,bias,norm", CASES)
def test_kernel_matches_the_plain_form(channels, taps, bias, norm):
    """float32 throughout: y and every gradient within 1e-5 of the plain
    form's norm, padding exactly zero in y and in the gradient of x."""
    norm = norm_of(channels, norm)
    ops, weight = operands(channels, taps, bias)
    want_y, want = value_and_grads(plain.conv_silu, ops, weight, jnp.float32,
                                   norm)
    got_y, got = value_and_grads(fused.conv_silu, ops, weight, jnp.float32,
                                 norm)
    assert float(jnp.abs(want_y).max()) > 1e-2
    assert got_y.dtype == jnp.float32 and gap(got_y, want_y) < 1e-5
    pad = np.asarray(SEG) == 0
    assert pad.sum() == 9
    assert float(np.abs(np.asarray(got_y)[pad]).max()) == 0.0
    for leaf, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, leaf
        assert gap(a, b) < 1e-5, leaf
    assert float(np.abs(np.asarray(got[0])[pad]).max()) == 0.0


@pytest.mark.parametrize("norm", list(NORMS))
def test_no_tap_crosses_a_documents_first_token(norm):
    """A document's outputs are no function of the document before it, and
    its inputs take no gradient from the document after it."""
    norm = norm_of(384, norm)
    ops, weight = operands(384, 4, True)
    first = (np.asarray(SEG) == 1)[..., None]
    other = (jnp.where(first, -ops[0], ops[0]),) + ops[1:]
    run = program(fused.conv_silu, len(ops), jnp.float32, norm)
    y, _ = run(weight, *ops)
    y_other, _ = run(weight, *other)
    second = np.asarray(SEG) == 2
    np.testing.assert_array_equal(np.asarray(y)[second],
                                  np.asarray(y_other)[second])
    assert np.abs(np.asarray(y - y_other)[first[..., 0]]).max() > 1e-2
    # only the second document's outputs weigh: nothing reaches the first's x
    _, grads = run(weight * second[..., None], *ops)
    assert float(np.abs(np.asarray(grads[0])[first[..., 0]]).max()) == 0.0
    assert float(np.abs(np.asarray(grads[0])[second]).max()) > 1e-2


@pytest.mark.parametrize("channels,bias,norm", [
    (256, True, "no_norm"), (384, False, "head_128"), (384, False, "head_96")])
def test_bfloat16_is_rounded_where_the_plain_form_rounds_it(channels, bias,
                                                            norm):
    """The projection in bfloat16, float32 inside, one rounding at the
    output: y is the plain form's to a unit in the last place here and there
    (a sum made in another order), and every gradient stands as near the
    float32 one as the plain form's does."""
    dtype = jnp.bfloat16
    norm = norm_of(channels, norm)
    ops, weight = operands(channels, 4, bias, dtype, seed=1)
    plain_y, plain_grads = value_and_grads(plain.conv_silu, ops, weight,
                                           dtype, norm)
    got_y, got = value_and_grads(fused.conv_silu, ops, weight, dtype, norm)
    _, want = value_and_grads(plain.conv_silu, ops, weight, jnp.float32,
                              norm)
    assert got_y.dtype == dtype and got[0].dtype == dtype
    differ = np.asarray(got_y != plain_y)
    assert differ.mean() < 2e-3 and gap(got_y, plain_y) < 2e-4
    for leaf, a, b, w in zip(NAMES, got, plain_grads, want):
        assert a.dtype == b.dtype, leaf
        assert gap(a, w) < max(1.2 * gap(b, w), 1e-5), (leaf, gap(b, w))


def test_rows_longer_than_a_block_carry_their_taps_over_its_edge(monkeypatch):
    """Blocks of 16 tokens in rows of 64: a tap reads the window's rows
    before the block, the backward the block after."""
    monkeypatch.setattr(fused, "ROW_BLOCK", 16)
    monkeypatch.setattr(fused, "LANE_BLOCK", 128)
    assert fused.conv_tiling(384, T, 4, (128, 256, 128)) == (128, 16)
    norm = (128, 256, 128)
    ops, weight = operands(384, 4, True, seed=2)
    want_y, want = value_and_grads(plain.conv_silu, ops, weight, jnp.float32,
                                   norm)
    got_y, got = value_and_grads(fused.conv_silu, ops, weight, jnp.float32,
                                 norm)
    assert gap(got_y, want_y) < 1e-5
    for leaf, a, b in zip(NAMES, got, want):
        assert gap(a, b) < 1e-5, leaf


# --- selection and attribution ------------------------------------------------

HYBRID = dict(
    model_family="decoder", embed_dim=32, num_blocks=2, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=["mamba", "mamba"],
    layer_heads=[0, 0], layer_mlps=["dense"] * 2, ffn_dim=48, norm_eps=1e-5,
    position_embedding="nope", ssm_heads=12, ssm_head_size=8,
    ssm_state_size=16, ssm_conv_width=4, ssm_groups=1, ssm_chunk=8,
    pack_tokens=32, pack_images=4, batch_size=1, dtype="float32")


@pytest.mark.parametrize("shape,why", [
    ((96, 64, 4), "96 channels are no multiple of 128"),
    ((256, 72, 4), "rows of 72 tokens"),
    ((256, 64, 9), "9 taps reach past"),
    ((256, 64, 4, (96, 192, 96)), "heads of 96 fill whole lane tiles 384 "
                                  "channels at a time"),
    ((640, 64, 4, (160, 320, 160)), "640 channels at a time, which 512 do "
                                    "not hold"),
    ((512, 2 ** 17, 4), "does not fit VMEM"),
])
def test_shapes_the_kernel_cannot_tile_say_why(shape, why):
    words = fused.conv_tiling(*shape)
    assert isinstance(words, str) and why in words


def test_selection_by_backend_and_by_shape(monkeypatch):
    cfg = Config(**HYBRID).validate()
    assert choose_kernels(cfg).conv is None             # the CPU, unforced
    assert kernel_lines(cfg, choose_kernels(cfg))[2] == (
        "mixer convolution: plain (no TPU)")
    impl = choose_kernels(cfg, None, force_tpu_kernels=True).conv
    assert impl.vitax_name == ("fused kernel (128 channels a grid step in "
                               "blocks of 32 tokens)")
    # a channel count that is no multiple of 128
    narrow = Config(**{**HYBRID, "ssm_heads": 8}).validate()
    chosen = choose_kernels(narrow, None, force_tpu_kernels=True)
    assert chosen.conv is None
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(programs, "backend_platform", lambda: "tpu")
        assert kernel_lines(narrow, chosen)[2] == (
            "mixer convolution: plain (96 channels are no multiple of 128)")
    # no recurrent layer
    none = Config(**{**HYBRID, "layer_kinds": ["attention"] * 2,
                     "layer_heads": [4, 4]}).validate()
    chosen = choose_kernels(none, None, force_tpu_kernels=True)
    assert chosen.conv is None
    assert len(kernel_lines(none, chosen)) == 1         # the attention core's
    # the three cells': the widest lanes that divide the channels and hold
    # whole heads in whole lane tiles, blocks of 128 tokens
    from tests.test_hybrid_decoder import GRANITE
    from tests.test_latent_decoder import LING
    from tests.test_olmo_decoder import OLMO
    for cell, words in (
            (GRANITE, "256 channels a grid step in blocks of 128 tokens)"),
            (LING, "512 channels a grid step in blocks of 128 tokens)"),
            (OLMO, "384 channels a grid step in blocks of 128 tokens)")):
        said = choose_kernels(Config(**cell).validate(), None,
                              True).conv.vitax_name
        assert said == "fused kernel (" + words
    monkeypatch.setattr(fused, "LANE_BLOCK", 256)
    assert fused.conv_tiling(6144, 4096, 4, (128, 4096, 2048)) == (256, 128)
    olmo = Config(**OLMO).validate()
    assert choose_kernels(olmo, None, True).conv is None
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")
    assert "heads of 96" in kernel_lines(olmo, Kernels())[-1]


def test_on_a_mesh_the_rows_are_shared_out_and_nothing_else_changes():
    """`choose_kernels` on a mesh of two devices: the kernels under
    `shard_map` over the batch axes, a row a device, the taps' gradient summed
    over them; y and every gradient are the unsharded kernels' (the taps' and
    the bias's to a sum's rounding)."""
    from vitax.parallel.mesh import build_mesh
    cfg = Config(**{**LATENT, "batch_size": 2, "pack_tokens": T}).validate()
    impl = choose_kernels(cfg, build_mesh(cfg, jax.devices()[:2]), True).conv
    assert impl.vitax_name.endswith("blocks of 64 tokens) + shard_map")
    norm = (128, 512, 256)
    ops, weight = operands(768, 4, True, seed=3)
    want_y, want = value_and_grads(fused.conv_silu, ops, weight, jnp.float32,
                                   norm)
    got_y, got = value_and_grads(impl, ops, weight, jnp.float32, norm)
    np.testing.assert_array_equal(got_y, want_y)
    np.testing.assert_array_equal(got[0], want[0])
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def _mixer_grad(mixer, tokens=T):
    u = jnp.ones((1, tokens, 32), jnp.float32)
    seg = segment_ids([[tokens // 2, tokens // 4]], tokens)
    variables = jax.eval_shape(mixer.init, jax.random.key(0), u, seg)
    return jax.grad(lambda v, u: jnp.sum(mixer.apply(v, u, seg))), variables, u


def _shifted_passes(equations):
    """The (row, tokens + j, channels) float32 pads of `causal_conv`."""
    return [v.aval.shape for e, inside in equations
            if not inside and e.primitive.name == "pad" for v in e.outvars
            if len(v.aval.shape) == 3 and v.aval.dtype == jnp.float32]


def _mixers(forced: bool):
    """The three recurrent mixers at shapes whose convolutions tile, with
    the chosen convolution or the plain one."""
    from vitax.models.kda import (GatedDeltaMixer, GatedDeltaShape, KDAMixer,
                                  KDAShape)
    hybrid = Config(**HYBRID).validate()
    conv = choose_kernels(hybrid, None, True).conv if forced else None
    return {
        "ssm_conv": plain.SSDMixer(
            plain.MixerShape(12, 8, 16, 4, 1, 8), 1e-5, jnp.float32,
            conv=conv),
        "kda_conv": KDAMixer(KDAShape(2, 128, 4, -5.0), 1e-5, jnp.float32,
                             conv=conv),
        "kda_conv (heads of 32)": GatedDeltaMixer(
            GatedDeltaShape(2, 32, 64, 4), 1e-5, jnp.float32, conv=conv)}


@pytest.mark.parametrize("name", list(_mixers(False)))
def test_the_scopes_a_metric_reads_are_in_the_lowered_fused_program(name):
    """`ssm_mixer_busy_pct` and `kda_mixer_busy_pct` join on `ssm_conv` and
    `kda_conv`: both kernels lie under the mixer's scope, forward and
    backward, and no float32 (tokens, channels) pad of a shifted pass is left
    outside them."""
    scope = name.split()[0]
    grad, variables, u = _mixer_grad(_mixers(True)[name])
    jaxpr = jax.make_jaxpr(grad)(variables, u).jaxpr
    kernels = collections.Counter(
        _kernel_name(e) for e, _ in _every_equation(jaxpr)
        if e.primitive.name == "pallas_call")
    assert kernels == {"conv_silu_fwd": 1, "conv_silu_bwd": 1}
    assert not _shifted_passes(_every_equation(jaxpr))
    # the jitted calls that hold them carry the scope in their name stack,
    # the backward's too: what becomes the compiled ops' `op_name` path
    # (tests/test_aot_tpu_compile.py holds the compiled text to it)
    stacks = {e.params["name"]: str(e.source_info.name_stack)
              for e, _ in _every_equation(jaxpr)
              if str(e.params.get("name", "")).startswith("_conv_")}
    assert sorted(stacks) == ["_conv_backward", "_conv_forward"]
    assert all(scope in stack.replace("(", "/").replace(")", "/").split("/")
               for stack in stacks.values()), stacks
    # the plain mixer: no kernel, and the shifted passes' pads
    grad, variables, u = _mixer_grad(_mixers(False)[name])
    equations = list(_every_equation(jax.make_jaxpr(grad)(variables,
                                                          u).jaxpr))
    assert not [e for e, _ in equations if e.primitive.name == "pallas_call"]
    assert _shifted_passes(equations)


@pytest.mark.parametrize("name", list(_mixers(False)))
def test_the_fused_mixer_equals_the_plain_mixer(name):
    """The whole layer either way: the same output, the same gradient of
    every leaf and of the input."""
    seg = segment_ids([[30, 25]], T)
    u = jax.random.normal(jax.random.key(1), (1, T, 32))
    w = jax.random.normal(jax.random.key(2), u.shape)
    mixers = [_mixers(forced)[name] for forced in (False, True)]
    variables = jax.jit(mixers[0].init)(jax.random.key(0), u, seg)
    want, got = (jax.jit(jax.value_and_grad(lambda v, u, m=m: jnp.sum(
        m.apply(v, u, seg) * w), argnums=(0, 1)))(variables, u)
        for m in mixers)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert gap(a, b) < 2e-4


def test_the_plain_mixers_have_no_kernel_and_the_model_the_text_it_had():
    """Off the TPU, unforced: `build_model_for` hands the mixers no
    convolution, and the model lowers to the text of one built without the
    argument."""
    from vitax.models import decoder
    from vitax.parallel.mesh import build_mesh
    from vitax.programs.builder import build_model_for
    cfg = Config(**HYBRID).validate()
    model = build_model_for(cfg, build_mesh(cfg, jax.devices()[:1]))
    assert model.kernels.conv is None
    batch = decoder.sample_documents(cfg, 1)
    variables = jax.eval_shape(model.init, jax.random.key(0), batch, True)

    def text(m):
        return jax.jit(lambda v, b: m.apply(v, b, True)).lower(
            variables, batch).as_text()

    assert text(model) == text(decoder.build_decoder(cfg))


def test_a_program_traces_each_kernel_body_once(monkeypatch):
    """Two runs of kda layers around an attention layer, remat on, under
    `jax.grad`: six sites call the kernels (each run's forward, its remat's
    forward and its backward), the init before them two more. `_conv_forward`
    and `_conv_backward` are `jax.jit`s and the rules trace under the primal's
    context, so Python runs each kernel's body once, and a module lowers a
    jaxpr once and calls it."""
    from vitax.models import decoder
    cfg = Config(**{**LATENT, "num_blocks": 3, "layer_mlps": ["dense"] * 3,
                    "layer_kinds": ["kda", "attention", "kda"],
                    "layer_heads": [2, 2, 2]}).validate()
    model = decoder.build_decoder(cfg, kernels=Kernels(
        conv=choose_kernels(cfg, None, True).conv))
    assert model.grad_ckpt and len(model.runs()) == 3
    ran = collections.Counter()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def body(*args, _body=getattr(fused, name), _name=name, **kwargs):
            ran[_name] += 1
            return _body(*args, **kwargs)
        monkeypatch.setattr(fused, name, body)
    fused._conv_forward.clear_cache()
    fused._conv_backward.clear_cache()
    batch = decoder.sample_documents(cfg, 1)
    variables = jax.eval_shape(model.init, jax.random.key(0), batch, True)
    traced = jax.jit(jax.grad(lambda v, b: jnp.sum(
        model.apply(v, b, True)))).trace(variables, batch)
    assert ran == {"_fwd_kernel": 1, "_bwd_kernel": 1}, ran
    sites, jaxprs = collections.Counter(), collections.defaultdict(set)
    for eqn, _ in _every_equation(traced.jaxpr.jaxpr):
        inner = eqn.params.get("jaxpr")
        for held in getattr(getattr(inner, "jaxpr", None), "eqns", ()):
            if held.primitive.name == "pallas_call":
                sites[_kernel_name(held)] += 1
                jaxprs[_kernel_name(held)].add(id(inner))
    assert sites == {"conv_silu_fwd": 4, "conv_silu_bwd": 2}, sites
    # a remat's partial evaluation makes the forward's jaxpr again with the
    # segment ids, which it knows, moved behind what it does not: two jaxprs
    # of one traced body, each lowered once and called twice
    assert {k: len(v) for k, v in jaxprs.items()} == {
        "conv_silu_fwd": 2, "conv_silu_bwd": 1}, jaxprs
    text = traced.lower().as_text()
    assert text.count("func.func private @_conv_backward") == 1
    assert text.count("call @_conv_backward") == 2
    assert text.count("func.func private @_conv_forward") == 2
    assert text.count("call @_conv_forward") == 4
