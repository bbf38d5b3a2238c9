"""The fused delta rule (vitax/ops/kda.py) in interpret mode at small shapes
that tile (heads of 128, sub-chunks of 16): against the plain `kda`
(vitax/models/kda.py), which stays the oracle, and against the float32
token-by-token recurrence of the plain reference (tests/test_kda.py), o and
the gradients of q, k, v, g and beta, over layouts with a document boundary
inside a chunk, a chunk wholly of padding, a document over several chunks and
the gate at its bound; bfloat16 operands rounded where the plain form rounds
them; the inverse alone and its closed-form cotangent; which form
`choose_kernels` chooses, and that the chosen kernels are found by name, under
the scope a metric reads, with no (chunk, chunk) array left outside them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_kda import segment_ids, token_by_token
from tests.test_ssd_kernel import _every_equation, _kernel_name, gap
from vitax.config import Config
from vitax.models import kda as plain
from vitax.ops import kda as fused
from vitax.programs import kernels as programs
from vitax.programs.kernels import Kernels, choose_kernels, kernel_lines

BOUND = -5.0
H, D = 2, 128
NAMES = ("q", "k", "v", "g", "beta")

# name: (rows of document lengths, tokens a row, chunk, sub)
LAYOUTS = {
    "a_boundary_inside_a_chunk": ([[45, 50, 20]], 128, 32, 16),
    "a_chunk_of_padding_only": ([[40, 30]], 128, 32, 16),
    "a_document_over_several_chunks": ([[10, 110, 8]], 128, 32, 16),
    "a_boundary_on_a_chunks_edge": ([[64, 33]], 128, 32, 16),
    "two_rows_chunks_of_64": ([[70, 100, 22], [192]], 192, 64, 16),
    "one_sub_chunk_a_chunk": ([[20, 44]], 64, 16, 16),
}
HEADS_A_STEP = {"a_boundary_inside_a_chunk": 2}     # the others 1


@functools.partial(jax.jit, static_argnames=("decay", "dtype", "seed"))
def operands(seg, decay="mixed", dtype=jnp.float32, seed=0):
    """q, k unit length a head (q times D ** -0.5), v, g in (BOUND, 0) and
    beta in (0, 1), zero at padding, as the mixer hands them over."""
    r, t = seg.shape
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (r, t, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (r, t, H, D)))
    v = jax.random.normal(ks[2], (r, t, H, D))
    z = jax.random.normal(ks[3], (r, t, H, D))
    g = BOUND * jax.nn.sigmoid({"mild": z - 2.0, "mixed": 3.0 * z,
                                "at_the_bound": z + 30.0}[decay])
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, t, H)))
    valid = (seg > 0)[..., None]
    q, k, v = (jnp.where(valid[..., None], x, 0.0).astype(dtype)
               for x in (q, k, v))
    return ((q, k, v, jnp.where(valid[..., None], g, 0.0),
             jnp.where(valid, beta, 0.0)),
            jax.random.normal(ks[5], (r, t, H, D)))


def value_and_grads(rule, seg, ops, weight, chunk, sub, dtype=jnp.float32):
    def total(*ops):
        o = rule(*ops, seg, chunk, sub, dtype)
        return jnp.sum(o * weight), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        total, argnums=tuple(range(5)), has_aux=True))(*ops)
    return o, grads


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_kernel_matches_the_plain_form(name, monkeypatch):
    """float32 throughout: o within 1e-5 of the plain form's norm, every
    gradient within 2e-4 (the inverse's takes the closed form)."""
    lengths, tokens, chunk, sub = LAYOUTS[name]
    monkeypatch.setattr(fused, "HEADS_PER_STEP", HEADS_A_STEP.get(name, 1))
    seg = segment_ids(lengths, tokens)
    ops, weight = operands(seg)
    want_o, want = value_and_grads(plain.kda, seg, ops, weight, chunk, sub)
    got_o, got = value_and_grads(fused.kda_fused, seg, ops, weight, chunk,
                                 sub)
    assert float(jnp.abs(want_o).max()) > 1e-2
    assert got_o.dtype == jnp.float32 and gap(got_o, want_o) < 1e-5
    assert float(jnp.abs(got_o * (seg == 0)[..., None, None]).max()) == 0.0
    for leaf, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, leaf
        assert gap(a, b) < 2e-4, leaf


@pytest.mark.parametrize("name,decay", [
    ("a_boundary_inside_a_chunk", "mixed"),
    ("a_boundary_inside_a_chunk", "at_the_bound"),
    ("a_chunk_of_padding_only", "at_the_bound"),
    ("two_rows_chunks_of_64", "at_the_bound")])
def test_kernel_matches_the_token_by_token_recurrence(name, decay):
    """Document by document against S_t = (I - b k k^T) Diag(e^g) S + b k v^T,
    o_t = S_t^T q_t in float32; the gate at its bound over whole sub-chunks
    (exponents of -5 * 16 / 2) stays finite."""
    lengths, tokens, chunk, sub = LAYOUTS[name]
    seg = segment_ids(lengths, tokens)
    ops, weight = operands(seg, decay, seed=1)
    if decay == "at_the_bound":
        assert float(jnp.min(ops[3])) < BOUND + 1e-6
    got_o, got = value_and_grads(fused.kda_fused, seg, ops, weight, chunk,
                                 sub)
    want_o, want = value_and_grads(lambda *a: token_by_token(*a[:6]), seg,
                                   ops, weight, chunk, sub)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-6)
    pad = np.asarray(seg) == 0
    for leaf, a, b in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(a)).all(), leaf
        assert gap(a, b) < 2e-4, leaf
        # nothing reaches padding but the rounding of terms that cancel
        assert float(np.abs(np.asarray(a)[pad]).max(initial=0.0)) < 1e-6, leaf


def test_bfloat16_operands_are_rounded_where_the_plain_form_rounds_them():
    """Same roundings, same o: a product rounded elsewhere would stand 1e-3
    off. Every gradient stands as near the float32 one as the plain form's
    does (within a fifth more), g's through the middles' rounding too."""
    lengths, tokens, chunk, sub = LAYOUTS["two_rows_chunks_of_64"]
    dtype = jnp.bfloat16
    seg = segment_ids(lengths, tokens)
    ops, weight = operands(seg, dtype=dtype, seed=2)
    plain_o, plain_grads = value_and_grads(plain.kda, seg, ops, weight, chunk,
                                           sub, dtype)
    got_o, got = value_and_grads(fused.kda_fused, seg, ops, weight, chunk,
                                 sub, dtype)
    exact = tuple(a.astype(jnp.float32) for a in ops)
    _, want = value_and_grads(plain.kda, seg, exact, weight, chunk, sub)
    assert got_o.dtype == jnp.float32
    assert gap(got_o, plain_o) < 1e-5
    for leaf, a, b, w in zip(NAMES, got, plain_grads, want):
        assert a.dtype == b.dtype, leaf
        assert gap(a, w) < max(1.2 * gap(b, w), 5e-3), (leaf, gap(b, w))


# --- the inverse alone --------------------------------------------------------

def lower_triangles(c, seed=0, n=3):
    # entries the size of b (k . k) decay: below 1, about head_size ** -0.5
    return jnp.tril(jax.random.normal(jax.random.key(seed), (n, c, c)), -1) \
        * 0.5 / max(c, 4) ** 0.5


@pytest.mark.parametrize("c", [16, 32, 64])
def test_the_inverse_is_the_plain_forms_to_1e_5(c):
    a = lower_triangles(c, seed=c)
    want = jax.jit(plain.unit_lower_inverse)(a)
    got = jax.jit(lambda a: jnp.stack(
        [fused.unit_lower_inverse(m) for m in a]))(a)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    eye = np.eye(c)
    for x, m in zip(np.asarray(got, np.float64), np.asarray(a, np.float64)):
        assert np.abs(x @ (eye + m) - eye).max() < 1e-5


@pytest.mark.parametrize("c", [16, 64])
def test_the_inverses_closed_form_cotangent_is_jax_grad_of_the_doublings(c):
    a = lower_triangles(c, seed=c + 1, n=1)[0]
    dx = jax.random.normal(jax.random.key(7), (c, c))
    want = jax.jit(jax.grad(
        lambda a: jnp.sum(plain.unit_lower_inverse(a) * dx)))(a)
    got = jax.jit(lambda a: fused.unit_lower_inverse_vjp(
        fused.unit_lower_inverse(a), dx))(a)
    # JAX's is the cotangent of every entry; the kernel keeps the strictly
    # lower ones, the others being no function of anything
    assert gap(jnp.tril(got, -1), jnp.tril(want, -1)) < 1e-5


# --- selection and attribution ------------------------------------------------

LATENT = dict(
    model_family="decoder", embed_dim=32, num_blocks=2, vocab_rows=48,
    kv_heads=2, head_size=D, layer_kinds=["kda", "kda"], layer_heads=[2, 2],
    layer_mlps=["dense"] * 2, ffn_dim=48, norm_eps=1e-5, kda_conv_width=4,
    kda_gate_bound=BOUND, pack_tokens=128, pack_images=4, batch_size=1,
    dtype="float32")


@pytest.mark.parametrize("shape,why", [
    ((16, 64, 64, 16), "head size 64 is no multiple of 128"),
    ((16, 128, 64, 8), "sub-chunks of 8 in chunks of 64"),
    ((16, 128, 2, 2), "sub-chunks of 2 in chunks of 2"),
    ((512, 128, 64, 16), "do not fit VMEM"),
])
def test_shapes_the_kernel_cannot_tile_say_why(shape, why):
    words = fused.kda_tiling(*shape)
    assert isinstance(words, str) and why in words


@pytest.mark.parametrize("change,why", [
    (dict(head_size=64), "head size 64"),
    (dict(kda_gate_bound=-30.0), "sub-chunks of 2"),
    (dict(pack_tokens=72), "sub-chunks of 8 in chunks of 8"),
])
def test_configurations_the_kernel_cannot_tile_fall_back(change, why,
                                                         monkeypatch):
    cfg = Config(**{**LATENT, **change}).validate()
    chosen = choose_kernels(cfg, None, force_tpu_kernels=True)
    assert chosen.rule is None
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")
    words = kernel_lines(cfg, chosen)[1]     # as on the chip
    assert words.startswith("delta rule: plain (") and why in words


def test_selection_by_backend_and_by_shape(monkeypatch):
    cfg = Config(**LATENT).validate()
    assert choose_kernels(cfg).rule is None             # the CPU, unforced
    assert kernel_lines(cfg, choose_kernels(cfg))[1] == (
        "delta rule: plain (no TPU)")
    impl = choose_kernels(cfg, None, force_tpu_kernels=True).rule
    assert impl.vitax_name == ("fused kernel (chunk 64, sub-chunks of 16, "
                               "2 heads a grid step)")
    ling = dict(layer_heads=[16, 16], pack_tokens=4096)
    assert fused.chunk_tiling(4096, BOUND) == (64, 16)
    assert fused.kda_tiling(16, 128, 64, 16) == 16
    assert choose_kernels(Config(**{**LATENT, **ling}).validate(), None,
                          True).rule.vitax_name == (
        "fused kernel (chunk 64, sub-chunks of 16, 16 heads a grid step)")
    # the most heads that divide the layer's, up to HEADS_PER_STEP
    assert fused.kda_tiling(12, 128, 64, 16) == 12
    assert fused.kda_tiling(40, 128, 64, 16) == 10
    monkeypatch.setattr(fused, "HEADS_PER_STEP", 4)
    assert fused.kda_tiling(16, 128, 64, 16) == 4
    no_kda = Config(**{**LATENT, "layer_kinds": ["attention"] * 2,
                       "layer_heads": [4, 4], "head_size": 8}).validate()
    chosen = choose_kernels(no_kda, None, True)
    assert chosen.rule is None and chosen.conv is None
    assert len(kernel_lines(no_kda, chosen)) == 1       # the attention core's


def test_on_a_mesh_the_rows_are_shared_out_and_nothing_else_changes():
    """`choose_kernels` on a mesh of two devices: the kernels under `shard_map`
    over the batch axes, a row a device; o and every gradient are the
    unsharded kernels' to the bit."""
    from vitax.parallel.mesh import build_mesh
    cfg = Config(**{**LATENT, "batch_size": 2}).validate()
    impl = choose_kernels(cfg, build_mesh(cfg, jax.devices()[:2]), True).rule
    assert impl.vitax_name.endswith("2 heads a grid step) + shard_map")
    lengths, tokens, chunk, sub = LAYOUTS["two_rows_chunks_of_64"]
    seg = segment_ids(lengths, tokens)
    ops, weight = operands(seg, seed=3)
    want_o, want = value_and_grads(fused.kda_fused, seg, ops, weight, chunk,
                                   sub)
    got_o, got = value_and_grads(impl, seg, ops, weight, chunk, sub)
    np.testing.assert_array_equal(got_o, want_o)
    for leaf, a, b in zip(NAMES, got, want):
        np.testing.assert_array_equal(a, b, err_msg=leaf)


CHUNK = 64


def _mixer(rule):
    shape = plain.KDAShape(heads=H, head_size=D, conv_width=4,
                           gate_bound=BOUND)
    mixer = plain.KDAMixer(shape, 1e-5, jnp.float32, rule=rule)
    u = jnp.ones((1, 2 * CHUNK, 32), jnp.float32)
    seg = segment_ids([[70, 50]], 2 * CHUNK)
    variables = jax.eval_shape(mixer.init, jax.random.key(0), u, seg)
    return jax.grad(lambda v, u: jnp.sum(mixer.apply(v, u, seg))), variables, u


def _chunk_products(equations):
    return [v.aval.shape for e, inside in equations if not inside
            for v in e.outvars
            if len(v.aval.shape) >= 2 and v.aval.shape[-2:] == (CHUNK, CHUNK)]


def test_the_plain_mixer_has_no_kernel_and_the_text_it_had():
    """Off the TPU, unforced: `build_model_for` hands the mixer no rule, and
    the model lowers to the text of one built without the argument."""
    from vitax.models import decoder
    from vitax.parallel.mesh import build_mesh
    from vitax.programs.builder import build_model_for
    cfg = Config(**LATENT).validate()
    model = build_model_for(cfg, build_mesh(cfg, jax.devices()[:1]))
    assert model.kernels.rule is None
    batch = decoder.sample_documents(cfg, 1)
    variables = jax.eval_shape(model.init, jax.random.key(0), batch, True)

    def text(m):
        return jax.jit(lambda v, b: m.apply(v, b, True)).lower(
            variables, batch).as_text()

    assert text(model) == text(decoder.build_decoder(cfg))
    grad, variables, u = _mixer(None)
    equations = list(_every_equation(jax.make_jaxpr(grad)(variables, u).jaxpr))
    assert not [e for e, _ in equations if e.primitive.name == "pallas_call"]
    assert _chunk_products(equations)       # what the kernels keep in VMEM


def test_the_fused_mixer_keeps_every_chunk_product_inside_its_kernels():
    cfg = Config(**LATENT).validate()
    grad, variables, u = _mixer(choose_kernels(cfg, None, True).rule)
    equations = list(_every_equation(jax.make_jaxpr(grad)(variables, u).jaxpr))
    kernels = sorted({_kernel_name(e) for e, _ in equations
                      if e.primitive.name == "pallas_call"})
    assert kernels == ["kda_bwd", "kda_fwd"]
    assert _chunk_products(equations) == []
    # nor the decayed keys, a sub-chunk's worth of them a chunk
    assert not [v.aval.shape for e, inside in equations if not inside
                for v in e.outvars if len(v.aval.shape) >= 6]


def test_the_scopes_a_metric_reads_are_in_the_lowered_fused_program():
    """`kda_roofline` and `kda_mixer_busy_pct` join on `kda_chunk` (and
    `kda_state`, which the fused form folds into it): the kernels and the
    cumsum beside them lie under it, forward and backward."""
    cfg = Config(**LATENT).validate()
    grad, variables, u = _mixer(choose_kernels(cfg, None, True).rule)
    text = jax.jit(grad).lower(variables, u).as_text(debug_info=True)
    for scope in ("kda_conv", "kda_gate", "kda_chunk", "kda_out_norm"):
        assert f"{scope}/" in text, scope
    assert "kda_state/" not in text


def test_the_fused_mixer_equals_the_plain_mixer():
    """The whole layer either way: the same output, the same gradient of every
    leaf and of the input."""
    cfg = Config(**LATENT).validate()
    shape = plain.KDAShape(heads=H, head_size=D, conv_width=4,
                           gate_bound=BOUND)
    seg = segment_ids([[70, 50]], 2 * CHUNK)
    u = jax.random.normal(jax.random.key(1), (1, 2 * CHUNK, 32))
    w = jax.random.normal(jax.random.key(2), u.shape)
    mixers = [plain.KDAMixer(shape, 1e-5, jnp.float32, rule=rule)
              for rule in (None, choose_kernels(cfg, None, True).rule)]
    variables = jax.jit(mixers[0].init)(jax.random.key(0), u, seg)
    want, got = (jax.jit(jax.value_and_grad(lambda v, u, m=m: jnp.sum(
        m.apply(v, u, seg) * w), argnums=(0, 1)))(variables, u)
        for m in mixers)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert gap(a, b) < 2e-4


def test_a_program_traces_each_kernel_body_once(monkeypatch):
    """Two runs of kda layers around an attention layer, remat on, under
    `jax.grad`: six sites call the kernels (each run's forward, its remat's
    forward and its backward), the init before them two more. `_forward` and
    `_backward` are `jax.jit`s, so Python runs each kernel's body once, and
    the sites share one jaxpr a set of outputs (the rule's forward with the
    states the backward reads, the primal's without them), which a module
    lowers once and calls: what a cached run's set-up pays is a trace a
    kernel, not a trace a site."""
    import collections

    from vitax.models import decoder
    cfg = Config(**{**LATENT, "num_blocks": 3, "layer_mlps": ["dense"] * 3,
                    "layer_kinds": ["kda", "attention", "kda"],
                    "layer_heads": [2, 2, 2]}).validate()
    model = decoder.build_decoder(cfg, kernels=Kernels(
        rule=choose_kernels(cfg, None, force_tpu_kernels=True).rule))
    assert model.grad_ckpt and len(model.runs()) == 3
    ran = collections.Counter()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def body(*args, _body=getattr(fused, name), _name=name, **kwargs):
            ran[_name] += 1
            return _body(*args, **kwargs)
        monkeypatch.setattr(fused, name, body)
    fused._forward.clear_cache()
    fused._backward.clear_cache()
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
    assert sites == {"kda_fwd": 4, "kda_bwd": 2}, sites
    assert {k: len(v) for k, v in jaxprs.items()} == {"kda_fwd": 2,
                                                      "kda_bwd": 1}, jaxprs
    text = traced.lower().as_text()
    assert text.count("func.func private @_backward") == 1
    assert text.count("call @_backward") == 2
