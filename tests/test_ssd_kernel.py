"""The fused state-space scan (vitax/ops/ssd.py) in interpret mode at small
shapes that tile (chunk 128, state 128, heads of 64): against the plain `ssd`
(vitax/models/ssm.py), which stays the oracle, and against the float32
token-by-token recurrence of the plain reference (benchmark/reference/
granite.py), y and the gradients of x, delta, A, B, C and D, over layouts
whose boundaries fall inside a chunk, on a chunk's edge, over three chunks and
more, before a padded tail and a chunk of padding only; bfloat16 operands
rounded where the plain form rounds them; which form `choose_kernels`
chooses, and that the chosen kernels are found by name with no (chunk,
chunk) array left outside them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite as reference
from tests.decoder_cases import kernel_name as _kernel_name
from tests.test_ssm import documents
from vitax.config import Config
from vitax.data.packing import document_layout
from vitax.models.ssm import MixerShape, SSDMixer, ssd
from vitax.ops import ssd as fused
from vitax.programs import kernels as programs
from vitax.programs.kernels import choose_kernels, kernel_lines

CHUNK, STATE, HEAD = 128, 128, 64
ROW = 512
NAMES = ("x", "delta", "A", "B", "C", "D")

# name: (rows of document lengths, heads, groups, heads a grid step at most,
# queries a row block)
LAYOUTS = {
    "one_document": ([[512]], 4, 2, 8, 128),
    "boundary_inside_a_chunk": ([[200, 312]], 4, 2, 8, 128),
    "boundary_on_a_chunks_edge": ([[256, 128, 128]], 4, 2, 8, 128),
    "a_document_over_every_chunk": ([[50, 400, 62]], 4, 2, 8, 128),
    "padded_tail_and_a_chunk_of_padding": ([[100, 150]], 4, 2, 8, 128),
    "two_rows_one_group": ([[130, 300, 82], [384, 70]], 4, 1, 8, 128),
    "two_grid_steps_a_group": ([[200, 100, 150]], 4, 1, 2, 128),
    "two_row_blocks_a_chunk": ([[130, 300, 60]], 4, 2, 8, 64),
}


def operands(lengths, heads, groups, dtype=jnp.float32, head=HEAD, seed=0):
    """Eager, a dozen small programs: drawn under one jit, delta and A come
    out a rounding off and the head of 128's gradient of A reads 1.04e-4
    against the plain form's, over the 1e-4 held (7.6e-5 on these)."""
    seg = jnp.asarray(document_layout(lengths, ROW, 8)["segment_ids"])
    r, t = seg.shape
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (r, t, heads, head)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (r, t, heads)) - 2.0)
    a_head = -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.0))
    b = (0.3 * jax.random.normal(ks[3], (r, t, groups, STATE))).astype(dtype)
    c = (0.3 * jax.random.normal(ks[4], (r, t, groups, STATE))).astype(dtype)
    d_skip = jax.random.normal(ks[5], (heads,))
    weight = jax.random.normal(ks[6], (r, t, heads, head))
    return seg, (x, delta, a_head, b, c, d_skip), weight


def value_and_grads(scan, seg, ops, weight, dtype=jnp.float32):
    """(y, the six gradients of sum(y * weight)); x is zero at padding, as
    the mixer's convolution leaves it."""
    valid = (seg > 0)[..., None, None]

    def total(x, *rest):
        y = scan(jnp.where(valid, x, jnp.zeros((), x.dtype)), *rest, seg,
                 CHUNK, dtype)
        return jnp.sum(y * weight), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        total, argnums=tuple(range(6)), has_aux=True))(*ops)
    return y, grads


def gap(got, want):
    got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_kernel_matches_the_plain_form(name, monkeypatch):
    """float32 throughout: y within 1e-5 of the plain form's norm, every
    gradient within 1e-4 (the log-decay's takes another route to the same
    sums)."""
    lengths, heads, groups, most, row_block = LAYOUTS[name]
    monkeypatch.setattr(fused, "HEADS_PER_STEP", most)
    monkeypatch.setattr(fused, "ROW_BLOCK", row_block)
    seg, ops, weight = operands(lengths, heads, groups)
    want_y, want = value_and_grads(ssd, seg, ops, weight)
    got_y, got = value_and_grads(fused.ssd_fused, seg, ops, weight)
    assert float(jnp.abs(want_y).max()) > 1.0
    assert gap(got_y, want_y) < 1e-5
    assert float(jnp.abs(got_y * (seg == 0)[..., None, None]).max()) == 0.0
    for leaf, a, b in zip(NAMES, got, want):
        assert gap(a, b) < 1e-4, leaf


@pytest.mark.parametrize("name", ["a_document_over_every_chunk",
                                  "padded_tail_and_a_chunk_of_padding",
                                  "two_row_blocks_a_chunk"])
def test_kernel_matches_the_token_by_token_recurrence(name, monkeypatch):
    """Document by document against S_t = exp(delta_t A) S_{t-1} + delta_t
    x_t (x) B_t, y_t = S_t C_t + D x_t in float32."""
    lengths, heads, groups, _, row_block = LAYOUTS[name]
    monkeypatch.setattr(fused, "ROW_BLOCK", row_block)
    seg, ops, weight = operands(lengths, heads, groups)
    got_y, got = value_and_grads(fused.ssd_fused, seg, ops, weight)
    per_group = heads // groups

    def plain(x, delta, a_head, b, c, d_skip):
        total = 0.0
        for r, at in documents(seg):
            y = reference.recurrence(
                x[r, at], delta[r, at], a_head,
                jnp.repeat(b[r, at], per_group, axis=1),
                jnp.repeat(c[r, at], per_group, axis=1))
            y = y + d_skip[:, None] * x[r, at]
            total += jnp.sum(y * weight[r, at])
        return total

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(plain, argnums=tuple(range(6))))(*ops)
    valid = np.asarray(seg > 0)
    for leaf, a, b in zip(NAMES, got, want):
        if leaf in ("x", "delta", "B", "C"):    # per token: none at padding
            assert not np.asarray(a.astype(jnp.float32))[~valid].any(), leaf
        assert gap(a, b) < 2e-4, leaf


def test_bfloat16_operands_are_rounded_where_the_plain_form_rounds_them():
    """Same roundings, same y: a product rounded elsewhere would stand 1e-3
    off. Every gradient stands within 1e-2 of the float32 one, as the plain
    form's do (it reads 2e-3 to 1.4e-2 on these operands)."""
    lengths, heads, groups = LAYOUTS["two_rows_one_group"][:3]
    dtype = jnp.bfloat16
    seg, ops, weight = operands(lengths, heads, groups, dtype)
    plain_y, plain = value_and_grads(ssd, seg, ops, weight, dtype)
    got_y, got = value_and_grads(fused.ssd_fused, seg, ops, weight, dtype)
    exact = tuple(a.astype(jnp.float32) for a in ops)
    _, want = value_and_grads(ssd, seg, exact, weight)
    assert got_y.dtype == jnp.float32
    assert gap(got_y, plain_y) < 1e-5
    for leaf, a, b, w in zip(NAMES, got, plain, want):
        assert a.dtype == b.dtype, leaf
        assert gap(a, w) < 1e-2, (leaf, gap(b, w))


def test_a_head_of_a_whole_lane_tile():
    """Head size 128: one head a tile, nothing to select."""
    seg, ops, weight = operands([[300, 100, 60]], 2, 1, head=128)
    want_y, want = value_and_grads(ssd, seg, ops, weight)
    got_y, got = value_and_grads(fused.ssd_fused, seg, ops, weight)
    assert gap(got_y, want_y) < 1e-5
    for leaf, a, b in zip(NAMES, got, want):
        assert gap(a, b) < 1e-4, leaf


# --- selection and attribution ----------------------------------------------

HYBRID = dict(
    model_family="decoder", embed_dim=32, num_blocks=2, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=["mamba", "attention"],
    layer_heads=[0, 4], layer_mlps=["dense"] * 2, ffn_dim=48, norm_eps=1e-5,
    position_embedding="nope", tie_embeddings=True, ssm_heads=4,
    ssm_head_size=HEAD, ssm_state_size=STATE, ssm_conv_width=4, ssm_groups=2,
    ssm_chunk=CHUNK, pack_tokens=2 * CHUNK, pack_images=4, batch_size=1,
    dtype="float32")


@pytest.mark.parametrize("change,why", [
    (dict(ssm_chunk=64, pack_tokens=128), "chunk 64"),
    (dict(ssm_state_size=16), "state size 16"),
    (dict(ssm_head_size=48), "head size 48"),
    (dict(ssm_heads=2, ssm_groups=2), "1 heads a group"),
    (dict(ssm_heads=512, ssm_groups=1, ssm_head_size=128, ssm_state_size=128),
     "do not fit VMEM"),
])
def test_shapes_the_kernel_cannot_tile_fall_back(change, why, monkeypatch):
    cfg = Config(**{**HYBRID, **change}).validate()
    chosen = choose_kernels(cfg, None, force_tpu_kernels=True)
    assert chosen.scan is None
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")
    words = kernel_lines(cfg, chosen)[1]     # as on the chip
    assert words.startswith("state-space scan: plain (") and why in words


def test_selection_by_backend_and_by_shape():
    cfg = Config(**HYBRID).validate()
    assert choose_kernels(cfg).scan is None             # the CPU, unforced
    assert kernel_lines(cfg, choose_kernels(cfg))[1] == (
        "state-space scan: plain (no TPU)")
    impl = choose_kernels(cfg, None, force_tpu_kernels=True).scan
    assert impl.vitax_name == "fused kernel (chunk 128, 2 heads a grid step)"
    granite = dict(ssm_heads=64, ssm_head_size=64, ssm_state_size=128,
                   ssm_groups=1, ssm_chunk=256, pack_tokens=4096)
    assert fused.scan_tiling(64, 64, 128, 1, 256) == (16, 2)
    assert choose_kernels(Config(**{**HYBRID, **granite}).validate(), None,
                          True).scan.vitax_name == (
        "fused kernel (chunk 256, 16 heads a grid step)")
    no_mamba = Config(**{**HYBRID, "layer_kinds": ["attention"] * 2,
                         "layer_heads": [4, 4]}).validate()
    chosen = choose_kernels(no_mamba, None, True)
    assert chosen.scan is None and chosen.conv is None
    assert len(kernel_lines(no_mamba, chosen)) == 1     # the attention core's


def _every_equation(jaxpr, inside_kernel=False):
    """(equation, whether it lies inside a pallas_call) over a jaxpr and
    every jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_kernel
        inner = inside_kernel or eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _every_equation(sub, inner)


def _mixer_grad_jaxpr(scan):
    shape = MixerShape(heads=4, head_size=HEAD, state_size=STATE,
                       conv_width=4, groups=2, chunk=CHUNK)
    mixer = SSDMixer(shape, 1e-5, jnp.float32, scan=scan)
    u = jnp.ones((1, 3 * CHUNK, 32), jnp.float32)
    seg = jnp.asarray(document_layout([[200, 150]], 3 * CHUNK,
                                      4)["segment_ids"])
    variables = jax.eval_shape(mixer.init, jax.random.key(0), u, seg)
    return jax.make_jaxpr(jax.grad(
        lambda v, u: jnp.sum(mixer.apply(v, u, seg))))(variables, u).jaxpr


def test_the_plain_mixer_has_no_kernel_and_the_text_it_had():
    """Off the TPU, unforced: `build_model_for` hands the mixer no scan, and
    the model lowers to the text of one built without the argument."""
    from vitax.models import decoder
    from vitax.parallel.mesh import build_mesh
    from vitax.programs.builder import build_model_for
    cfg = Config(**HYBRID).validate()
    model = build_model_for(cfg, build_mesh(cfg, jax.devices()[:1]))
    assert model.kernels.scan is None
    batch = decoder.sample_documents(cfg, 1)
    variables = jax.eval_shape(model.init, jax.random.key(0), batch, True)

    def text(m):
        return jax.jit(lambda v, b: m.apply(v, b, True)).lower(
            variables, batch).as_text()

    assert text(model) == text(decoder.build_decoder(cfg))
    assert not [e for e, _ in _every_equation(_mixer_grad_jaxpr(None))
                if e.primitive.name == "pallas_call"]
    plain = [v.aval.shape for e, _ in _every_equation(_mixer_grad_jaxpr(None))
             for v in e.outvars if v.aval.shape[-2:] == (CHUNK, CHUNK)]
    assert plain                        # what the kernel keeps in VMEM


def test_the_fused_mixer_keeps_every_chunk_product_inside_its_kernels():
    cfg = Config(**HYBRID).validate()
    impl = choose_kernels(cfg, None, force_tpu_kernels=True).scan
    equations = list(_every_equation(_mixer_grad_jaxpr(impl)))
    kernels = sorted({_kernel_name(e) for e, _ in equations
                      if e.primitive.name == "pallas_call"})
    assert kernels == ["ssd_bwd", "ssd_fwd"]
    outside = [v.aval.shape for e, inside in equations if not inside
               for v in e.outvars
               if len(v.aval.shape) >= 2
               and v.aval.shape[-2:] == (CHUNK, CHUNK)]
    assert outside == []
