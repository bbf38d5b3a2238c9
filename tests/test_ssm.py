"""The state-space mixer (vitax/models/ssm.py) at small sizes on the CPU:
the chunked dual form against the token-by-token recurrence of the plain
reference (benchmark/reference/granite.py) with document boundaries inside
chunks, a document packed among others against the document alone, one chunk
size against another, the convolution at a document's first tokens, the
step's counters of the scan's work, and the document kernels with a score
scale of their own against the dense path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite as reference
from vitax.data.packing import document_layout
from vitax.models import decoder
from vitax.models.ssm import (MixerShape, SSDMixer, causal_conv,
                              mixer_param_count, ssd)

SHAPE = MixerShape(heads=8, head_size=8, state_size=16, conv_width=4,
                   groups=2, chunk=8)
EMBED = 32
LENGTHS = [[13, 5, 9], [20, 7]]         # rows of 32: 5 and 5 slots of padding
ROW = 32


def segments(lengths=LENGTHS, row=ROW):
    return jnp.asarray(document_layout(lengths, row, 4)["segment_ids"])


def documents(seg):
    """(row, the positions of one document in it), document by document."""
    seg = np.asarray(seg)
    return [(r, np.where(seg[r] == s)[0]) for r in range(seg.shape[0])
            for s in range(1, seg[r].max() + 1)]


@functools.cache
def seeded_mixer(shape=SHAPE, seed=0):
    """The mixer with every leaf moved off its initial value: one program,
    run once a shape and seed."""
    mixer = SSDMixer(shape, 1e-5, jnp.float32)

    @jax.jit
    def seeded():
        variables = mixer.init(
            jax.random.key(seed), jnp.zeros((1, shape.chunk, EMBED)),
            jnp.ones((1, shape.chunk), jnp.int32))
        leaves, tree = jax.tree.flatten(variables)
        keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
        return jax.tree.unflatten(tree, [
            a + 0.1 * jax.random.normal(k, a.shape)
            for a, k in zip(leaves, keys)])
    return mixer, seeded()


@functools.partial(jax.jit, static_argnames="shape")
def reference_mixer(u, params, shape=SHAPE):
    with jax.default_matmul_precision("highest"):
        return reference.mamba_mixer(
            u, params, 1e-5, n_heads=shape.heads, d_head=shape.head_size,
            d_state=shape.state_size, d_conv=shape.conv_width,
            n_groups=shape.groups, conv_bias=True)


def inputs(seed=3, rows=2, row=ROW):
    """Data only: numpy, so that taking a document's rows compiles nothing."""
    return np.asarray(jax.random.normal(jax.random.key(seed),
                                        (rows, row, EMBED), jnp.float32))


def test_the_mixer_matches_the_recurrence_document_by_document():
    """Chunks of 8 over documents of 13, 5, 9 and 20, 7 tokens: every later
    document starts inside a chunk."""
    mixer, variables = seeded_mixer()
    seg, u = segments(), inputs()
    u = u * (np.asarray(seg) > 0)[..., None]
    got = np.asarray(jax.jit(mixer.apply)(variables, u, seg))
    assert np.abs(got).max() > 0.05
    for r, at in documents(seg):
        want = reference_mixer(u[r, at], variables["params"])
        np.testing.assert_allclose(got[r, at], want, rtol=2e-4, atol=2e-5)
    assert np.abs(got[np.asarray(seg) == 0]).max() == 0.0    # padding


def test_every_gradient_of_the_mixer_matches_the_recurrences():
    mixer, variables = seeded_mixer()
    seg, u = segments(), inputs()
    u = u * (np.asarray(seg) > 0)[..., None]
    w = np.asarray(jax.random.normal(jax.random.key(9), u.shape, jnp.float32))

    def program(v, u):
        return jnp.sum(mixer.apply(v, u, seg) * w)

    def plain(v, u):
        with jax.default_matmul_precision("highest"):
            return sum(jnp.sum(reference_mixer(u[r, at], v["params"])
                               * w[r, at]) for r, at in documents(seg))

    got = jax.jit(jax.grad(program, (0, 1)))(variables, u)
    want = jax.jit(jax.grad(plain, (0, 1)))(variables, u)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == 9            # 8 leaves of the mixer, and its input
    for (path, b), a in zip(flat, jax.tree.leaves(got)):
        assert reference.relative_gap(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_a_document_packed_among_others_equals_the_document_alone():
    """Neither the state nor the convolution leaks over a boundary: the
    outputs of the 9-token document packed third in its row are those of the
    same tokens in a row of their own, whatever the neighbours hold."""
    mixer, variables = seeded_mixer()
    seg, u = segments(), inputs()
    apply = jax.jit(mixer.apply)
    packed = apply(variables, u, seg)             # padding slots hold noise
    alone_seg = segments([[9]], 16)
    at = np.where(np.asarray(seg[0]) == 3)[0]
    alone = apply(variables, jnp.pad(u[:1, at], ((0, 0), (0, 7), (0, 0))),
                  alone_seg)
    np.testing.assert_allclose(packed[0, at], alone[0, :9], rtol=1e-5,
                               atol=1e-6)
    other = u.copy()
    other[0, :18] *= 7.0                          # other documents' tokens
    np.testing.assert_allclose(apply(variables, other, seg)[0, at],
                               packed[0, at], rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(packed)[np.asarray(seg) == 0]).max() == 0.0


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_one_chunk_size_equals_another(chunk):
    mixer, variables = seeded_mixer()
    seg, u = segments(), inputs()
    other = SSDMixer(SHAPE._replace(chunk=chunk), 1e-5, jnp.float32)
    w = jax.random.normal(jax.random.key(5), u.shape, jnp.float32)
    for fn in (lambda m, v: m.apply(v, u, seg),
               lambda m, v: jax.grad(
                   lambda v: jnp.sum(m.apply(v, u, seg) * w))(v)):
        for a, b in zip(*(jax.tree.leaves(jax.jit(fn, static_argnums=0)(
                m, variables)) for m in (other, mixer))):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_blocks_of_chunks_change_nothing(monkeypatch):
    """The scan a block of chunks at a time (as at the cell's size, where
    all chunks' products at once would not fit) and all at once."""
    from vitax.models import ssm
    seg = segments()
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (2, ROW, 8, 8), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (2, ROW, 8)))
    b = jax.random.normal(ks[2], (2, ROW, 2, 16), jnp.float32)
    c = jax.random.normal(ks[3], (2, ROW, 2, 16), jnp.float32)
    a_head = -jnp.exp(jax.random.normal(ks[4], (8,)))

    def run():      # eager: under one jit the two forms fuse differently and
        # 2 of 4,096 elements part by 2.1e-6, over the 1e-6 held here
        return ssd(x, delta, a_head, b, c, jnp.ones((8,)), seg, 8,
                   jnp.float32)
    whole = run()
    monkeypatch.setattr(ssm, "SSD_BLOCK_BYTES", 1)       # a chunk a block
    assert ssm._chunk_block(2, 4, 8, 8) == 1
    np.testing.assert_allclose(run(), whole, rtol=1e-6, atol=1e-6)


def test_the_convolution_stops_at_a_documents_first_token():
    seg = segments([[3, 5]], 8)
    x = jnp.arange(1.0, 9.0).reshape(1, 8, 1)
    kernel = jnp.asarray([[1000.0], [100.0], [10.0], [1.0]])
    got = np.asarray(jax.jit(causal_conv)(x, seg, kernel, jnp.zeros(1)))[
        0, :, 0]
    # token 4 (value 4) opens the second document: it sees itself alone
    np.testing.assert_array_equal(
        got, [1, 12, 123, 4, 45, 456, 4567, 5678])
    want = jax.jit(lambda x: reference.convolution(x, kernel, None))(
        x[0, 3:])[:, 0]
    np.testing.assert_array_equal(got[3:], want)


def test_closed_form_parameter_count_of_the_mixer():
    _, variables = seeded_mixer()
    assert sum(a.size for a in jax.tree.leaves(variables)) \
        == mixer_param_count(SHAPE, EMBED)
    granite = MixerShape(64, 64, 128, 4, 1, 256)
    assert (granite.inner, granite.conv_channels, granite.projected) \
        == (4096, 4352, 8512)
    # in-projection, convolution and its bias, dt_bias / A_log / D, the gated
    # norm, the out-projection (ISSUE 35; with the two norms and the MLP a
    # mamba layer holds 76,182,976)
    assert mixer_param_count(granite, 2048) == (
        2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048)
    assert mixer_param_count(granite, 2048) + 2 * 2048 \
        + 3 * 2048 * 8192 == 76_182_976


@pytest.mark.parametrize("group,scale", [(4, 0.015625), (4, 0.0), (2, 0.3)],
                         ids=["4q_scale_1_64", "4q_default", "2q_scale_0.3"])
def test_document_kernels_with_a_scale_match_the_dense_path(group, scale):
    """`document_flash_attention(..., scale=)` in interpret mode at head
    size 64 against the dense mask with the same scale, values and
    gradients; scale 0 is Dh ** -0.5 in both."""
    from vitax.ops.flash_blocked import document_flash_attention
    r, t, kv, dh = 1, 256, 2, 64
    seg = segments([[150, 60, 30]], t)
    ks = jax.random.split(jax.random.key(group), 4)
    q = jax.random.normal(ks[0], (r, t, kv * group, dh), jnp.float32)
    k = jax.random.normal(ks[1], (r, t, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (r, t, kv, dh), jnp.float32)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)

    def kernel(q, k, v):
        return document_flash_attention(q, k, v, seg, 0, 128, 128,
                                        scale=scale)

    def dense(q, k, v, scale=scale):
        return decoder.causal_masked_attention(q, k, v, seg, 0, jnp.float32,
                                               scale)

    dense_at = jax.jit(dense, static_argnums=3)
    out = jax.jit(kernel)(q, k, v)
    np.testing.assert_allclose(out, dense_at(q, k, v, scale), rtol=1e-4,
                               atol=1e-5)
    if scale == 0.0:
        np.testing.assert_allclose(out, dense_at(q, k, v, dh ** -0.5),
                                   rtol=1e-6, atol=1e-6)
    else:
        assert float(jnp.max(jnp.abs(out - dense_at(q, k, v, 0.0)))) > 1e-2
    got, want = (jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                                  (0, 1, 2)))(q, k, v)
                 for f in (kernel, dense))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)
