"""Kimi Delta Attention (vitax/models/kda.py) at small sizes on the CPU: the
chunked form against the recurrence itself, token by token and document by
document (benchmark/reference/ling.py: delta_rule), values and gradients,
with document boundaries inside chunks, a chunk of padding only, two chunk
lengths and a decay at the bound; the triangular inverse; the grid the
program chooses; the mixer against the reference's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling as reference
from vitax.models import kda as K
from vitax.ops.kda import KDA_EXP_RANGE

BOUND = -5.0
H, DK, DV = 2, 8, 8
# rows of 32 tokens: boundaries at 13 and 18 and padding from 27 on; one
# document and then 12 tokens of padding (a whole chunk of 8 among them)
LAYOUTS = {
    "boundaries_inside_chunks": [[13, 5, 9], [20, 7]],
    "a_chunk_of_padding_only": [[20], [3, 2, 11]],
    "a_document_a_token": [[1, 1, 30], [32]],
}


def segment_ids(lengths, tokens=32):
    rows = []
    for row in lengths:
        ids = np.concatenate([np.full(n, i + 1) for i, n in enumerate(row)])
        rows.append(np.pad(ids, (0, tokens - len(ids))))
    return jnp.asarray(np.stack(rows), jnp.int32)


@functools.partial(jax.jit, static_argnames=("decay", "seed"))
def inputs(seg, decay: str, seed=0):
    """q, k unit-length a head (q times DK ** -0.5), v, g in (BOUND, 0) and
    beta in (0, 1), zero at padding, as the mixer hands them over."""
    r, t = seg.shape
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (r, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (r, t, H, DK)))
    v = jax.random.normal(ks[2], (r, t, H, DV))
    z = jax.random.normal(ks[3], (r, t, H, DK))
    g = BOUND * jax.nn.sigmoid({"mild": z - 2.0, "mixed": 3.0 * z,
                                "at_the_bound": z + 30.0}[decay])
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, t, H)))
    valid = (seg > 0)[..., None]
    q, k, v, g = (jnp.where(valid[..., None], x, 0.0) for x in (q, k, v, g))
    return q, k, v, g, jnp.where(valid, beta, 0.0)


@jax.jit
def _one_document(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return reference.delta_rule(q, k, v, g, beta)


def token_by_token(q, k, v, g, beta, seg):
    """The recurrence on each document alone (followed by zeros up to the
    row's length, which no token of it can see, so that one compiled program
    serves them all), zeros at padding. `seg` is concrete: a caller jits
    this with the layout closed over."""
    seg = np.asarray(seg)
    t = seg.shape[1]
    out = jnp.zeros(v.shape, jnp.float32)
    for r in range(seg.shape[0]):
        for s in range(1, seg[r].max() + 1):
            at = np.where(seg[r] == s)[0]
            alone = [jnp.pad(x[r, at], ((0, t - len(at)),) + ((0, 0),) * (
                x.ndim - 2)) for x in (q, k, v, g, beta)]
            out = out.at[r, at].set(_one_document(*alone)[:len(at)])
    return out


kda = jax.jit(K.kda, static_argnums=(6, 7, 8))


@functools.cache
def recurrence(layout, decay):
    """(segment ids, inputs, the recurrence's values), made once for the
    chunkings that are held to them."""
    seg = segment_ids(LAYOUTS[layout])
    args = inputs(seg, decay)
    return seg, args, jax.jit(lambda *a: token_by_token(*a, seg))(*args)


@functools.cache
def recurrence_gradients(layout, decay):
    """(segment ids, inputs, a weight, the recurrence's gradients of the
    weighted sum), likewise."""
    seg = segment_ids(LAYOUTS[layout])
    args = inputs(seg, decay, seed=1)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    return seg, args, w, jax.jit(jax.grad(
        lambda *a: jnp.sum(token_by_token(*a, seg) * w), range(5)))(*args)


@pytest.mark.parametrize("decay", ["mild", "mixed", "at_the_bound"])
@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 8), (16, 16), (32, 8)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_chunked_form_equals_the_recurrence(layout, chunk, sub, decay):
    seg, args, want = recurrence(layout, decay)
    if decay == "at_the_bound":
        assert float(jnp.min(args[3])) < BOUND + 1e-6
    got = kda(*args, seg, chunk, sub, jnp.float32)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(got * (seg == 0)[..., None, None]))) == 0.0
    assert float(jnp.max(jnp.abs(want))) > 1e-2


@pytest.mark.parametrize("decay", ["mild", "at_the_bound"])
@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 8)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_gradients_equal_the_recurrences(layout, chunk, sub, decay):
    seg, args, w, want = recurrence_gradients(layout, decay)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(K.kda(
        *a, seg, chunk, sub, jnp.float32) * w), range(5)))(*args)
    pad = np.asarray(seg) == 0
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert reference.relative_gap(a, b) < 2e-4, name
        # nothing reaches padding but the rounding of terms that cancel
        # (the running sum a sub-chunk's exponents are taken from)
        assert np.abs(np.asarray(a)[pad]).max(initial=0.0) < 1e-6


def test_blocks_of_chunks_give_what_one_block_gives(monkeypatch):
    """The per-chunk part made a block of chunks at a time (`lax.map` under
    `jax.checkpoint`): the same values and gradients."""
    seg = segment_ids(LAYOUTS["boundaries_inside_chunks"])
    args = inputs(seg, "mixed", seed=2)

    def loss(*a):
        return jnp.sum(jnp.sin(K.kda(*a, seg, 8, 4, jnp.float32)))

    whole = jax.jit(jax.value_and_grad(loss, range(5)))(*args)
    per_chunk = 4 * 2 * (8 // 4) * 8 * H * DK
    monkeypatch.setattr(K, "KDA_BLOCK_BYTES", 2 * per_chunk)
    assert K._chunk_block(per_chunk, 4) == 2
    blocked = jax.jit(jax.value_and_grad(loss, range(5)))(*args)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_bfloat16_operands_stay_close_and_finite_at_the_bound():
    """Products on operands of the model's dtype, accumulated in float32:
    at a decay at the bound over a whole sub-chunk the exponents stay inside
    float32 (and bfloat16's range, which is the same)."""
    seg = segment_ids([[64], [40, 20]], tokens=64)
    args = inputs(seg, "at_the_bound", seed=3)
    chunk, sub = K.chunk_tiling(64, BOUND)
    assert (chunk, sub) == (64, 16)
    want = kda(*args, seg, chunk, sub, jnp.float32)
    q, k, v, g, beta = args

    def rounded(g):
        return K.kda(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16), g, beta, seg, chunk, sub,
                     jnp.bfloat16)

    got = jax.jit(rounded)(g)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    assert reference.relative_gap(got, want) < 2e-2
    grads = jax.jit(jax.grad(lambda g: jnp.sum(rounded(g))))(g)
    assert np.isfinite(np.asarray(grads)).all()


@pytest.mark.parametrize("c", [1, 2, 5, 16, 64])
def test_unit_lower_inverse(c):
    # entries the size of b (k . k) decay: below 1, about head_size ** -0.5
    a = jnp.tril(jax.random.normal(jax.random.key(c), (3, c, c)), -1) \
        * 0.5 / max(c, 4) ** 0.5
    want = np.linalg.inv(np.eye(c) + np.asarray(a))
    np.testing.assert_allclose(jax.jit(K.unit_lower_inverse)(a), want,
                               rtol=1e-4,
                               atol=1e-4 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("tokens,bound,want", [
    (4096, -5.0, (64, 16)), (32, -5.0, (32, 16)), (96, -5.0, (32, 16)),
    (4096, -1.0, (64, 64)), (4096, -30.0, (64, 2)), (50, -5.0, (2, 2))])
def test_tiling_follows_the_row_and_the_bound(tokens, bound, want):
    chunk, sub = K.chunk_tiling(tokens, bound)
    assert (chunk, sub) == want
    assert tokens % chunk == 0 and chunk % sub == 0
    # half a sub-chunk at the bound stays inside the range of an exponent
    assert abs(bound) * sub / 2 <= KDA_EXP_RANGE or sub == 1


def seeded_mixer(shape, u, seg, dtype=jnp.float32):
    mixer = K.KDAMixer(shape, 1e-6, dtype)

    @jax.jit
    def seeded(u, seg):
        p = mixer.init(jax.random.key(0), u, seg)["params"]
        leaves, tree = jax.tree.flatten(p)
        keys = jax.random.split(jax.random.key(4), len(leaves))
        return jax.tree.unflatten(tree, [
            a + 0.1 * jax.random.normal(k, a.shape)
            for a, k in zip(leaves, keys)])
    return mixer, seeded(u, seg)


def test_mixer_equals_the_references_values_and_gradients():
    """The whole mixer (projections, the three convolutions within a
    document, the L2 norms, the bounded gate, the delta rule, the normed and
    head-wise gated output) against the reference's, one document at a
    time; every leaf's gradient."""
    shape = K.KDAShape(heads=3, head_size=8, conv_width=4, gate_bound=BOUND)
    seg = segment_ids(LAYOUTS["boundaries_inside_chunks"])
    u = jax.random.normal(jax.random.key(1), (2, 32, 24))
    mixer, p = seeded_mixer(shape, u, seg)
    w = jax.random.normal(jax.random.key(2), u.shape)
    assert sum(a.size for a in jax.tree.leaves(p)) == K.kda_param_count(
        shape, 24)

    def program(p):
        return jnp.sum(mixer.apply({"params": p}, u, seg) * w)

    @jax.jit
    def alone(p, u, w):     # a document followed by zeros it cannot see
        return jnp.sum(reference.kda_mixer(
            u, p, 1e-6, head_dim=8, taps=4, gate_bound=BOUND) * w)

    def plain(p):
        total, rows = 0.0, np.asarray(seg)
        for r in range(2):
            for s in range(1, rows[r].max() + 1):
                at = np.where(rows[r] == s)[0]
                fill = ((0, 32 - len(at)), (0, 0))
                total += alone(p, jnp.pad(u[r, at], fill),
                               jnp.pad(w[r, at], fill))
        return total

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(plain))(p)
    got, got_grads = jax.jit(jax.value_and_grad(program))(p)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    assert len(flat) == 11
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        assert reference.relative_gap(a, b) < 5e-4, jax.tree_util.keystr(path)
    out = jax.jit(mixer.apply)({"params": p}, u, seg)
    pad = np.asarray(seg) == 0
    # padding receives nothing but what W_o makes of zeros
    assert float(jnp.max(jnp.abs(out[pad]))) == 0.0


def test_the_scopes_a_metric_reads_are_in_the_lowered_program():
    shape = K.KDAShape(heads=2, head_size=8, conv_width=4, gate_bound=BOUND)
    seg = segment_ids(LAYOUTS["boundaries_inside_chunks"])
    u = jnp.ones((2, 32, 16))
    mixer, p = seeded_mixer(shape, u, seg)
    text = jax.jit(jax.grad(lambda p: jnp.sum(mixer.apply(
        {"params": p}, u, seg)))).lower(p).as_text(debug_info=True)
    for scope in ("kda_conv", "kda_gate", "kda_chunk", "kda_state",
                  "kda_out_norm"):
        assert f"{scope}/" in text, scope
