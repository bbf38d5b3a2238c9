"""The delta rule with ONE decay a head (Gated DeltaNet; vitax/models/kda.py:
`kda` given `g` of (R, T, H)) at small sizes on the CPU: the chunked form
against the recurrence itself, token by token and document by document
(benchmark/reference/olmo_hybrid.py: delta_rule), values and gradients, with
a key width that differs from the value width, document boundaries inside
chunks, a chunk of padding only, beta at 1.99 on repeated keys and a decay of
-60 a token; and the per-channel form held to the bit to what it was before
this form came beside it, on tests/test_kda.py's cases."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from tests import test_kda
from tests.test_kda import LAYOUTS, segment_ids
from vitax.models import kda as K

H, DK, DV = 2, 6, 12        # K != V, as the 96 x 192 state


@functools.partial(jax.jit, static_argnames=("case", "seed"))
def inputs(seg, case: str, seed=0):
    """q, k unit-length a head (q times DK ** -0.5), v, g <= 0 ONE a head and
    beta in (0, 2), zero at padding, as the mixer hands them over."""
    r, t = seg.shape
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (r, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (r, t, H, DK)))
    v = jax.random.normal(ks[2], (r, t, H, DV))
    z = jax.random.normal(ks[3], (r, t, H))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (r, t, H)))
    if case == "mild":                  # the seeded model's: -16 * 0.1 at most
        g = -jax.nn.softplus(z - 2.0)
    elif case == "minus_60_a_token":    # no bound holds it: e^{-60 * chunk}
        g = -60.0 * jax.nn.sigmoid(3.0 * z)
    else:                               # beta near 2 on keys that repeat:
        assert case == "beta_1.99_repeated_keys"    # (I - b k k^T) flips k
        g = -0.05 * jax.nn.sigmoid(z)
        k = jnp.broadcast_to(k[:, :1], k.shape)
        beta = jnp.full((r, t, H), 1.99)
    valid = (seg > 0)[..., None]
    q, k, v = (jnp.where(valid[..., None], x, 0.0) for x in (q, k, v))
    return q, k, v, jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)


@jax.jit
def _one_document(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return reference.delta_rule(q, k, v, g, beta)


def token_by_token(q, k, v, g, beta, seg):
    """tests/test_kda.py's, over this file's recurrence (g a head); a caller
    jits it with the concrete `seg` closed over."""
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


CASES = ["mild", "minus_60_a_token", "beta_1.99_repeated_keys"]
STATIC = (6, 7, 8)      # chunk, sub, dtype: one compile serves every layout
rule = jax.jit(K.kda, static_argnums=STATIC)


def gradients(form, args, seg, chunk, sub, dtype):
    """d sum(form(...) * w) / d (q, k, v, g, beta), w seeded."""
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    return jax.jit(jax.grad(lambda *a: jnp.sum(
        form(*a, seg, chunk, sub, dtype) * w), range(5)))(*args)


# every layout and case at one chunk length, and the other lengths once
VALUE_CASES = [(layout, 8, case) for layout in sorted(LAYOUTS)
               for case in CASES] + [
    ("boundaries_inside_chunks", 16, "beta_1.99_repeated_keys"),
    ("a_chunk_of_padding_only", 32, "minus_60_a_token")]


@pytest.mark.parametrize("layout,chunk,case", VALUE_CASES)
def test_scalar_decay_chunked_form_equals_the_recurrence(layout, chunk, case):
    seg = segment_ids(LAYOUTS[layout])
    args = inputs(seg, case)
    if case == "minus_60_a_token":
        assert float(jnp.min(args[3])) < -55.0
    with jax.default_matmul_precision("highest"):
        # `sub` plays no part in this form: any value gives the same bits
        got = rule(*args, seg, chunk, chunk, jnp.float32)
        other = rule(*args, seg, chunk, 4, jnp.float32)
    want = jax.jit(lambda *a: token_by_token(*a, seg))(*args)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    assert got.shape == seg.shape + (H, DV)
    np.testing.assert_array_equal(got, other)
    # values reach 7 where beta is 1.99 and the keys repeat
    np.testing.assert_allclose(
        got, want, rtol=2e-4,
        atol=2e-6 * max(1.0, float(jnp.max(jnp.abs(want)))))
    assert float(jnp.max(jnp.abs(got * (seg == 0)[..., None, None]))) == 0.0
    assert float(jnp.max(jnp.abs(want))) > 1e-2


@pytest.mark.parametrize("layout,case", [
    ("boundaries_inside_chunks", "mild"),
    ("boundaries_inside_chunks", "minus_60_a_token"),
    ("boundaries_inside_chunks", "beta_1.99_repeated_keys"),
    ("a_chunk_of_padding_only", "mild")])
def test_scalar_decay_gradients_equal_the_recurrences(layout, case):
    seg = segment_ids(LAYOUTS[layout])
    args = inputs(seg, case, seed=1)
    with jax.default_matmul_precision("highest"):
        got = gradients(K.kda, args, seg, 8, 8, jnp.float32)
    want = gradients(lambda *a: token_by_token(*a[:6]), args, seg, 8, 8,
                     jnp.float32)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        if float(jnp.max(jnp.abs(b))) < 1e-12:   # e^{-60}: nothing to hold
            assert float(jnp.max(jnp.abs(a))) < 1e-10, name
        else:
            assert reference.relative_gap(a, b) < 2e-4, name
        # every exponent is a difference taken first: nothing reaches padding
        assert float(jnp.max(jnp.abs(
            a * (seg == 0).reshape(seg.shape + (1,) * (a.ndim - 2))))) == 0.0


def test_scalar_decay_in_blocks_of_chunks_and_in_bfloat16(monkeypatch):
    """The per-chunk part made a block of chunks at a time gives what one
    block gives; bf16 operands stay finite and close at a decay no bound
    holds (every exp is of a difference that is at most 0)."""
    seg = segment_ids(LAYOUTS["boundaries_inside_chunks"])
    args = inputs(seg, "minus_60_a_token")
    whole = rule(*args, seg, 8, 8, jnp.float32)
    monkeypatch.setattr(K, "KDA_BLOCK_BYTES", 1)
    blocked = jax.jit(lambda *a: K.kda(*a, seg, 8, 8, jnp.float32))
    assert "while" in blocked.lower(*args).as_text()    # lax.map's loop
    np.testing.assert_allclose(blocked(*args), whole, rtol=1e-6, atol=1e-7)
    monkeypatch.undo()
    mild = inputs(seg, "mild")
    low = rule(*(x.astype(jnp.bfloat16) for x in mild[:3]), *mild[3:], seg,
               16, 16, jnp.bfloat16)
    assert low.dtype == jnp.float32 and np.isfinite(np.asarray(low)).all()
    assert reference.relative_gap(
        low, rule(*mild, seg, 16, 16, jnp.float32)) < 3e-2


# --- the per-channel form: to the bit what it was ---------------------------------

def kda_as_pr43(q, k, v, g, beta, segment_ids, chunk, sub, dtype):
    """`vitax.models.kda.kda` as PR 41 wrote it and PR 43 left it, letter for
    letter but for this name: the per-channel form before it gained a second
    form beside it."""
    r, t, h, dk = q.shape
    dv = v.shape[-1]
    c, nc, a = chunk, t // chunk, chunk // sub
    f32 = jnp.float32
    seg = segment_ids.reshape(r, nc, c)
    last = seg[:, :, -1]                            # who owns what a chunk leaves
    owner = jnp.pad(last, ((0, 0), (1, 0)))[:, :nc]     # ... and what it is given
    at = jnp.arange(c)
    not_after = at[:, None] >= at[None, :]          # key not after query
    # a key meets the queries of its own and of later sub-chunks
    upto = (at[None, :] // sub) <= jnp.arange(a)[:, None]       # (a, c)

    @jax.checkpoint
    def block(args):
        seg, owner, q, k, v, g, beta = args         # (R, chunks a block, c, ...)
        n = seg.shape[1]
        with jax.named_scope("kda_chunk"):
            q32, k32 = q.astype(f32), k.astype(f32)
            run = jnp.cumsum(g, axis=2)                             # R n l h k
            by_sub = run.reshape(r, n, a, sub, h, dk)
            mid = by_sub[:, :, :, sub // 2]                         # R n a h k
            row = jnp.exp(by_sub - mid[:, :, :, None])              # R n a s h k
            col = jnp.exp(jnp.where(
                upto[None, None, :, :, None, None],
                mid[:, :, :, None] - run[:, :, None], -jnp.inf))    # R n a j h k
            keys = (k32[:, :, None] * col).astype(dtype)

            def scores(x32):        # (x_l e^{G_l}) . (k_j e^{-G_j}): R n h l j
                rows = (x32.reshape(r, n, a, sub, h, dk) * row).astype(dtype)
                return jnp.einsum("rnashk,rnajhk->rnhasj", rows, keys,
                                  preferred_element_type=f32).reshape(
                                      r, n, h, c, c)

            see = ((seg[:, :, :, None] == seg[:, :, None, :])
                   & (seg[:, :, :, None] > 0))[:, :, None]          # R n 1 l j
            bh = beta.transpose(0, 1, 3, 2)                         # R n h l
            qk = jnp.where(see & not_after, scores(q32), 0.0)
            kk = jnp.where(see & (not_after & ~not_after.T), scores(k32),
                           0.0) * bh[..., None]
            solve = K.unit_lower_inverse(kk).astype(dtype)            # R n h l s
            reads = ((seg == owner[..., None]) & (seg > 0))[..., None, None]
            from_start = jnp.where(reads, jnp.exp(run), 0.0)        # R n l h k
            b4 = beta[..., None]
            w = jnp.einsum("rnhls,rnshk->rnhlk", solve,
                           (k32 * from_start * b4).astype(dtype),
                           preferred_element_type=f32)
            u0 = jnp.einsum("rnhls,rnshv->rnhlv", solve,
                            (v.astype(f32) * b4).astype(dtype),
                            preferred_element_type=f32)
            mine = ((seg == seg[:, :, -1:]) & (seg > 0))[..., None, None]
            to_end = jnp.exp(jnp.where(mine, run[:, :, -1:] - run, -jnp.inf))
            return (qk.astype(dtype), w.astype(dtype), u0,
                    (q32 * from_start).astype(dtype).transpose(0, 1, 3, 2, 4),
                    (k32 * to_end).astype(dtype).transpose(0, 1, 3, 2, 4),
                    jnp.exp(run[:, :, -1]))                         # R n h k

    def chunks(x):      # (R, T, ...) -> (R, nc, c, ...)
        return x.reshape(r, nc, c, *x.shape[2:])

    cb = K._chunk_block(4 * r * a * c * h * dk, nc)

    def blocked(x):     # (R, nc, ...) -> (nc / cb, R, cb, ...)
        return jnp.moveaxis(x.reshape(r, nc // cb, cb, *x.shape[2:]), 1, 0)

    def whole(x):       # and back
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape(r, nc, *x.shape[3:])

    args = (seg, owner, chunks(q), chunks(k), chunks(v), chunks(g),
            chunks(beta))
    if cb == nc:
        parts = block(args)
    else:
        parts = tuple(map(whole, jax.lax.map(block,
                                             tuple(map(blocked, args)))))
    qk, w, u0, q_start, k_end, decay_end = parts

    with jax.named_scope("kda_state"):
        through = jnp.where(((last == owner) & (last > 0))[..., None, None],
                            decay_end, 0.0)                         # R nc h k

        @jax.checkpoint
        def carry(state, inputs):
            qk, w, u0, q_start, k_end, through = inputs
            given = state.astype(dtype)
            u = (u0 - jnp.einsum("rhlk,rhkv->rhlv", w, given,
                                 preferred_element_type=f32)).astype(dtype)
            o = (jnp.einsum("rhlk,rhkv->rhlv", q_start, given,
                            preferred_element_type=f32)
                 + jnp.einsum("rhls,rhsv->rhlv", qk, u,
                              preferred_element_type=f32))
            state = state * through[..., None] + jnp.einsum(
                "rhlk,rhlv->rhkv", k_end, u, preferred_element_type=f32)
            return state, o

        _, o = jax.lax.scan(
            carry, jnp.zeros((r, h, dk, dv), f32),
            tuple(jnp.moveaxis(x, 1, 0)
                  for x in (qk, w, u0, q_start, k_end, through)))
        # (nc, R, h, c, v) -> (R, T, h, v)
        return o.transpose(1, 0, 3, 2, 4).reshape(r, t, h, dv)



rule_as_pr43 = jax.jit(kda_as_pr43, static_argnums=STATIC)


@pytest.mark.parametrize("decay", ["mixed", "at_the_bound"])
@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 16)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_per_channel_form_is_unchanged_to_the_bit(layout, chunk, sub, decay):
    seg = segment_ids(LAYOUTS[layout])
    args = test_kda.inputs(seg, decay)
    assert args[3].ndim == 4
    np.testing.assert_array_equal(
        rule(*args, seg, chunk, sub, jnp.float32),
        rule_as_pr43(*args, seg, chunk, sub, jnp.float32))


def test_per_channel_gradients_are_unchanged_to_the_bit():
    seg = segment_ids(LAYOUTS["boundaries_inside_chunks"])
    args = test_kda.inputs(seg, "mixed", seed=1)
    got, want = (gradients(form, args, seg, 16, 8, jnp.bfloat16)
                 for form in (K.kda, kda_as_pr43))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
