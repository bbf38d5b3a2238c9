"""The second level of liveness in the packed kernels
(vitax/ops/flash_blocked.py): inside a live block pair, the sub-tiles that
hold a pair some query may see. The table against the dense mask and against
the areas counted for the two packed cells' layouts; the kernels in interpret
mode, walking sub-tiles, against their own `skip=False` arm and a dense
softmax, on layouts whose image and document edges fall inside a sub-tile,
on one, and on a block pair with a single live sub-tile.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.data.packing import document_layout
from vitax.ops import flash_blocked as fb

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "traffic")


def traffic(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def rows_of(lengths, t):
    """(R, T) segment ids of rows holding images / documents of these lengths."""
    return document_layout(lengths, t, max(map(len, lengths)))["segment_ids"]


def moonvit_cell():
    cell = traffic("packed_2x8192_docmix")
    return rows_of([[h * w for h, w in row] for row in cell["rows"]],
                   cell["row_tokens"])


def laguna_cell():
    cell = traffic("packed_1x8192_codemix")
    return rows_of(cell["rows"], cell["row_tokens"])


def random_rows(seed, t=1024, r=3):
    rng = np.random.default_rng(seed)
    lengths = []
    for _ in range(r):
        row, left = [], t - int(rng.integers(0, 200))
        while left > 8:
            n = int(rng.integers(8, 400))
            row.append(min(n, left))
            left -= row[-1]
        lengths.append(row)
    return rows_of(lengths, t)


def dense_mask(seg, causal, window):
    """(R, T, T) bool: the pairs a query may see."""
    at = np.arange(seg.shape[1])
    see = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
    if causal:
        back = at[:, None] - at[None, :]
        see &= (back >= 0)[None]
        if window > 0:
            see &= (back < window)[None]
    return see


def tile_bits(seg, bq, bk, sub, skip=True, causal=False, window=0):
    """The table's sub-tile bits as (R, T / sq, T / sk) bool, beside the
    whole-pair table (R, T / bq, T / bk)."""
    sq, sk = sub
    r, t = seg.shape
    na, nc = bq // sq, bk // sk
    bits, _, bits_kq, _ = (np.asarray(x) for x in fb.packed_block_tables(
        jnp.asarray(seg), bq, bk, skip, causal, window, sub))
    bits = bits.reshape(r, t // bq, t // bk)
    np.testing.assert_array_equal(bits_kq.reshape(r, t // bk, t // bq),
                                  bits.transpose(0, 2, 1))
    tiles = ((bits.astype(np.uint32)[..., None] >> np.arange(na * nc,
                                                             dtype=np.uint32))
             & 1).astype(bool)
    tiles = tiles.reshape(r, t // bq, t // bk, na, nc).transpose(0, 1, 3, 2, 4)
    pairs = np.asarray(fb.packed_block_tables(
        jnp.asarray(seg), bq, bk, skip, causal, window)[0])
    return (tiles.reshape(r, t // sq, t // sk),
            pairs.reshape(r, t // bq, t // bk).astype(bool))


MASKS = {"segment": dict(), "causal": dict(causal=True),
         "window": dict(causal=True, window=512)}
LAYOUTS = {"moonvit_cell": moonvit_cell, "laguna_cell": laguna_cell,
           "random_0": lambda: random_rows(0), "random_1": lambda: random_rows(1),
           "random_2": lambda: random_rows(2)}


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_no_sub_tile_a_query_may_see_into_is_dead(layout, mask):
    seg = LAYOUTS[layout]()
    terms = dict(MASKS[mask])
    if "random" in layout and mask == "window":
        terms["window"] = 100          # cuts inside a sub-tile of 128
    bq, bk = (256, 512) if "random" in layout else (512, 1024)
    see = dense_mask(seg, terms.get("causal", False), terms.get("window", 0))
    r, t = seg.shape
    for sub in ((128, 128), (256, 128), (128, 256), (256, 256)):
        sq, sk = sub
        tiles, pairs = tile_bits(seg, bq, bk, sub, **terms)
        holds = see.reshape(r, t // sq, sq, t // sk, sk).any(axis=(2, 4))
        assert not (holds & ~tiles).any(), sub
        # a sub-tile is live only inside a live pair
        inside = np.repeat(np.repeat(pairs, bq // sq, 1), bk // sk, 2)
        assert not (tiles & ~inside).any(), sub
        every, _ = tile_bits(seg, bq, bk, sub, skip=False, **terms)
        assert every.all(), sub


@pytest.mark.parametrize("layout,mask,blocks,pairs,tiles256,needed", [
    ("moonvit_cell", "segment", (512, 1024), 119, 729, 40152304),
    ("laguna_cell", "causal", (512, 1024), 37, 234, 13028435),
    ("laguna_cell", "window", (512, 512), 31, 90, 3561562),
])
def test_computed_areas_of_the_two_cells(layout, mask, blocks, pairs, tiles256,
                                         needed):
    """ISSUE 33's table: live (512, 1024) or (512, 512) pairs today, live
    (256, 256) tiles inside them, and the pairs the mask lets through."""
    seg = LAYOUTS[layout]()
    terms = MASKS[mask]
    bq, bk = blocks
    tiles, whole = tile_bits(seg, bq, bk, (256, 256), **terms)
    assert (int(whole.sum()), int(tiles.sum())) == (pairs, tiles256)
    see = dense_mask(seg, terms.get("causal", False), terms.get("window", 0))
    assert int(see.sum()) == needed
    if layout == "moonvit_cell":
        assert pairs * bq * bk == 62390272 and tiles256 * 65536 == 47775744
    # the step's counter: the same tables, the kernels' own shapes, a mean
    # weighted by each kernel's matmuls
    want = sum(
        matmuls * sq * sk
        * int(tile_bits(seg, bq, bk, (sq, sk), **terms)[0].sum())
        for (sq, sk), matmuls in zip(
            fb._sub_tiles(terms.get("window", 0), bq, bk),
            fb.TILE_MATMULS)) / 9
    got = float(fb.computed_pairs(jnp.asarray(seg), **terms))
    assert got == pytest.approx(want, rel=1e-6)
    assert needed <= got <= pairs * bq * bk


# --- the kernels, interpret mode ---------------------------------------------

T, BQ, BK = 1024, 256, 512
EDGES = {
    # image and document edges inside a sub-tile of 128
    "inside": [[300, 340, 60], [90, 700]],
    # on sub-tile edges
    "on_an_edge": [[256, 384, 128], [128, 640, 256]],
    # 64 tokens at 512: block pair (q 2, k 1) has one live sub-tile of 128
    "single_tile": [[512, 64], [1024]],
}


def dense(q, k, v, see, heads, group):
    """o and lse of (R * H, T, Dh) q over (R * KV, T, Dh) k, v, in float32."""
    k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
    see = jnp.repeat(jnp.asarray(see), heads, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(see, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    finite = jnp.where(jnp.isfinite(lse), lse, 0.0)          # padding rows
    p = jnp.where(see, jnp.exp(s - finite[..., None]), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v), lse


@functools.partial(jax.jit, static_argnums=(5, 6))
def dense_and_grads(q, k, v, do, see, heads, group):
    """(o, lse, the gradients of sum(o * do) by q, k, v) of the dense softmax:
    one program a head layout, shared by the cases that differ in `see`."""
    def weighted(q, k, v):
        o, lse = dense(q, k, v, see, heads, group)
        return jnp.sum(o * do), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(
        weighted, (0, 1, 2), has_aux=True)(q, k, v)
    return o, lse, grads


@pytest.mark.parametrize("mask", ["segment", "causal", "window100", "window128"])
@pytest.mark.parametrize("edges", list(EDGES))
def test_kernels_walking_sub_tiles_equal_the_every_tile_arm(edges, mask):
    """Forward, lse and the three gradients: sub-tiles skipped against every
    sub-tile run, to the tolerances of tests/test_moonvit.py and
    tests/test_decoder.py, and both against a dense softmax."""
    seg = rows_of(EDGES[edges], T)
    r = seg.shape[0]
    causal = mask != "segment"
    window = int(mask[6:]) if mask.startswith("window") else 0
    heads, kv = (4, 2) if causal else (2, 2)
    group, dh = heads // kv, 16
    terms = dict(causal=causal, window=window, grouped=causal)
    tiles = fb.Tiles(fwd=(128, 256), dkv=(128, 128), dq=(256, 128))
    keys = jax.random.split(jax.random.key(len(edges) + window), 4)
    q, do = (jax.random.normal(kk, (r * heads, T, dh)) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (r * kv, T, dh)) for kk in keys[2:])
    hb = group if causal else heads
    segj = jnp.asarray(seg)

    def run(skip):
        o, lse = fb._packed_fwd(q, k, v, segj, dh ** -0.5, BQ, BK, hb, heads,
                                skip, tiles=tiles, **terms)
        grads = fb._packed_bwd(q, k, v, o, lse, do, segj, dh ** -0.5, BQ, BK,
                               hb, heads, skip, tiles=tiles, **terms)
        return (o, lse[:, 0], *grads)

    got, every = run(True), run(False)
    for a, b, atol in zip(got, every, (2e-5, 2e-5, 5e-5, 5e-5, 5e-5)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)
    see = dense_mask(seg, causal, window)
    want_o, want_lse, want_g = dense_and_grads(q, k, v, do, see, heads,
                                               group)
    valid = np.repeat(seg > 0, heads, axis=0)
    np.testing.assert_allclose(got[0], want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1])[valid],
                               np.asarray(want_lse)[valid], rtol=1e-5, atol=2e-5)
    for a, b in zip(got[2:], want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)
    assert not np.asarray(got[0])[~valid].any()            # padding: zero
    if edges == "single_tile" and mask == "segment":
        tile, _ = tile_bits(seg, BQ, BK, (128, 128))
        assert tile[0, 4:6, 4:8].sum() == 1                # pair (q 2, k 1)


def test_public_entries_walk_sub_tiles_of_the_default_shapes():
    """`packed_flash_attention` and `document_flash_attention` at blocks the
    default sub-tile shapes divide in several: values and gradients equal the
    `skip=False` arm."""
    seg = jnp.asarray(rows_of(EDGES["inside"][:1], T))
    keys = jax.random.split(jax.random.key(5), 4)
    q, w = (jax.random.normal(kk, (1, T, 4, 16)) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (1, T, 2, 16)) for kk in keys[2:])

    def both(fn, *a):
        return fn(*a), jax.grad(lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2))(*a)

    arms = [both(lambda q, k, v: fb.document_flash_attention(
        q, k, v, seg, 100, 512, 1024, skip), q, k, v) for skip in (True, False)]
    for a, b in zip(jax.tree.leaves(arms[0]), jax.tree.leaves(arms[1])):
        np.testing.assert_allclose(a, b, atol=5e-5)
    arms = [both(lambda q, k, v: fb.packed_flash_attention(
        q, k, v, seg, 512, 1024, skip), q, q * 0.5, q + 1.0)
        for skip in (True, False)]
    for a, b in zip(jax.tree.leaves(arms[0]), jax.tree.leaves(arms[1])):
        np.testing.assert_allclose(a, b, atol=5e-5)
