"""The expert layer's loops over blocks of sorted rows (vitax/models/
experts.py: `routed_experts`) against a per-token loop that knows no sort,
no block and no buffer: the value and the gradients of tokens, router and
the three stacked kernels, at every place a block's edge can fall."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.models import experts
from vitax.models.experts import SharedRoutedExperts

D, F, ROUTED, K, TOKENS = 32, 16, 8, 4, 1280     # 5,120 slots
ALL, NONE = (2, 3, 4, 5), (0, 1, 6, 7)           # held: experts 2..5
SKIP_ONE, LAST = (2, 4, 0, 1), (5, 0, 1, 7)

# name: (held, first, [(tokens, their experts in order of score)], padding
# tokens among the first, live slots); B is 512 with 4 of 8 held
CASES = {
    "nothing_routed_here": (4, 2, [(1280, NONE)], 0, 0),
    "under_one_block": (4, 2, [(100, ALL), (1180, NONE)], 0, 400),
    "exactly_one_block": (4, 2, [(128, ALL), (1152, NONE)], 0, 512),
    # 300 rows an expert: the edges at 512 and 1,024 fall inside two of them
    "three_blocks_an_expert_across_an_edge": (
        4, 2, [(300, ALL), (980, NONE)], 0, 1200),
    # loads 200, 0, 200, 150: the edge at 512 inside the last
    "an_empty_expert_between_two": (
        4, 2, [(200, SKIP_ONE), (150, LAST), (930, NONE)], 0, 550),
    "padding_tokens": (4, 2, [(300, ALL), (980, NONE)], 40, 1040),
    # ten blocks: the backward's second chunk holds two, above the first
    # chunk's stale rows
    "two_chunks_of_blocks": (4, 2, [(1280, ALL)], 0, 5120),
    # every row live (B is 1,024 here): the same work as one whole pass
    "every_expert_held": (8, 0, [(640, ALL), (640, NONE)], 0, 5120),
}


def layer_and_inputs(held, first, groups):
    layer = SharedRoutedExperts(ROUTED, held, first, K, F, 0, 2.5,
                                jnp.float32)
    keys = jax.random.split(jax.random.key(7), 3)
    x = 0.3 * jax.random.normal(keys[0], (1, TOKENS, D), jnp.float32)
    # the first ROUTED features ARE the router's logits: the j-th choice of a
    # token reads 3 - 0.4 j, every other expert -3
    chosen = np.concatenate([np.tile(e, (n, 1)) for n, e in groups])
    logits = np.full((TOKENS, ROUTED), -3.0, np.float32)
    np.put_along_axis(logits, chosen, np.float32(3.0) - 0.4 * np.arange(
        K, dtype=np.float32)[None], axis=1)
    x = x.at[0, :, :ROUTED].set(logits + 0.05 * x[0, :, :ROUTED])
    p = jax.jit(layer.init)(keys[1], x, jnp.ones((1, TOKENS), bool))["params"]
    p = jax.tree.map(lambda a: a + 0.2 * jax.random.normal(
        keys[2], a.shape, a.dtype), p)
    p["router"] = {"kernel": jnp.eye(D, ROUTED)
                   + 0.01 * p["router"]["kernel"]}
    return layer, x, p


def per_token(layer, p, x, valid):
    """One token at a time, one choice at a time: y = sum over the choices
    whose expert is held of w_j * down_e(silu(gate_e x) * up_e x)."""
    def one(x, valid):
        s = jax.nn.sigmoid(x @ p["router"]["kernel"])
        top, chosen = jax.lax.top_k(s, K)
        w = layer.routed_scale * top / jnp.sum(top)
        y = jnp.zeros_like(x)
        for j in range(K):
            e = chosen[j] - layer.expert_first
            held = (e >= 0) & (e < layer.experts_held) & valid
            gate, up, down = (p[name]["kernel"][jnp.clip(
                e, 0, layer.experts_held - 1)] for name in (
                    "experts_gate", "experts_up", "experts_down"))
            y = y + jnp.where(held, w[j], 0.0) * (
                (jax.nn.silu(x @ gate) * (x @ up)) @ down)
        return y
    return jax.vmap(one)(x[0], valid[0])[None]


@pytest.mark.parametrize("name", list(CASES))
def test_blocks_of_sorted_rows_against_a_per_token_loop(name):
    held, first, groups, padding, live = CASES[name]
    layer, x, p = layer_and_inputs(held, first, groups)
    valid = jnp.ones((1, TOKENS), bool).at[0, :padding].set(False)
    block = experts.block_rows(TOKENS * K, held, ROUTED)
    assert block == (1024 if held == ROUTED else 512)
    assert experts.BLOCKS_A_CHUNK * 512 < TOKENS * K      # "two_chunks"
    push = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def blocked(p, x):
        y, cols = layer.apply({"params": p}, x, valid,
                              mutable=["intermediates"])
        return jnp.sum(y * push), (y, cols["intermediates"])

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            y = per_token(layer, p, x, valid)
        return jnp.sum(y * push), y

    (_, (y, sown)), got = jax.jit(jax.value_and_grad(
        blocked, argnums=(0, 1), has_aux=True))(p, x)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True))(p, x)
    assert int(jnp.sum(sown["expert_load"][0])) == live
    assert int(sown["expert_rows_computed"][0]) == -(-live // block) * block
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5)
    if live:
        assert float(jnp.max(jnp.abs(want_y))) > 0.05
    flat_got, flat_want = (
        {jax.tree_util.keystr(k): v for k, v in
         jax.tree_util.tree_leaves_with_path(t)} for t in (got, want))
    assert len(flat_got) == 5               # router, three kernels, tokens
    for leaf, g in flat_got.items():
        w = flat_want[leaf]
        assert np.isfinite(np.asarray(g)).all(), leaf
        gap = float(jnp.linalg.norm(g - w))
        assert gap <= 1e-5 * max(float(jnp.linalg.norm(w)), 1e-3), (leaf, gap)
    if live:
        assert all(float(jnp.linalg.norm(w)) > 0 for w in flat_want.values())
