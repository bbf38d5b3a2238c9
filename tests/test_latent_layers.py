"""The layers the third decoder shape added (Ling-3.0-flash), one by one at
small sizes on the CPU: latent attention against the reference's explicit
per-head keys, the two-width `flash_latent_*` kernels in interpret mode
against the dense mask, the grouped and biased choice against a written-out
loop, and ONE share test: the expert and head shares add up to the uncut
reference's layers. The whole model: tests/test_latent_decoder.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling as reference
from tests.decoder_cases import moved
from tests.test_latent_decoder import LENGTHS
from vitax.data.packing import document_layout
from vitax.models import decoder
from vitax.models.experts import SharedRoutedExperts, choose
from vitax.models.kda import KDAMixer, KDAShape


# --- latent attention -----------------------------------------------------------

def test_latent_attention_equals_explicit_per_head_keys():
    """`LatentAttention` (one shared rotated key laid beside each head's own
    part) against the reference's layer with every head's key written out,
    one document at a time: values and every leaf's gradient."""
    shape = decoder.LatentShape(rank=12, nope=8, rope=4, value=8)
    layer = decoder.LatentAttention(heads=3, shape=shape, head_gate=True,
                                    norm_eps=1e-6, dtype=jnp.float32)
    lay = document_layout(LENGTHS, 32, 4)
    seg = jnp.asarray(lay["segment_ids"])
    u = jax.random.normal(jax.random.key(1), (2, 32, 24))
    w = jax.random.normal(jax.random.key(2), u.shape)
    positions = jnp.asarray(lay["positions"])
    rope = decoder.rope_tables(positions, decoder.rope_inv_freq(4, 6e6))
    p = moved(jax.jit(layer.init)(jax.random.key(0), u, seg, rope)["params"],
              by=0.1)

    def program(p):
        return jnp.sum(layer.apply({"params": p}, u, seg, rope) * w)

    @jax.jit
    def alone(p, u, w):     # a document followed by zeros it cannot see
        return jnp.sum(reference.latent_mixer(
            u, p, 1e-6, rank=12, nope=8, rope=4, value=8, theta=6e6) * w)

    def plain(p):
        total = 0.0
        for r in range(2):
            for s in range(1, lay["segment_ids"][r].max() + 1):
                at = np.where(lay["segment_ids"][r] == s)[0]
                fill = ((0, 32 - len(at)), (0, 0))
                total += alone(p, jnp.pad(u[r, at], fill),
                               jnp.pad(w[r, at], fill))
        return total

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(plain))(p)
    got, got_grads = jax.jit(jax.value_and_grad(program))(p)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    assert len(flat) == 6
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        assert reference.relative_gap(a, b) < 5e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("heads,dqk,dv,skip", [
    (4, 48, 32, True), (4, 48, 32, False), (3, 24, 16, True),
    (8, 192, 128, True)],
    ids=["48_32", "48_32_every_pair", "3_heads_24_16", "192_128"])
def test_latent_kernels_match_a_dense_mask(heads, dqk, dv, skip):
    """Interpret mode against the dense mask: q and k wider than v, every
    head a key of its own, causal within documents, padding; values and
    gradients, the output and dV at v's width."""
    from vitax.ops.flash_blocked import document_flash_attention
    r, t = (2, 512) if dqk < 192 else (1, 256)
    rows = [[200, 130, 90], [300, 180]] if r == 2 else [[150, 60, 30]]
    seg = jnp.asarray(document_layout(rows, t, 4)["segment_ids"])
    ks = jax.random.split(jax.random.key(heads + dqk), 4)
    q = jax.random.normal(ks[0], (r, t, heads, dqk), jnp.float32)
    k = jax.random.normal(ks[1], (r, t, heads, dqk), jnp.float32)
    v = jax.random.normal(ks[2], (r, t, heads, dv), jnp.float32)
    w = jax.random.normal(ks[3], v.shape, jnp.float32)

    def kernel(q, k, v):
        return document_flash_attention(q, k, v, seg, 0, 128, 128, skip)

    def dense(q, k, v):
        return decoder.causal_masked_attention(q, k, v, seg, 0, jnp.float32)

    out = jax.jit(kernel)(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, jax.jit(dense)(q, k, v), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(out * (seg == 0)[..., None, None]))) == 0.0
    got, want = (jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                                  (0, 1, 2)))(q, k, v)
                 for f in (kernel, dense))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)


def test_the_kernels_refuse_two_widths_with_grouped_keys_or_a_window():
    from vitax.ops.flash_blocked import document_flash_attention
    seg = jnp.ones((1, 128), jnp.int32)
    q = jnp.zeros((1, 128, 4, 24))
    with pytest.raises(AssertionError):
        document_flash_attention(q, q[:, :, :2], jnp.zeros((1, 128, 2, 16)),
                                 seg)
    with pytest.raises(AssertionError):
        document_flash_attention(q, q, jnp.zeros((1, 128, 4, 16)), seg,
                                 window=8)



# --- the router -----------------------------------------------------------------

def choice_by_a_loop(scores, bias, k, groups, kept):
    """DeepSeek-V3's selection written out a token at a time."""
    n, e = scores.shape
    per = e // groups
    out = []
    for t in range(n):
        ranked = scores[t] + (0.0 if bias is None else bias)
        group_score = [np.sort(ranked[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(groups)]
        best = np.argsort(group_score)[::-1][:kept]
        allowed = [i for g in best for i in range(g * per, (g + 1) * per)]
        allowed.sort(key=lambda i: -ranked[i])
        out.append(sorted(allowed[:k]))
    return np.array(out)


@pytest.mark.parametrize("groups,kept,k,biased", [
    (4, 2, 4, False), (4, 2, 4, True), (8, 4, 8, True), (2, 1, 3, True),
    (0, 0, 4, True)], ids=["groups", "groups_bias", "8_groups_of_8",
                           "one_group_kept", "bias_alone"])
def test_the_choice_against_a_written_out_loop(groups, kept, k, biased):
    e = 64 if groups == 8 else 16
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(k), (40, e)))
    bias = 0.3 * jax.random.normal(jax.random.key(7), (e,)) if biased \
        else None
    top, chosen, kept_groups = jax.jit(choose, static_argnums=(2, 3, 4))(
        scores, bias, k, groups, kept)
    want = choice_by_a_loop(np.asarray(scores), None if bias is None
                            else np.asarray(bias), k, groups or 1,
                            kept or 1)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), axis=-1), want)
    # the weights come from the UNBIASED scores of the chosen
    np.testing.assert_array_equal(
        top, np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1))
    if groups:
        assert kept_groups.shape == (40, groups)
        assert (np.asarray(kept_groups).sum(-1) == kept).all()
        inside = np.take_along_axis(
            np.repeat(np.asarray(kept_groups), e // groups, axis=1),
            np.asarray(chosen), -1)
        assert inside.all()
    else:
        assert kept_groups is None
    # the reference's own selection agrees
    ref = jax.jit(lambda s, b: reference.chosen_experts(
        s, b, top_k=k, groups=groups or 1, groups_kept=kept or 1))(
            scores, bias)
    np.testing.assert_array_equal(np.sort(np.asarray(ref), axis=-1), want)


def test_a_bias_changes_the_choice_and_not_the_weights():
    """With the bias at zero the layer is the unbiased one; a bias that
    favours experts a token would not choose changes WHICH experts add, and
    each chosen expert's weight is still its unbiased score over the chosen
    ones' sum; the bias receives no gradient."""
    d, routed, k = 32, 16, 4
    layer = SharedRoutedExperts(routed, routed, 0, k, 16, 16, 2.5,
                                jnp.float32, route_groups=4,
                                groups_per_token=2, route_bias=True)
    x = jax.random.normal(jax.random.key(1), (1, 24, d))
    valid = jnp.ones((1, 24), bool)
    p = moved(jax.jit(layer.init)(jax.random.key(3), x, valid)["params"],
              key=4, by=0.2)
    zero = dict(p, router_bias={"bias": jnp.zeros((routed,))})
    pushed = dict(p, router_bias={"bias": jnp.zeros((routed,)).at[
        jnp.asarray([3, 7, 11, 15])].set(5.0)})

    @jax.jit
    def layer_out(p):
        return layer.apply({"params": p}, x, valid)

    @jax.jit
    def plain(p):
        with jax.default_matmul_precision("highest"):
            return reference.routed_and_shared(
                x.reshape(-1, d), p, top_k=k, groups=4, groups_kept=2,
                scale=2.5, bias=True, experts_routed=routed,
                experts_held=None).reshape(x.shape)

    for params in (zero, pushed, p):
        np.testing.assert_allclose(layer_out(params), plain(params),
                                   rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(layer_out(zero) - layer_out(pushed)))) > 1e-2
    scores = jax.nn.sigmoid(x.reshape(-1, d) @ p["router"]["kernel"])
    (top0, chosen0, _), (top1, chosen1, _) = (
        jax.jit(choose, static_argnums=(2, 3, 4))(
            scores, q["router_bias"]["bias"], k, 4, 2) for q in (zero, pushed))
    assert (np.sort(chosen0, -1) != np.sort(chosen1, -1)).any()
    # two of the four favoured experts lie in each pair of kept groups
    assert (np.isin(chosen1, [3, 7, 11, 15]).sum(-1) == 2).all()
    assert float(jnp.max(top1)) < 1.0           # scores, not scores + 5
    grads = jax.jit(jax.grad(lambda p: jnp.sum(layer_out(p) ** 2)))(pushed)
    assert float(jnp.max(jnp.abs(grads["router_bias"]["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["router"]["kernel"]))) > 0.0


def test_zero_groups_and_no_bias_is_the_plain_layer():
    """Laguna's program does not change: without groups and bias the layer
    has no `router_bias` leaf, sows no group count and lowers to the text it
    lowered to before."""
    d = 32
    x = jax.random.normal(jax.random.key(1), (1, 12, d))
    valid = jnp.ones((1, 12), bool)
    plain = SharedRoutedExperts(8, 2, 0, 2, 16, 16, 1.0, jnp.float32)
    p = jax.jit(plain.init)(jax.random.key(3), x, valid)
    assert "router_bias" not in p["params"]
    _, cols = jax.jit(lambda p: plain.apply(
        p, x, valid, mutable=["intermediates"]))(p)
    assert sorted(cols["intermediates"]) == ["expert_load",
                                             "expert_rows_computed"]
    grouped = SharedRoutedExperts(8, 2, 0, 2, 16, 16, 1.0, jnp.float32,
                                  route_groups=2, groups_per_token=1)
    _, cols = jax.jit(lambda p: grouped.apply(
        p, x, valid, mutable=["intermediates"]))(p)
    assert sorted(cols["intermediates"]) == ["expert_load",
                                             "expert_rows_computed",
                                             "tokens_choosing_held_group"]
    assert 0 <= int(cols["intermediates"]["tokens_choosing_held_group"][0]) \
        <= 12


# --- the shares ---------------------------------------------------------------------

def test_the_expert_and_head_shares_add_up_to_the_uncut_layers():
    """ONE share test over the deployment's cut: all 64 expert shares (128
    experts in 8 groups, 2 held a chip) and both head shares (4 heads, 2 a
    chip), with what every chip computes alike (shared expert, router and
    its bias, the latent's down-projection and norm, the output norm's
    weight) counted once: the parts add up to the uncut reference's layer
    output, for the sparse feed-forward, the kda mixer and the latent
    layer."""
    d, n = 32, 24
    x = jax.random.normal(jax.random.key(1), (1, n, d), jnp.float32)
    seg = jnp.asarray(document_layout([[14, 7]], n, 2)["segment_ids"])
    valid = seg > 0
    at = [np.where(np.asarray(seg[0]) == s)[0] for s in (1, 2)]

    # the sparse feed-forward: 64 shares of 2 of 128 experts
    routed, held, k = 128, 2, 8
    whole = SharedRoutedExperts(routed, routed, 0, k, 16, 16, 2.5,
                                jnp.float32, route_groups=8,
                                groups_per_token=4, route_bias=True)
    p = moved(jax.jit(whole.init)(jax.random.key(3), x, valid)["params"],
              key=4, by=0.2)

    @jax.jit
    def plain(p):
        with jax.default_matmul_precision("highest"):
            return reference.routed_and_shared(
                x.reshape(-1, d), p, top_k=k, groups=8, groups_kept=4,
                scale=2.5, bias=True, experts_routed=routed,
                experts_held=None), \
                reference.swiglu(x.reshape(-1, d), p["shared"])

    @jax.jit
    def share_of(p, first):
        # `first` traced, so that ONE program serves the 64 shares: the layer
        # only subtracts it from and divides it into what it chose
        share = SharedRoutedExperts(routed, held, first, k, 16, 16, 2.5,
                                    jnp.float32, route_groups=8,
                                    groups_per_token=4, route_bias=True)
        cut = dict(p, **{
            name: {"kernel": jax.lax.dynamic_slice_in_dim(
                p[name]["kernel"], first, held)}
            for name in ("experts_gate", "experts_up", "experts_down")})
        return share.apply({"params": cut}, x, valid,
                           mutable=["intermediates"])

    want, shared = plain(p)
    total, slots, kept = shared, 0, []
    for first in range(0, routed, held):
        out, cols = share_of(p, first)
        total = total + (out.reshape(-1, d) - shared)
        slots += int(jnp.sum(cols["intermediates"]["expert_load"][0]))
        kept.append(int(
            cols["intermediates"]["tokens_choosing_held_group"][0]))
    np.testing.assert_allclose(total * valid.reshape(-1, 1),
                               want * valid.reshape(-1, 1), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.05  # the routed part
    assert slots == 21 * k              # every valid token's every choice
    # a token keeps 4 of the 8 groups; the 8 chips of a group count alike
    assert sum(kept) == 21 * 4 * 8 and len(set(kept[:8])) == 1

    # the kda mixer: two shares of 2 of 4 heads
    heads, dh = 4, 8
    mixer = KDAMixer(KDAShape(heads, dh, 4, -5.0), 1e-6, jnp.float32)
    p = moved(jax.jit(mixer.init)(jax.random.key(5), x, seg)["params"],
              key=6, by=0.1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: jnp.concatenate([reference.kda_mixer(
            x[0, a], p, 1e-6, head_dim=dh, taps=4, gate_bound=-5.0)
            for a in at]))(p)
    inner = heads * dh
    total = 0.0
    for first in (0, 2):
        cols = slice(first * dh, (first + 2) * dh)
        hs = slice(first, first + 2)
        conv = p["conv"]["kernel"]
        cut = {
            **{name: {"kernel": p[name]["kernel"][:, cols]}
               for name in ("wq", "wk", "wv", "wf")},
            "conv": {"kernel": jnp.concatenate(
                [conv[:, part * inner:(part + 1) * inner][:, cols]
                 for part in range(3)], axis=1)},
            "A_log": {"scale": p["A_log"]["scale"][hs]},
            "dt_bias": {"bias": p["dt_bias"]["bias"][cols]},
            "wb": {"kernel": p["wb"]["kernel"][:, hs]},
            "head_gate": {"kernel": p["head_gate"]["kernel"][:, hs]},
            "out_norm": p["out_norm"],
            "wo": {"kernel": p["wo"]["kernel"][cols]}}
        share = KDAMixer(KDAShape(2, dh, 4, -5.0), 1e-6, jnp.float32)
        total = total + jax.jit(share.apply)({"params": cut}, x, seg)[0, :21]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)

    # the latent layer: two shares of 2 of 4 heads, the latent counted once
    shape = decoder.LatentShape(rank=12, nope=8, rope=4, value=8)
    lay = document_layout([[14, 7]], n, 2)
    rope = decoder.rope_tables(jnp.asarray(lay["positions"]),
                               decoder.rope_inv_freq(4, 6e6))
    layer = decoder.LatentAttention(heads=4, shape=shape, head_gate=True,
                                    norm_eps=1e-6, dtype=jnp.float32)
    p = moved(jax.jit(layer.init)(jax.random.key(7), x, seg, rope)["params"],
              key=8, by=0.1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: jnp.concatenate([reference.latent_mixer(
            x[0, a], p, 1e-6, rank=12, nope=8, rope=4, value=8, theta=6e6)
            for a in at]))(p)
    total = 0.0
    for first in (0, 2):
        hs = slice(first, first + 2)
        cut = {
            "wq": {"kernel": p["wq"]["kernel"].reshape(d, 4, 12)[:, hs]
                   .reshape(d, 24)},
            "wkva": p["wkva"], "latent_norm": p["latent_norm"],
            "wkvb": {"kernel": p["wkvb"]["kernel"].reshape(12, 4, 16)[:, hs]
                     .reshape(12, 32)},
            "head_gate": {"kernel": p["head_gate"]["kernel"][:, hs]},
            "wo": {"kernel": p["wo"]["kernel"].reshape(4, 8, d)[hs]
                   .reshape(16, d)}}
        share = decoder.LatentAttention(
            heads=2, shape=shape, head_gate=True, norm_eps=1e-6,
            dtype=jnp.float32)
        total = total + jax.jit(share.apply)({"params": cut}, x, seg,
                                             rope)[0, :21]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
