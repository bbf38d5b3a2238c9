"""The token decoder family (vitax/models/decoder.py, vitax/models/experts.py,
the document kernels of vitax/ops/flash_blocked.py) at small sizes on the CPU,
seeded weights: the program against the plain reference
(benchmark/reference/laguna.py) for the share and for the whole model, the
shares adding up, the kernels in interpret mode against a dense mask, routing
without drops at its edges, RoPE against closed forms, the step's counters,
and the loop on fake documents."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as reference
from tests import decoder_cases as cases
from vitax.config import Config
from vitax.data.packing import document_layout, pack_documents
from vitax.models import decoder
from vitax.models.experts import SharedRoutedExperts
from vitax.programs.kernels import Kernels

KINDS = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"]
ATTN_FACTOR = 1.4158883083359672
TINY = dict(
    model_family="decoder", embed_dim=64, num_blocks=5, vocab_rows=96,
    kv_heads=2, head_size=16, layer_kinds=KINDS, layer_heads=[6, 8, 8, 8, 6],
    layer_mlps=["dense", "sparse", "sparse", "sparse", "sparse"],
    window_tokens=8, ffn_dim=96, expert_dim=32, shared_expert_dim=32,
    experts_routed=16, experts_held=4, expert_first=4, experts_per_token=4,
    routed_scale=2.5, head_gate=True, rope_theta_full=500000.0,
    rope_fraction_full=0.5, yarn_factor=64.0, yarn_orig_len=16,
    yarn_beta_fast=64.0, yarn_beta_slow=1.0, yarn_attn_factor=ATTN_FACTOR,
    rope_theta_window=10000.0, rope_fraction_window=1.0, pack_tokens=64,
    pack_images=4, batch_size=2, dtype="float32")
ROPE = {"full_attention": dict(
    rope_theta=500000.0, rope_type="yarn", factor=64.0,
    original_max_position_embeddings=16, beta_slow=1.0, beta_fast=64.0,
    attention_factor=ATTN_FACTOR, partial_rotary_factor=0.5),
    "sliding_attention": dict(rope_type="default", rope_theta=10000.0,
                              partial_rotary_factor=1)}
LENGTHS = [[30, 12, 9], [20, 40]]


def reference_shape(cfg, routed=None):
    return dict(
        layer_types=list(cfg.layer_kinds), heads=list(cfg.layer_heads),
        mlp_types=list(cfg.layer_mlps), kv_heads=cfg.kv_heads,
        head_dim=cfg.head_size, window=cfg.window_tokens, eps=cfg.norm_eps,
        rope=ROPE, gating=True, top_k=cfg.experts_per_token,
        routed_scale=cfg.routed_scale,
        experts_routed=routed or cfg.experts_routed)


@pytest.fixture(scope="module", params=["share", "whole"])
def case(request):
    share = request.param == "share"
    cfg = Config(**{**TINY, **({} if share else dict(
        experts_held=16, expert_first=0))}).validate()
    held = (cfg.expert_first, cfg.experts_held) if share else None
    return cases.DecoderCase(
        cfg, reference, dict(reference_shape(cfg), experts_held=held),
        LENGTHS)


def test_logits_match_the_reference(case):
    case.check_logits(padded=False)


def test_loss_and_every_gradient_leaf_match_the_reference(case):
    want_loss, want = case.loss_and_grads
    loss, grads, _ = case.plain
    norms, want_norms = (jax.jit(reference.leaf_norms)(g)
                         for g in (grads, want))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(case.plain_loss, want_loss, rtol=1e-5)
    flat_want = jax.tree_util.tree_leaves_with_path(want_norms)
    flat_got = jax.tree.leaves(norms)
    assert len(flat_want) == len(flat_got) > 30
    for (path, a), b in zip(flat_want, flat_got):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(*(jax.jit(reference.global_norm)(n)
                                 for n in (norms, want_norms)), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(grads)):
        assert reference.relative_gap(b, a) < 2e-3
    case.check_first_rows()


def test_the_shares_add_up():
    """The 8 shares' routed parts plus the shared expert, counted once, are
    the uncut reference's layer."""
    d, routed, held, k = 64, 16, 2, 4
    whole = SharedRoutedExperts(routed, routed, 0, k, 32, 32, 2.5,
                                jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, d), jnp.float32)
    valid = jnp.ones((2, 24), bool)
    p = seeded_layer(whole, x, valid)

    @jax.jit
    def plain(p):
        with jax.default_matmul_precision("highest"):
            return reference.routed_and_shared(
                x.reshape(-1, d), p, top_k=k, routed_scale=2.5,
                experts_routed=routed, experts_held=None), \
                reference.swiglu(x.reshape(-1, d), p["shared"])

    want, shared = plain(p)
    total = shared
    for first in range(0, routed, held):
        share = SharedRoutedExperts(routed, held, first, k, 32, 32, 2.5,
                                    jnp.float32)
        cut = dict(p, **{
            name: {"kernel": p[name]["kernel"][first:first + held]}
            for name in ("experts_gate", "experts_up", "experts_down")})
        out = jax.jit(share.apply)({"params": cut}, x, valid)
        total = total + (out.reshape(-1, d) - shared)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.05  # the routed part


def seeded_layer(layer, x, valid):
    return cases.moved(jax.jit(layer.init)(jax.random.key(3), x, valid)[
        "params"], key=4, by=0.2)


def test_a_token_with_no_held_expert_and_an_expert_with_no_token():
    """Routing without drops at its edges: the router is set so that no token
    chooses held expert 1 and the tokens of the second row choose no held
    expert at all. Those tokens get the shared expert alone, the empty
    expert's gradient is zero, every token's slot is counted, nothing is
    dropped and nothing is not finite."""
    d, routed, held, k = 32, 8, 2, 2
    layer = SharedRoutedExperts(routed, held, 0, k, 16, 16, 1.0, jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (2, 12, d))) + 0.1
    x = x.at[1].multiply(-1.0)            # row 1: every feature negative
    valid = jnp.ones((2, 12), bool).at[0, -2:].set(False)     # and padding
    p = seeded_layer(layer, x, valid)
    router = jnp.zeros((d, routed))
    # positive features -> experts 0 (held) and 4; negative -> 5 and 6
    router = router.at[:, 0].set(1.0).at[:, 4].set(0.5)
    router = router.at[:, 5].set(-1.0).at[:, 6].set(-0.5)  # 1: chosen by none
    p = dict(p, router={"kernel": router})

    def run(p):
        y, cols = layer.apply({"params": p}, x, valid,
                              mutable=["intermediates"])
        return jnp.sum(y * y), (y, cols["intermediates"]["expert_load"][0])

    (_, (y, load)), grads = jax.jit(jax.value_and_grad(run, has_aux=True))(p)
    assert load.tolist() == [10, 0]       # row 0's valid tokens; none
    with jax.default_matmul_precision("highest"):
        shared = jax.jit(reference.swiglu)(x.reshape(-1, d), p["shared"])
    np.testing.assert_allclose(y[1], shared.reshape(2, 12, d)[1], rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(y[0, -2:], shared.reshape(2, 12, d)[0, -2:],
                               rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(y[0, :10] - shared.reshape(2, 12, d)[0, :10]
                                 ))) > 1e-3
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    for name in ("experts_gate", "experts_up", "experts_down"):
        g = np.asarray(grads[name]["kernel"])
        assert np.abs(g[1]).max() == 0.0 and np.abs(g[0]).max() > 0.0


# --- the kernels ------------------------------------------------------------

@pytest.mark.parametrize("group,window,skip", [
    (6, 0, True), (6, 0, False), (8, 96, True), (8, 96, False), (1, 40, True)],
    ids=["causal_6q", "causal_6q_every_pair", "window96_8q",
         "window96_8q_every_pair", "window40_1q"])
def test_document_kernels_match_a_dense_mask(group, window, skip):
    """Interpret mode against the dense mask: causal, window, documents,
    padding and 6 or 8 query heads a key/value head, values and gradients,
    with dead block pairs skipped and not."""
    from vitax.ops.flash_blocked import document_flash_attention
    r, t, kv, dh = 2, 512, 2, 32
    seg = jnp.asarray(document_layout([[200, 130, 90], [300, 180]], t,
                                      4)["segment_ids"])
    ks = jax.random.split(jax.random.key(group + window), 4)
    q = jax.random.normal(ks[0], (r, t, kv * group, dh), jnp.float32)
    k = jax.random.normal(ks[1], (r, t, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (r, t, kv, dh), jnp.float32)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)

    def kernel(q, k, v):
        return document_flash_attention(q, k, v, seg, window, 128, 128, skip)

    def dense(q, k, v):
        return decoder.causal_masked_attention(q, k, v, seg, window,
                                               jnp.float32)

    out = jax.jit(kernel)(q, k, v)
    np.testing.assert_allclose(out, jax.jit(dense)(q, k, v), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(out * (seg == 0)[..., None, None]))) == 0.0
    got, want = (jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                                  (0, 1, 2)))(q, k, v)
                 for f in (kernel, dense))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)


def test_block_tables_skip_what_the_masks_kill():
    """By hand, one row of four blocks of 128 holding documents of 300 and
    150 tokens: causal kills the pairs above the diagonal, the documents the
    pairs between them, a window of 100 everything two blocks back."""
    from vitax.ops.flash_blocked import packed_block_tables
    seg = jnp.asarray(document_layout([[300, 150]], 512, 4)["segment_ids"])

    def live(**kw):
        return np.asarray(packed_block_tables(seg, 128, 128, True, **kw)[0]
                          ).reshape(4, 4)
    both = np.array([[1, 1, 1, 0], [1, 1, 1, 0], [1, 1, 1, 1], [0, 0, 1, 1]])
    np.testing.assert_array_equal(live(), both)
    np.testing.assert_array_equal(live(causal=True), np.tril(both))
    near = np.tril(both) * (np.subtract.outer(np.arange(4), np.arange(4)) < 2)
    np.testing.assert_array_equal(live(causal=True, window=100), near)
    every = np.asarray(packed_block_tables(seg, 128, 128, False, True, 100)[0])
    assert every.all()


def test_model_through_the_kernels_equals_the_dense_path():
    cfg = Config(**{**TINY, "pack_tokens": 256}).validate()
    batch = cases.make_batch(cfg, [[120, 70, 40], [200, 30]])
    dense = decoder.build_decoder(cfg)
    variables = cases.seeded(dense, cfg)
    from vitax.ops.attention import make_attention_impl
    impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
    assert "causal" in impl.vitax_name
    through = decoder.build_decoder(cfg, kernels=Kernels(attention=impl))
    got, want = (jax.jit(lambda v, m=m: m.apply(v, batch, True))(variables)
                 for m in (through, dense))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_remat_keeps_o_and_lse_by_the_layers_span(monkeypatch):
    """PR 30's rule by run: a full layer's span is the row, a sliding
    layer's its window; the kept kernels are named, and the policy finds
    the name on the `pallas_call` equation of a traced forward."""
    from vitax.models import vit
    from vitax.ops.attention import make_attention_impl
    cfg = Config(**{**TINY, "pack_tokens": 2048, "window_tokens": 512,
                    "dtype": "bfloat16"}).validate()
    model = decoder.build_decoder(cfg, kernels=Kernels(
        attention=make_attention_impl(cfg, None, force_tpu_kernels=True)))
    assert model.span("full_attention") == 2048
    assert model.span("sliding_attention") == 512
    assert decoder.keeps_attention_residuals(model, "full_attention")
    assert not decoder.keeps_attention_residuals(model, "sliding_attention")
    assert decoder.run_remat_policy(model, "sliding_attention", 3) is None
    # a run of one layer is left to the compiler's merge
    assert decoder.run_remat_policy(model, "full_attention", 1) \
        is decoder._decoder_nothing_saveable
    cases.check_the_policy_keeps_by_the_traced_name(
        model, cfg, decoder.run_remat_policy(model, "full_attention", 2),
        "flash_causal_fwd")
    assert [n for _, n in model.runs()] == [1, 3, 1]
    monkeypatch.setattr(vit, "ATTN_KEEP_MIN_SPAN", 4096)
    assert not decoder.keeps_attention_residuals(model, "full_attention")


# --- RoPE against closed forms ----------------------------------------------

def test_plain_rope_against_the_closed_form():
    """Pair (i, i + rot / 2) turns by position * theta^(-2i / rot); scores
    depend on the offset only; dimensions past the rotated share pass."""
    inv = decoder.rope_inv_freq(8, 10000.0)
    np.testing.assert_allclose(inv, [10000.0 ** (-i / 4) for i in range(4)])
    x = jax.random.normal(jax.random.key(0), (1, 6, 1, 12))
    pos = jnp.asarray([[0, 1, 2, 5, 9, 30]])
    y = decoder.apply_rope(x, *decoder.rope_tables(pos, inv))
    x, y = np.asarray(x), np.asarray(y)
    for t, p in enumerate(np.asarray(pos[0])):
        for i in range(4):
            a, b = float(x[0, t, 0, i]), float(x[0, t, 0, i + 4])
            c, s = math.cos(p * inv[i]), math.sin(p * inv[i])
            np.testing.assert_allclose(
                [y[0, t, 0, i], y[0, t, 0, i + 4]],
                [a * c - b * s, b * c + a * s], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])


def test_yarn_against_the_closed_form():
    """Laguna-XS.2's full layers: 64 rotated dimensions, base 500,000, factor
    64 from 4,096 positions, beta 64 / 1. The dimension at which a frequency
    turns r times over 4,096 positions is 64 ln(4096 / (2 pi r)) / (2 ln
    500000): 5.66 for r = 64, 15.80 for r = 1, so frequencies 0-5 stay,
    16-31 are divided by 64 and 6-15 blend linearly in (i - 5) / 11; the
    published attention factor is 0.1 ln(64) + 1."""
    got = decoder.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], plain[16:] / 64.0, rtol=1e-12)
    for i in range(6, 16):
        ramp = (i - 5) / 11.0
        np.testing.assert_allclose(
            got[i], plain[i] * ((1 - ramp) + ramp / 64.0), rtol=1e-12)
    np.testing.assert_allclose(ATTN_FACTOR, 0.1 * math.log(64.0) + 1.0,
                               rtol=1e-12)
    full = dict(ROPE["full_attention"], original_max_position_embeddings=4096)
    ref, factor = reference.inv_frequencies(full, 128)
    np.testing.assert_allclose(ref, got, rtol=1e-12)
    assert factor == ATTN_FACTOR
    cos, sin = decoder.rope_tables(jnp.asarray([[3]]), got, ATTN_FACTOR)
    np.testing.assert_allclose(cos[0, 0, 0], ATTN_FACTOR * np.cos(3 * got),
                               rtol=1e-5)
    np.testing.assert_allclose(sin[0, 0, 0], ATTN_FACTOR * np.sin(3 * got),
                               rtol=1e-5)


# --- the step, the packer, the loop -----------------------------------------

def test_train_step_counters_against_a_layout_counted_by_hand():
    """Documents of 30, 12, 9 and 20, 40 tokens in two rows of 64, window 8:
    111 tokens, 17 of padding, 106 targets; causal pairs 465 + 78 + 45 + 210
    + 820; window pairs 36 + (n - 8) * 8 each."""
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3}).validate()
    _, state, step = cases.assembled(cfg)
    batch = cases.make_batch(cfg, LENGTHS)
    _, m, losses = cases.take_steps(step, state, batch, 4)
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "window_pairs")}
    assert got == dict(tokens=111, padding_tokens=17, images=5, targets=106,
                       causal_pairs=1618, window_pairs=748)
    # beside them what the kernels compute: the live sub-tiles of the tables
    # the kernels read (rows of 64 pad to one block of 128 each)
    from vitax.ops.flash_blocked import packed_block_tables
    seg = jnp.pad(batch["segment_ids"], ((0, 0), (0, 64)))
    for kind, window in (("causal", 0), ("window", cfg.window_tokens)):
        live = np.asarray(packed_block_tables(seg, 128, 128, True, True,
                                              window)[0])
        assert float(m[f"{kind}_computed_pairs"]) == live.sum() * 128 * 128
    load = np.asarray(m["expert_load"])
    assert load.shape == (4, 4)           # sparse layers x held experts
    assert int(m["expert_slots_here"]) == load.sum() <= 111 * 4 * 4
    # ... and the sorted rows the four layers' loops worked on for them:
    # whole blocks, under a block a layer more than the slots
    from vitax.models.experts import block_rows
    block = block_rows(128 * 4, 4, 16)
    rows = int(m["expert_rows_computed"])
    assert rows == sum(-(-int(n) // block) * block for n in load.sum(axis=1))
    assert load.sum() <= rows < load.sum() + 4 * block
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    from benchmark import flops_laguna
    assert flops_laguna.layout_counts(LENGTHS, 8) == dict(
        tokens=111, documents=5, targets=106, causal_pairs=1618,
        window_pairs=748)


def test_packer_places_every_token_once_and_splits_no_document():
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, 50, n).astype(np.int32)
            for n in (40, 30, 25, 20, 10, 7, 3, 60)]
    batch, counts = pack_documents(docs, rows=2, row_tokens=64,
                                   docs_per_row=3)
    placed = [i for i in range(len(docs)) if i not in counts["left"]]
    assert counts["documents"] == len(placed) and counts["left"]
    assert counts["tokens"] == sum(len(docs[i]) for i in placed)
    seg, pos = batch["segment_ids"], batch["positions"]
    found = reference.unpack(batch["tokens"], seg)
    assert sorted(map(tuple, found)) == sorted(tuple(docs[i]) for i in placed)
    for r in range(2):
        for s in range(1, seg[r].max() + 1):
            at = np.where(seg[r] == s)[0]
            assert (np.diff(at) == 1).all()
            np.testing.assert_array_equal(pos[r, at], np.arange(len(at)))
    assert (batch["tokens"][seg == 0] == 0).all()


@pytest.mark.parametrize("change,message", [
    (dict(tp_size=2), "tensor-, sequence- or pipeline-parallel"),
    (dict(sp_size=2), "tensor-, sequence- or pipeline-parallel"),
    (dict(pp_size=2), "tensor-, sequence- or pipeline-parallel"),
    (dict(ep_size=2), "--ep_size > 1 is not built"),
    (dict(att_dropout=0.1), "no dropout arm"),
    (dict(grad_accum_steps=2), "--grad_accum_steps"),
    (dict(task="probe"), "serving a decoder are not built"),
    (dict(layer_heads=[6, 8, 8, 8, 5]), "multiple of --kv_heads"),
    (dict(experts_held=14), "must lie within"),
    (dict(layer_kinds=KINDS[:4]), "one entry for each"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_every_config_field_is_a_knob_or_a_declared_shape():
    """The rule `benchmark/tests` holds (PERF.md section 7 (t)), called from
    tier-1: a field added to `Config` is a knob no file may set, or a shape
    some committed family declares (the decoder's: shapes/laguna.json)."""
    import dataclasses
    import os
    from benchmark import forms
    from benchmark import manifest as mf
    shapes = os.path.join(mf.BENCH_DIR, "shapes")
    declared = set()
    for name in sorted(os.listdir(shapes)):
        declared |= forms.declared_keys(
            mf.read_json(os.path.join(shapes, name)))
    knobs = forms.knob_keys(forms.rules())
    fields = {f.name for f in dataclasses.fields(Config)}
    assert fields - knobs - declared == set()
    assert not knobs & declared
    laguna = forms.declared_keys(mf.read_json(
        os.path.join(shapes, "laguna.json")))
    assert {"model_family", "layer_heads", "experts_held", "expert_first",
            "yarn_attn_factor"} <= laguna
    assert forms.manifest_problems(mf.Manifest()) == {}


def test_training_through_the_cli_path(tmp_path):
    """`python -m vitax.train --fake_data --model_family decoder` at the
    small shape (its flags through `parse_config`, then the loop the entry
    point calls): packed fake documents, `build_program("train")`, a
    checkpoint save, a falling loss and the decoder's counters on the step
    records."""
    _, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "128",
        "--pack_images", "6", "--embed_dim", "64", "--num_blocks", "5",
        "--vocab_rows", "96", "--kv_heads", "2", "--head_size", "16",
        "--layer_kinds", ",".join(KINDS), "--layer_heads", "6,8,8,8,6",
        "--layer_mlps", "dense,sparse,sparse,sparse,sparse",
        "--window_tokens", "8", "--ffn_dim", "96", "--expert_dim", "32",
        "--shared_expert_dim", "32", "--experts_routed", "16",
        "--experts_held", "4", "--experts_per_token", "4",
        "--routed_scale", "2.5", "--head_gate", "--rope_theta_full",
        "500000", "--rope_fraction_full", "0.5", "--yarn_factor", "64",
        "--yarn_orig_len", "16", "--yarn_beta_fast", "64",
        "--yarn_attn_factor", str(ATTN_FACTOR))
    for r in steps:
        assert r["targets"] > 0 and r["causal_pairs"] >= r["window_pairs"] > 0
        assert np.asarray(r["expert_load"]).shape == (4, 4)
        assert r["expert_slots_here"] == np.asarray(r["expert_load"]).sum()
        assert r["expert_slots_here"] <= r["expert_rows_computed"]
        assert r["expert_rows_over_slots"] == (
            r["expert_rows_computed"] / r["expert_slots_here"])
