"""The fifth decoder shape (LFM2-MoE: gated short convolutions to one
grouped-query attention layer with a norm a head, a dense SwiGLU first and
routed experts ranked by a bias the trainer balances, no shared expert, a
tied table; vitax/models/decoder.py, gconv.py, experts.py) at small sizes on
the CPU, seeded weights: the program against the plain reference
(benchmark/reference/lfm2_moe.py) for the whole 5-layer model in float32 and
in bf16 beside a float8 control, the eight shares of the experts tied to the
uncut layer, the norm a head against the whole-width form, the balance rule,
the closed-form parameter count, the step's counters, the flags and the
loop. The mixer itself: tests/test_gated_conv.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from tests import decoder_cases as cases
from tests.test_latent_decoder import LENGTHS
from vitax.config import Config
from vitax.models import decoder
from vitax.models.experts import SharedRoutedExperts
from vitax.train import step as train_step

KINDS = ["conv", "conv", "full_attention", "conv", "conv"]
MLPS = ["dense"] + ["sparse"] * 4
TINY = dict(
    model_family="decoder", embed_dim=32, num_blocks=5, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=KINDS, layer_heads=[0, 0, 4, 0, 0],
    layer_mlps=MLPS, ffn_dim=48, expert_dim=24, experts_routed=16,
    experts_held=8, expert_first=0, experts_per_token=4, routed_scale=1.0,
    norm_eps=1e-5, rope_theta_full=1e6, tie_embeddings=True, head_norm=True,
    gconv_width=3, route_bias=True, route_weight_eps=1e-6, pack_tokens=32,
    pack_images=4, batch_size=2, dtype="float32")
# the configuration of the benchmark's cell under the program's names
LFM2 = dict(
    model_family="decoder", embed_dim=2048, num_blocks=5, vocab_rows=8192,
    kv_heads=8, head_size=64, layer_kinds=KINDS, layer_heads=[0, 0, 32, 0, 0],
    layer_mlps=MLPS, ffn_dim=11776, expert_dim=1536, experts_routed=64,
    experts_held=8, expert_first=0, experts_per_token=4, routed_scale=1,
    norm_eps=1e-5, rope_theta_full=1000000, tie_embeddings=True,
    head_norm=True, gconv_width=3, route_bias=True, route_weight_eps=1e-6,
    pack_tokens=8192, pack_images=6, batch_size=2)


def reference_shape(cfg):
    return dict(
        layer_types=list(cfg.layer_kinds), mlp_types=list(cfg.layer_mlps),
        heads=max(cfg.layer_heads), kv_heads=cfg.kv_heads,
        head_dim=cfg.head_size, eps=cfg.norm_eps,
        rope={"rope_theta": cfg.rope_theta_full, "rope_type": "default"},
        taps=cfg.gconv_width, top_k=cfg.experts_per_token,
        routed_scale=cfg.routed_scale, experts_routed=cfg.experts_routed,
        experts_held=(cfg.expert_first, cfg.experts_held))


@pytest.fixture(scope="module")
def case():
    cfg = Config(**TINY).validate()
    return cases.DecoderCase(cfg, reference, reference_shape(cfg), LENGTHS)


# --- (a) the whole model --------------------------------------------------------

def test_logits_match_the_reference(case):
    got = case.logits
    assert np.abs(got).max() > 0.2
    case.check_logits(padded=True)
    seg = np.asarray(case.batch["segment_ids"])
    assert float(np.abs(got[seg == 0]).max()) < 10.0      # finite at padding


def test_loss_and_every_gradient_leaf_match_the_reference(case):
    want_loss, want = case.loss_and_grads
    loss, grads, _ = case.plain
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want)
    # table and final norm; the dense conv run's 3 mixer leaves, 3 of the MLP
    # and 2 norms; three sparse runs' 3 (or the attention's 6) mixer leaves,
    # 5 of the experts and 2 norms
    assert len(flat) == len(jax.tree.leaves(grads)) == 2 + 8 + 10 + 13 + 10
    for (path, a), b in zip(flat, jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # a buffer: no gradient, either side
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        assert float(jnp.max(jnp.abs(a))) > 0.0, name
        assert reference.relative_gap(b, a) < 2e-3, name
    np.testing.assert_allclose(*(
        jax.jit(lambda g: reference.global_norm(reference.leaf_norms(g)))(g)
        for g in (grads, want)), rtol=1e-4)
    case.check_first_rows()


def test_bfloat16_stays_inside_limits_that_a_float8_control_breaks(case):
    """The benchmark's control (weights rounded to float8_e4m3 for the
    program, the reference on the seeded ones) against the program in the
    precision the configuration states, gradient by gradient and on the
    logits: one limit between the two, as the cell's `correct` has."""
    from benchmark.generators.train_gated_conv_packed import (
        round_to_float8, watched_leaves)
    from vitax.train.step import decoder_loss
    cfg, variables, batch, plain = (case.cfg, case.variables, case.batch,
                                    case.plain)
    model = decoder.build_decoder(Config(**{**TINY, "dtype": "bfloat16"}))

    @jax.jit
    def grads_and_logits(v):
        grads = jax.grad(lambda v: decoder_loss(
            model.apply(v, batch, True), batch))(v)
        return watched_leaves(grads, cfg), model.apply(v, batch, True)

    want = watched_leaves(plain[1], cfg)
    assert sorted(want) == [
        "attention.k_norm", "attention.q_norm", "attention.wq", "first.conv",
        "first.in_proj", "first.out_proj", "last.conv", "last.in_proj",
        "last.out_proj", "sparse1.experts_gate", "sparse1.router",
        "sparse2.router", "sparse3.router", "sparse4.router"]
    assert want["first.conv"].shape == (3, 32)
    assert want["last.in_proj"].shape == (32, 96)
    assert want["attention.q_norm"].shape == (8,)       # one weight a head
    assert want["sparse1.experts_gate"].shape == (8, 32, 24)
    (sound, logits), (control, off) = (
        grads_and_logits(variables),
        grads_and_logits(jax.jit(round_to_float8)(variables)))
    rows = plain[2]
    at = [0, LENGTHS[0][0] - 1]
    # at 32 wide with every leaf moved by 0.05 bf16 reads 0.007 on the logits
    # and 0.015-0.040 on the leaves, the control 0.055 and 0.13-0.24
    assert reference.relative_gap(logits[0, at], rows[0]) < 0.02
    assert reference.relative_gap(off[0, at], rows[0]) > 0.02
    for name in want:
        # a router's gradient hangs on which tokens chose which expert: a
        # token whose fifth score lies within the rounding of its fourth goes
        # elsewhere than in the float32 reference (bf16 0.017-0.108, the
        # control 0.15-0.26)
        limit = 0.128 if name.endswith("router") else 0.07
        assert reference.relative_gap(sound[name], want[name]) < limit, name
        assert reference.relative_gap(control[name], want[name]) > limit, name


def test_the_layer_pattern_and_its_runs(case):
    cfg, variables = case.cfg, case.variables
    assert decoder.layer_runs(cfg.layer_kinds, cfg.layer_heads,
                              cfg.layer_mlps) == [
        (("conv", 0, "dense"), 1), (("conv", 0, "sparse"), 1),
        (("full_attention", 4, "sparse"), 1), (("conv", 0, "sparse"), 2)]
    p = variables["params"]
    assert sorted(p) == ["embed", "norm", "run0", "run1", "run2", "run3"]
    assert sorted(p["run3"]["blocks"]["mixer"]) == ["conv", "in_proj",
                                                    "out_proj"]
    assert p["run3"]["blocks"]["mixer"]["in_proj"]["kernel"].shape \
        == (2, 32, 96)
    attn = p["run2"]["blocks"]["attn"]
    assert sorted(attn) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape \
        == (1, 8)
    moe = p["run1"]["blocks"]["moe"]
    assert sorted(moe) == ["experts_down", "experts_gate", "experts_up",
                           "router", "router_bias"]        # no shared expert
    assert moe["router_bias"]["bias"].shape == (1, 16)


def test_the_scopes_a_metric_reads_are_in_the_lowered_program(case):
    model, variables, batch = case.model, case.variables, case.batch
    # a jitted helper that another file's mixer traced first in this process
    # (`jnp.pad` under `ssm.conv_silu`) would print that call stack here
    jax.clear_caches()
    text = jax.jit(lambda v: model.apply(v, batch, True)).lower(
        variables).as_text(debug_info=True)
    for scope in ("gconv_in", "gconv", "gconv_out", "qk_norm", "rope1d",
                  "moe_route", "moe_dispatch", "expert_ffn", "moe_combine",
                  "lm_head_loss"):
        assert f"/{scope}/" in text, scope
    assert "shared_expert" not in text and "conv_silu" not in text


def test_remat_keeps_o_and_lse_of_the_attention_layer_only():
    from vitax.programs.kernels import Kernels
    cfg = Config(**{**TINY, "pack_tokens": 2048,
                    "dtype": "bfloat16"}).validate()
    model = decoder.build_decoder(
        cfg, kernels=Kernels(attention=lambda *a: a[0]))
    assert decoder.keeps_attention_residuals(model, "full_attention")
    assert not decoder.keeps_attention_residuals(model, "conv")
    assert decoder.run_remat_policy(model, "conv", 2) is None


# --- (b) the share tied to the model --------------------------------------------

def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """What the eight chips that divide the 16 experts hold, each run alone
    by the PROGRAM's layer (router whole, two experts held), adds up to what
    the uncut REFERENCE gives for the whole layer: there is no shared expert
    to count once. And each share is the reference's on that share."""
    d, width, experts, k, n = 32, 24, 16, 4, 40
    x = jax.random.normal(jax.random.key(3), (1, n, d))
    valid = jnp.ones((1, n), bool)

    def layer(held, first):
        return SharedRoutedExperts(
            experts_routed=experts, experts_held=held, expert_first=first,
            experts_per_token=k, expert_dim=width, shared_dim=0,
            dtype=jnp.float32, route_bias=True, weight_eps=1e-6)

    whole = layer(experts, 0)
    p = cases.moved(jax.jit(whole.init)(jax.random.key(0), x, valid))[
        "params"]
    assert float(jnp.max(jnp.abs(p["router_bias"]["bias"]))) > 0.01

    @jax.jit
    def plain(p, held=None):
        with jax.default_matmul_precision("highest"):
            return reference.routed_experts(
                x[0], p, top_k=k, routed_scale=1.0, experts_routed=experts,
                experts_held=held)

    uncut = plain(p)
    total = 0.0
    for share in range(8):
        first = 2 * share
        part = {**p, **{f"experts_{m}": {"kernel": p[f"experts_{m}"][
            "kernel"][first:first + 2]} for m in ("gate", "up", "down")}}
        with jax.default_matmul_precision("highest"):
            got = jax.jit(layer(2, first).apply)({"params": part}, x,
                                                 valid)[0]
        np.testing.assert_allclose(
            got, jax.jit(lambda q, f=first: reference.routed_experts(
                x[0], q, top_k=k, routed_scale=1.0, experts_routed=experts,
                experts_held=(f, 2)))(part), rtol=2e-4, atol=2e-6)
        total = total + got
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(uncut))) > 0.02
    # the program's whole layer is the reference's too
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(whole.apply)({"params": p}, x, valid)[0], uncut,
            rtol=2e-4, atol=2e-6)


# --- (c) the norm a head --------------------------------------------------------

def test_the_norm_a_head_is_the_whole_width_form_on_one_head():
    """With ONE query and one key/value head the two forms of the QK-norm
    are one computation: the same weights give the same output. With four
    heads they differ, and the head form has one weight of head_size."""
    d, dh, n = 32, 8, 20
    x = jax.random.normal(jax.random.key(4), (1, n, d))
    seg = jnp.ones((1, n), jnp.int32)

    def attention(heads, head_norm):
        return decoder.DecoderAttention(
            heads=heads, kv_heads=heads, head_size=dh, window=0,
            head_gate=False, dtype=jnp.float32, qk_norm=1e-5,
            head_norm=head_norm)

    p = cases.moved(jax.jit(attention(1, True).init)(
        jax.random.key(0), x, seg, None))
    assert p["params"]["q_norm"]["scale"].shape == (dh,)
    np.testing.assert_allclose(
        jax.jit(attention(1, True).apply)(p, x, seg, None),
        jax.jit(attention(1, False).apply)(p, x, seg, None), rtol=1e-6)

    a_head = cases.moved(jax.jit(attention(4, True).init)(
        jax.random.key(0), x, seg, None))
    whole = jax.eval_shape(attention(4, False).init, jax.random.key(0), x,
                           seg, None)
    assert a_head["params"]["q_norm"]["scale"].shape == (dh,)
    assert whole["params"]["q_norm"]["scale"].shape == (4 * dh,)
    # the head form with its weight laid out four times is NOT the
    # whole-width form: the mean square is a head's, not the width's
    tiled = jax.tree.map(lambda a: a, a_head)
    tiled["params"]["q_norm"] = {"scale": jnp.tile(
        a_head["params"]["q_norm"]["scale"], 4)}
    tiled["params"]["k_norm"] = {"scale": jnp.tile(
        a_head["params"]["k_norm"]["scale"], 4)}
    got = jax.jit(attention(4, True).apply)(a_head, x, seg, None)
    other = jax.jit(attention(4, False).apply)(tiled, x, seg, None)
    assert float(jnp.max(jnp.abs(got - other))) > 1e-3
    # and it is the reference's attention mixer
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q: reference.attention_mixer(
            x[0], q, heads=4, kv_heads=4, head_dim=dh, eps=1e-5,
            rope={"rope_theta": 1e6}))(a_head["params"])
        positions = jnp.arange(n)[None]
        rope = decoder.rope_tables(positions,
                                   decoder.rope_inv_freq(dh, 1e6))
        np.testing.assert_allclose(
            jax.jit(attention(4, True).apply)(a_head, x, seg, rope)[0], want,
            rtol=2e-4, atol=2e-6)


# --- (d) the balance rule -------------------------------------------------------

def test_the_balance_rule_on_a_hand_case():
    bias = jnp.asarray([[0.0, 0.5, -0.5, 0.25], [1.0, 1.0, 1.0, 1.0]])
    load = jnp.asarray([[10, 2, 4, 4], [3, 3, 3, 3]], jnp.int32)
    got = jax.jit(train_step.balance_router_bias)(bias, load)
    u = train_step.BALANCE_RATE
    assert u == 1e-3
    # mean 5: the first is over it, the others under; a layer in balance
    # (and an expert AT the mean) does not move
    np.testing.assert_allclose(
        got, [[0.0 - u, 0.5 + u, -0.5 + u, 0.25 + u], [1.0] * 4], rtol=1e-6)
    np.testing.assert_allclose(
        jax.jit(train_step.balance_router_bias, static_argnums=2)(
            bias, load, 0.02)[0], [-0.02, 0.52, -0.48, 0.27], rtol=1e-6)


def test_the_bias_takes_no_gradient_and_the_step_moves_it_by_the_rule():
    """The step's own `route_load` moves every `router_bias` leaf by the
    rate, up or down or not at all, after the optimizer (whose decay of a
    leaf at 0 is 0); no other leaf of the tree is touched by the rule."""
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3}).validate()
    batch = cases.make_batch(cfg, LENGTHS)
    geom, state, step = cases.assembled(cfg)
    before = jax.device_get(state.params)
    grads = jax.jit(jax.grad(lambda v: train_step.decoder_loss(
        geom.model.apply(v, train_step.decoder_inputs(batch), True),
        batch)))(state.params)
    loads = jax.jit(lambda v: train_step.route_loads(geom.model.apply(
        v, train_step.decoder_inputs(batch), True,
        mutable=["intermediates"])[1]))(state.params)
    assert sorted(loads) == ["run1/blocks/moe", "run2/blocks/moe",
                             "run3/blocks/moe"]
    assert loads["run3/blocks/moe"].shape == (2, 16)
    state, m = step(state, batch, jax.random.key(1))
    for run, load in loads.items():
        name = run.split("/")[0]
        assert int(jnp.sum(load)) == 54 * 4 * load.shape[0]   # real tokens
        leaf = grads["params"][name]["blocks"]["moe"]["router_bias"]["bias"]
        assert not np.asarray(leaf).any()
        moved = (state.params["params"][name]["blocks"]["moe"]["router_bias"]
                 ["bias"] - before["params"][name]["blocks"]["moe"][
                     "router_bias"]["bias"])
        load = np.asarray(load, np.float64)
        np.testing.assert_allclose(
            moved, 1e-3 * np.sign(load.mean(-1, keepdims=True) - load),
            atol=1e-9)
    worst = max(float(np.max(np.asarray(v).max(-1) / np.asarray(v).mean(-1)))
                for v in loads.values())
    np.testing.assert_allclose(float(m["route_load_max_over_mean"]), worst,
                               rtol=1e-6)


def test_two_hundred_steps_of_the_rule_balance_a_skewed_router():
    """A seeded router whose logits are offset by expert (-1 to 1 over the
    16) sends its fullest expert over twice the mean; 200 steps of
    the trainer's rule on the same tokens bring every expert within a tenth
    of the mean."""
    d, experts, k, n = 32, 16, 4, 2048
    keys = jax.random.split(jax.random.key(5), 2)
    x = jax.random.normal(keys[0], (n, d))
    router = 0.1 * jax.random.normal(keys[1], (d, experts))
    offset = jnp.linspace(-1.0, 1.0, experts)

    def load_of(bias):
        from vitax.models.experts import choose
        scores = jax.nn.sigmoid(x @ router + offset)
        _, chosen, _ = choose(scores, bias, k, 0, 0)
        return jnp.sum(jax.nn.one_hot(chosen, experts, dtype=jnp.int32),
                       axis=(0, 1))

    @jax.jit
    def run(bias):
        def one(bias, _):
            return train_step.balance_router_bias(
                bias, load_of(bias), 5e-3), None
        return jax.lax.scan(one, bias, None, length=200)[0]

    start = np.asarray(jax.jit(load_of)(jnp.zeros(experts)))
    assert start.max() / start.mean() > 2.0
    bias = run(jnp.zeros(experts))
    end = np.asarray(jax.jit(load_of)(bias))
    assert end.sum() == start.sum() == n * k
    assert end.max() / end.mean() < 1.1
    # the under-loaded experts were raised, the over-loaded lowered
    assert float(bias[0]) > 0.1 and float(bias[-1]) < -0.1


# --- (e) counts, counters, configuration ----------------------------------------

def _count(cfg):
    shapes = jax.eval_shape(
        lambda: decoder.build_decoder(cfg).init(
            jax.random.key(0), decoder.sample_documents(cfg, 1), True))
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


def test_closed_form_parameter_count_and_the_configurations(case):
    assert sum(a.size for a in jax.tree.leaves(case.variables)) \
        == decoder.expected_param_count(case.cfg)
    # the configuration of the benchmark's cell, by shapes alone
    real = Config(**LFM2).validate()
    assert _count(real) == decoder.expected_param_count(real) == 469_285_248
    from benchmark import flops_lfm2
    from benchmark import manifest as mf
    config = mf.Manifest().config("lfm2_24b_a2b_ep8")
    assert flops_lfm2.param_count(config) == config["parameters"] \
        == 469_285_248
    built = Config(**mf.Manifest().config_kwargs(config), pack_tokens=8192,
                   pack_images=6, batch_size=2).validate()
    for key in LFM2:        # the nested block is the shape above
        assert getattr(built, key) == getattr(real, key), key
    # the whole published model: 40 layers, two dense, 64 experts, 65,536
    # rows: the model's own name, 24B-A2B
    kinds = (["conv", "conv", "full_attention"]
             + ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"])
    whole = Config(**{
        **LFM2, "num_blocks": 40, "layer_kinds": kinds,
        "layer_heads": [32 if k == "full_attention" else 0 for k in kinds],
        "layer_mlps": ["dense"] * 2 + ["sparse"] * 38, "experts_held": 64,
        "vocab_rows": 65536}).validate()
    assert decoder.expected_param_count(whole) == 23_843_661_440
    parts = flops_lfm2.param_counts_by_part(config)
    assert parts["conv_mixer"] == 16_783_360
    assert parts["attention_mixer"] == 10_485_888
    assert parts["dense_mlp"] == 72_351_744
    active = (decoder.expected_param_count(whole)
              - 38 * 60 * 3 * 2048 * 1536)      # 4 of 64 experts a token
    assert round(active / 1e9, 2) == 2.33


def test_train_step_counters_and_the_first_steps_moments():
    """Documents of 13, 5, 9 and 20, 7 tokens in two rows of 32: 54 tokens,
    10 of padding, 49 targets; causal pairs 91 + 15 + 45 + 210 + 28; the
    slots routed here are counted over the four sparse layers. And what the
    benchmark holds the TIMED step to: the gradients read from the optimizer
    state its first call left (`step_gradients`) are the model's own, with
    the clip at work."""
    from benchmark.generators import train_gated_conv_packed
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3,
                    "clip_grad_norm": 0.05}).validate()
    batch = cases.make_batch(cfg, LENGTHS)
    geom, step, state, first = cases.check_first_steps_moments(
        train_gated_conv_packed, cfg, batch, clipped=True)
    assert geom.model.kernels.conv is None
    _, m, losses = cases.take_steps(step, state, batch, 3)
    losses.insert(0, float(first["loss"]))
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs")}
    assert got == dict(tokens=54, padding_tokens=10, images=5, targets=49,
                       causal_pairs=389)
    load = np.asarray(m["expert_load"])
    assert load.shape == (4, 8) and load.sum() == m["expert_slots_here"]
    assert 0 < load.sum() <= 4 * 54 * 4
    assert float(m["route_load_max_over_mean"]) >= 1.0
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert "kda_pairs" not in m and "ssd_pairs" not in m
    from benchmark import flops_lfm2
    # the cell's layout (ISSUE 48)
    assert flops_lfm2.layout_counts(
        [[4300, 2100, 1150, 560], [3000, 2200, 1400, 900, 450, 180]],
        8192) == dict(tokens=16_240, documents=10, targets=16_230,
                      causal_pairs=20_698_620, padding_tokens=144)


def test_flops_count_the_new_kind():
    from vitax.telemetry.flops import decoder_flops_per_step
    cfg = Config(**LFM2).validate()
    slots = 4 * 8120.0
    flops = decoder_flops_per_step(cfg, 16_240, 16_230, 20_698_620, 0, slots)
    # ISSUE 48: 186.1M matmul parameters a token at an eighth of the slots
    # held, 6 FLOPs each, and half a TFLOP of attention
    assert 18.0e12 < flops < 19.0e12
    fewer = decoder_flops_per_step(cfg, 16_239, 16_230, 20_698_620, 0, slots)
    d = 2048
    per_token = (4 * 2 * 4 * d * d                     # the conv mixers
                 + 2 * (2 * d * d + 2 * d * 512)       # the attention layer's
                 + 6 * d * 11776 + 4 * 2 * d * 64)     # dense MLP, routers
    assert flops - fewer == 3 * per_token
    no_pairs = decoder_flops_per_step(cfg, 16_240, 16_230, 0, 0, slots)
    assert flops - no_pairs == 3 * 4 * 20_698_620 * 32 * 64


@pytest.mark.parametrize("change,message", [
    (dict(gconv_width=0), "a conv layer needs --gconv_width"),
    (dict(qk_norm=True), "two forms of one norm"),
    (dict(route_weight_eps=-1.0), "--route_weight_eps must be >= 0"),
    (dict(layer_heads=[0, 0, 3, 0, 0]), "multiple of --kv_heads"),
    (dict(layer_kinds=["conv", "conv", "full_attention", "conv", "gconv"]),
     "gconv"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_the_family_declares_the_new_shape_fields():
    assert {"gconv_width", "head_norm", "route_weight_eps", "route_bias",
            "tie_embeddings"} <= cases.family_declares("lfm2_moe")


def test_training_through_the_cli_path(tmp_path, capsys):
    """`python -m vitax.train --fake_data --model_family decoder` with conv
    layers, a norm a head and a balanced router bias (the flags through
    `parse_config`, then the loop the entry point calls): a falling loss, the
    balance counter on the step records, and the start-up line that says
    which convolution runs; no flag selects a form. (`--logits_scaling` is no
    part of the shape: three steps on random ids have a loss to bring down
    only where the tied table's logits start large, as in the hybrid shape's
    case.)"""
    cfg, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "64",
        "--pack_images", "6", "--embed_dim", "32", "--num_blocks", "5",
        "--vocab_rows", "48", "--kv_heads", "2", "--head_size", "8",
        "--layer_kinds", "conv,conv,full_attention,conv,conv",
        "--layer_heads", "0,0,4,0,0",
        "--layer_mlps", "dense,sparse,sparse,sparse,sparse",
        "--ffn_dim", "48", "--expert_dim", "24", "--experts_routed", "16",
        "--experts_held", "8", "--experts_per_token", "4", "--norm_eps",
        "1e-5", "--rope_theta_full", "1e6", "--tie_embeddings",
        "--head_norm", "--gconv_width", "3", "--route_bias",
        "--route_weight_eps", "1e-6", "--logits_scaling", "0.05")
    assert cfg.head_norm and cfg.gconv_width == 3 and cfg.route_bias
    assert cfg.route_weight_eps == 1e-6
    out = capsys.readouterr().out
    assert "mixer convolution: plain (no TPU)" in out
    assert "delta rule" not in out and "state-space scan" not in out
    assert "in conv layers" not in out
    for r in steps:
        assert r["route_load_max_over_mean"] >= 1.0
        assert r["expert_slots_here"] > 0
        assert "kda_pairs" not in r and "ssd_pairs" not in r


def test_the_start_up_line_says_why_the_plain_convolution_runs(monkeypatch):
    """On a TPU (here: forced) the gated convolution has no kernel: `plain
    (<why>)`, and no impl."""
    from vitax.programs import kernels as programs
    cfg = Config(**LFM2).validate()
    chosen = programs.choose_kernels(cfg, None, force_tpu_kernels=True)
    assert chosen.conv is None and chosen.scan is None and chosen.rule is None
    assert programs.kernel_lines(cfg, chosen)[1:] == [
        "mixer convolution: plain (no TPU)"]
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")
    assert programs.kernel_lines(cfg, chosen)[1].startswith(
        "mixer convolution: plain (a gated convolution")
