"""The fourth decoder shape (Olmo-Hybrid: Gated-DeltaNet layers to one
full-attention layer in Olmo's norm-after block with QK-norm over the whole
projected width; vitax/models/decoder.py, kda.py) at small sizes on the CPU,
seeded weights: the program against the plain reference
(benchmark/reference/olmo_hybrid.py) for the whole 4-layer model in float32
and in bf16 beside a float8 control, the share of the heads tied to the
uncut layer, the closed-form parameter count and the catalog's count a
layer, the step's counters, the configuration's sentences, the flags and the
loop. The delta rule itself: tests/test_gated_delta.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from tests import decoder_cases as cases
from tests.test_latent_decoder import LENGTHS
from vitax.config import Config
from vitax.models import decoder
from vitax.models.kda import GatedDeltaMixer, GatedDeltaShape
from vitax.programs.kernels import Kernels

KINDS = ["linear_attention"] * 3 + ["full_attention"]
TINY = dict(
    model_family="decoder", embed_dim=32, num_blocks=4, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=KINDS, layer_heads=[2] * 4,
    layer_mlps=["dense"] * 4, ffn_dim=48, norm_eps=1e-6,
    position_embedding="nope", gdn_key_size=6, gdn_value_size=12,
    gdn_conv_width=4, norm_after=True, qk_norm=True, pack_tokens=32,
    pack_images=4, batch_size=2, dtype="float32")
# the configuration of the benchmark's cell under the program's names
OLMO = dict(
    model_family="decoder", embed_dim=3840, num_blocks=4, vocab_rows=12544,
    kv_heads=15, head_size=128, layer_kinds=KINDS, layer_heads=[15] * 4,
    layer_mlps=["dense"] * 4, ffn_dim=11008, norm_eps=1e-6,
    position_embedding="nope", gdn_key_size=96, gdn_value_size=192,
    gdn_conv_width=4, norm_after=True, qk_norm=True, pack_tokens=4096,
    pack_images=5, batch_size=1)


def reference_shape(cfg):
    return dict(layer_types=list(cfg.layer_kinds), head_dim=cfg.head_size,
                eps=cfg.norm_eps,
                linear=dict(key_dim=cfg.gdn_key_size,
                            value_dim=cfg.gdn_value_size,
                            taps=cfg.gdn_conv_width))


@pytest.fixture(scope="module")
def case():
    cfg = Config(**TINY).validate()
    return cases.DecoderCase(cfg, reference, reference_shape(cfg), LENGTHS)


# --- the whole model ----------------------------------------------------------

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
    # embedding, head, final norm; the linear run's 11 mixer leaves, 3 of
    # the MLP and 2 norms; the attention run's 6, 3 and 2
    assert len(flat) == len(jax.tree.leaves(grads)) == 3 + 16 + 11
    for (path, a), b in zip(flat, jax.tree.leaves(grads)):
        assert float(jnp.max(jnp.abs(a))) > 0.0, path
        assert reference.relative_gap(b, a) < 2e-3, jax.tree_util.keystr(path)
    np.testing.assert_allclose(*(
        jax.jit(lambda g: reference.global_norm(reference.leaf_norms(g)))(g)
        for g in (grads, want)), rtol=1e-4)
    case.check_first_rows()


def test_bfloat16_stays_inside_limits_that_a_float8_control_breaks(case):
    """The benchmark's control (weights rounded to float8_e4m3 for the
    program, the reference on the seeded ones) against the program in the
    precision the configuration states, gradient by gradient and on the
    logits: one limit between the two, as the cell's `correct` has."""
    from benchmark.generators.train_gated_delta_packed import (
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
        "attention.q_norm", "attention.wq", "first.conv", "first.post_norm",
        "first.wa", "first.wb", "first.wq", "first.wz", "last.conv",
        "last.wa", "last.wb", "last.wq", "last.wz", "linear.A_log",
        "linear.dt_bias"]
    # A_log and dt_bias of the three linear layers together
    assert want["linear.A_log"].shape == want["linear.dt_bias"].shape == (6,)
    assert want["first.conv"].shape == (4, 2 * (6 + 6 + 12))
    assert want["attention.q_norm"].shape == (16,)
    (sound, logits), (control, off) = (
        grads_and_logits(variables),
        grads_and_logits(jax.jit(round_to_float8)(variables)))
    rows = plain[2]
    at = [0, LENGTHS[0][0] - 1]
    # at 32 wide with every leaf moved by 0.05 bf16 reads 0.07 on the logits
    # and 0.18-0.53 on the leaves, the control 0.60 and 0.94-5.8
    assert reference.relative_gap(logits[0, at], rows[0]) < 0.2
    assert reference.relative_gap(off[0, at], rows[0]) > 0.2
    for name in want:
        assert reference.relative_gap(sound[name], want[name]) < 0.7, name
        assert reference.relative_gap(control[name], want[name]) > 0.7, name


def test_the_layer_pattern_and_its_runs(case):
    cfg, variables = case.cfg, case.variables
    assert decoder.layer_runs(cfg.layer_kinds, cfg.layer_heads,
                              cfg.layer_mlps) == [
        (("linear_attention", 2, "dense"), 3),
        (("full_attention", 2, "dense"), 1)]
    mixer = variables["params"]["run0"]["blocks"]["mixer"]
    assert sorted(mixer) == ["A_log", "conv", "dt_bias", "out_norm", "wa",
                             "wb", "wk", "wo", "wq", "wv", "wz"]
    assert mixer["conv"]["kernel"].shape == (3, 4, 2 * (6 + 6 + 12))
    assert mixer["wa"]["kernel"].shape == (3, 32, 2)      # one decay a head
    assert mixer["A_log"]["scale"].shape == (3, 2)
    assert mixer["dt_bias"]["bias"].shape == (3, 2)
    assert mixer["wz"]["kernel"].shape == (3, 32, 24)     # a head AND channel
    assert mixer["out_norm"]["scale"].shape == (3, 12)
    attn = variables["params"]["run1"]["blocks"]["attn"]
    assert sorted(attn) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert attn["q_norm"]["scale"].shape == (1, 16)       # the whole width


def test_the_scopes_a_metric_reads_are_in_the_lowered_program(case):
    model, variables, batch = case.model, case.variables, case.batch
    text = jax.jit(lambda v: model.apply(v, batch, True)).lower(
        variables).as_text(debug_info=True)
    for scope in ("kda_conv", "kda_gate", "kda_chunk", "kda_state",
                  "kda_out_norm", "post_norm", "qk_norm"):
        assert f"{scope}/" in text, scope
    # a pre-norm model has neither of the two new ones
    plain_cfg = Config(**{**TINY, "norm_after": False, "qk_norm": False})
    plain_model = decoder.build_decoder(plain_cfg)
    shapes = jax.eval_shape(lambda: plain_model.init(
        jax.random.key(0), decoder.sample_documents(plain_cfg, 1), True))
    text = jax.jit(lambda v: plain_model.apply(v, batch, True)).lower(
        shapes).as_text(debug_info=True)
    assert "post_norm/" not in text and "qk_norm/" not in text


def test_the_model_through_the_convolutions_kernels_equals_the_plain_path():
    """The linear layers' convolution and silu forced to the kernel pair of
    vitax/ops/conv.py (interpret mode; keys of 32 and values of 64 make the
    256 channels they tile, four heads of 32 to a lane tile as the cell's
    four of 96 to three): logits, loss and every leaf's gradient are the
    plain path's."""
    from tests.test_ssd_kernel import gap
    from vitax.programs.kernels import choose_kernels
    cfg = Config(**{**TINY, "gdn_key_size": 32,
                    "gdn_value_size": 64}).validate()
    conv = choose_kernels(cfg, None, force_tpu_kernels=True).conv
    assert conv.vitax_name == ("fused kernel (256 channels a grid step in "
                               "blocks of 32 tokens)")
    cases.check_conv_kernels_match_the_plain_path(
        cfg, conv, cases.make_batch(cfg, LENGTHS), gap)


def test_remat_keeps_o_and_lse_of_the_attention_layer_only():
    cfg = Config(**{**TINY, "pack_tokens": 2048,
                    "dtype": "bfloat16"}).validate()
    model = decoder.build_decoder(
        cfg, kernels=Kernels(attention=lambda *a: a[0]))
    assert decoder.keeps_attention_residuals(model, "full_attention")
    assert not decoder.keeps_attention_residuals(model, "linear_attention")
    assert decoder.run_remat_policy(model, "linear_attention", 3) is None


# --- the share tied to the model ------------------------------------------------

def _heads(leaf, heads, width, axis, part):
    """The `part`-th half of the heads of a leaf whose `axis` is heads x
    width."""
    shape = leaf.shape
    split = leaf.reshape(*shape[:axis], heads, width, *shape[axis + 1:])
    half = jnp.take(split, jnp.arange(heads // 2) + part * (heads // 2),
                    axis=axis)
    return half.reshape(*shape[:axis], heads // 2 * width, *shape[axis + 1:])


def test_the_two_halves_of_the_heads_add_up_to_the_uncut_layer():
    """What the two chips that divide the heads hold of a Gated-DeltaNet
    mixer and of the attention layer, each run alone, adds up BEFORE the norm
    after W_o to what the uncut reference gives for the whole layer: the
    mixer's per-head states, channels and output rows as they are, the
    attention's with the QK-norm's mean square taken over the whole width
    (the one statistic of the mixer itself that would cross the chips). And
    the program on a half is the reference on that half, each taking the
    statistic over what it holds."""
    d, heads, dk, dv, dh, n = 32, 4, 6, 12, 8, 24
    x = jax.random.normal(jax.random.key(3), (n, d))
    seg = jnp.ones((1, n), jnp.int32)

    # Gated DeltaNet
    whole = GatedDeltaMixer(GatedDeltaShape(heads, dk, dv, 4), 1e-6,
                            jnp.float32)
    p = cases.moved(jax.jit(whole.init)(jax.random.key(0), x[None], seg))[
        "params"]
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(lambda p: reference.gated_delta_mixer(
            x, p, 1e-6, key_dim=dk, value_dim=dv, taps=4))(p)
    taps = p["conv"]["kernel"]
    q_taps, k_taps, v_taps = jnp.split(taps, [heads * dk, 2 * heads * dk], 1)
    total = 0.0
    for part in (0, 1):
        half = {
            "wq": {"kernel": _heads(p["wq"]["kernel"], heads, dk, 1, part)},
            "wk": {"kernel": _heads(p["wk"]["kernel"], heads, dk, 1, part)},
            "wv": {"kernel": _heads(p["wv"]["kernel"], heads, dv, 1, part)},
            "wz": {"kernel": _heads(p["wz"]["kernel"], heads, dv, 1, part)},
            "wo": {"kernel": _heads(p["wo"]["kernel"], heads, dv, 0, part)},
            "wa": {"kernel": _heads(p["wa"]["kernel"], heads, 1, 1, part)},
            "wb": {"kernel": _heads(p["wb"]["kernel"], heads, 1, 1, part)},
            "A_log": {"scale": _heads(p["A_log"]["scale"], heads, 1, 0, part)},
            "dt_bias": {"bias": _heads(p["dt_bias"]["bias"], heads, 1, 0,
                                       part)},
            "conv": {"kernel": jnp.concatenate(
                [_heads(q_taps, heads, dk, 1, part),
                 _heads(k_taps, heads, dk, 1, part),
                 _heads(v_taps, heads, dv, 1, part)], axis=1)},
            "out_norm": p["out_norm"]}
        held = GatedDeltaMixer(GatedDeltaShape(heads // 2, dk, dv, 4), 1e-6,
                               jnp.float32)
        with jax.default_matmul_precision("highest"):
            got = jax.jit(held.apply)({"params": half}, x[None], seg)[0]
            want = jax.jit(lambda p: reference.gated_delta_mixer(
                x, p, 1e-6, key_dim=dk, value_dim=dv, taps=4))(half)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        total = total + got
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(uncut))) > 0.05

    # the attention layer
    whole = decoder.DecoderAttention(
        heads=heads, kv_heads=heads, head_size=dh, window=0, head_gate=False,
        dtype=jnp.float32, qk_norm=1e-6)
    p = cases.moved(jax.jit(whole.init)(jax.random.key(1), x[None], seg,
                                        None))["params"]

    @jax.jit
    def plain(p, mean_squares=None):
        with jax.default_matmul_precision("highest"):
            return reference.attention_mixer(x, p, 1e-6, head_dim=dh,
                                             mean_squares=mean_squares)

    with jax.default_matmul_precision("highest"):
        uncut = plain(p)
        of_whole = jax.jit(lambda p: tuple(
            jnp.mean(jnp.square(x @ p[w]["kernel"]), axis=-1, keepdims=True)
            for w in ("wq", "wk")))(p)
    total = 0.0
    for part in (0, 1):
        half = {w: {"kernel": _heads(p[w]["kernel"], heads, dh, 1, part)}
                for w in ("wq", "wk", "wv")}
        half["wo"] = {"kernel": _heads(p["wo"]["kernel"], heads, dh, 0, part)}
        for w in ("q_norm", "k_norm"):
            half[w] = {"scale": _heads(p[w]["scale"], heads, dh, 0, part)}
        held = decoder.DecoderAttention(
            heads=heads // 2, kv_heads=heads // 2, head_size=dh, window=0,
            head_gate=False, dtype=jnp.float32, qk_norm=1e-6)
        with jax.default_matmul_precision("highest"):
            # program and reference alike: over what is held
            np.testing.assert_allclose(
                jax.jit(held.apply)({"params": half}, x[None], seg, None)[0],
                plain(half), rtol=2e-4, atol=2e-5)
            total = total + plain(half, of_whole)
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(uncut))) > 0.05


# --- counts, counters, configuration ----------------------------------------------

def _count(cfg):
    shapes = jax.eval_shape(
        lambda: decoder.build_decoder(cfg).init(
            jax.random.key(0), decoder.sample_documents(cfg, 1), True))
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


def test_closed_form_parameter_count_and_the_configurations(case):
    assert sum(a.size for a in jax.tree.leaves(case.variables)) \
        == decoder.expected_param_count(case.cfg)
    # the configuration of the benchmark's cell, by shapes alone
    real = Config(**OLMO).validate()
    assert _count(real) == decoder.expected_param_count(real) == 766_241_946
    from benchmark import flops_olmo
    from benchmark import manifest as mf
    config = mf.Manifest().config("olmo_hybrid_7b_tp2vp8")
    assert flops_olmo.param_count(config) == config["parameters"] \
        == 766_241_946
    built = Config(**mf.Manifest().config_kwargs(config), pack_tokens=4096,
                   pack_images=5, batch_size=1).validate()
    for key in OLMO:        # the nested block is the shape above
        assert getattr(built, key) == getattr(real, key), key
    # every head of a layer (the published 30): the catalog's 208M a layer,
    # which a mixer without W_z or with a gate a head would not give
    whole = Config(**{**OLMO, "kv_heads": 30, "layer_heads": [30] * 4})
    tables = 2 * 12544 * 3840 + 3840
    a_layer = (decoder.expected_param_count(whole) - tables) / 4
    assert 208.0e6 < a_layer < 208.2e6
    parts = flops_olmo.param_counts_by_part(
        {**config, "num_attention_heads": 30, "num_key_value_heads": 30,
         "linear_num_key_heads": 30, "linear_num_value_heads": 30,
         "source_values": {}})
    assert round(parts["linear_mixer"] / 1e6, 2) == 88.75
    assert round(parts["attention_mixer"] / 1e6, 2) == 58.99
    assert round(parts["mlp"] / 1e6, 2) == 126.81


def test_train_step_counters_and_the_first_steps_moments():
    """Documents of 13, 5, 9 and 20, 7 tokens in two rows of 32, the delta
    rule's grid one chunk of 32 a row: 54 tokens, 10 of padding, 49 targets;
    causal pairs 91 + 15 + 45 + 210 + 28; inside a chunk the same pairs (a
    row is one chunk), both chunks live. And what the benchmark holds the
    TIMED step to: the gradients read from the optimizer state its first call
    left (`step_gradients`) are the model's own, with the clip at work."""
    from benchmark.generators import train_gated_delta_packed
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3,
                    "clip_grad_norm": 0.05}).validate()
    batch = cases.make_batch(cfg, LENGTHS)
    geom, step, state, first = cases.check_first_steps_moments(
        train_gated_delta_packed, cfg, batch, clipped=True)
    assert geom.model.kernels.rule is None
    _, m, losses = cases.take_steps(step, state, batch, 3)
    losses.insert(0, float(first["loss"]))
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "kda_pairs", "kda_live_chunks")}
    assert got == dict(tokens=54, padding_tokens=10, images=5, targets=49,
                       causal_pairs=389, kda_pairs=389, kda_live_chunks=2)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert "ssd_pairs" not in m
    from benchmark import flops_olmo
    # the cell's layout (ISSUE 44) on the counters' fixed grid of 64
    assert flops_olmo.layout_counts([[1900, 1100, 600, 300, 130]], 4096) \
        == dict(tokens=4030, documents=5, targets=4025,
                causal_pairs=2_645_465, kda_pairs=128_577,
                kda_live_chunks=63, padding_tokens=66)


def test_flops_count_the_new_kind_at_its_two_widths():
    from vitax.telemetry.flops import decoder_flops_per_step
    cfg = Config(**OLMO).validate()
    flops = decoder_flops_per_step(cfg, 4030, 4025, 2_645_465, 0, 0, 0.0,
                                   128_577)
    # ISSUE 44: about 718M matmul parameters a token, 6 FLOPs each
    assert 4.1e9 < flops / 4030 < 4.6e9
    without = decoder_flops_per_step(cfg, 4030, 4025, 2_645_465, 0, 0, 0.0,
                                     0.0)
    assert flops - without == 3 * 3 * 15 * (6 * 96 + 4 * 192) * 128_577
    fewer = decoder_flops_per_step(cfg, 4029, 4025, 2_645_465, 0, 0, 0.0,
                                   128_577)
    per_token = (3 * (2 * 3840 * 15 * (2 * 96 + 3 * 192 + 2)
                      + 6 * 15 * 96 * 192 + 6 * 3840 * 11008)
                 + 2 * 4 * 3840 * 15 * 128 + 6 * 3840 * 11008)
    assert flops - fewer == 3 * per_token


@pytest.mark.parametrize("change,message", [
    (dict(gdn_key_size=0), "a linear_attention layer needs"),
    (dict(gdn_value_size=0), "a linear_attention layer needs"),
    (dict(gdn_conv_width=0), "a linear_attention layer needs"),
    (dict(layer_heads=[2, 0, 2, 2]), "a linear_attention layer needs heads"),
    (dict(layer_heads=[2, 2, 2, 3]), "multiple of --kv_heads"),
    (dict(layer_kinds=["linear_attention"] * 3 + ["gated_delta"]),
     "gated_delta"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_a_linear_layers_heads_are_not_held_to_the_kv_heads():
    cfg = Config(**{**TINY, "layer_heads": [3, 3, 3, 2]}).validate()
    assert decoder.expected_param_count(cfg) == _count(cfg)


def test_the_family_declares_the_new_shape_fields():
    assert {"gdn_key_size", "gdn_value_size", "gdn_conv_width", "norm_after",
            "qk_norm"} <= cases.family_declares("olmo_hybrid")


def test_training_through_the_cli_path(tmp_path, capsys):
    """`python -m vitax.train --fake_data --model_family decoder` with
    linear_attention layers in a norm-after block with QK-norm (the flags
    through `parse_config`, then the loop the entry point calls): a falling
    loss, the delta rule's counters on the step records, and the start-up
    line that says which delta rule runs and why; no flag selects a form."""
    cfg, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "64",
        "--pack_images", "6", "--embed_dim", "32", "--num_blocks", "4",
        "--vocab_rows", "48", "--kv_heads", "2", "--head_size", "8",
        "--layer_kinds",
        "linear_attention,linear_attention,linear_attention,full_attention",
        "--layer_heads", "2,2,2,2", "--layer_mlps", "dense,dense,dense,dense",
        "--ffn_dim", "48", "--norm_eps", "1e-6", "--position_embedding",
        "nope", "--gdn_key_size", "6", "--gdn_value_size", "12",
        "--gdn_conv_width", "4", "--norm_after", "--qk_norm")
    assert cfg.norm_after and cfg.qk_norm and cfg.gdn_value_size == 12
    out = capsys.readouterr().out
    assert "delta rule: plain (no TPU)" in out
    assert "mixer convolution: plain (no TPU)" in out
    assert "in linear_attention layers" not in out
    for r in steps:
        assert 0 < r["kda_pairs"] <= r["causal_pairs"]
        assert 0 < r["kda_live_chunks"] <= 8 * 64 // 64
        assert "ssd_pairs" not in r


def test_the_start_up_line_says_why_the_plain_rule_runs(monkeypatch):
    """On a TPU (here: forced) the 96 x 192 state under one decay a head is
    none the kernels tile: `plain (<why>)`, and no impl."""
    from vitax.programs import kernels as programs
    cfg = Config(**OLMO).validate()
    chosen = programs.choose_kernels(cfg, None, force_tpu_kernels=True)
    assert chosen.rule is None
    assert programs.kernel_lines(cfg, chosen)[1] == "delta rule: plain (no TPU)"
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")
    assert programs.kernel_lines(cfg, chosen)[1].startswith(
        "delta rule: plain (a 96 x 192 state")
