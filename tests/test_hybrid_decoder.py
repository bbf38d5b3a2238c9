"""The hybrid of state-space and attention layers in the token decoder
(vitax/models/decoder.py with vitax/models/ssm.py; Granite 4.0-H's shape) at
small sizes on the CPU, seeded weights: the float32 program against the plain
per-token reference (benchmark/reference/granite.py) on packed documents
whose boundaries fall inside chunks, the vocabulary slice tied to the model,
the closed-form parameter count, the step's counters, what the float8
control does to the comparison, the flags and the loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite as reference
from tests import decoder_cases as cases
from vitax.config import Config
from vitax.models import decoder

KINDS = ["mamba", "mamba", "attention", "mamba"]
TINY = dict(
    model_family="decoder", embed_dim=32, num_blocks=4, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=KINDS, layer_heads=[0, 0, 4, 0],
    layer_mlps=["dense"] * 4, ffn_dim=48, norm_eps=1e-5,
    position_embedding="nope", tie_embeddings=True, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=0.2, logits_scaling=8.0,
    ssm_heads=8, ssm_head_size=8, ssm_state_size=16, ssm_conv_width=4,
    ssm_groups=1, ssm_chunk=8, pack_tokens=32, pack_images=4, batch_size=2,
    dtype="float32")
LENGTHS = [[13, 5, 9], [20, 7]]
# the configuration of the benchmark's cell under the program's names
GRANITE = dict(
    model_family="decoder", embed_dim=2048, num_blocks=10, vocab_rows=12544,
    kv_heads=8, head_size=64,
    layer_kinds=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    layer_heads=[0] * 5 + [32] + [0] * 4, layer_mlps=["dense"] * 10,
    ffn_dim=8192, norm_eps=1e-5, position_embedding="nope",
    tie_embeddings=True, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, ssm_heads=64,
    ssm_head_size=64, ssm_state_size=128, ssm_conv_width=4, ssm_groups=1,
    ssm_chunk=256, pack_tokens=4096, pack_images=4, batch_size=1)


def reference_shape(cfg):
    return dict(
        layer_types=list(cfg.layer_kinds),
        heads=max(cfg.layer_heads), kv_heads=cfg.kv_heads,
        head_dim=cfg.head_size, eps=cfg.norm_eps,
        attention_multiplier=cfg.attention_multiplier,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
        mamba=dict(n_heads=cfg.ssm_heads, d_head=cfg.ssm_head_size,
                   d_state=cfg.ssm_state_size, d_conv=cfg.ssm_conv_width,
                   n_groups=cfg.ssm_groups, conv_bias=True))


@pytest.fixture(scope="module")
def case():
    cfg = Config(**TINY).validate()
    return cases.DecoderCase(cfg, reference, reference_shape(cfg), LENGTHS)


def test_logits_match_the_reference(case):
    assert np.abs(case.logits).max() > 0.2
    case.check_logits(padded=False)


def test_loss_and_every_gradient_leaf_match_the_reference(case):
    want_loss, want = case.loss_and_grads
    loss, grads, _ = case.plain
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(case.plain_loss, want_loss, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want)
    # embedding (the tied head's too), final norm; a mamba run's 13 leaves
    # twice and the attention run's 9
    assert len(flat) == len(jax.tree.leaves(grads)) == 2 + 13 + 9 + 13
    for (path, a), b in zip(flat, jax.tree.leaves(grads)):
        assert reference.relative_gap(b, a) < 2e-3, \
            jax.tree_util.keystr(path)
    np.testing.assert_allclose(*(
        jax.jit(lambda g: reference.global_norm(reference.leaf_norms(g)))(g)
        for g in (grads, want)), rtol=1e-4)
    case.check_first_rows()


def test_the_model_through_the_convolutions_kernels_equals_the_plain_path():
    """The mixers' convolution forced to the kernel pair of vitax/ops/conv.py
    (interpret mode; 12 heads of 8 and two states of 16 make the 128 channels
    they tile), the scan plain either way: logits, loss and every leaf's
    gradient are the plain path's."""
    from tests.test_ssd_kernel import gap
    from vitax.programs.kernels import choose_kernels
    cfg = Config(**{**TINY, "ssm_heads": 12}).validate()
    conv = choose_kernels(cfg, None, force_tpu_kernels=True).conv
    assert conv.vitax_name.startswith("fused kernel (128 channels")
    cases.check_conv_kernels_match_the_plain_path(
        cfg, conv, cases.make_batch(cfg, LENGTHS), gap)


def test_the_vocabulary_slice_is_tied_to_the_model(case):
    """A chip that holds rows 0-k of the tied table: on ids drawn from the
    slice its logits are those columns of the whole table's (the table is
    embedding and head at once, so the slice cuts both)."""
    variables, held = case.variables, 24
    batch = cases.make_batch(case.cfg, LENGTHS, seed=5, rows_held=held)
    cut_cfg = Config(**{**TINY, "vocab_rows": held}).validate()
    cut = jax.tree.map(lambda a: a, variables)
    cut["params"]["embed"]["embedding"] = \
        variables["params"]["embed"]["embedding"][:held]
    whole, got = (jax.jit(lambda v, m=m: m.apply(v, batch, True))(v)
                  for m, v in ((case.model, variables),
                               (decoder.build_decoder(cut_cfg), cut)))
    assert got.shape[-1] == held
    np.testing.assert_allclose(got, whole[..., :held], rtol=1e-5, atol=1e-6)
    assert "lm_head" not in variables["params"]


def test_closed_form_parameter_count(case):
    assert sum(a.size for a in jax.tree.leaves(case.variables)) \
        == decoder.expected_param_count(case.cfg)
    # the configuration of the benchmark's cell, by shapes alone
    real = Config(**GRANITE).validate()
    shapes = jax.eval_shape(
        lambda: decoder.build_decoder(real).init(
            jax.random.key(0), decoder.sample_documents(real, 1), True))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == decoder.expected_param_count(real) == 772_160_448
    assert decoder.layer_runs(real.layer_kinds, real.layer_heads,
                              real.layer_mlps) == [
        (("mamba", 0, "dense"), 5), (("attention", 32, "dense"), 1),
        (("mamba", 0, "dense"), 4)]
    from benchmark import flops_granite
    from benchmark import manifest as mf
    config = mf.Manifest().config("granite4h_micro_vp8")
    assert flops_granite.param_count(config) == 772_160_448
    assert flops_granite.layer_param_counts(config) == {
        "mamba": 76_182_976, "attention": 60_821_504}


def test_the_float8_control_is_told_from_the_program(case):
    """The benchmark's control (weights rounded to float8_e4m3 for the
    program, the reference on the seeded ones) is off the reference by tens
    of times what the program is, gradient by gradient."""
    from benchmark.generators import train_hybrid_packed
    want = case.check_float8_control(train_hybrid_packed, [
        "attention.wq", "first.conv", "first.in_proj", "last.conv",
        "last.in_proj", "mamba.A_log", "mamba.dt_bias"])
    # A_log and dt_bias of the three mamba layers together
    assert want["mamba.A_log"].shape == (3, case.cfg.ssm_heads)


@pytest.mark.parametrize("clip", [0.05, 1.0])
def test_the_first_steps_moments_hand_back_its_gradients(clip):
    """What the benchmark holds the TIMED step to: the gradients read from
    the optimizer state its first call left (`step_gradients`) are the
    model's own, with the clip at work (the norm here is 0.15) and
    without."""
    from benchmark.generators import train_hybrid_packed
    cfg = Config(**{**TINY, "clip_grad_norm": clip}).validate()
    cases.check_first_steps_moments(
        train_hybrid_packed, cfg, cases.make_batch(cfg, LENGTHS),
        clipped=clip == 0.05)


def test_train_step_counters_against_a_layout_counted_by_hand():
    """Documents of 13, 5, 9 and 20, 7 tokens in two rows of 32, chunks of 8:
    54 tokens, 10 of padding, 49 targets; causal pairs 91 + 15 + 45 + 210 +
    28; row 0's chunks hold 8 | 5 + 3 | 2 + 6 | 3 tokens of one document
    each, row 1's 8 | 8 | 4 + 4 | 3: pairs 36 + 15 + 6 + 3 + 21 + 6, and 36
    + 36 + 10 + 10 + 6; all 8 chunks hold a token."""
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3}).validate()
    _, state, step = cases.assembled(cfg)
    _, m, losses = cases.take_steps(
        step, state, cases.make_batch(cfg, LENGTHS), 4)
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "ssd_pairs", "ssd_live_chunks")}
    assert got == dict(tokens=54, padding_tokens=10, images=5, targets=49,
                       causal_pairs=389, ssd_pairs=185, ssd_live_chunks=8)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert int(m["expert_slots_here"]) == 0
    from benchmark import flops_granite
    assert flops_granite.layout_counts(LENGTHS, 32, 8) == dict(
        tokens=54, documents=5, targets=49, causal_pairs=389, ssd_pairs=185,
        ssd_live_chunks=8, padding_tokens=10)
    # the cell's layout (ISSUE 35)
    assert flops_granite.layout_counts([[2300, 1000, 480, 200]], 4096, 256) \
        == dict(tokens=3980, documents=4, targets=3976,
                causal_pairs=3_282_190, ssd_pairs=484_158,
                ssd_live_chunks=16, padding_tokens=116)
    # a Laguna-shaped model counts no scan
    from tests.test_decoder import TINY as LAGUNA
    from vitax.train.step import decoder_counts
    assert "ssd_pairs" not in jax.eval_shape(
        lambda batch: decoder_counts(Config(**LAGUNA).validate(), batch),
        {"segment_ids": jnp.ones((2, 64), jnp.int32)})


def test_flops_count_the_mixer():
    from vitax.telemetry.flops import decoder_flops_per_step
    cfg = Config(**GRANITE).validate()
    flops = decoder_flops_per_step(cfg, 3980, 3976, 3_282_190, 0, 0,
                                   484_158)
    # ISSUE 35: 4.75 GFLOP a valid token forward + backward
    assert 4.70e9 < flops / 3980 < 4.80e9
    scan = 3 * 9 * (2 * (128 + 4096) * 484_158 + 4 * 4096 * 128 * 3980)
    without = decoder_flops_per_step(
        Config(**{**GRANITE, "ssm_state_size": 128}).validate(), 3980, 3976,
        3_282_190, 0, 0, 0.0)
    assert flops - without == 3 * 9 * 2 * (128 + 4096) * 484_158
    assert 0.015 < scan / flops < 0.03


@pytest.mark.parametrize("change,message", [
    (dict(ssm_conv_width=0), "a mamba layer needs"),
    (dict(ssm_groups=3), "multiple of --ssm_groups"),
    (dict(ssm_chunk=12), "multiple of --ssm_chunk"),
    (dict(ssm_state_size=0), "a mamba layer needs"),
    (dict(position_embedding="alibi"), "unknown --position_embedding"),
    (dict(logits_scaling=0.0), "must be > 0"),
    (dict(layer_kinds=["mamba", "mamba", "linear", "mamba"]), "linear"),
    (dict(layer_heads=[0, 0, 3, 0]), "multiple of --kv_heads"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_the_family_declares_the_new_shape_fields():
    assert {"ssm_heads", "ssm_head_size", "ssm_state_size", "ssm_conv_width",
            "ssm_groups", "ssm_chunk",
            "position_embedding", "tie_embeddings", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier",
            "logits_scaling"} <= cases.family_declares("granite")


def test_training_through_the_cli_path(tmp_path, capsys):
    """`python -m vitax.train --fake_data --model_family decoder` with mamba
    layers (the flags through `parse_config`, then the loop the entry point
    calls): a falling loss and the scan's counters on the step records.
    (`--logits_scaling` 0.05, not the model's 8: fresh random ids every step
    leave nothing to learn below ln(vocabulary rows), which is where a tied
    table of std 0.02 divided by 8 starts.)"""
    cfg, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "64",
        "--pack_images", "6", "--embed_dim", "32", "--num_blocks", "4",
        "--vocab_rows", "48", "--kv_heads", "2", "--head_size", "8",
        "--layer_kinds", ",".join(KINDS), "--layer_heads", "0,0,4,0",
        "--layer_mlps", "dense,dense,dense,dense", "--ffn_dim", "48",
        "--norm_eps", "1e-5", "--position_embedding", "nope",
        "--tie_embeddings", "--embedding_multiplier", "12",
        "--residual_multiplier", "0.22", "--attention_multiplier", "0.2",
        "--logits_scaling", "0.05", "--ssm_heads", "8", "--ssm_head_size", "8",
        "--ssm_state_size", "16", "--ssm_conv_width", "4", "--ssm_chunk",
        "8")
    assert cfg.tie_embeddings and cfg.ssm_groups == 1
    # which form of the scan and of the convolution in front of it runs,
    # beside the attention core's line
    out = capsys.readouterr().out
    assert "state-space scan: plain (no TPU)" in out
    assert "mixer convolution: plain (no TPU)" in out
    for r in steps:
        assert 0 < r["ssd_pairs"] <= r["causal_pairs"]
        assert 0 < r["ssd_live_chunks"] <= 8 * 64 // 8
        assert r["expert_slots_here"] == 0 and r["expert_load"] == []
