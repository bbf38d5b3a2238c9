"""The third decoder shape (Ling-3.0-flash: Kimi-Delta-Attention layers to one
latent-attention layer, group-limited bias-corrected routing; vitax/models/
decoder.py, kda.py, experts.py, the `flash_latent_*` kernels of
vitax/ops/flash_blocked.py) at small sizes on the CPU, seeded weights: the
program against the plain reference (benchmark/reference/ling.py) for the
whole 7-layer model, the model through the two-width kernels in interpret
mode, the closed-form parameter count, the step's counters, the
configuration's sentences, the flags and the loop. The layers one by one,
the router's choice and the shares: tests/test_latent_layers.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling as reference
from tests import decoder_cases as cases
from vitax.config import Config
from vitax.models import decoder
from vitax.programs.kernels import Kernels

# the cell's pattern: a dense kda layer, then one whole period
KINDS = ["kda"] * 5 + ["latent_attention", "kda"]
TINY = dict(
    model_family="decoder", embed_dim=32, num_blocks=7, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=KINDS, layer_heads=[2] * 7,
    layer_mlps=["dense"] + ["sparse"] * 6, ffn_dim=48, expert_dim=16,
    shared_expert_dim=16, experts_routed=16, experts_held=4, expert_first=4,
    experts_per_token=4, routed_scale=2.5, head_gate=True, norm_eps=1e-6,
    rope_theta_full=6e6, rope_fraction_full=0.5, kda_conv_width=4,
    kda_gate_bound=-5.0, latent_rank=12, qk_nope_size=8, qk_rope_size=4,
    v_head_size=8, route_groups=4, groups_per_token=2, route_bias=True,
    pack_tokens=32, pack_images=4, batch_size=2, dtype="float32")
LENGTHS = [[13, 5, 9], [20, 7]]
# the configuration of the benchmark's cell under the program's names
LING = dict(
    model_family="decoder", embed_dim=2560, num_blocks=7, vocab_rows=19648,
    kv_heads=16, head_size=128, layer_kinds=KINDS, layer_heads=[16] * 7,
    layer_mlps=["dense"] + ["sparse"] * 6, ffn_dim=6144, expert_dim=768,
    shared_expert_dim=768, experts_routed=512, experts_held=8, expert_first=0,
    experts_per_token=8, routed_scale=2.5, head_gate=True, norm_eps=1e-6,
    rope_theta_full=6e6, rope_fraction_full=0.5, kda_conv_width=4,
    kda_gate_bound=-5.0, latent_rank=512, qk_nope_size=128, qk_rope_size=64,
    v_head_size=128, route_groups=8, groups_per_token=4, route_bias=True,
    pack_tokens=4096, pack_images=5, batch_size=1)


def reference_shape(cfg):
    return dict(
        kinds=["latent" if k == "latent_attention" else k
               for k in cfg.layer_kinds],
        dense_layers=list(cfg.layer_mlps).count("dense"),
        heads=max(cfg.layer_heads), head_dim=cfg.head_size, eps=cfg.norm_eps,
        taps=cfg.kda_conv_width, gate_bound=cfg.kda_gate_bound,
        latent=dict(rank=cfg.latent_rank, nope=cfg.qk_nope_size,
                    rope=cfg.qk_rope_size, value=cfg.v_head_size,
                    theta=cfg.rope_theta_full),
        router=dict(top_k=cfg.experts_per_token, groups=cfg.route_groups,
                    groups_kept=cfg.groups_per_token, scale=cfg.routed_scale,
                    bias=cfg.route_bias, experts_routed=cfg.experts_routed))


@pytest.fixture(scope="module")
def case():
    cfg = Config(**TINY).validate()
    return cases.DecoderCase(
        cfg, reference, dict(reference_shape(cfg), experts_held=(
            cfg.expert_first, cfg.experts_held)), LENGTHS)


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
    # embedding, head, final norm; the dense kda run's 16 leaves, the sparse
    # kda runs' 21 twice, the latent run's 16
    assert len(flat) == len(jax.tree.leaves(grads)) == 3 + 16 + 21 + 16 + 21
    for (path, a), b in zip(flat, jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # it only chooses: no gradient
            assert float(jnp.max(jnp.abs(a))) == 0.0
            assert float(jnp.max(jnp.abs(b))) == 0.0
            continue
        assert reference.relative_gap(b, a) < 2e-3, name
    np.testing.assert_allclose(*(
        jax.jit(lambda g: reference.global_norm(reference.leaf_norms(g)))(g)
        for g in (grads, want)), rtol=1e-4)
    case.check_first_rows()


def test_the_layer_pattern_and_its_runs(case):
    cfg, variables = case.cfg, case.variables
    assert decoder.layer_runs(cfg.layer_kinds, cfg.layer_heads,
                              cfg.layer_mlps) == [
        (("kda", 2, "dense"), 1), (("kda", 2, "sparse"), 4),
        (("latent_attention", 2, "sparse"), 1), (("kda", 2, "sparse"), 1)]
    blocks = variables["params"]["run2"]["blocks"]
    assert sorted(blocks["attn"]) == ["head_gate", "latent_norm", "wkva",
                                      "wkvb", "wo", "wq"]
    assert blocks["attn"]["wkva"]["kernel"].shape == (1, 32, 12 + 4)
    assert blocks["attn"]["wkvb"]["kernel"].shape == (1, 12, 2 * (8 + 8))
    assert blocks["moe"]["router_bias"]["bias"].shape == (1, 16)
    mixer = variables["params"]["run1"]["blocks"]["mixer"]
    assert mixer["conv"]["kernel"].shape == (4, 4, 3 * 16)
    assert mixer["wf"]["kernel"].shape == (4, 32, 16)      # full rank
    assert mixer["A_log"]["scale"].shape == (4, 2)
    assert mixer["dt_bias"]["bias"].shape == (4, 16)


# --- through the kernels ---------------------------------------------------------

def test_model_through_the_kernels_equals_the_dense_path(case):
    cfg = Config(**{**TINY, "pack_tokens": 256}).validate()
    batch = cases.make_batch(cfg, [[120, 70, 40], [200, 30]])
    dense = decoder.build_decoder(cfg)
    variables = case.variables      # no leaf's shape or seed knows the row
    from vitax.ops.attention import make_attention_impl
    impl = make_attention_impl(cfg, None, force_tpu_kernels=True)
    through = decoder.build_decoder(cfg, kernels=Kernels(attention=impl))
    programs = [jax.jit(lambda v, m=m: m.apply(v, batch, True))
                for m in (through, dense)]
    text = programs[0].lower(variables).as_text(debug_info=True)
    assert "flash_latent_fwd" in text and "mla_latent/" in text
    got, want = (program(variables) for program in programs)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_model_through_the_convolutions_kernels_equals_the_plain_path():
    """The kda layers' three convolutions, silu and the L2 norms of q and k
    forced to the kernel pair of vitax/ops/conv.py (interpret mode; heads of
    128, the width whose norm the kernel takes as its epilogue), the delta
    rule plain either way: logits, loss and every leaf's gradient are the
    plain path's."""
    from tests.test_ssd_kernel import gap
    from vitax.programs.kernels import choose_kernels
    cfg = Config(**{**TINY, "head_size": 128,
                    "qk_rope_size": 64}).validate()
    conv = choose_kernels(cfg, None, force_tpu_kernels=True).conv
    assert conv.vitax_name == ("fused kernel (384 channels a grid step in "
                               "blocks of 32 tokens)")
    cases.check_conv_kernels_match_the_plain_path(
        cfg, conv, cases.make_batch(cfg, LENGTHS), gap)


def test_remat_keeps_o_and_lse_of_the_latent_layer_only():
    from vitax.ops.attention import make_attention_impl
    cfg = Config(**{**TINY, "pack_tokens": 2048,
                    "dtype": "bfloat16"}).validate()
    model = decoder.build_decoder(cfg, kernels=Kernels(
        attention=make_attention_impl(cfg, None, force_tpu_kernels=True)))
    assert model.span("latent_attention") == 2048
    assert decoder.keeps_attention_residuals(model, "latent_attention")
    assert not decoder.keeps_attention_residuals(model, "kda")
    assert decoder.run_remat_policy(model, "kda", 3) is None
    assert decoder.run_remat_policy(model, "latent_attention", 1) \
        is decoder._decoder_nothing_saveable
    cases.check_the_policy_keeps_by_the_traced_name(
        model, cfg, decoder.run_remat_policy(model, "latent_attention", 2),
        "flash_latent_fwd")


# --- counts, counters, configuration ----------------------------------------------

def test_closed_form_parameter_count_and_the_configurations(case):
    assert sum(a.size for a in jax.tree.leaves(case.variables)) \
        == decoder.expected_param_count(case.cfg)
    # the configuration of the benchmark's cell, by shapes alone
    real = Config(**LING).validate()
    shapes = jax.eval_shape(
        lambda: decoder.build_decoder(real).init(
            jax.random.key(0), decoder.sample_documents(real, 1), True))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == decoder.expected_param_count(real) == 648_853_344
    from benchmark import flops_ling
    from benchmark import manifest as mf
    config = mf.Manifest().config("ling3_flash_vl_ep64tp2")
    assert flops_ling.param_count(config) == config["parameters"] \
        == 648_853_344
    built = Config(**mf.Manifest().config_kwargs(config), pack_tokens=4096,
                   pack_images=5, batch_size=1).validate()
    for key in LING:        # the nested block is the shape above
        assert getattr(built, key) == getattr(real, key), key
    assert flops_ling.param_counts_by_part(config) == {
        "kda_mixer": 26_323_088, "latent_mixer": 16_720_384,
        "sparse_ffn": 54_395_392, "dense_mlp": 47_185_920,
        "layer_norms": 5_120, "embedding_head_final_norm": 100_600_320}


def test_train_step_counters_against_a_layout_counted_by_hand():
    """Documents of 13, 5, 9 and 20, 7 tokens in two rows of 32, the delta
    rule's grid one chunk of 32 a row: 54 tokens, 10 of padding, 49 targets;
    causal pairs 91 + 15 + 45 + 210 + 28; inside a chunk the same pairs (a
    row is one chunk), both chunks live. The slots routed here and the
    tokens that kept the held experts' group, over the six sparse layers."""
    from vitax.ops.kda import chunk_tiling
    assert chunk_tiling(32, -5.0) == (32, 16)
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3}).validate()
    _, state, step = cases.assembled(cfg)
    _, m, losses = cases.take_steps(
        step, state, cases.make_batch(cfg, LENGTHS), 4)
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "kda_pairs", "kda_live_chunks")}
    assert got == dict(tokens=54, padding_tokens=10, images=5, targets=49,
                       causal_pairs=389, kda_pairs=389, kda_live_chunks=2)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert m["expert_load"].shape == (6, 4) and "ssd_pairs" not in m
    assert int(m["expert_slots_here"]) == int(jnp.sum(m["expert_load"]))
    # a token that chose a held expert kept its group (4 of 16 experts are
    # one group of the 4, of which a token keeps 2)
    kept = int(m["tokens_choosing_held_group"])
    assert 0 < kept <= 6 * 54
    assert (np.asarray(m["expert_load"]).max(axis=1) <= 54).all()
    assert int(m["expert_slots_here"]) <= 4 * kept
    from benchmark import flops_ling
    assert flops_ling.layout_counts(LENGTHS, 32) == dict(
        tokens=54, documents=5, targets=49, causal_pairs=389, kda_pairs=389,
        kda_live_chunks=2, padding_tokens=10)
    # the cell's layout (ISSUE 41) on the counters' fixed grid of 64, which
    # is a constant of its own beside the chunk the program runs
    from vitax.models.kda import count_chunk
    assert chunk_tiling(4096, -5.0) == (64, 16)
    assert (count_chunk(4096), count_chunk(32), flops_ling.KDA_GRID) \
        == (64, 32, 64)
    assert flops_ling.layout_counts([[2600, 900, 350, 150, 60]], 4096) \
        == dict(tokens=4060, documents=5, targets=4055,
                causal_pairs=3_861_330, kda_pairs=128_042,
                kda_live_chunks=64, padding_tokens=36)


def test_the_delta_rules_counters_do_not_follow_the_programs_chunk(
        monkeypatch):
    """`kda_pairs` and `kda_live_chunks` are counted on a grid of 64 tokens
    that is a constant of its own: with the chunk the program runs halved,
    the step counts what it counted, and what the benchmark's arithmetic
    counts on ITS constant (a perf_opt that changes KDA_CHUNK moves the time
    and not the need)."""
    from benchmark import flops_ling
    from vitax.ops import kda
    from vitax.train.step import decoder_counts
    cfg = Config(**{**TINY, "pack_tokens": 256, "batch_size": 1}).validate()
    lengths = [[150, 56, 28, 6]]
    batch = cases.make_batch(cfg, lengths)

    def counted():
        got = jax.jit(lambda b: decoder_counts(cfg, b))(batch)
        return int(got["kda_pairs"]), int(got["kda_live_chunks"])

    want = flops_ling.layout_counts(lengths, 256)
    assert counted() == (want["kda_pairs"], want["kda_live_chunks"])
    assert kda.chunk_tiling(256, -5.0)[0] == 64
    monkeypatch.setattr(kda, "KDA_CHUNK", 32)
    assert kda.chunk_tiling(256, -5.0)[0] == 32
    assert counted() == (want["kda_pairs"], want["kda_live_chunks"])
    assert want["kda_live_chunks"] == 4 and want["kda_pairs"] < 240 * 65 / 2


def test_flops_count_the_two_mixers():
    from vitax.telemetry.flops import decoder_flops_per_step
    cfg = Config(**LING).validate()
    flops = decoder_flops_per_step(cfg, 4060, 4055, 3_861_330, 0, 520,
                                   0.0, 128_042)
    # ISSUE 41: about 320M active parameters a token, 6 FLOPs each
    assert 1.8e9 < flops / 4060 < 2.3e9
    without = decoder_flops_per_step(cfg, 4060, 4055, 3_861_330, 0, 520,
                                     0.0, 0.0)
    assert flops - without == 3 * 6 * 10 * 16 * 128 * 128_042
    no_pairs = decoder_flops_per_step(cfg, 4060, 4055, 0, 0, 520, 0.0,
                                      128_042)
    assert flops - no_pairs == 3 * 2 * 3_861_330 * 16 * (192 + 128)


@pytest.mark.parametrize("change,message", [
    (dict(kda_conv_width=0), "a kda layer needs"),
    (dict(kda_gate_bound=0.0), "a kda layer needs"),
    (dict(kda_gate_bound=2.0), "lower bound of its log-decay"),
    (dict(latent_rank=0), "a latent_attention layer needs"),
    (dict(v_head_size=0), "a latent_attention layer needs"),
    (dict(qk_rope_size=6), "rotates its --qk_rope_size"),
    (dict(position_embedding="nope"), "rotates its --qk_rope_size"),
    (dict(route_groups=3, groups_per_token=2), "--route_groups 3 must divide"),
    (dict(groups_per_token=0), "--groups_per_token"),
    (dict(groups_per_token=5), "--groups_per_token"),
    (dict(route_groups=8, groups_per_token=1), "must hold"),
    (dict(layer_heads=[2, 2, 2, 2, 2, 3, 2]), "multiple of --kv_heads"),
    (dict(layer_kinds=["kda"] * 6 + ["delta"]), "delta"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_the_family_declares_the_new_shape_fields():
    assert {"kda_conv_width", "kda_gate_bound", "latent_rank",
            "qk_nope_size", "qk_rope_size", "v_head_size", "route_groups",
            "groups_per_token", "route_bias"} <= cases.family_declares("ling")


def test_the_float8_control_is_told_from_the_program(case):
    """The benchmark's control (weights rounded to float8_e4m3 for the
    program, the reference on the seeded ones) is off the reference by tens
    of times what the program is, gradient by gradient."""
    from benchmark.generators import train_latent_packed
    want = case.check_float8_control(train_latent_packed, [
        "kda.A_log", "kda.conv", "kda.dt_bias", "kda.wb", "kda.wf",
        "latent.wkva", "latent.wkvb", "latent.wq", "sparse.experts_gate",
        "sparse.router"])
    # the kda leaves of the six kda layers together
    assert want["kda.A_log"].shape == (6 * 2,)
    assert want["kda.wf"].shape == (6 * 32 * 16,)


@pytest.mark.parametrize("clip", [0.05, 100.0])
def test_the_first_steps_moments_hand_back_its_gradients(clip):
    """What the benchmark holds the TIMED step to: the gradients read from
    the optimizer state its first call left (`step_gradients`) are the
    model's own, with the clip at work and without."""
    from benchmark.generators import train_latent_packed
    cfg = Config(**{**TINY, "clip_grad_norm": clip, "num_blocks": 2,
                    "layer_kinds": ["kda", "latent_attention"],
                    "layer_heads": [2, 2],
                    "layer_mlps": ["sparse", "sparse"]}).validate()
    cases.check_first_steps_moments(
        train_latent_packed, cfg, cases.make_batch(cfg, LENGTHS),
        clipped=clip == 0.05)


def test_training_through_the_cli_path(tmp_path, capsys):
    """`python -m vitax.train --fake_data --model_family decoder` with kda
    and latent_attention layers and the grouped, biased router (the flags
    through `parse_config`, then the loop the entry point calls): a falling
    loss and the new counters on the step records; no flag selects a form of
    the new layers."""
    cfg, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "64",
        "--pack_images", "6", "--embed_dim", "32", "--num_blocks", "4",
        "--vocab_rows", "48", "--kv_heads", "2", "--head_size", "8",
        "--layer_kinds", "kda,kda,latent_attention,kda",
        "--layer_heads", "2,2,2,2", "--layer_mlps",
        "dense,sparse,sparse,sparse", "--ffn_dim", "48", "--expert_dim", "16",
        "--shared_expert_dim", "16", "--experts_routed", "16",
        "--experts_held", "4", "--expert_first", "4", "--experts_per_token",
        "4", "--routed_scale", "2.5", "--head_gate", "--norm_eps", "1e-6",
        "--rope_theta_full", "6000000", "--rope_fraction_full", "0.5",
        "--kda_conv_width", "4", "--kda_gate_bound", "-5", "--latent_rank",
        "12", "--qk_nope_size", "8", "--qk_rope_size", "4", "--v_head_size",
        "8", "--route_groups", "4", "--groups_per_token", "2", "--route_bias")
    assert cfg.route_bias and cfg.kda_gate_bound == -5.0
    assert "in kda layers" not in capsys.readouterr().out
    for r in steps:
        assert 0 < r["kda_pairs"] <= r["causal_pairs"]
        assert 0 < r["kda_live_chunks"] <= 8 * 64 // 64
        assert 0 < r["expert_slots_here"] <= 3 * 8 * 64 * 4
        assert 0 < r["tokens_choosing_held_group"] <= 3 * 8 * 64
        assert len(r["expert_load"]) == 3 and "ssd_pairs" not in r
