"""What PR 35 added to the benchmark, off the chip: the scan's need by hand,
the layout's counts, the four readers on a made-up run and on a hand-made
trace, the arithmetic against the program's, and the configuration file
against the source's catalog row and the rules of form."""

import json
import os
import types

import pytest

from benchmark import flops_granite, forms, roofline_granite, scopes
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
CELL = MANIFEST.cell("granite4h_micro_vp8_train_packed4k")
CONFIG = MANIFEST.config("granite4h_micro_vp8")
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["ssd_roofline", "ssm_mixer_busy_pct", "nope_attention_roofline",
           "hybrid_mfu_pct"]
COUNTS = {"tokens": 3980.0, "padding_tokens": 116.0, "images": 4.0,
          "targets": 3976.0, "causal_pairs": 3282190.0,
          "ssd_pairs": 484158.0, "ssd_live_chunks": 16.0}

HLO = '''
HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[4,64,256,256]{3,2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/ssd_chunk/exp"}
  %fusion.2 = f32[16,64,64,128]{3,2,1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run0/blocks/mixer/ssd_state/dot_general"}
  %fusion.3 = f32[4096,4352]{1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/mixer/ssm_conv/mul"}
  %fusion.4 = bf16[4096,4096]{1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/mixer/ssm_gate_norm/rsqrt"}
  %fusion.5 = bf16[4096,8512]{1,0} fusion(%e), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/in_proj/dot_general"}
  ROOT %flash = bf16[8,4096,64] custom-call(%f), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/attn/flash_causal_fwd"}
}
'''


def test_the_scans_need_by_hand():
    # 2 heads of 4 with a state of 8, one group, 10 tokens holding 30 pairs
    # in 2 live chunks, one layer: forward 2 * (8 + 2 * 4) a pair and
    # 4 * 8 * 8 a token, three times with the backward
    tiny = dict(mamba_n_heads=2, mamba_d_head=4, mamba_d_state=8,
                mamba_n_groups=1, mamba_expand=1, hidden_size=8)
    flops, nbytes = roofline_granite.ssd_need(tiny, 10, 30, 2, 1)
    assert flops == 3 * (2 * (8 + 8) * 30 + 4 * 8 * 8 * 10)
    # x, B, C three times and y twice in bf16, delta three times in float32;
    # the chunk states and their gradients, written and read, in float32
    assert nbytes == 10 * ((3 * (8 + 16) + 2 * 8) * 2 + 3 * 2 * 4) \
        + 4 * 2 * 8 * 8 * 4
    # the cell's: ISSUE 35's 2 * (128 + 64 * 64) a pair and 4 * 64 * 64 *
    # 128 a token
    assert flops_granite.scan_flops_per_layer(CONFIG, 3980, 484158) \
        == 2 * (128 + 64 * 64) * 484158 + 4 * 64 * 64 * 128 * 3980
    flops, nbytes = roofline_granite.ssd_need(CONFIG, 3980, 484158, 16, 9)
    assert 0.33e12 < flops < 0.34e12 and 2.7e9 < nbytes < 2.8e9


def test_the_layout_is_what_the_traffic_file_says():
    counts = flops_granite.layout_counts(TRAFFIC["rows"],
                                         TRAFFIC["row_tokens"],
                                         CONFIG["mamba_chunk_size"])
    assert {**counts, "documents": 4} == {**TRAFFIC["layout"]}
    assert counts["ssd_pairs"] == 484158 and counts["ssd_live_chunks"] == 16
    # every later document starts inside a chunk of the scan's grid
    starts = [2300, 3300, 3780]
    assert all(s % CONFIG["mamba_chunk_size"] for s in starts)
    # 4.75 GFLOP a valid token forward + backward (ISSUE 35)
    per_step = flops_granite.model_flops_per_step(
        CONFIG, 3980, 3976, 3282190, 484158)
    assert 18.7e12 < per_step < 19.1e12


def made_up_run(trace=None, program=None, **records):
    return types.SimpleNamespace(
        trace=trace, records=records, program=program or {}, config=CONFIG,
        chips=1, peaks=PEAKS)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a program without the scan's counters or scopes (the parent's, any
    other cell's) each new reader returns None and does not raise, traced or
    not."""
    read = mf.metric_reader(name).read
    assert read(made_up_run(steps=3, window_s=1.0)) is None
    laguna = {"tokens": 7970.0, "targets": 7966.0, "causal_pairs": 1.3e7}
    ops = [tr.Op(0, 10, "flash", "custom-call", "flash_causal_fwd",
                 self_ns=10.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 10)])],
                            [])
    assert read(made_up_run(trace, {"op_scopes": {}}, packed_counts=laguna,
                            steps=3, window_s=1.0)) is None


def test_readers_on_counters_and_a_hand_made_trace():
    from benchmark.generators import train_hybrid_packed as gen
    found = scopes.index(HLO, gen.SCOPES)
    assert found == {"fusion.1": "ssd_chunk", "fusion.2": "ssd_state",
                     "fusion.3": "ssm_conv", "fusion.4": "ssm_gate_norm"}
    # one step in a window of 1 ms: 400 us of scan, 100 of convolution and
    # gated norm, 300 of projections, 100 in the attention kernel
    ops = [tr.Op(0, 300e3, "fusion.1", "fusion kLoop", "", self_ns=300e3),
           tr.Op(300e3, 400e3, "fusion.2", "fusion kOutput", "",
                 self_ns=100e3),
           tr.Op(400e3, 460e3, "fusion.3", "fusion kLoop", "", self_ns=60e3),
           tr.Op(460e3, 500e3, "fusion.4", "fusion kLoop", "", self_ns=40e3),
           tr.Op(500e3, 800e3, "fusion.5", "fusion kOutput", "",
                 self_ns=300e3),
           tr.Op(800e3, 900e3, "flash", "custom-call",
                 "flash_causal_fwd", self_ns=100e3)]
    trace = tr.ReducedTrace(
        (0, 1e6), [tr.DeviceTrace("d", ops, [(0, 900e3)])], [])
    run = made_up_run(trace, {"op_scopes": found}, packed_counts=COUNTS,
                      steps=1, window_s=1e-3)
    assert mf.metric_reader("ssm_mixer_busy_pct").read(run) \
        == pytest.approx(100 * 500 / 900)
    flops, nbytes = roofline_granite.ssd_need(CONFIG, 3980, 484158, 16, 9)
    assert nbytes / 819e9 > flops / 197e12          # memory-bound
    assert mf.metric_reader("ssd_roofline").read(run) == pytest.approx(
        100 * (nbytes / 819e9) / 400e-6)
    assert run.records["ssd_bound"] == "memory"
    assert run.records["ssd_scan_s"] == pytest.approx(400e-6)
    # one attention layer of 32 heads over 8 of 64
    need = 6 * 2 * 32 * 3282190 * 64
    assert mf.metric_reader("nope_attention_roofline").read(run) \
        == pytest.approx(100 * (need / 197e12) / 100e-6)
    assert run.records["flash_causal_bound"] == "compute"
    want = flops_granite.model_flops_per_step(CONFIG, 3980, 3976, 3282190,
                                              484158)
    assert mf.metric_reader("hybrid_mfu_pct").read(run) == pytest.approx(
        100 * want * 1000 / 197e12)


def test_the_arithmetic_equals_the_programs():
    from benchmark.generators import train_hybrid_packed as gen
    cfg = gen.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC, 1, 0)
    pairs = flops_granite.against_program(CONFIG, TRAFFIC, cfg)
    assert [what for what, _, _ in pairs] == ["FLOPs a step", "parameters"]
    for what, ours, programs in pairs:
        assert ours == programs, what
    assert flops_granite.param_count(CONFIG) == CONFIG["parameters"] \
        == 772_160_448
    # the four multipliers reach `Config` from the top level, under the one
    # name the source and the program give them
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) \
        == (12, 0.22, 0.015625, 8)
    assert cfg.position_embedding == "nope" and cfg.tie_embeddings


def test_the_configuration_keeps_the_rules_of_form():
    import dataclasses

    from vitax.config import Config
    assert "granite4h_micro_vp8" not in forms.manifest_problems(MANIFEST)
    family = MANIFEST.family(CONFIG["family"])
    fields = {f.name for f in dataclasses.fields(Config)}
    for key in ("mamba_d_state", "mamba_chunk_size", "mamba_n_heads",
                "shared_intermediate_size", "residual_multiplier",
                "rms_norm_eps"):
        broken = json.loads(json.dumps(CONFIG))
        broken["reduced"].append(key)
        broken["source_values"][key] = broken[key] * 2
        assert f"`{key}` is a width: a width is never reduced" in \
            forms.problems(broken, family, forms.rules(), fields), key
    assert forms.period_of(CONFIG["source_values"]["layer_types"]) == 10
    assert CONFIG["chips_sharing_a_layer"] == 8
    sizing = CONFIG["sizing"]
    assert sizing["step_bytes"] <= 15.75e9
    assert sizing["step_bytes"] > 0.25 * 16.909e9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert CONFIG["source"] == row["source_url"]
    assert sorted(CONFIG["reduced"]) == ["layer_types", "num_hidden_layers",
                                         "vocab_size"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:10]
