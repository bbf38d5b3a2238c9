"""What PR 44 added to the benchmark, off the chip: the delta rule's need at
96 / 192 by hand, the layout's counts, the four readers on a made-up run and
on a hand-made trace, the arithmetic against the program's, the configuration
file against the source's catalog row and the rules of form, and the new
cell rehearsed."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import (flops_ling, flops_olmo, forms, roofline_laguna,
                       roofline_ling, roofline_olmo, scopes)
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
NAME = "olmo_hybrid_7b_tp2vp8_train_packed4k"
CELL = MANIFEST.cell(NAME)
CONFIG = MANIFEST.config("olmo_hybrid_7b_tp2vp8")
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["olmo_hybrid_mfu_pct", "gated_delta_roofline",
           "mha_attention_roofline", "norm_after_busy_pct"]
APPENDED_TO = ["device_idle_pct", "fused_optimizer_roofline",
               "nonmatmul_busy_pct", "step_hbm_gb", "compiles_in_window",
               "train_tokens_per_s_chip", "packing_padding_pct",
               "decoder_attention_busy_pct", "kda_mixer_busy_pct"]
COUNTS = {"tokens": 4030.0, "padding_tokens": 66.0, "images": 5.0,
          "targets": 4025.0, "causal_pairs": 2645465.0,
          "kda_pairs": 128577.0, "kda_live_chunks": 63.0}

HLO = '''
HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[1,64,15,64,64]{4,3,2,1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/kda_chunk/dot_general"}
  %fusion.2 = f32[1,15,96,192]{3,2,1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run0/blocks/mixer/kda_state/while/body/dot_general"}
  %fusion.3 = f32[1,4096,5760]{2,1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/kda_conv/mul"}
  %fusion.4 = f32[1,4096,15]{2,1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/kda_gate/softplus"}
  %fusion.5 = bf16[1,4096,2880]{2,1,0} fusion(%e), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/kda_out_norm/rsqrt"}
  %fusion.6 = bf16[4096,2880]{1,0} fusion(%f), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/wz/dot_general"}
  %fusion.7 = bf16[1,4096,3840]{2,1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/post_norm/norm1/rsqrt"}
  %fusion.8 = bf16[1,4096,1920]{2,1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run1/blocks/attn/qk_norm/q_norm/mul"}
  ROOT %flash = bf16[15,4096,128] custom-call(%j), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/attn/flash_causal_fwd"}
}
'''


def test_the_delta_rules_need_by_hand():
    # 2 heads of 3 keys and 5 values, 10 tokens holding 30 pairs in 2 live
    # chunks, one layer: forward (6 * 3 + 4 * 5) a pair and 6 * 3 * 5 a token
    # a head, three times with the backward
    tiny = dict(linear_num_key_heads=2, linear_num_value_heads=2,
                linear_key_head_dim=3, linear_value_head_dim=5)
    flops, nbytes = roofline_olmo.gated_delta_need(tiny, 10, 30, 2, 1)
    assert flops == 3 * (2 * (6 * 3 + 4 * 5) * 30 + 6 * 2 * 3 * 5 * 10)
    # q, k of 3 and v of 5 three times and o of 5 twice in bf16, g and beta
    # ONE float32 a head each three times; the chunk states and their
    # gradients, written and read
    assert nbytes == 10 * 2 * ((3 * (3 + 3 + 5) + 2 * 5) * 2 + 3 * 2 * 4) \
        + 4 * 2 * 2 * 3 * 5 * 4
    # at K = V and one decay a channel it would be Ling's yardstick
    square = dict(tiny, linear_key_head_dim=4, linear_value_head_dim=4)
    ling = dict(num_attention_heads=2, head_dim=4)
    assert roofline_olmo.gated_delta_need(square, 10, 30, 2, 1)[0] \
        == roofline_ling.kda_need(ling, 10, 30, 2, 1)[0]
    # the cell's: 15 heads, 96 / 192, three layers
    assert flops_olmo.delta_rule_flops_per_layer(CONFIG, 4030, 128577) \
        == 15 * (6 * 96 + 4 * 192) * 128577 + 6 * 15 * 96 * 192 * 4030
    flops, nbytes = roofline_olmo.gated_delta_need(CONFIG, 4030, 128577, 63,
                                                   3)
    assert 0.08e12 < flops < 0.09e12 and 1.3e9 < nbytes < 1.5e9
    assert nbytes / 819e9 > flops / 197e12          # memory-bound
    flops, nbytes = roofline_laguna.attention_need(2645465, 4030, 15, 15,
                                                   128, 1)
    assert flops == 6 * 2 * 15 * 2645465 * 128
    assert flops_olmo.head_dim(CONFIG) == 128


def test_the_layout_is_what_the_traffic_file_says():
    chunk = flops_olmo.KDA_GRID
    assert chunk == flops_ling.KDA_GRID == 64
    counts = flops_olmo.layout_counts(TRAFFIC["rows"], TRAFFIC["row_tokens"])
    assert counts == TRAFFIC["layout"] == {
        "documents": 5, "tokens": 4030, "padding_tokens": 66,
        "targets": 4025, "causal_pairs": 2645465, "kda_pairs": 128577,
        "kda_live_chunks": 63}
    # ... against a count from the rows, by hand: the tokens of each
    # document in each 64-token chunk it touches
    pairs, at = 0, 0
    for n in TRAFFIC["rows"][0]:
        for t in range(at, at + n):
            pairs += t - max(at, t // chunk * chunk) + 1
        at += n
    assert pairs == TRAFFIC["layout"]["kda_pairs"]
    assert -(-at // chunk) == TRAFFIC["layout"]["kda_live_chunks"] == 63
    tiny = TRAFFIC["rehearse"]
    assert flops_olmo.layout_counts(tiny["rows"], tiny["row_tokens"]) \
        == tiny["layout"]
    assert TRAFFIC["rows"] == [[1900, 1100, 600, 300, 130]]
    assert (TRAFFIC["kind"], TRAFFIC["rows_per_chip"], TRAFFIC["row_tokens"],
            TRAFFIC["docs_per_row"], TRAFFIC["warm_steps"],
            TRAFFIC["run_ahead"], TRAFFIC["logit_positions"],
            TRAFFIC["expect_decreasing"]) == (
        "train_gated_delta_packed", 1, 4096, 5, 3, 2, 64, True)
    # every later document starts inside a chunk of the delta rule's grid,
    # and the row's last chunk is all padding
    assert all(s % chunk for s in (1900, 3000, 3600, 3900))
    assert at <= 4096 - chunk
    # about 718M matmul parameters a token, 6 FLOPs each with the backward
    per_step = flops_olmo.model_flops_per_step(
        CONFIG, 4030, 4025, 2645465, 128577)
    assert 17e12 < per_step < 18.5e12


def test_the_manifests_new_entries():
    assert forms.manifest_problems(MANIFEST) == {}
    data = MANIFEST.data
    assert data["configs"][-1]["name"] == "olmo_hybrid_7b_tp2vp8"
    assert data["workloads"][-1] == {
        "name": NAME, "config": "olmo_hybrid_7b_tp2vp8",
        "traffic": "packed_1x4096_webmix", "chips": 1,
        "why": CELL["why"]}
    assert len(CELL["why"]) <= 200 and len(data["configs"][-1]["why"]) <= 200
    assert [m["name"] for m in data["per_layer"][-4:]] == READERS
    layers = {m["layer"] for m in data["per_layer"][:-4]}
    for entry in data["per_layer"][-4:]:
        assert entry["workloads"] == [NAME] and entry["layer"] in layers
        assert entry["moves"] == "train_images_per_s_chip"
        assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                                 "unit", "workloads"]
    listed = {m["name"]: m.get("workloads", []) for m in data["per_layer"]}
    for name in APPENDED_TO:
        assert listed[name][-1] == NAME, name
    assert data["end_to_end"][0]["workloads"][-1] == NAME
    per_layer = [m["name"] for m in MANIFEST.metrics("per_layer", NAME)]
    assert sorted(per_layer) == sorted(APPENDED_TO + READERS)
    assert [m["name"] for m in MANIFEST.metrics("end_to_end", NAME)] == [
        "train_images_per_s_chip", "setup_s"]


def made_up_run(trace=None, program=None, config=CONFIG, **records):
    return types.SimpleNamespace(
        trace=trace, records=records, program=program or {}, config=config,
        chips=1, peaks=PEAKS)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a program without the new scopes, or in another family's cell (the
    parent's, Ling's with the same counters), each new reader returns None
    and does not raise, traced or not."""
    read = mf.metric_reader(name).read
    assert read(made_up_run(steps=3, window_s=1.0)) is None
    ling = MANIFEST.config("ling3_flash_vl_ep64tp2")
    ops = [tr.Op(0, 10, "flash", "custom-call", "flash_causal_fwd",
                 self_ns=10.0),
           tr.Op(10, 20, "fusion.1", "fusion", "", self_ns=10.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 20)])],
                            [])
    assert read(made_up_run(trace, {"op_scopes": {"fusion.1": "kda_chunk"}},
                            config=ling, packed_counts=COUNTS, steps=3,
                            window_s=1.0)) is None


def test_readers_on_counters_and_a_hand_made_trace():
    from benchmark.generators import train_gated_delta_packed as gen
    found = scopes.index(HLO, gen.SCOPES)
    assert found == {"fusion.1": "kda_chunk", "fusion.2": "kda_state",
                     "fusion.3": "kda_conv", "fusion.4": "kda_gate",
                     "fusion.5": "kda_out_norm", "fusion.7": "post_norm",
                     "fusion.8": "qk_norm"}
    # one step in a window of 1 ms: 300 us of delta rule, 100 of the mixer's
    # other scopes, 200 of a projection, 60 of the norms, 100 in the kernel
    spans = [("fusion.1", 200e3), ("fusion.2", 100e3), ("fusion.3", 50e3),
             ("fusion.4", 30e3), ("fusion.5", 20e3), ("fusion.6", 200e3),
             ("fusion.7", 40e3), ("fusion.8", 20e3)]
    ops, at = [], 0.0
    for name, ns in spans:
        ops.append(tr.Op(at, at + ns, name, "fusion", "", self_ns=ns))
        at += ns
    ops.append(tr.Op(at, at + 100e3, "flash", "custom-call",
                     "flash_causal_fwd", self_ns=100e3))
    trace = tr.ReducedTrace(
        (0, 1e6), [tr.DeviceTrace("d", ops, [(0, 760e3)])], [])
    run = made_up_run(trace, {"op_scopes": found}, packed_counts=COUNTS,
                      steps=1, window_s=1e-3)
    assert mf.metric_reader("kda_mixer_busy_pct").read(run) \
        == pytest.approx(100 * 400 / 760)
    assert mf.metric_reader("norm_after_busy_pct").read(run) \
        == pytest.approx(100 * 60 / 760)
    assert mf.metric_reader("decoder_attention_busy_pct").read(run) \
        == pytest.approx(100 * 100 / 760)
    flops, nbytes = roofline_olmo.gated_delta_need(CONFIG, 4030, 128577, 63,
                                                   3)
    least = max(flops / 197e12, nbytes / 819e9)
    assert mf.metric_reader("gated_delta_roofline").read(run) \
        == pytest.approx(100 * least / 300e-6)
    assert run.records["gated_delta_bound"] == "memory"
    assert run.records["gated_delta_rule_s"] == pytest.approx(300e-6)
    need = 6 * 2 * 15 * 2645465 * 128
    assert mf.metric_reader("mha_attention_roofline").read(run) \
        == pytest.approx(100 * (need / 197e12) / 100e-6)
    assert run.records["flash_causal_bound"] == "compute"
    want = flops_olmo.model_flops_per_step(CONFIG, 4030, 4025, 2645465,
                                           128577)
    assert mf.metric_reader("olmo_hybrid_mfu_pct").read(run) \
        == pytest.approx(100 * want * 1000 / 197e12)
    # Ling's own share of the delta rule does not read this cell's file
    with pytest.raises(KeyError):
        mf.metric_reader("kda_roofline").read(run)


def test_the_arithmetic_equals_the_programs():
    from benchmark.generators import train_gated_delta_packed as gen
    cfg = gen.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC, 1, 0)
    pairs = flops_olmo.against_program(CONFIG, TRAFFIC, cfg)
    assert [what for what, _, _ in pairs] == ["FLOPs a step", "parameters"]
    for what, ours, programs in pairs:
        assert ours == programs, what
    assert flops_olmo.param_count(CONFIG) == CONFIG["parameters"] \
        == 766_241_946
    assert sum(CONFIG["sizing"]["parameters_by_part"][k] * n for k, n in zip(
        CONFIG["sizing"]["parameters_by_part"], (3, 1, 4, 4, 1))) \
        == CONFIG["parameters"]
    # the shares reach `Config` from the nested block: 15 of 30 heads in
    # every mixer, an eighth of the rows; the block's form
    assert cfg.layer_heads == (15,) * 4 and cfg.kv_heads == 15
    assert cfg.vocab_rows * 8 == CONFIG["source_values"]["vocab_size"]
    assert cfg.layer_kinds == ("linear_attention",) * 3 + ("full_attention",)
    assert cfg.norm_after and cfg.qk_norm
    assert cfg.position_embedding == "nope"
    assert (cfg.gdn_key_size, cfg.gdn_value_size, cfg.gdn_conv_width) \
        == (96, 192, 4)


def test_the_configuration_keeps_the_rules_of_form():
    import dataclasses

    from vitax.config import Config
    family = MANIFEST.family(CONFIG["family"])
    fields = {f.name for f in dataclasses.fields(Config)}
    for key in ("hidden_size", "intermediate_size", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "head_dim", "rms_norm_eps"):
        broken = json.loads(json.dumps(CONFIG))
        broken["reduced"].append(key)
        broken["source_values"][key] = broken[key] * 2
        assert f"`{key}` is a width: a width is never reduced" in \
            forms.problems(broken, family, forms.rules(), fields), key
    # a width inside the nested block cannot part from the source's key
    broken = json.loads(json.dumps(CONFIG))
    broken["decoder"]["gdn_value_size"] = 128
    assert any("gdn_value_size" in line for line in forms.problems(
        broken, family, forms.rules(), fields))
    # three layers are no whole period
    broken = json.loads(json.dumps(CONFIG))
    broken["num_hidden_layers"] = broken["decoder"]["num_blocks"] = 3
    broken["layer_types"] = broken["layer_types"][:3]
    assert any("under the floor" in line for line in forms.problems(
        broken, family, forms.rules(), fields))
    assert CONFIG["chips_sharing_a_layer"] == 8
    assert "2-way" in CONFIG["deployment"] and "8-way" in CONFIG["deployment"]
    assert "mean square" in CONFIG["deployment"]
    for key in ("assumed", "left_out", "source_values", "deployment"):
        assert CONFIG[key], key
    assert {"block", "qk_norm", "rope", "head_dim", "gated_delta_net", "init",
            "optimizer", "chunk"} <= set(CONFIG["assumed"])
    sizing = CONFIG["sizing"]
    assert sizing["step_bytes"] <= 15.75e9
    assert sizing["step_bytes"] > 0.25 * 16.909e9
    from benchmark.reference import olmo_hybrid as reference
    shape = reference.shape_of(CONFIG)
    assert shape["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert shape["head_dim"] == 128 and shape["linear"] == dict(
        key_dim=96, value_dim=192, taps=4)
    with pytest.raises(AssertionError):     # a rotation it does not build
        reference.shape_of(dict(CONFIG,
                                rope_parameters={"rope_theta": 500000}))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    assert sorted(CONFIG["reduced"]) == [
        "layer_types", "linear_num_key_heads", "linear_num_value_heads",
        "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "vocab_size"]
    entry = next(c for c in MANIFEST.data["configs"]
                 if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


def test_the_new_cell_rehearses(tmp_path):
    """The cell end to end at the family's tiny shapes on the CPU: the timed
    step against the reference, the counters against the layout, `correct`
    true, and every value null."""
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
         NAME, "--rehearse", "--seconds", "1", "--trace", "0", "--seed",
         "3000000019", "--out_dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=mf.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True, \
        line.get("failures")
    assert set(line["metrics"]) == {"train_images_per_s_chip", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    checks = line["checks"]
    assert sorted(checks["leaf_gaps"]) == [
        "attention.q_norm", "attention.wq", "first.conv", "first.post_norm",
        "first.wa", "first.wb", "first.wq", "first.wz", "last.conv",
        "last.wa", "last.wb", "last.wq", "last.wz", "linear.A_log",
        "linear.dt_bias"]
    assert checks["loss_rel_gap"] < checks["loss_rtol"]
