"""What PR 41 added to the benchmark, off the chip: the two mixers' needs by
hand, the layout's counts, the five readers on a made-up run and on a
hand-made trace, the arithmetic against the program's, the configuration
file against the source's catalog row and the rules of form, and the new
cell rehearsed on seeds 0 and 1."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops_ling, forms, roofline_ling, scopes
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
NAME = "ling3_flash_vl_ep64tp2_train_packed4k"
CELL = MANIFEST.cell(NAME)
CONFIG = MANIFEST.config("ling3_flash_vl_ep64tp2")
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["latent_hybrid_mfu_pct", "kda_roofline", "kda_mixer_busy_pct",
           "mla_attention_roofline", "sparse_ffn_busy_pct"]
COUNTS = {"tokens": 4060.0, "padding_tokens": 36.0, "images": 5.0,
          "targets": 4055.0, "causal_pairs": 3861330.0,
          "kda_pairs": 128042.0, "kda_live_chunks": 64.0,
          "expert_slots_here": 3100.0, "tokens_choosing_held_group": 12000.0}

HLO = '''
HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[1,64,16,64,64]{4,3,2,1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/mixer/kda_chunk/dot_general"}
  %fusion.2 = f32[1,16,128,128]{3,2,1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run1/blocks/mixer/kda_state/while/body/dot_general"}
  %fusion.3 = f32[1,4096,6144]{2,1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/kda_conv/mul"}
  %fusion.4 = f32[1,4096,16,128]{3,2,1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run3/blocks/mixer/kda_gate/logistic"}
  %fusion.5 = bf16[1,4096,2048]{2,1,0} fusion(%e), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run3/blocks/mixer/kda_out_norm/rsqrt"}
  %fusion.6 = bf16[4096,2048]{1,0} fusion(%f), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/mixer/wq/dot_general"}
  %fusion.7 = f32[4096,512]{1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_route/top_k"}
  %fusion.8 = bf16[32768,2560]{1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_dispatch/gather"}
  %fusion.9 = bf16[4096,4096]{1,0} fusion(%i), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/attn/mla_latent/wkvb/dot_general"}
  ROOT %flash = bf16[16,4096,128] custom-call(%j), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/attn/flash_latent_fwd"}
}
'''


def test_the_mixers_needs_by_hand():
    # 2 heads of 4 channels, 10 tokens holding 30 pairs in 2 live chunks, one
    # layer: forward (6 * 4 + 4 * 4) a pair and 6 * 4 * 4 a token a head,
    # three times with the backward
    tiny = dict(num_attention_heads=2, head_dim=4, qk_nope_head_dim=4,
                qk_rope_head_dim=2, v_head_dim=4)
    flops, nbytes = roofline_ling.kda_need(tiny, 10, 30, 2, 1)
    assert flops == 3 * (10 * 2 * 4 * 30 + 6 * 2 * 4 * 4 * 10)
    # q, k, v three times and o twice in bf16, g and beta three times in
    # float32; the chunk states and their gradients, written and read
    assert nbytes == 10 * 2 * ((3 * 3 * 4 + 2 * 4) * 2 + 3 * (4 + 1) * 4) \
        + 4 * 2 * 2 * 4 * 4 * 4
    # latent attention: forward 2 * (6 + 4) a pair and a head, backward dV
    # and dP at 4, dQ and dK at 6; q, k at 6 and v, o at 4, three times
    flops, nbytes = roofline_ling.latent_attention_need(tiny, 50, 10, 1)
    assert flops == (2 * (6 + 4) + 2 * (2 * 4 + 2 * 6)) * 2 * 50
    assert nbytes == 3 * 10 * 2 * 2 * (6 + 4) * 2
    # the cell's: 10 * 128 a pair and 6 * 128 * 128 a token, 16 heads
    assert flops_ling.delta_rule_flops_per_layer(CONFIG, 4060, 128042) \
        == 10 * 16 * 128 * 128042 + 6 * 16 * 128 * 128 * 4060
    flops, nbytes = roofline_ling.kda_need(CONFIG, 4060, 128042, 64, 6)
    assert 0.16e12 < flops < 0.17e12 and 3.2e9 < nbytes < 3.4e9
    assert nbytes / 819e9 > flops / 197e12          # memory-bound
    flops, _ = roofline_ling.latent_attention_need(CONFIG, 3861330, 4060, 1)
    assert flops == 6 * 320 * 16 * 3861330


def test_the_layout_is_what_the_traffic_file_says():
    # the delta rule's pairs and live chunks on the yardstick's own grid, a
    # constant and not the chunk the program's `tiling` runs
    chunk = flops_ling.KDA_GRID
    assert chunk == 64
    counts = flops_ling.layout_counts(TRAFFIC["rows"], TRAFFIC["row_tokens"])
    assert counts == TRAFFIC["layout"]
    tiny = TRAFFIC["rehearse"]
    assert flops_ling.layout_counts(tiny["rows"], tiny["row_tokens"]) \
        == tiny["layout"]
    assert TRAFFIC["rows"] == [[2600, 900, 350, 150, 60]]
    assert (TRAFFIC["rows_per_chip"], TRAFFIC["row_tokens"],
            TRAFFIC["docs_per_row"], TRAFFIC["warm_steps"],
            TRAFFIC["run_ahead"], TRAFFIC["logit_positions"],
            TRAFFIC["expect_decreasing"]) == (1, 4096, 5, 3, 2, 64, True)
    assert counts["tokens"] == 4060 and counts["padding_tokens"] == 36
    # every later document starts inside a chunk of the delta rule's grid
    starts = [2600, 3500, 3850, 4000]
    assert all(s % chunk for s in starts)
    # about 320M parameters a token, 6 FLOPs each with the backward
    per_step = flops_ling.model_flops_per_step(
        CONFIG, 4060, 4055, 3861330, 128042, 520)
    assert 7.5e12 < per_step < 9.5e12


def made_up_run(trace=None, program=None, **records):
    return types.SimpleNamespace(
        trace=trace, records=records, program=program or {}, config=CONFIG,
        chips=1, peaks=PEAKS)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a program without the delta rule's counters or scopes (the
    parent's, any other cell's) each new reader returns None and does not
    raise, traced or not."""
    read = mf.metric_reader(name).read
    assert read(made_up_run(steps=3, window_s=1.0)) is None
    laguna = {"tokens": 7970.0, "targets": 7966.0, "causal_pairs": 1.3e7,
              "expert_slots_here": 8000.0}
    ops = [tr.Op(0, 10, "flash", "custom-call", "flash_causal_fwd",
                 self_ns=10.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 10)])],
                            [])
    assert read(made_up_run(trace, {"op_scopes": {}}, packed_counts=laguna,
                            steps=3, window_s=1.0)) is None


def test_readers_on_counters_and_a_hand_made_trace():
    from benchmark.generators import train_latent_packed as gen
    found = scopes.index(HLO, gen.SCOPES)
    assert found == {"fusion.1": "kda_chunk", "fusion.2": "kda_state",
                     "fusion.3": "kda_conv", "fusion.4": "kda_gate",
                     "fusion.5": "kda_out_norm", "fusion.7": "moe_route",
                     "fusion.8": "moe_dispatch", "fusion.9": "mla_latent"}
    # one step in a window of 1 ms: 300 us of delta rule, 100 of the
    # mixer's other scopes, 200 of projections, 150 of the sparse
    # feed-forward, 50 in the latent projections, 100 in the kernel
    spans = [("fusion.1", 200e3), ("fusion.2", 100e3), ("fusion.3", 50e3),
             ("fusion.4", 30e3), ("fusion.5", 20e3), ("fusion.6", 200e3),
             ("fusion.7", 50e3), ("fusion.8", 100e3), ("fusion.9", 50e3)]
    ops, at = [], 0.0
    for name, ns in spans:
        ops.append(tr.Op(at, at + ns, name, "fusion", "", self_ns=ns))
        at += ns
    ops.append(tr.Op(at, at + 100e3, "flash", "custom-call",
                     "flash_latent_fwd", self_ns=100e3))
    trace = tr.ReducedTrace(
        (0, 1e6), [tr.DeviceTrace("d", ops, [(0, 900e3)])], [])
    run = made_up_run(trace, {"op_scopes": found}, packed_counts=COUNTS,
                      steps=1, window_s=1e-3)
    assert mf.metric_reader("kda_mixer_busy_pct").read(run) \
        == pytest.approx(100 * 400 / 900)
    assert mf.metric_reader("sparse_ffn_busy_pct").read(run) \
        == pytest.approx(100 * 150 / 900)
    flops, nbytes = roofline_ling.kda_need(CONFIG, 4060, 128042, 64, 6)
    least = max(flops / 197e12, nbytes / 819e9)
    assert mf.metric_reader("kda_roofline").read(run) == pytest.approx(
        100 * least / 300e-6)
    assert run.records["kda_bound"] == (
        "memory" if nbytes / 819e9 > flops / 197e12 else "compute")
    assert run.records["kda_delta_rule_s"] == pytest.approx(300e-6)
    need = 6 * 320 * 16 * 3861330
    assert mf.metric_reader("mla_attention_roofline").read(run) \
        == pytest.approx(100 * (need / 197e12) / 100e-6)
    assert run.records["flash_latent_bound"] == "compute"
    want = flops_ling.model_flops_per_step(CONFIG, 4060, 4055, 3861330,
                                           128042, 3100)
    assert mf.metric_reader("latent_hybrid_mfu_pct").read(run) \
        == pytest.approx(100 * want * 1000 / 197e12)


def test_the_expert_layers_accepted_readers_read_this_cell_as_it_is():
    """`expert_ffn_roofline` and `moe_route_busy_pct` (PR 32's, unedited)
    find what they read in this cell's records: the configuration file's
    `hidden_size`, `moe_intermediate_size`, `num_experts` held and
    `mlp_layer_types`, the step's `expert_slots_here`, the `ragged-dot`
    kernels and the three scopes; the cell is on both lists."""
    from benchmark import roofline_laguna
    from benchmark.generators import train_latent_packed as gen
    cell = "ling3_flash_vl_ep64tp2_train_packed4k"
    listed = {m["name"]: m.get("workloads", [])
              for m in mf.Manifest().data["per_layer"]}
    for name in ("expert_ffn_roofline", "moe_route_busy_pct",
                 "expert_load_max_over_mean"):
        assert listed[name][-1] == cell and "laguna" in listed[name][0]
    found = scopes.index(HLO, gen.SCOPES)
    ops = [tr.Op(0, 400e3, "ragged-dot.3", "custom-call", "ragged-dot.3",
                 self_ns=400e3),
           tr.Op(400e3, 450e3, "fusion.7", "fusion", "", self_ns=50e3),
           tr.Op(450e3, 550e3, "fusion.8", "fusion", "", self_ns=100e3),
           tr.Op(550e3, 800e3, "fusion.6", "fusion", "", self_ns=250e3)]
    trace = tr.ReducedTrace(
        (0, 1e6), [tr.DeviceTrace("d", ops, [(0, 800e3)])], [])
    run = made_up_run(trace, {"op_scopes": found}, packed_counts=COUNTS,
                      steps=1, window_s=1e-3)
    flops, nbytes = roofline_laguna.expert_ffn_need(3100, 2560, 768, 8, 6)
    assert flops == 9 * 2 * 3100 * 2560 * 768
    assert mf.metric_reader("expert_ffn_roofline").read(run) \
        == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 400e-6)
    assert run.records["expert_ffn_bound"] == "memory"     # the weights
    assert mf.metric_reader("moe_route_busy_pct").read(run) \
        == pytest.approx(100 * 150 / 800)


def test_the_arithmetic_equals_the_programs():
    from benchmark.generators import train_latent_packed as gen
    cfg = gen.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC, 1, 0)
    pairs = flops_ling.against_program(CONFIG, TRAFFIC, cfg)
    assert [what for what, _, _ in pairs] == ["FLOPs a step", "parameters"]
    for what, ours, programs in pairs:
        assert ours == programs, what
    assert flops_ling.param_count(CONFIG) == CONFIG["parameters"] \
        == 648_853_344
    assert sum(CONFIG["sizing"]["parameters_by_part"][k] * n for k, n in zip(
        CONFIG["sizing"]["parameters_by_part"], (6, 1, 6, 1, 7, 1))) \
        == CONFIG["parameters"]
    # the shares reach `Config` from the nested block: 16 of 32 heads in
    # every layer, 8 of 512 experts from the first on, an eighth of the rows
    assert cfg.layer_heads == (16,) * 7 and cfg.kv_heads == 16
    assert (cfg.experts_routed, cfg.experts_held, cfg.expert_first) \
        == (512, 8, 0)
    assert cfg.vocab_rows * 8 == CONFIG["source_values"]["vocab_size"]
    assert (cfg.route_groups, cfg.groups_per_token, cfg.route_bias) \
        == (8, 4, True)
    assert cfg.layer_kinds == ("kda",) * 5 + ("latent_attention", "kda")


def test_the_configuration_keeps_the_rules_of_form():
    import dataclasses

    from vitax.config import Config
    assert "ling3_flash_vl_ep64tp2" not in forms.manifest_problems(MANIFEST)
    family = MANIFEST.family(CONFIG["family"])
    fields = {f.name for f in dataclasses.fields(Config)}
    for key in ("kv_lora_rank", "qk_rope_head_dim", "v_head_dim", "head_dim",
                "moe_intermediate_size", "short_conv_kernel_size",
                "kda_lower_bound", "n_group", "topk_group",
                "num_experts_per_tok", "layer_group_size"):
        broken = json.loads(json.dumps(CONFIG))
        broken["reduced"].append(key)
        broken["source_values"][key] = broken[key] * 2
        assert f"`{key}` is a width: a width is never reduced" in \
            forms.problems(broken, family, forms.rules(), fields), key
    # forms.py holds the heads to no less than one of ALL the chips that
    # share a layer would hold; the file states the two-way split of the
    # heads (and the 64-way one of the experts) in `deployment`
    assert CONFIG["chips_sharing_a_layer"] == 64
    assert "2-way" in CONFIG["deployment"] and "64-way" in CONFIG["deployment"]
    for key in ("assumed", "left_out", "source_values", "deployment"):
        assert CONFIG[key], key
    assert {"kda_gate", "use_qk_norm", "beta", "output_gate", "rope",
            "router", "router_bias", "linear_attention_heads", "init",
            "optimizer", "chunk"} <= set(CONFIG["assumed"])
    assert set(CONFIG["left_out"]) == {
        "vision_tower", "multi_token_prediction", "swiglu_limits"}
    sizing = CONFIG["sizing"]
    assert sizing["step_bytes"] <= 15.75e9
    assert sizing["step_bytes"] > 0.25 * 16.909e9
    # the reference refuses a kept layer with a clamp it does not build
    from benchmark.reference import ling as reference
    clamped = dict(CONFIG, expert_swiglu_limit_list=[0, 0, 4] + [0] * 39)
    with pytest.raises(AssertionError):
        reference.shape_of(clamped)
    assert reference.shape_of(CONFIG)["kinds"] == [
        "kda"] * 5 + ["latent", "kda"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert CONFIG["source"] == row["source_url"]
    assert sorted(CONFIG["reduced"]) == [
        "first_k_dense_replace", "num_attention_heads", "num_experts",
        "num_hidden_layers", "num_key_value_heads", "vocab_size"]
    entry = next(c for c in MANIFEST.data["configs"]
                 if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


@pytest.mark.parametrize("seed", [0, 1])
def test_the_new_cell_rehearses(seed, tmp_path):
    """The cell end to end at the family's tiny shapes on the CPU: the timed
    step against the reference, the counters against the layout, `correct`
    true on two seeds, and every value null."""
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
         NAME, "--rehearse", "--seconds", "1", "--trace", "0", "--seed",
         str(seed), "--out_dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=mf.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True, \
        line.get("failures")
    assert set(line["metrics"]) == {"train_images_per_s_chip", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    checks = line["checks"]
    assert sorted(checks["leaf_gaps"]) == [
        "kda.A_log", "kda.conv", "kda.dt_bias", "kda.wb", "kda.wf",
        "latent.wkva", "latent.wkvb", "latent.wq", "sparse.experts_gate",
        "sparse.router"]
    assert checks["loss_rel_gap"] < checks["loss_rtol"]
