"""What PR 51 added to the benchmark, off the chip: the layout's counts against
a count from the rows, the three readers on a made-up run and the accepted
readers reading this cell's file unedited on a hand-made trace, the
arithmetic against the program's, the configuration file against the source's
catalog row and the rules of form, and the new cell rehearsed on two seeds."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops_smallthinker, forms, roofline_laguna, scopes
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
NAME = "smallthinker_21b_a3b_ep8_train_longrow"
CELL = MANIFEST.cell(NAME)
CONFIG = MANIFEST.config("smallthinker_21b_a3b_ep8")
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["smallthinker_mfu_pct", "reglu_live_pct", "window_pairs_share_pct"]
APPENDED_TO = ["full_attention_roofline", "window_attention_roofline",
               "decoder_attention_busy_pct", "expert_ffn_roofline",
               "moe_route_busy_pct", "expert_load_max_over_mean",
               "fused_optimizer_roofline", "nonmatmul_busy_pct",
               "step_hbm_gb", "compiles_in_window", "train_tokens_per_s_chip",
               "packing_padding_pct", "device_idle_pct"]
SLOTS = 4 * 12090.0                 # 16,120 tokens x 6 x 8 / 64 a layer
COUNTS = {"tokens": 16120.0, "padding_tokens": 264.0, "images": 4.0,
          "targets": 16116.0, "causal_pairs": 76081260.0,
          "window_pairs": 44840700.0, "expert_slots_here": SLOTS,
          "expert_rows_computed": 4 * 14336.0,
          "expert_hidden_live": 0.5 * SLOTS * 768}

HLO = '''
HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[16384,64]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_route/top_k"}
  %fusion.2 = bf16[98304,2560]{1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_dispatch/gather"}
  %fusion.3 = f32[2048,768]{1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/expert_ffn/max"}
  %fusion.4 = f32[16384,2560]{1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run0/blocks/moe/moe_combine/mul"}
  %fusion.5 = bf16[1,16384,28,128]{3,2,1,0} fusion(%e), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/attn/rope1d/concatenate"}
  %fusion.6 = bf16[16384,3584]{1,0} fusion(%f), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/attn/wq/dot_general"}
  ROOT %flash = bf16[28,16384,128] custom-call(%g), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/attn/flash_window_fwd"}
}
'''


def test_the_layout_is_what_the_traffic_file_says():
    counts = flops_smallthinker.layout_counts(
        TRAFFIC["rows"], TRAFFIC["row_tokens"], CONFIG["sliding_window_size"])
    assert counts == TRAFFIC["layout"] == {
        "documents": 4, "tokens": 16120, "padding_tokens": 264,
        "targets": 16116, "causal_pairs": 76081260, "window_pairs": 44840700}
    # ... against a count from the rows, by hand
    sizes = TRAFFIC["rows"][0]
    assert sizes == [12000, 2600, 1100, 420]
    assert sum(sum(min(t + 1, 4096) for t in range(n)) for n in sizes) \
        == 44840700
    assert sum(n * (n + 1) // 2 for n in sizes) == 76081260
    assert round(100 * 44840700 / 76081260, 1) == 58.9
    assert round(100 * 264 / 16384, 1) == 1.6
    tiny = TRAFFIC["rehearse"]
    family = MANIFEST.family(CONFIG["family"])
    assert flops_smallthinker.layout_counts(
        tiny["rows"], tiny["row_tokens"],
        family["rehearse"]["sliding_window_size"]) == tiny["layout"]
    # the rehearsal's long document is longer than its window too
    assert max(tiny["rows"][0]) > family["rehearse"]["sliding_window_size"]
    assert (TRAFFIC["kind"], TRAFFIC["rows_per_chip"], TRAFFIC["row_tokens"],
            TRAFFIC["docs_per_row"], TRAFFIC["warm_steps"],
            TRAFFIC["run_ahead"], TRAFFIC["logit_positions"],
            TRAFFIC["expect_decreasing"]) == (
        "train_early_router_packed", 1, 16384, 4, 3, 2, 64, True)
    assert TRAFFIC["row_tokens"] == CONFIG["max_position_embeddings"]
    # ISSUE 51: about 9.0 TFLOP of attention beside 14.6 of the rest
    per_step = flops_smallthinker.model_flops_per_step(
        CONFIG, 16120, 16116, 76081260, 44840700, SLOTS)
    attention = 3 * 4 * (76081260 + 3 * 44840700) * 28 * 128
    assert attention == pytest.approx(9.06e12, rel=1e-2)
    assert per_step - attention == pytest.approx(14.6e12, rel=2e-2)


def test_the_manifests_new_entries():
    assert forms.manifest_problems(MANIFEST) == {}
    data = MANIFEST.data
    entry = next(c for c in data["configs"] if c["name"] == CONFIG["name"])
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b_ep8.json"
    assert CELL == {"name": NAME, "config": "smallthinker_21b_a3b_ep8",
                    "traffic": "packed_1x16384_longmix", "chips": 1,
                    "why": CELL["why"]}
    assert len(CELL["why"]) <= 200 and len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in data["per_layer"]}
    older = {m["layer"] for m in data["per_layer"] if m["name"] not in READERS}
    for name in READERS:
        reader = by_name[name]
        assert reader["workloads"] == [NAME]
        assert reader["moves"] == "train_images_per_s_chip"
        assert sorted(reader) == ["better", "layer", "moves", "name",
                                  "source", "unit", "workloads"]
        assert reader["layer"] in older, name     # no new layer
    assert by_name["reglu_live_pct"]["layer"] \
        == by_name["expert_ffn_roofline"]["layer"]
    assert by_name["window_pairs_share_pct"]["layer"] \
        == by_name["window_attention_roofline"]["layer"]
    for name in APPENDED_TO:
        assert NAME in by_name[name]["workloads"], name
    # LFM2's reader keys on `conv_L_cache` and cannot read this cell
    assert NAME not in by_name["routed_ffn_busy_pct"]["workloads"]
    assert NAME in data["end_to_end"][0]["workloads"]
    per_layer = [m["name"] for m in MANIFEST.metrics("per_layer", NAME)]
    assert sorted(per_layer) == sorted(APPENDED_TO + READERS)
    assert [m["name"] for m in MANIFEST.metrics("end_to_end", NAME)] == [
        "train_images_per_s_chip", "setup_s"]
    # at most a quarter of the cells ask for four chips
    cells = data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(len(cells) // 4, 1)
    assert NAME in [c["name"] for c in cells]


def made_up_run(trace=None, program=None, config=CONFIG, **records):
    return types.SimpleNamespace(
        trace=trace, records=records, program=program or {}, config=config,
        chips=1, peaks=PEAKS)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a run without the step's counters, or in another family's cell
    whose step sows no live count and counts no window (the parent's, LFM2's),
    each new reader returns None and does not raise, traced or not."""
    read = mf.metric_reader(name).read
    assert read(made_up_run(steps=3, window_s=1.0)) is None
    lfm2 = MANIFEST.config("lfm2_24b_a2b_ep8")
    counts = {k: v for k, v in COUNTS.items()
              if k not in ("window_pairs", "expert_hidden_live")}
    ops = [tr.Op(0, 10, "flash", "custom-call", "flash_causal_fwd",
                 self_ns=10.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 10)])],
                            [])
    assert read(made_up_run(trace, config=lfm2, packed_counts=counts,
                            steps=3, window_s=1.0)) is None


def test_readers_on_counters_and_a_hand_made_trace():
    from benchmark.generators import train_early_router_packed as gen
    found = scopes.index(HLO, gen.SCOPES)
    assert found == {"fusion.1": "moe_route", "fusion.2": "moe_dispatch",
                     "fusion.3": "expert_ffn", "fusion.4": "moe_combine",
                     "fusion.5": "rope1d"}
    # one step in a window of 200 ms: the router 1 ms, dispatch 3, the ReGLU's
    # elementwise part 2, combine 2, the rotation 1, a projection 5; the
    # window kernels 60 ms, the causal ones 30, the grouped products 12, the
    # optimizer 14
    spans = [("fusion.1", "fusion", "", 1e6), ("fusion.2", "fusion", "", 3e6),
             ("fusion.3", "fusion", "", 2e6), ("fusion.4", "fusion", "", 2e6),
             ("fusion.5", "fusion", "", 1e6), ("fusion.6", "fusion", "", 5e6),
             ("flash.w", "custom-call", "flash_window_fwd", 60e6),
             ("flash.c", "custom-call", "flash_causal_dq", 30e6),
             ("ragged-dot.3", "custom-call", "ragged-dot.3", 12e6),
             ("adamw", "custom-call", "fused_adamw_kernel", 14e6)]
    ops, at = [], 0.0
    for name, category, kernel, ns in spans:
        ops.append(tr.Op(at, at + ns, name, category, kernel, self_ns=ns))
        at += ns
    assert at == 130e6
    trace = tr.ReducedTrace((0, 2e8), [tr.DeviceTrace("d", ops, [(0, at)])],
                            [])
    run = made_up_run(
        trace, {"op_scopes": found, "step_bytes": 9.47e9,
                "params": CONFIG["parameters"]},
        packed_counts=COUNTS, steps=1, window_s=0.2, compiles_in_window=0)
    read = {name: mf.metric_reader(name).read
            for name in READERS + APPENDED_TO}
    # the three new readers
    want = flops_smallthinker.model_flops_per_step(
        CONFIG, 16120, 16116, 76081260, 44840700, SLOTS)
    assert read["smallthinker_mfu_pct"](run) \
        == pytest.approx(100 * want * 5 / 197e12)
    assert read["reglu_live_pct"](run) == pytest.approx(50.0)
    assert read["window_pairs_share_pct"](run) \
        == pytest.approx(100 * 44840700 / 76081260)
    assert 50 < read["window_pairs_share_pct"](run) < 70
    # the accepted readers read this cell's file unedited, through its
    # aliases: 28 query heads over 4 key/value heads of 128, one full layer
    # and three sliding ones
    need = roofline_laguna.attention_need(44840700, 16120, 28, 4, 128, 3)
    assert need[0] == 6 * 2 * 28 * 44840700 * 128 * 3
    assert read["window_attention_roofline"](run) \
        == pytest.approx(100 * (need[0] / 197e12) / 60e-3)
    assert run.records["flash_window_bound"] == "compute"
    need = roofline_laguna.attention_need(76081260, 16120, 28, 4, 128, 1)
    assert read["full_attention_roofline"](run) \
        == pytest.approx(100 * (need[0] / 197e12) / 30e-3)
    flops, nbytes = roofline_laguna.expert_ffn_need(SLOTS, 2560, 768, 8, 4)
    assert flops == 3 * 3 * 2 * SLOTS * 2560 * 768
    assert read["expert_ffn_roofline"](run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 12e-3)
    assert read["decoder_attention_busy_pct"](run) \
        == pytest.approx(100 * 90 / 130)
    assert read["moe_route_busy_pct"](run) == pytest.approx(100 * 6 / 130)
    assert read["fused_optimizer_roofline"](run) == pytest.approx(
        100 * (28 * CONFIG["parameters"] / 819e9) / 14e-3)
    assert read["nonmatmul_busy_pct"](run) is not None
    assert read["step_hbm_gb"](run) == pytest.approx(9.47)
    assert read["compiles_in_window"](run) == 0.0
    assert read["train_tokens_per_s_chip"](run) == pytest.approx(80600.0)
    assert read["packing_padding_pct"](run) \
        == pytest.approx(100 * 264 / 16384)
    assert read["device_idle_pct"](run) == pytest.approx(35.0)
    run.records["expert_load"] = [[1500, 1650, 1350, 1500, 1575, 1425, 1500,
                                   1500]] * 4
    assert read["expert_load_max_over_mean"](run) == pytest.approx(1.1)
    # LFM2's two readers that key on its file stay silent here
    assert mf.metric_reader("routed_ffn_busy_pct").read(run) is None
    assert mf.metric_reader("lfm2_moe_mfu_pct").read(run) is None
    # no share of a roofline or of the peak passes 100 on these made-up times
    for name in ("smallthinker_mfu_pct", "window_attention_roofline",
                 "full_attention_roofline", "expert_ffn_roofline",
                 "fused_optimizer_roofline"):
        assert 0 < read[name](run) <= 100, name


def test_the_arithmetic_equals_the_programs():
    from benchmark.generators import train_early_router_packed as gen
    cfg = gen.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC, 1, 0)
    pairs = flops_smallthinker.against_program(CONFIG, TRAFFIC, cfg)
    assert [what for what, _, _ in pairs] == ["FLOPs a step", "parameters"]
    for what, ours, programs in pairs:
        assert ours == programs, what
    assert flops_smallthinker.param_count(CONFIG) == CONFIG["parameters"] \
        == 370_547_200
    by_part = CONFIG["sizing"]["parameters_by_part"]
    assert sum(by_part[k] * n for k, n in zip(by_part, (4, 4, 4, 4, 1))) \
        == CONFIG["parameters"]
    # the shares reach `Config` from the nested block: 8 of 64 experts, an
    # eighth of the rows; the model's form
    assert (cfg.experts_held, cfg.experts_routed, cfg.expert_first,
            cfg.experts_per_token) == (8, 64, 0, 6)
    assert cfg.vocab_rows * 8 == CONFIG["source_values"]["vocab_size"]
    assert cfg.layer_kinds == ("full_attention",) + ("sliding_attention",) * 3
    assert cfg.layer_mlps == ("sparse",) * 4 and cfg.layer_heads == (28,) * 4
    assert (cfg.route_form, cfg.route_early, cfg.expert_activation) == (
        "softmax_chosen", True, "relu")
    assert (cfg.rope_fraction_full, cfg.rope_fraction_window,
            cfg.rope_theta_window) == (0.0, 1.0, 1.5e6)
    assert (cfg.window_tokens, cfg.head_size, cfg.kv_heads, cfg.embed_dim,
            cfg.expert_dim) == (4096, 128, 4, 2560, 768)
    assert cfg.position_embedding == "rope" and not cfg.tie_embeddings
    assert cfg.shared_expert_dim == 0 and not cfg.route_bias
    assert (cfg.pack_tokens, cfg.pack_images, cfg.batch_size) == (16384, 4, 1)
    # B = 2,048 sorted rows a trip of the expert loops at these shapes
    from vitax.models.experts import block_rows
    assert block_rows(16384 * 6, 8, 64) == 2048


def test_the_configuration_keeps_the_rules_of_form():
    import dataclasses

    from vitax.config import Config
    family = MANIFEST.family(CONFIG["family"])
    fields = {f.name for f in dataclasses.fields(Config)}
    for key in ("hidden_size", "head_dim", "moe_ffn_hidden_size",
                "moe_num_active_primary_experts", "sliding_window_size",
                "rope_theta", "moe_intermediate_size", "sliding_window"):
        broken = json.loads(json.dumps(CONFIG))
        broken["reduced"].append(key)
        broken["source_values"][key] = broken[key] * 2
        assert f"`{key}` is a width: a width is never reduced" in \
            forms.problems(broken, family, forms.rules(), fields), key
    # a width inside the nested block, or an alias, cannot part from the
    # source's key
    for block, key, value in (("decoder", "expert_dim", 384),
                              ("decoder", "window_tokens", 512),
                              ("decoder", "experts_per_token", 4),
                              ("decoder", "rope_fraction_full", 1.0),
                              ("decoder", "layer_heads", [28, 28, 28, 14]),
                              (None, "moe_intermediate_size", 1536),
                              (None, "sliding_window", 512),
                              (None, "num_experts", 16)):
        broken = json.loads(json.dumps(CONFIG))
        (broken[block] if block else broken)[key] = value
        assert any(key in line for line in forms.problems(
            broken, family, forms.rules(), fields)), key
    # four layers are the floor and a whole period
    assert forms.period_of(CONFIG["source_values"]["rope_layout"]) == 4
    broken = json.loads(json.dumps(CONFIG))
    broken["num_hidden_layers"] = broken["decoder"]["num_blocks"] = 3
    for key in ("rope_layout", "sliding_window_layout"):
        broken[key] = broken[key][:3]
    assert any("under the floor" in line for line in forms.problems(
        broken, family, forms.rules(), fields))
    # seven experts are under the floor of eight, 18,991 rows under an eighth
    for key, nested, value in (("moe_num_primary_experts", "experts_held", 7),
                               ("vocab_size", "vocab_rows", 18991)):
        broken = json.loads(json.dumps(CONFIG))
        broken[key] = broken["decoder"][nested] = value
        assert any("under" in line for line in forms.problems(
            broken, family, forms.rules(), fields)), key
    assert CONFIG["vocab_size"] * 8 == 151936
    assert CONFIG["chips_sharing_a_layer"] == 8
    assert sorted(CONFIG["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "rope_layout",
        "sliding_window_layout", "vocab_size"]
    for key in ("assumed", "left_out", "source_values", "deployment"):
        assert CONFIG[key], key
    assert {"early_router", "router", "experts", "attention", "block",
            "init", "optimizer"} <= set(CONFIG["assumed"])
    assert {"auxiliary_balance_loss", "secondary_experts"} \
        <= set(CONFIG["left_out"])
    sizing = CONFIG["sizing"]
    assert sizing["cell"] == NAME
    assert 0.25 * 16.909e9 < sizing["step_bytes"] <= 15.75 * 2 ** 30
    from benchmark.reference import smallthinker as reference
    shape = reference.shape_of(CONFIG)
    assert shape["rope_layout"] == shape["window_layout"] == [0, 1, 1, 1]
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"],
            shape["window"], shape["top_k"], shape["experts_routed"],
            shape["theta"], shape["eps"]) == (
        28, 4, 128, 4096, 6, 64, 1500000, 1e-6)
    with pytest.raises(AssertionError):     # a sigmoid router: another model
        reference.shape_of(dict(CONFIG,
                                moe_primary_router_apply_softmax=False))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
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
    # every width of the source
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["moe_ffn_hidden_size"],
            CONFIG["source_values"]["moe_num_primary_experts"],
            CONFIG["moe_num_active_primary_experts"],
            CONFIG["sliding_window_size"], CONFIG["rope_theta"]) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 1500000)


def test_the_held_share_nearest_to_an_eighth_by_hand():
    """`nearest_share`: of 16 experts' loads the 4 that sum nearest to a
    quarter of the slots; a collapsed router (two experts take nearly all) and
    a flat one."""
    from benchmark.generators.train_early_router_packed import nearest_share
    load = [900, 700, 10, 3, 0, 0, 40, 25, 5, 1, 0, 2, 60, 30, 20, 4]
    assert sum(load) == 1800                     # a quarter: 450
    chosen = nearest_share(load, 4)
    assert len(set(chosen)) == 4 and chosen == sorted(chosen)
    # no four of them sum to 450: 0 and 1 overshoot, the rest reach 155
    assert sum(load[e] for e in chosen) == 155
    flat = [100] * 16
    assert sum(flat[e] for e in nearest_share(flat, 4)) == 400
    skew = [0] * 12 + [1000, 500, 300, 200]      # a quarter: 500
    assert sum(skew[e] for e in nearest_share(skew, 4)) == 500


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(mf.BENCH_DIR, "reference", "smallthinker.py")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "import vitax" not in text and "from vitax" not in text
    assert 'PRECISION = "highest"' in text


@pytest.mark.parametrize("seed", [3000000019, 3])
def test_the_new_cell_rehearses(tmp_path, seed):
    """The cell end to end at the family's tiny shapes on the CPU: the timed
    step against the reference, the counters against the layout, `correct`
    true, and every value null."""
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
        "full.norm1", "full.wk", "full.wq", "layer0.experts_gate",
        "layer0.router", "sliding.norm1", "sliding.wk", "sliding.wq"]
    assert checks["loss_rel_gap"] < checks["loss_rtol"]
    with open(tmp_path / f"{NAME}.trace0.seed{seed}.json") as f:
        record = json.load(f)
    counts = record["records"]["packed_counts"]
    assert counts["window_pairs"] < counts["causal_pairs"]
    assert 0 < counts["expert_hidden_live"] \
        < counts["expert_slots_here"] * 48
    assert counts["expert_rows_computed"] >= counts["expert_slots_here"] > 0
    # set-up relabelled every layer's router to a fair share of the slots
    fair = record["records"]["fair_share"]
    assert fair["slots_a_fair_share"] == 125 * 3 * 4 * 8 / 16
    assert all(abs(held - 125 * 3 * 8 / 16) <= 2 for held in fair["held_after"])
    assert abs(counts["expert_slots_here"] - fair["slots_a_fair_share"]) <= 12
