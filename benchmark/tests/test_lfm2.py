"""What PR 48 added to the benchmark, off the chip: the gated convolution's
need by hand, the layout's counts against a count from the rows, the five
readers on a made-up run and on a hand-made trace, the arithmetic against the
program's, the configuration file against the source's catalog row and the
rules of form, the balance rule at work in the rehearsal's step, and the new
cell rehearsed."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import flops_lfm2, forms, roofline_lfm2, scopes
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
NAME = "lfm2_24b_a2b_ep8_train_packed8k"
CELL = MANIFEST.cell(NAME)
CONFIG = MANIFEST.config("lfm2_24b_a2b_ep8")
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ["lfm2_moe_mfu_pct", "gconv_roofline", "gconv_mixer_busy_pct",
           "headnorm_attention_roofline", "routed_ffn_busy_pct"]
APPENDED_TO = ["device_idle_pct", "fused_optimizer_roofline",
               "nonmatmul_busy_pct", "step_hbm_gb", "compiles_in_window",
               "train_tokens_per_s_chip", "packing_padding_pct",
               "decoder_attention_busy_pct", "expert_ffn_roofline",
               "moe_route_busy_pct", "expert_load_max_over_mean"]
COUNTS = {"tokens": 16240.0, "padding_tokens": 144.0, "images": 10.0,
          "targets": 16230.0, "causal_pairs": 20698620.0,
          "expert_slots_here": 32480.0, "route_load_max_over_mean": 1.1}

HLO = '''
HloModule jit_train_step
ENTRY %main {
  %fusion.1 = f32[2,8192,2048]{2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run0/blocks/mixer/gconv_in/mul"}
  %fusion.2 = f32[2,8192,2048]{2,1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run3/blocks/mixer/gconv/add"}
  %fusion.3 = bf16[2,8192,2048]{2,1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/mixer/gconv_out/jit(_where)/select_n"}
  %fusion.4 = bf16[16384,6144]{1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/mixer/in_proj/dot_general"}
  %fusion.5 = bf16[2,8192,32,64]{3,2,1,0} fusion(%e), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/attn/qk_norm/q_norm/rsqrt"}
  %fusion.6 = f32[16384,64]{1,0} fusion(%f), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_route/top_k"}
  %fusion.7 = bf16[65536,2048]{1,0} fusion(%g), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_dispatch/gather"}
  %fusion.8 = f32[65536,1536]{1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/expert_ffn/mul"}
  %fusion.9 = f32[16384,2048]{1,0} fusion(%i), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run1/blocks/moe/moe_combine/mul"}
  ROOT %flash = bf16[32,8192,64] custom-call(%j), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(Decoder)/run2/blocks/attn/flash_causal_fwd"}
}
'''


def test_the_gated_convolutions_need_by_hand():
    """One layer, 1,000 tokens of 2,048 channels, 3 taps: forward the
    projection's three streams read and one written, backward seven more, in
    bf16; 23 FLOPs a token and channel; the taps' few KB."""
    flops, nbytes = roofline_lfm2.gated_conv_need(1000, 2048, 3, 1)
    assert flops == (1 + 5 + 1 + 2 + 2 + 5 + 6 + 1) * 1000 * 2048
    assert nbytes == (3 + 1 + 3 + 1 + 3) * 1000 * 2048 * 2 + 3 * 3 * 2048 * 4
    # four layers are four times one
    assert roofline_lfm2.gated_conv_need(1000, 2048, 3, 4) \
        == (4 * flops, 4 * nbytes)
    # memory bounds it on the v5e: 23 FLOPs against 22 bytes a channel
    assert flops / PEAKS["bf16_flops"] < nbytes / PEAKS["hbm_bytes_per_s"]
    # the cell's step: 16,240 tokens in four conv layers, 2.9 GB: 3.6 ms
    _, step_bytes = roofline_lfm2.gated_conv_need(16240, 2048, 3, 4)
    assert 3.5e-3 < step_bytes / PEAKS["hbm_bytes_per_s"] < 3.7e-3


def test_the_layout_is_what_the_traffic_file_says():
    counts = flops_lfm2.layout_counts(TRAFFIC["rows"], TRAFFIC["row_tokens"])
    assert counts == TRAFFIC["layout"] == {
        "documents": 10, "tokens": 16240, "padding_tokens": 144,
        "targets": 16230, "causal_pairs": 20698620}
    # ... against a count from the rows, by hand
    pairs = [sum(t + 1 for n in row for t in range(n))
             for row in TRAFFIC["rows"]]
    assert pairs == [12272105, 8426515] and sum(pairs) == 20698620
    assert [8192 - sum(row) for row in TRAFFIC["rows"]] == [82, 62]
    assert round(100 * 144 / 16384, 2) == 0.88
    tiny = TRAFFIC["rehearse"]
    assert flops_lfm2.layout_counts(tiny["rows"], tiny["row_tokens"]) \
        == tiny["layout"]
    assert TRAFFIC["rows"] == [[4300, 2100, 1150, 560],
                               [3000, 2200, 1400, 900, 450, 180]]
    assert (TRAFFIC["kind"], TRAFFIC["rows_per_chip"], TRAFFIC["row_tokens"],
            TRAFFIC["docs_per_row"], TRAFFIC["warm_steps"],
            TRAFFIC["run_ahead"], TRAFFIC["logit_positions"],
            TRAFFIC["expect_decreasing"]) == (
        "train_gated_conv_packed", 2, 8192, 6, 3, 2, 64, True)
    # ISSUE 48's pre-pass is left out by its own rule (the file says why)
    assert "balance" not in TRAFFIC and "pre-pass" in TRAFFIC["why"]
    # 186.1M matmul parameters a token at an eighth of the slots, 6 FLOPs
    # each with the backward, and half a TFLOP of attention
    per_step = flops_lfm2.model_flops_per_step(
        CONFIG, 16240, 16230, 20698620, 4 * 8120)
    assert 18.0e12 < per_step < 19.0e12
    assert 3 * 4 * 20698620 * 32 * 64 == pytest.approx(0.509e12, rel=1e-3)


def test_the_manifests_new_entries():
    assert forms.manifest_problems(MANIFEST) == {}
    data = MANIFEST.data
    entry = next(c for c in data["configs"] if c["name"] == CONFIG["name"])
    assert entry["file"] == "benchmark/configs/lfm2_24b_a2b_ep8.json"
    assert CELL == {"name": NAME, "config": "lfm2_24b_a2b_ep8",
                    "traffic": "packed_rows8192_tunemix", "chips": 1,
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
        if not name.startswith("gconv"):    # the one new layer is the mixer
            assert reader["layer"] in older, name
    assert by_name["gconv_roofline"]["layer"] \
        == by_name["gconv_mixer_busy_pct"]["layer"]
    for name in APPENDED_TO:
        assert NAME in by_name[name]["workloads"], name
    assert NAME in data["end_to_end"][0]["workloads"]
    per_layer = [m["name"] for m in MANIFEST.metrics("per_layer", NAME)]
    assert sorted(per_layer) == sorted(APPENDED_TO + READERS)
    assert [m["name"] for m in MANIFEST.metrics("end_to_end", NAME)] == [
        "train_images_per_s_chip", "setup_s"]
    # at most a quarter of the cells ask for four chips
    cells = data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(len(cells) // 4, 1)


def made_up_run(trace=None, program=None, config=CONFIG, **records):
    return types.SimpleNamespace(
        trace=trace, records=records, program=program or {}, config=config,
        chips=1, peaks=PEAKS)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a program without the new scopes, or in another family's cell (the
    parent's, Ling's with its own counters and scopes), each new reader
    returns None and does not raise, traced or not."""
    read = mf.metric_reader(name).read
    assert read(made_up_run(steps=3, window_s=1.0)) is None
    ling = MANIFEST.config("ling3_flash_vl_ep64tp2")
    ops = [tr.Op(0, 10, "flash", "custom-call", "flash_causal_fwd",
                 self_ns=10.0),
           tr.Op(10, 20, "fusion.1", "fusion", "", self_ns=10.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 20)])],
                            [])
    assert read(made_up_run(trace, {"op_scopes": {"fusion.1": "moe_route"}},
                            config=ling, packed_counts=COUNTS, steps=3,
                            window_s=1.0)) is None


def test_readers_on_counters_and_a_hand_made_trace():
    from benchmark.generators import train_gated_conv_packed as gen
    found = scopes.index(HLO, gen.SCOPES)
    assert found == {"fusion.1": "gconv_in", "fusion.2": "gconv",
                     "fusion.3": "gconv_out", "fusion.5": "qk_norm",
                     "fusion.6": "moe_route", "fusion.7": "moe_dispatch",
                     "fusion.8": "expert_ffn", "fusion.9": "moe_combine"}
    # one step in a window of 10 ms: 600 us of the mixer, 1,000 of a
    # projection, 100 of the norm a head, 2,500 of the expert layer, 800 in
    # the kernel
    spans = [("fusion.1", 100e3), ("fusion.2", 300e3), ("fusion.3", 200e3),
             ("fusion.4", 1000e3), ("fusion.5", 100e3), ("fusion.6", 200e3),
             ("fusion.7", 700e3), ("fusion.8", 1200e3), ("fusion.9", 400e3)]
    ops, at = [], 0.0
    for name, ns in spans:
        ops.append(tr.Op(at, at + ns, name, "fusion", "", self_ns=ns))
        at += ns
    ops.append(tr.Op(at, at + 800e3, "flash", "custom-call",
                     "flash_causal_fwd", self_ns=800e3))
    trace = tr.ReducedTrace(
        (0, 1e7), [tr.DeviceTrace("d", ops, [(0, 5000e3)])], [])
    run = made_up_run(trace, {"op_scopes": found}, packed_counts=COUNTS,
                      steps=1, window_s=1e-2)
    assert mf.metric_reader("gconv_mixer_busy_pct").read(run) \
        == pytest.approx(100 * 600 / 5000)
    assert mf.metric_reader("routed_ffn_busy_pct").read(run) \
        == pytest.approx(100 * 2500 / 5000)
    assert mf.metric_reader("moe_route_busy_pct").read(run) \
        == pytest.approx(100 * 1300 / 5000)
    assert mf.metric_reader("decoder_attention_busy_pct").read(run) \
        == pytest.approx(100 * 800 / 5000)
    flops, nbytes = roofline_lfm2.gated_conv_need(16240, 2048, 3, 4)
    assert mf.metric_reader("gconv_roofline").read(run) \
        == pytest.approx(100 * (nbytes / 819e9) / 600e-6)
    assert run.records["gconv_bound"] == "memory"
    assert run.records["gconv_s"] == pytest.approx(600e-6)
    need = 6 * 2 * 32 * 20698620 * 64
    assert mf.metric_reader("headnorm_attention_roofline").read(run) \
        == pytest.approx(100 * (need / 197e12) / 800e-6)
    assert run.records["flash_causal_bound"] == "compute"
    want = flops_lfm2.model_flops_per_step(CONFIG, 16240, 16230, 20698620,
                                           32480)
    assert mf.metric_reader("lfm2_moe_mfu_pct").read(run) \
        == pytest.approx(100 * want * 100 / 197e12)
    # the accepted readers this cell is appended to read its file unedited
    assert mf.metric_reader("sparse_ffn_busy_pct").read(run) is None
    run.records["expert_load"] = [[1000, 1100, 900, 1000, 1050, 950, 1000,
                                   1000]] * 4
    assert mf.metric_reader("expert_load_max_over_mean").read(run) \
        == pytest.approx(1.1)
    assert mf.metric_reader("packing_padding_pct").read(run) \
        == pytest.approx(100 * 144 / 16384)


def test_the_arithmetic_equals_the_programs():
    from benchmark.generators import train_gated_conv_packed as gen
    cfg = gen.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC, 1, 0)
    pairs = flops_lfm2.against_program(CONFIG, TRAFFIC, cfg)
    assert [what for what, _, _ in pairs] == ["FLOPs a step", "parameters"]
    for what, ours, programs in pairs:
        assert ours == programs, what
    assert flops_lfm2.param_count(CONFIG) == CONFIG["parameters"] \
        == 469_285_248
    by_part = CONFIG["sizing"]["parameters_by_part"]
    assert sum(by_part[k] * n for k, n in zip(by_part, (4, 1, 4, 1, 5, 1))) \
        == CONFIG["parameters"]
    # the shares reach `Config` from the nested block: 8 of 64 experts, an
    # eighth of the rows; the model's form
    assert (cfg.experts_held, cfg.experts_routed, cfg.expert_first,
            cfg.experts_per_token) == (8, 64, 0, 4)
    assert cfg.vocab_rows * 8 == CONFIG["source_values"]["vocab_size"]
    assert cfg.layer_kinds == ("conv", "conv", "full_attention", "conv",
                               "conv")
    assert cfg.layer_mlps == ("dense",) + ("sparse",) * 4
    assert cfg.head_norm and not cfg.qk_norm and cfg.tie_embeddings
    assert cfg.route_bias and cfg.route_weight_eps == 1e-6
    assert cfg.shared_expert_dim == 0 and cfg.route_groups == 0
    assert (cfg.gconv_width, cfg.head_size, cfg.kv_heads) == (3, 64, 8)
    assert cfg.position_embedding == "rope" and cfg.rope_theta_full == 1e6


def test_the_configuration_keeps_the_rules_of_form():
    import dataclasses

    from vitax.config import Config
    family = MANIFEST.family(CONFIG["family"])
    fields = {f.name for f in dataclasses.fields(Config)}
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "conv_L_cache", "head_dim",
                "norm_eps"):
        broken = json.loads(json.dumps(CONFIG))
        broken["reduced"].append(key)
        broken["source_values"][key] = broken[key] * 2
        assert f"`{key}` is a width: a width is never reduced" in \
            forms.problems(broken, family, forms.rules(), fields), key
    # a width inside the nested block cannot part from the source's key
    for key, value in (("expert_dim", 768), ("gconv_width", 4),
                       ("route_weight_eps", 0.0)):
        broken = json.loads(json.dumps(CONFIG))
        broken["decoder"][key] = value
        assert any(key in line for line in forms.problems(
            broken, family, forms.rules(), fields)), key
    # four layers after one leading dense layer are the floor and a period
    assert forms.period_of(
        CONFIG["source_values"]["layer_types"][CONFIG["num_dense_layers"]:]
    ) == 4
    broken = json.loads(json.dumps(CONFIG))
    broken["num_hidden_layers"] = broken["decoder"]["num_blocks"] = 4
    broken["layer_types"] = broken["layer_types"][:4]
    assert any("under the floor" in line for line in forms.problems(
        broken, family, forms.rules(), fields))
    # seven experts are under the floor of eight
    broken = json.loads(json.dumps(CONFIG))
    broken["num_experts"] = broken["decoder"]["experts_held"] = 7
    assert any("under the floor" in line for line in forms.problems(
        broken, family, forms.rules(), fields))
    assert CONFIG["chips_sharing_a_layer"] == 8
    assert CONFIG["deployment"].count("8-way") == 2
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert "mlp_layer_types" not in CONFIG["reduced"]
    assert "mlp_layer_types" not in family["roles"]["per_layer"]
    for key in ("assumed", "left_out", "source_values", "deployment"):
        assert CONFIG[key], key
    assert {"tied_table", "head_dim", "gated_convolution", "attention",
            "block", "router", "router_bias", "init", "optimizer"} \
        <= set(CONFIG["assumed"])
    sizing = CONFIG["sizing"]
    assert sizing["step_bytes"] <= 15.75 * 2 ** 30
    assert sizing["step_bytes"] > 0.7 * 16.909e9
    from benchmark.reference import lfm2_moe as reference
    shape = reference.shape_of(CONFIG)
    assert shape["layer_types"] == ["conv", "conv", "full_attention", "conv",
                                    "conv"]
    assert (shape["heads"], shape["kv_heads"], shape["head_dim"],
            shape["taps"], shape["top_k"], shape["experts_routed"]) == (
        32, 8, 64, 3, 4, 64)
    with pytest.raises(AssertionError):     # a bias on the taps: not built
        reference.shape_of(dict(CONFIG, conv_bias=True))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert CONFIG["source"] == row["source_url"]
    assert sorted(CONFIG["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
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


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(mf.BENCH_DIR, "reference", "lfm2_moe.py")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "import vitax" not in text and "from vitax" not in text


def test_the_trainers_rule_moves_the_bias_in_the_rehearsals_step():
    """The program the cell times, at the family's tiny shape on the CPU: the
    biases start at the 0 they are seeded with, every step moves each by the
    trainer's rate up, down or not at all, and nothing stands in set-up's way
    of that (no pre-pass: the traffic file has no `balance` block)."""
    import jax
    import numpy as np

    from benchmark.generators import train_gated_conv_packed as gen
    from vitax.programs.builder import Geometry, build_program
    from vitax.train.step import BALANCE_RATE

    config = json.loads(json.dumps(CONFIG))
    traffic = json.loads(json.dumps(TRAFFIC))
    mf.apply_rehearsal(config, traffic, MANIFEST.family(config["family"]))
    cfg = gen.build_config(MANIFEST.config_kwargs(config), traffic, 1, 5)
    geom = Geometry.assemble(cfg, gen.MAX_ITERATION, materialize=True,
                             devices=jax.devices()[:1])
    state, geom.state = geom.state, None
    step = build_program("train", geom)
    batch = gen.make_inputs(cfg, geom.mesh, 5,
                            gen.layout(cfg, traffic["rows"], 1))

    def biases(params):
        return [np.asarray(leaf) for path, leaf in
                jax.tree_util.tree_leaves_with_path(params)
                if "router_bias" in jax.tree_util.keystr(path)]

    assert len(biases(state.params)) == 3       # three runs of sparse layers
    assert not any(b.any() for b in biases(state.params))
    rng = jax.random.key(1)
    for n in (1, 2):
        state, metrics = step(state, batch, rng)
        for b in biases(state.params):
            assert b.shape[-1] == cfg.experts_routed
            assert np.abs(b).max() <= n * BALANCE_RATE * (1 + 1e-3)
            assert (np.abs(b) > 0.5 * BALANCE_RATE).mean() > 0.5
    assert float(metrics["route_load_max_over_mean"]) > 1.0


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
        "attention.k_norm", "attention.q_norm", "attention.wq", "first.conv",
        "first.in_proj", "first.out_proj", "last.conv", "last.in_proj",
        "last.out_proj", "sparse1.experts_gate", "sparse1.router",
        "sparse2.router", "sparse3.router", "sparse4.router"]
    assert checks["loss_rel_gap"] < checks["loss_rtol"]
    assert "balance_passes" not in checks
    with open(tmp_path / f"{NAME}.trace0.seed3000000019.json") as f:
        record = json.load(f)
    counts = record["records"]["packed_counts"]
    assert counts["route_load_max_over_mean"] >= 1.0
    assert counts["expert_slots_here"] > 0
