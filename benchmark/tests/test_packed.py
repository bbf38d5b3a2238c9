"""The packed cell's yardstick: the benchmark's copies of the FLOPs and
parameter arithmetic against the program's, the layout the traffic file
states against what its rows hold, and the packed readers on records made by
hand."""

import types

import pytest

from benchmark import flops_packed, roofline_packed
from benchmark import manifest as mf
from benchmark.generators import train_packed
from benchmark.metrics import (packed_attention_busy_pct,
                               packed_attention_roofline, packed_mfu_pct,
                               packing_padding_pct, train_tokens_per_s_chip)

MANIFEST = mf.Manifest()
CELL = MANIFEST.cell("moonvit_train_packed16k")
CONFIG = MANIFEST.config(CELL["config"])
TRAFFIC = MANIFEST.traffic(CELL["traffic"])
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_flops_and_parameters_equal_the_programs():
    from vitax.models.vit import expected_param_count
    from vitax.telemetry.flops import packed_flops_per_step
    config, traffic = CONFIG, TRAFFIC
    cfg = train_packed.build_config(MANIFEST.config_kwargs(config), traffic,
                                    1, 0)
    assert cfg.packed and cfg.mlp_hidden_dim == 4304
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_blocks, cfg.patch_size,
            cfg.pos_grid) == (1152, 16, 27, 14, 64)
    assert flops_packed.param_count(config) == expected_param_count(cfg) \
        == config["parameters"] == 418019032
    counts = flops_packed.layout_counts(traffic["rows"])
    assert flops_packed.model_flops_per_step(config, **counts) \
        == packed_flops_per_step(cfg, **counts)
    # the issue's arithmetic: 2.47 GFLOP of matmul a token, 3.77e13 a step,
    # 1.50e13 of attention
    assert flops_packed.model_flops_per_step(config, 1, 0, 0) \
        == pytest.approx(2.47e9, rel=2e-3)
    assert flops_packed.model_flops_per_step(
        config, 0, counts["token_pairs"], 0) == pytest.approx(1.498e13, rel=1e-3)


def test_the_layout_is_the_issues():
    """2 rows x 8,192 tokens, the nine grids in order, whatever the seed."""
    assert (TRAFFIC["rows_per_chip"], TRAFFIC["row_tokens"],
            TRAFFIC["images_per_row"]) == (2, 8192, 8)
    assert TRAFFIC["rows"] == [
        [[62, 50], [38, 20], [18, 36], [54, 62]],
        [[22, 28], [50, 54], [24, 22], [24, 16], [50, 64]]]
    counts = flops_packed.layout_counts(TRAFFIC["rows"])
    stated = TRAFFIC["layout"]
    assert counts == {"tokens": 15284, "token_pairs": 40152304, "images": 9}
    assert {k: stated[k] for k in counts} == counts
    assert stated["padding_tokens"] == 2 * 8192 - 15284 == 1100
    assert TRAFFIC["check_rows"][0] == TRAFFIC["rows"][0][:2]
    limit = CONFIG["native_res"]["max_image_tokens"]
    assert all(h * w <= limit and h % 2 == 0 and w % 2 == 0
               for row in TRAFFIC["rows"] for h, w in row)
    # the seed never reaches the layout
    cfg = train_packed.build_config(MANIFEST.config_kwargs(CONFIG), TRAFFIC,
                                    1, 12345)
    a = train_packed.layout(cfg, TRAFFIC["rows"], 1)
    assert int((a["segment_ids"] > 0).sum()) == 15284
    assert int(a["label_mask"].sum()) == 9


def test_rehearsal_shapes_come_from_the_family_and_the_traffic_file():
    """`--rehearse` shrinks a configuration by its family's `rehearse` block
    (the one place a family's tiny shapes are written) and by what the
    traffic file's block adds for its own kind: traffic keys, configuration
    keys, a nested block key by key."""
    import copy
    import os
    config, traffic = copy.deepcopy(CONFIG), copy.deepcopy(TRAFFIC)
    family = MANIFEST.family(config["family"])
    mf.apply_rehearsal(config, traffic, family)
    assert traffic["row_tokens"] == 128 and traffic["images_per_row"] == 4
    assert config["embed_dim"] == 64 and config["num_blocks"] == 2
    assert config["native_res"]["pos_grid"] == 8
    assert config["native_res"]["rope_base"] == CONFIG["native_res"]["rope_base"]
    kwargs = MANIFEST.config_kwargs(config)
    assert kwargs["mlp_dim"] == 96 and kwargs["max_image_tokens"] == 64
    assert "vocab_size" not in kwargs and "hidden_size" not in kwargs
    # a family's tiny shapes are keys it declares, written once: no traffic
    # file repeats one, and there is no other source
    declared = set(family["shape_keys"]) | set(family["nested"])
    assert set(family["rehearse"]) <= declared
    for w in MANIFEST.data["workloads"]:
        own = MANIFEST.traffic(w["traffic"])["rehearse"].get("config", {})
        assert not set(own) & set(family["rehearse"]), w["traffic"]
    assert not os.path.exists(os.path.join(mf.BENCH_DIR, "rehearse.json"))


class FakeTrace:
    """`flash_packed_*` ops take 0.4 s of 1.0 s busy on chip 0."""

    def seconds_matching(self, *marks, device=0):
        return 0.4 if marks == ("flash_packed_",) else 0.0

    def self_seconds(self, pred, device=0):
        return 1.0


def run_with(records, trace=None, chips=1):
    return types.SimpleNamespace(records=records, trace=trace, chips=chips,
                                 config=CONFIG, peaks=PEAKS)


COUNTS = {"tokens": 15284.0, "padding_tokens": 1100.0, "images": 9.0,
          "token_pairs": 40152304.0}


def test_packed_readers_on_hand_made_records():
    records = {"packed_counts": COUNTS, "steps": 10, "window_s": 10.0}
    run = run_with(records, FakeTrace())
    assert packing_padding_pct.read(run) == pytest.approx(100 * 1100 / 16384)
    assert train_tokens_per_s_chip.read(run) == pytest.approx(15284.0)
    assert packed_attention_busy_pct.read(run) == pytest.approx(40.0)
    # need: 6 matmuls of 2 * 16 * sum(n^2) * 72 a layer, 27 layers, 10 steps
    flops = 6 * 2 * 16 * 40152304 * 72 * 27 * 10
    nbytes = 12 * 15284 * 1152 * 2 * 27 * 10
    assert roofline_packed.packed_attention_need(
        40152304 * 10, 15284 * 10, 16, 72, 27) == (flops, nbytes)
    assert flops / 197e12 > nbytes / 819e9          # compute-bound
    assert packed_attention_roofline.read(run) \
        == pytest.approx(100 * flops / 197e12 / 0.4)
    assert records["packed_attention_bound"] == "compute"
    per_step = flops_packed.model_flops_per_step(
        CONFIG, 15284, 40152304, 9)
    assert packed_mfu_pct.read(run) == pytest.approx(100 * per_step / 197e12)
    # a kernel that does not skip does 2 * 8192^2 pairs for the same need:
    # the same reader reads 3.3 times lower
    assert 2 * 8192 ** 2 / 40152304 == pytest.approx(3.34, rel=1e-2)
    # four chips: per-chip shares
    four = run_with(records, FakeTrace(), chips=4)
    assert train_tokens_per_s_chip.read(four) == pytest.approx(15284.0 / 4)
    assert packed_mfu_pct.read(four) == pytest.approx(
        100 * per_step / 197e12 / 4)


def test_packed_readers_return_nothing_where_nothing_is_to_read():
    """A program without the packed step's counters (the parent's), an
    untraced run, a trace without the kernels: None, never an exception."""
    readers = (packed_attention_roofline, packed_attention_busy_pct,
               packed_mfu_pct, train_tokens_per_s_chip, packing_padding_pct)
    bare = run_with({"steps": 10, "window_s": 10.0}, FakeTrace())
    assert [r.read(bare) for r in readers] == [None] * 5
    untraced = run_with({"packed_counts": COUNTS, "steps": 10,
                         "window_s": 10.0})
    assert packed_attention_roofline.read(untraced) is None
    assert packed_attention_busy_pct.read(untraced) is None

    class NoKernels(FakeTrace):
        def seconds_matching(self, *marks, device=0):
            return 0.0

    assert packed_attention_roofline.read(run_with(
        {"packed_counts": COUNTS, "steps": 10, "window_s": 10.0},
        NoKernels())) is None
