"""What PR 32 added to the benchmark, off the chip: the join of trace ops to
named scopes, the decoder kernels' needs by hand, the readers on a made-up
run, and the configuration file against the source's catalog row."""

import json
import os
import types

import pytest

from benchmark import flops_laguna, roofline_laguna, scopes
from benchmark import manifest as mf
from benchmark import trace_reduce as tr

MANIFEST = mf.Manifest()
CONFIG = MANIFEST.config("laguna_xs2_ep8")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

HLO = '''
HloModule jit_train_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %x = f32[8] add(%p, %p), metadata={op_name="jit(train_step)/run1/blocks/moe/moe_route/add"}
}
ENTRY %main {
  %fusion.7 = f32[8,256]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(Decoder)/run1/blocks/moe/moe_route/top_k" stack_frame_id=3}
  %gather.2 = bf16[64,8] fusion(%b), kind=kCustom, metadata={op_name="jit(train_step)/transpose(jvp(Decoder))/run1/blocks/moe/moe_combine/take"}
  %conv.9 = bf16[8,8] fusion(%c), kind=kOutput, metadata={op_name="jit(train_step)/Decoder/run0/blocks/mlp/up/dot_general"}
  ROOT %ragged-dot-none = f32[64,8] custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}
'''


def test_scopes_join_instructions_to_named_scopes():
    found = scopes.index(HLO, ("moe_route", "moe_combine", "expert_ffn"))
    assert found == {"x": "moe_route", "fusion.7": "moe_route",
                     "gather.2": "moe_combine"}
    ops = [tr.Op(0, 10, "fusion.7", "fusion kLoop", "", self_ns=10.0),
           tr.Op(10, 40, "gather.2", "fusion kCustom", "", self_ns=30.0),
           tr.Op(40, 100, "conv.9", "fusion kOutput", "", self_ns=60.0)]
    trace = tr.ReducedTrace((0, 100), [tr.DeviceTrace("d", ops, [(0, 100)])],
                            [])
    assert scopes.seconds(trace, found, "moe_route") == 10e-9
    assert scopes.seconds(trace, found, "moe_route", "moe_combine") == 40e-9
    assert scopes.seconds(trace, {}, "moe_route") == 0.0


def test_needs_by_hand():
    # one document of 4 tokens, 2 heads on 1 key/value head of 8, one layer:
    # 10 causal pairs; 6 matmuls of 2 * heads * pairs * head_dim
    flops, nbytes = roofline_laguna.attention_need(10, 4, 2, 1, 8, 1)
    assert flops == 6 * 2 * 2 * 10 * 8
    assert nbytes == 6 * 4 * (2 + 1) * 8 * 2
    # 5 slots of hidden 16 into experts of width 4, 2 held, one layer
    flops, nbytes = roofline_laguna.expert_ffn_need(5, 16, 4, 2, 1)
    assert flops == 3 * 3 * 2 * 5 * 16 * 4
    assert nbytes == 3 * 2 * 3 * 16 * 4 * 2 + 3 * 5 * (2 * 16 + 3 * 4) * 2
    assert flops_laguna.layout_counts([[4600, 1900, 1050, 420]], 512) == {
        "tokens": 7970, "documents": 4, "targets": 7966,
        "causal_pairs": 13028435, "window_pairs": 3561562}
    assert MANIFEST.traffic("packed_1x8192_codemix")["layout"] == {
        "documents": 4, "tokens": 7970, "padding_tokens": 222,
        "targets": 7966, "causal_pairs": 13028435, "window_pairs": 3561562}


def made_up_run(**records):
    return types.SimpleNamespace(
        trace=None, records=records, program={}, config=CONFIG, chips=1,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("name", [
    "full_attention_roofline", "window_attention_roofline",
    "decoder_attention_busy_pct", "expert_ffn_roofline", "moe_route_busy_pct",
    "expert_load_max_over_mean", "decoder_mfu_pct"])
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    """On a program without the decoder's counters (the parent's, any other
    cell's) each new reader returns None and does not raise."""
    assert mf.metric_reader(name).read(made_up_run(steps=3,
                                                   window_s=1.0)) is None


def test_readers_on_counters():
    counts = {"tokens": 7970.0, "targets": 7966.0, "causal_pairs": 13028435.0,
              "window_pairs": 3561562.0, "expert_slots_here": 31880.0}
    run = made_up_run(packed_counts=counts, steps=10, window_s=4.0,
                      expert_load=[[10, 30], [20, 20]])
    assert mf.metric_reader("expert_load_max_over_mean").read(run) == 1.5
    want = flops_laguna.model_flops_per_step(CONFIG, 7970, 7966, 13028435,
                                             3561562, 31880)
    assert mf.metric_reader("decoder_mfu_pct").read(run) == pytest.approx(
        100 * want * 2.5 / 197e12)
    assert 15e12 < want < 17e12


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_key_of_the_catalog_row():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["leading_dense_layers"] == 1
    assert CONFIG["mlp_layer_types"][:2] == ["dense", "sparse"]
