"""The one place that chooses a program's kernels (vitax/programs/kernels.py):
what `choose_kernels` picks at the shapes of every configuration of
BENCHMARK.json (shape logic only: nothing is lowered), the words each name
carries as the cell's `train()` prints them on the chip, what `kernel_lines`
says where the plain form runs and why, the `shard_map` a mesh adds, and the
layering: the kernel layer imports nothing above itself."""

import dataclasses
import glob
import os

import pytest

from tests.test_assembly import _imports
from vitax.config import Config
from vitax.programs import kernels as programs
from vitax.programs.kernels import Kernels, choose_kernels, kernel_lines

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WHOLE_N = "pallas fused (4D whole-N), fused qkv"
PACKED = "pallas streaming, segment-masked (packed rows)"
DOCUMENTS = "pallas streaming, causal / window, grouped KV (packed documents)"
OLMO_RULE = ("plain (a 96 x 192 state under one decay a head: the kernels "
             "tile a square state of multiples of 128 under a decay a "
             "channel)")
GATED_CONV = ("plain (a gated convolution, C * conv(B * x) without an "
              "activation: the kernel pair has a silu behind its taps and no "
              "gate)")


def conv(lanes):
    return (f"fused kernel ({lanes} channels a grid step in blocks of 128 "
            f"tokens)")


# configuration: the lines its cell's train() prints on the chip, less the
# attention line's remat note (read off the parent of PR 47 before the four
# choosers went; PERF.md section 3 and README quote them)
CELLS = {
    "vit_l14": ["attention core: " + WHOLE_N],
    "vit10b_d2": ["attention core: " + WHOLE_N],
    "vit10b_fsdp4": ["attention core: " + WHOLE_N],
    "vit10b_d8": ["attention core: " + WHOLE_N],
    "moonvit_so400m": ["attention core: " + PACKED],
    "laguna_xs2_ep8": ["attention core: " + DOCUMENTS],
    "granite4h_micro_vp8": [
        "attention core: " + DOCUMENTS,
        "state-space scan: fused kernel (chunk 256, 16 heads a grid step)",
        "mixer convolution: " + conv(256)],
    "ling3_flash_vl_ep64tp2": [
        "attention core: " + DOCUMENTS,
        "delta rule: fused kernel (chunk 64, sub-chunks of 16, 16 heads a "
        "grid step)",
        "mixer convolution: " + conv(512)],
    "olmo_hybrid_7b_tp2vp8": [
        "attention core: " + DOCUMENTS,
        "delta rule: " + OLMO_RULE,
        "mixer convolution: " + conv(384)],
    "lfm2_24b_a2b_ep8": [       # PR 48: no kernel is asked of the new mixer
        "attention core: " + DOCUMENTS,
        "mixer convolution: " + GATED_CONV],
    "smallthinker_21b_a3b_ep8": [   # PR 51: both document kernels, no mixer
        "attention core: " + DOCUMENTS],
}
LINE_OF = {"scan": "state-space scan", "rule": "delta rule",
           "conv": "mixer convolution"}


def cell_config(name: str) -> Config:
    """The `Config` the first cell of configuration `name` runs."""
    from benchmark import manifest as mf
    man = mf.Manifest()
    cell = next(c for c in man.data["workloads"] if c["config"] == name)
    traffic = man.traffic(cell["traffic"])
    return mf.generator(traffic["kind"]).build_config(
        man.config_kwargs(man.config(name)), traffic, cell["chips"], 0)


@pytest.fixture
def on_the_chip(monkeypatch):
    """`kernel_lines` as on a TPU: it says why a plain form runs there."""
    monkeypatch.setattr(programs, "backend_platform", lambda: "tpu")


def test_the_table_names_every_configuration_of_the_benchmark():
    from benchmark import manifest as mf
    assert sorted(CELLS) == sorted(
        c["name"] for c in mf.Manifest().data["configs"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_what_a_cells_configuration_chooses(name, on_the_chip):
    cfg = cell_config(name)
    chosen = choose_kernels(cfg, None, force_tpu_kernels=True)
    lines = kernel_lines(cfg, chosen)
    assert lines == CELLS[name]
    # a member is a callable named as its line says, or None where the line
    # says plain or the model has no such layer
    said = dict(line.split(": ", 1) for line in lines)
    assert chosen.attention.vitax_name == said["attention core"]
    for member, label in LINE_OF.items():
        kernel = getattr(chosen, member)
        if said.get(label, "plain").startswith("plain"):
            assert kernel is None, (member, kernel)
        else:
            assert kernel.vitax_name == said[label]


def test_off_the_tpu_and_unforced_every_member_is_plain():
    for name in ("granite4h_micro_vp8", "ling3_flash_vl_ep64tp2"):
        cfg = cell_config(name)
        chosen = choose_kernels(cfg)
        assert chosen == Kernels()
        lines = kernel_lines(cfg, chosen)
        assert lines[0] == "attention core: dense jnp"
        assert [line.split(": ")[1] for line in lines[1:]] == [
            "plain (no TPU)"] * 2


# the flags of tests/test_hybrid_decoder.py::test_training_through_the_cli_path
REHEARSAL = dict(
    model_family="decoder", embed_dim=32, num_blocks=4, vocab_rows=48,
    kv_heads=2, head_size=8,
    layer_kinds=["mamba", "mamba", "full_attention", "mamba"],
    layer_heads=[0, 0, 4, 0], layer_mlps=["dense"] * 4, ffn_dim=48,
    norm_eps=1e-5, position_embedding="nope", ssm_heads=8, ssm_head_size=8,
    ssm_state_size=16, ssm_conv_width=4, ssm_chunk=8, pack_tokens=64,
    pack_images=6, batch_size=8)


def test_a_rehearsal_shape_that_does_not_tile_says_why(on_the_chip):
    cfg = Config(**REHEARSAL).validate()
    chosen = choose_kernels(cfg, None, force_tpu_kernels=True)
    assert chosen.scan is None and chosen.conv is None
    assert kernel_lines(cfg, chosen)[1:] == [
        "state-space scan: plain (chunk 8 is no multiple of 128)",
        "mixer convolution: plain (96 channels are no multiple of 128)"]


def test_the_lines_are_the_ones_train_prints_for_a_hybrid_configuration():
    """`python -m vitax.train` at REHEARSAL's flags printed these three lines
    at the parent of PR 47 (four `master_print`s in the loop); the loop now
    prints what `kernel_lines` returns and words no line itself."""
    cfg = Config(**REHEARSAL).validate()
    assert kernel_lines(cfg, choose_kernels(cfg)) == [
        "attention core: dense jnp",
        "state-space scan: plain (no TPU)",
        "mixer convolution: plain (no TPU)"]
    with open(os.path.join(REPO, "vitax", "train", "loop.py"),
              encoding="utf-8") as f:
        loop = f.read()
    assert "kernel_lines(cfg, " in loop
    for label in ("attention core: ", *LINE_OF.values()):
        assert f'"{label}' not in loop, label


@pytest.mark.parametrize("name", ["granite4h_micro_vp8",
                                  "ling3_flash_vl_ep64tp2",
                                  "olmo_hybrid_7b_tp2vp8"])
def test_on_a_mesh_every_chosen_name_says_shard_map(name, devices8):
    from vitax.parallel.mesh import build_mesh
    cfg = dataclasses.replace(cell_config(name), batch_size=2)
    one = choose_kernels(cfg, None, force_tpu_kernels=True)
    two = choose_kernels(cfg, build_mesh(cfg, devices8[:2]),
                         force_tpu_kernels=True)
    for member, alone, on_mesh in zip(Kernels._fields, one, two):
        assert (alone is None) == (on_mesh is None), member
        if alone is not None:
            assert on_mesh.vitax_name == alone.vitax_name + " + shard_map"


def test_a_vit_has_an_attention_core_and_no_other_line():
    cfg = Config(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                 num_blocks=2, num_classes=4, batch_size=16).validate()
    chosen = choose_kernels(cfg)
    assert chosen == Kernels()
    assert kernel_lines(cfg, chosen) == ["attention core: dense jnp"]


# --- the layering ------------------------------------------------------------

OPS = sorted(glob.glob(os.path.join(REPO, "vitax", "ops", "*.py")))


@pytest.mark.parametrize("path", OPS, ids=[os.path.basename(p) for p in OPS])
def test_the_kernel_layer_imports_nothing_above_itself(path):
    """At any depth of the syntax tree: inside a function too."""
    above = ("vitax.models", "vitax.programs", "vitax.train")
    bad = [name for name in _imports(path) if name.startswith(above)]
    assert not bad, bad


@pytest.mark.parametrize("family", ["conv", "kda", "ssd"])
def test_a_family_takes_no_private_name_of_another(family):
    path = os.path.join(REPO, "vitax", "ops", family + ".py")
    bad = [name for name in _imports(path) if name.startswith("vitax.ops.")
           and name.rsplit(".", 1)[1].startswith("_")]
    assert not bad, bad
    source = open(path, encoding="utf-8").read()
    for chooser in ("def make_", "_choice(", "cfg"):
        assert chooser not in source, chooser


def test_the_decoder_carries_one_record():
    from vitax.models import decoder
    for module in (decoder.Decoder, decoder.DecoderBlock):
        fields = {f.name for f in dataclasses.fields(module)}
        assert "kernels" in fields
        assert not fields & {"attention_impl", "scan_impl", "kda_impl",
                             "conv_impl"}
    cfg = Config(**REHEARSAL).validate()
    impl = lambda *a: a[0]  # noqa: E731
    model = decoder.build_decoder(cfg, kernels=Kernels(attention=impl))
    assert model.attention_impl is impl     # what the ViT's shared code reads
    assert decoder.build_decoder(cfg).attention_impl is None
