"""Every cell end to end at tiny shapes on the CPU (`--rehearse`), a new
cell added with data files alone, and a configuration of another model
family, cut to one chip's share of a deployment, held to the rules of form
with files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import forms
from benchmark import manifest as mf

RUN = os.path.join(mf.BENCH_DIR, "run.py")
MANIFEST = mf.Manifest()
TREE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tree")


def rehearse(workload, trace, manifest="", out_dir=""):
    cmd = [sys.executable, RUN, "--workload", workload, "--rehearse",
           "--seconds", "1", "--trace", str(trace), "--seed", "3"]
    if manifest:
        cmd += ["--manifest", manifest]
    if out_dir:
        cmd += ["--out_dir", out_dir]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          cwd=mf.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST.data["workloads"]])
def test_rehearsal(cell, trace, tmp_path):
    line = rehearse(cell, trace, out_dir=str(tmp_path))
    with open(tmp_path / f"{cell}.trace{trace}.seed3.json") as f:
        loaded = json.load(f)["program_modules"]
    # the program comes from its builder; no cell's process pays for the
    # trainer's loop (checkpoints, loaders, telemetry, control plane)
    assert "vitax.programs.builder" in loaded
    assert "vitax.train.loop" not in loaded
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == MANIFEST.cell(cell)["chips"]
    assert line["attempted"] > 0 and line["failed"] == 0
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in MANIFEST.metrics(section, cell)}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    # a CPU run gives no device number
    assert all(m["value"] is None for m in line["metrics"].values())
    if not trace:
        assert set(line["metrics"]) == allowed


def test_no_tpu_means_no_result():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload",
         MANIFEST.data["workloads"][0]["name"], "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=mf.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_new_cell_needs_only_data_files(tmp_path):
    """A traffic file and a manifest entry run a new cell of an existing
    traffic kind and configuration; no code is touched."""
    shutil.copytree(TREE, tmp_path / "root")
    root = tmp_path / "root"
    with open(root / "benchmark" / "traffic" / "resident_new.json", "w") as f:
        json.dump({"kind": "train_resident", "per_chip_batch": 2,
                   "reference_grad_norm": True, "run_ahead": 1,
                   "warm_steps": 1, "reference_sample": 2,
                   "expect_decreasing": True}, f)
    with open(root / "manifest.json") as f:
        man = json.load(f)
    man["workloads"].append({"name": "new_cell", "config": "l14_d2",
                             "traffic": "resident_new", "chips": 1,
                             "why": "added by a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "collective" not in m["name"]:
            m["workloads"].append("new_cell")
    with open(root / "manifest.json", "w") as f:
        json.dump(man, f)
    line = rehearse("new_cell", 0, manifest=str(root / "manifest.json"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_images_per_s_chip", "setup_s"}


# --- a configuration that is no ViT's, cut to a chip's share -------------------

TOY = "toy_share8"


def toy_tree(tmp_path, change=None):
    """A temporary tree with the fixture's share-cut configuration in its
    manifest (`change(config)` breaks the file first): data files only,
    nothing under this package is touched."""
    root = tmp_path / "root"
    shutil.copytree(TREE, root)
    path = root / "benchmark" / "configs" / f"{TOY}.json"
    with open(path) as f:
        config = json.load(f)
    if change is not None:
        change(config)
        with open(path, "w") as f:
            json.dump(config, f)
    with open(root / "manifest.json") as f:
        man = json.load(f)
    man["configs"].append({
        "name": TOY, "source": config["source"], "reduced": config["reduced"],
        "file": f"benchmark/configs/{TOY}.json", "why": "added by a test"})
    with open(root / "manifest.json", "w") as f:
        json.dump(man, f)
    return mf.Manifest(str(root / "manifest.json"))


def test_a_share_cut_configuration_of_another_family_needs_only_files(tmp_path):
    man = toy_tree(tmp_path)
    assert forms.manifest_problems(man) == {}
    config = man.config(TOY)
    assert set(config["reduced"]) == {"num_hidden_layers", "layer_types",
                                      "n_routed_experts", "vocab_size"}
    assert config["chips_sharing_a_layer"] == 8
    # its shape reaches `Config` through the nested block its family
    # declares, and through nothing else
    kwargs = man.config_kwargs(config)
    assert kwargs == config["decoder"] and "hidden_size" not in kwargs
    # the family is the tree's own file; the ViT's is this package's
    assert "decoder" in man.family("toy_decoder")["nested"]
    assert man.family("vit") == mf.Manifest().family("vit")


def _width_in_reduced(c):
    c["reduced"].append("moe_intermediate_size")
    c["source_values"]["moe_intermediate_size"] = 128


def _list_is_no_prefix(c):
    c["layer_types"] = ["full", "sliding", "full", "sliding", "full"]
    c["decoder"]["layer_kinds"] = c["layer_types"]


def _four_experts_held(c):
    c["n_routed_experts"] = c["decoder"]["experts_held"] = 4


def _thin_vocabulary(c):
    c["vocab_size"] = c["decoder"]["vocab_rows"] = 2048


def _no_deployment(c):
    del c["chips_sharing_a_layer"]


def _half_a_period(c):
    c["num_hidden_layers"] = c["decoder"]["num_blocks"] = 3
    c["layer_types"] = c["decoder"]["layer_kinds"] = c["layer_types"][:3]


def _no_source_value(c):
    del c["source_values"]["vocab_size"]


def _count_without_a_role(c):
    c["reduced"].append("first_k_dense_replace")
    c["first_k_dense_replace"] = 0
    c["source_values"]["first_k_dense_replace"] = 1


def _a_knob_in_the_nested_block(c):
    c["decoder"]["remat_policy"] = "dots_saveable"


def _a_knob_at_the_top(c):
    c["scan_blocks"] = False


def _a_width_cut_out_of_sight(c):
    c["decoder"]["embed_dim"] = 128


def _fewer_heads_than_a_share(c):
    c["reduced"].append("num_attention_heads")
    c["source_values"]["num_attention_heads"] = 64
    c["num_attention_heads"] = c["decoder"]["num_heads"] = 4


@pytest.mark.parametrize("change, message", [
    (_width_in_reduced, "`moe_intermediate_size` is a width"),
    (_list_is_no_prefix, "`layer_types`: the list is no shorter prefix"),
    (_four_experts_held, "4 experts held, under the floor of 8"),
    (_thin_vocabulary, "2048 rows, under 0.125 of the source's 32768"),
    (_no_deployment, "states no deployment"),
    (_half_a_period, "2 layers after the 1 leading ones, under the floor of 4"),
    (_no_source_value, "`vocab_size` is reduced but the file lacks it or its"),
    (_count_without_a_role, "`first_k_dense_replace` is reduced but has no role"),
    (_a_knob_in_the_nested_block, "`decoder.remat_policy` is not declared"),
    (_a_knob_at_the_top, "sets `scan_blocks`"),
    (_a_width_cut_out_of_sight, "`decoder.embed_dim` = 128 but `hidden_size` = 256"),
    (_fewer_heads_than_a_share, "4 heads held, under the share one of 8 chips holds of the source's 64"),
])
def test_a_broken_share_cut_fails_with_its_own_message(tmp_path, change,
                                                       message):
    found = forms.manifest_problems(toy_tree(tmp_path, change))
    assert list(found) == [TOY]
    assert len(found[TOY]) == 1 and message in found[TOY][0], found[TOY]


def test_no_rule_lets_a_width_into_reduced():
    """The contract's own list: a hidden, intermediate, latent, state or
    projection size, a key ending in `_dim` or `_rank`, a head size, an
    expansion factor, the experts per token; and counts are not widths."""
    widths = forms.rules()["widths"]
    vit, bare = mf.Manifest().family("vit"), {}
    for key in ("hidden_size", "embed_dim", "intermediate_size",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "head_dim",
                "v_head_dim", "mlp_ratio", "mlp_dim", "num_experts_per_tok",
                "sliding_window", "state_size", "patch_size", "latent_size",
                "proj_dim", "expansion_factor"):
        assert forms.is_width(key, bare, widths), key
    for key in ("num_blocks", "num_hidden_layers", "n_routed_experts",
                "num_experts", "num_attention_heads", "num_key_value_heads",
                "vocab_size", "layer_types"):
        assert not forms.is_width(key, bare, widths), key
    assert forms.is_width("pos_grid", vit, widths)      # the family's own
    # a changed group is no cut: it would hide which of its keys moved
    assert forms.cut_problems(
        "rope_scaling", {"factor": 8}, {"factor": 64}, bare, widths) == [
        "`rope_scaling`: {'factor': 8} against the source's {'factor': 64} "
        "is neither a smaller number nor a shorter list"]


def _family_without(path):
    def change(family):
        family["equal"] = [p for p in family["equal"] if path not in p]
    return change


def _family_pairs_inside(family):
    family["equal"] = [p for p in family["equal"] if "decoder.embed_dim" not in p]
    family["equal"].append(["decoder.embed_dim", "decoder.ffn_dim"])
    family["nested"]["decoder"] = family["nested"]["decoder"]


def _family_declares_a_knob(family):
    family["nested"]["decoder"].append("remat_policy")


@pytest.mark.parametrize("change, message", [
    (_family_without("decoder.embed_dim"),
     "declares `decoder.embed_dim`, a width, but its `equal` holds it to no key outside"),
    (_family_without("decoder.window_tokens"),
     "declares `decoder.window_tokens`, a width, but its `equal` holds it to no key outside"),
    (_family_pairs_inside,
     "declares `decoder.embed_dim`, a width, but its `equal` holds it to no key outside"),
    (_family_declares_a_knob, "the family declares `remat_policy`, a performance knob"),
])
def test_a_family_cannot_let_a_width_or_a_knob_through(tmp_path, change, message):
    """What a later PR's own family file could open: a nested width tied
    to nothing outside the nested blocks, or a knob declared as a shape."""
    man = toy_tree(tmp_path)
    path = os.path.join(man.root, "benchmark", "shapes", "toy_decoder.json")
    family = mf.read_json(path)
    change(family)
    with open(path, "w") as f:
        json.dump(family, f)
    found = forms.manifest_problems(man)[TOY]
    assert any(message in line for line in found), found


def test_every_config_field_is_a_knob_or_a_declared_shape():
    """A field added to `vitax.config.Config` has to be classified: a knob
    (`form_rules.json`, or the `knobs` list of a committed family file) or a
    shape some committed family declares. Otherwise the deny-list would let
    a family declare tomorrow's knob."""
    import dataclasses

    from vitax.config import Config
    shapes = os.path.join(mf.BENCH_DIR, "shapes")
    declared = set()
    for name in os.listdir(shapes):
        declared |= forms.declared_keys(mf.read_json(os.path.join(shapes, name)))
    knobs = forms.knob_keys(forms.rules())
    fields = {f.name for f in dataclasses.fields(Config)}
    assert fields - knobs - declared == set()
    assert not knobs & declared
    # a family file extends the knobs and takes none off
    assert set(forms.rules()["knobs"]["keys"]) <= knobs


# --- the timed path broken underneath: `correct` has to come out false -----------

BROKEN_STEP = '''
import runpy, sys
import jax
from vitax.programs import builder
real = builder.make_train_step


def a_step_that_returns_its_state_unchanged(*args, **kwargs):
    step = real(*args, **{**kwargs, "donate": False})
    return jax.jit(lambda state, batch, rng: (state, step(state, batch, rng)[1]))


builder.make_train_step = a_step_that_returns_its_state_unchanged
sys.argv = [sys.argv[1]] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
'''


def test_a_step_that_moves_nothing_is_not_correct():
    """The whole of a run (off the chip: `--rehearse`), with the program's
    step replaced underneath by one that computes the loss and hands its
    state back unchanged: the run ends, prints its line, and the line says
    `correct` false, for the loss that did not come down."""
    proc = subprocess.run(
        [sys.executable, "-c", BROKEN_STEP, RUN, "--workload",
         "l14_train_resident", "--rehearse", "--seconds", "1", "--seed", "4"],
        capture_output=True, text=True, timeout=600, cwd=mf.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] > 0
    assert any("did not come down" in what for what in line["failures"])
    assert "NOT CORRECT" in proc.stderr.splitlines()[-2]
