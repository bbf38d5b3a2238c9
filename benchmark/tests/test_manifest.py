"""`BENCHMARK.json` against the files: every name resolves, every name and
unit uses the allowed characters, no file sets a performance knob, and
`run.py` names no cell, configuration or metric."""

import os
import re

from benchmark import forms
from benchmark import manifest as mf

MANIFEST = mf.Manifest()
DATA = MANIFEST.data
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in DATA["workloads"]]
ALL_METRICS = DATA["end_to_end"] + DATA["per_layer"]


def test_top_level_keys_and_limits():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert DATA["command"] == ["python3", "benchmark/run.py"]
    assert DATA["paths"] == ["benchmark"]
    assert 1 <= DATA["run_seconds"] <= 51
    assert os.path.getsize(MANIFEST.path) < 64 * 1024
    four = sum(w["chips"] == 4 for w in DATA["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    # a full check with all 24 cells fits into 43200 s
    assert (2 + 14 * 24) * (DATA["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys():
    names = [m["name"] for m in ALL_METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    for m in ALL_METRICS:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in DATA["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in DATA["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in DATA["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in DATA["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in DATA["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert 1 <= len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in DATA["workloads"])
    files = [c["file"] for c in DATA["configs"]]
    assert len(files) == len(set(files))


def test_every_name_resolves_to_a_file():
    from vitax.config import Config
    for w in DATA["workloads"]:
        config = MANIFEST.config(w["config"])
        traffic = MANIFEST.traffic(w["traffic"])
        gen = mf.generator(traffic["kind"])
        assert all(hasattr(gen, f) for f in (
            "setup", "window", "finish", "build_config", "lower_described",
            "arithmetic"))
        # whether a configuration builds is its generator's to say: it
        # hands back a `Config` that passed `validate()`, of any family
        cfg = gen.build_config(MANIFEST.config_kwargs(config), traffic,
                               w["chips"], 0)
        assert isinstance(cfg, Config) and cfg.validate() == cfg
        assert gen.arithmetic.param_count(config) > 0
    for m in ALL_METRICS:
        assert callable(mf.metric_reader(m["name"]).read), m["name"]
    readers = {f[:-3] for f in os.listdir(os.path.join(mf.BENCH_DIR, "metrics"))
               if f.endswith(".py") and not f.startswith("_")}
    assert readers == {m["name"] for m in ALL_METRICS}


def test_configs_state_their_cut_and_set_no_knob():
    """Every configuration keeps the rules of form (benchmark/forms.py): a
    count held here may be reduced, with its source value; a width never;
    a share of a deployment keeps the floors; only what the family declares
    reaches `Config`, inside nested blocks too."""
    import dataclasses

    from vitax.config import Config
    fields = {f.name for f in dataclasses.fields(Config)}
    assert forms.manifest_problems(MANIFEST) == {}
    for w in DATA["workloads"]:
        traffic = MANIFEST.traffic(w["traffic"])
        assert not (set(traffic) & fields), w["traffic"]


def test_the_first_family_holds_the_keys_the_tuple_held():
    """`shapes/vit.json` is the tuple `manifest.py` held until PR 31, key
    for key; no family declares a performance knob."""
    vit = MANIFEST.family("vit")
    assert vit["shape_keys"] == [
        "image_size", "patch_size", "embed_dim", "num_heads", "num_blocks",
        "mlp_ratio", "num_classes", "moe_experts", "moe_top_k",
        "moe_capacity_factor"]
    assert vit["mesh_keys"] == ["dp_size", "fsdp_size", "tp_size", "sp_size",
                                "pp_size", "ep_size"]
    knobs = set(forms.rules()["knobs"]["keys"])
    assert {"scan_blocks", "remat_policy", "fused_optimizer",
            "serve_max_batch", "batch_size", "seed"} <= knobs
    shapes_dir = os.path.join(mf.BENCH_DIR, "shapes")
    for name in os.listdir(shapes_dir):
        family = MANIFEST.family(name[:-len(".json")])
        declared = family["shape_keys"] + family["mesh_keys"] + [
            k for keys in family.get("nested", {}).values() for k in keys]
        assert not set(declared) & knobs, name


def test_metric_coverage_of_each_cell():
    e2e = {m["name"]: m for m in DATA["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in CELLS:
        have = [m["name"] for m in MANIFEST.metrics("end_to_end", cell)]
        assert "setup_s" in have and len(have) >= 2, cell
        layers = MANIFEST.metrics("per_layer", cell)
        assert layers, cell
        for m in layers:        # reported only where the metric it moves is
            assert m["moves"] in have, (cell, m["name"])
    by_layer = {}
    for m in DATA["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_run_py_names_no_cell_config_or_metric():
    with open(os.path.join(mf.BENCH_DIR, "run.py"), encoding="utf-8") as f:
        source = f.read()
    names = (CELLS + [c["name"] for c in DATA["configs"]]
             + [m["name"] for m in ALL_METRICS]
             + [w["traffic"] for w in DATA["workloads"]])
    assert not [n for n in names if n in source]
