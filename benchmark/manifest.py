"""Where the benchmark's data lives, and how each piece is found by name.

`BENCHMARK.json` (at the root of the checkout) names cells, configurations
and metrics. Everything that belongs to one of them sits in a file of its
own, found from the name alone, so a later PR adds files and manifest
entries and edits nothing:

  configuration   the manifest entry's `file` (benchmark/configs/<name>.json)
  shape family    benchmark/shapes/<family>.json: the keys a configuration
                  of that family may hand to `Config`, and its tiny shapes
                  for `--rehearse`, named by the configuration file's `family`
  traffic mix     benchmark/traffic/<traffic>.json: parameters for the one
                  general generator its `kind` names
  generator       benchmark/generators/<kind>.py: set-up, window, check
  metric          benchmark/metrics/<name>.py: `read(run)` -> number or None

Data files (manifest, configurations, traffic mixes, shape families) resolve
against the directory that holds the manifest, so a test can point
`--manifest` at a temporary tree; code (generators, metric readers) is always
this package's, and so is a shape family the tree does not bring itself.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
PACKAGE = os.path.basename(BENCH_DIR)
MANIFEST_NAME = "BENCHMARK.json"


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, path: str = ""):
        self.path = os.path.abspath(path or os.path.join(ROOT, MANIFEST_NAME))
        self.root = os.path.dirname(self.path)
        self.data = read_json(self.path)

    def cell(self, name: str) -> dict:
        for entry in self.data["workloads"]:
            if entry["name"] == name:
                return entry
        known = [w["name"] for w in self.data["workloads"]]
        raise SystemExit(f"unknown workload {name!r}; {self.path} has {known}")

    def config(self, name: str) -> dict:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                return read_json(os.path.join(self.root, entry["file"]))
        raise SystemExit(f"workload names configuration {name!r}, which "
                         f"{self.path} does not list")

    def family(self, name: str) -> dict:
        """A shape family's declarations: the tree's own file, else this
        package's."""
        for base in (os.path.join(self.root, PACKAGE), BENCH_DIR):
            path = os.path.join(base, "shapes", f"{name}.json")
            if os.path.exists(path):
                return read_json(path)
        raise SystemExit(f"no benchmark/shapes/{name}.json: a configuration "
                         f"names its shape family under `family`")

    def config_kwargs(self, config: dict) -> dict:
        return config_kwargs(config, self.family(config["family"]))

    def traffic(self, name: str) -> dict:
        return read_json(os.path.join(self.root, PACKAGE, "traffic",
                                       f"{name}.json"))

    def metrics(self, section: str, cell_name: str) -> list:
        """The manifest's metrics of `section` (`end_to_end` / `per_layer`)
        that exist in this cell."""
        return [m for m in self.data[section]
                if cell_name in m.get("workloads", [cell_name])]


def config_kwargs(config: dict, family: dict) -> dict:
    """The `Config` fields a configuration file sets: the shape and mesh
    keys its family declares (benchmark/shapes/<family>.json), at the top
    level and inside the family's nested blocks, and nothing else. No
    performance knob (scan, remat, fused optimizer, batcher settings ...) is
    ever read from a file: a cell measures what the program's defaults give
    someone who names only the model."""
    out = {k: config[k] for k in family["shape_keys"] + family["mesh_keys"]
           if k in config}
    for block, keys in family.get("nested", {}).items():
        out.update({k: config[block][k] for k in keys
                    if k in config.get(block, {})})
    return out


def apply_rehearsal(config: dict, traffic: dict, family: dict) -> None:
    """`--rehearse`: tiny shapes over both. The configuration takes its
    family's `rehearse` block (the one place a family's tiny shapes are
    written), then what the traffic file's `rehearse` block puts under
    `config` for its own kind (a nested block key by key); the traffic takes
    the rest of that block. A configuration is shrunk only by the family it
    names and the traffic of a cell it runs in."""
    tiny = dict(traffic.get("rehearse", {}))
    for key, value in {**family.get("rehearse", {}),
                       **tiny.pop("config", {})}.items():
        if isinstance(value, dict) and isinstance(config.get(key), dict):
            value = {**config[key], **value}
        config[key] = value
    traffic.update(tiny)


def generator(kind: str):
    return importlib.import_module(f"{PACKAGE}.generators.{kind}")


def metric_reader(name: str):
    return importlib.import_module(f"{PACKAGE}.metrics.{name}")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip, by `device_kind`. A device that is not
    in the table is an error, not a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    for key, row in table["chips"].items():
        if key.lower() in device_kind.lower():
            return row
    raise SystemExit(f"benchmark/peaks.json has no entry for device kind "
                     f"{device_kind!r}: add it with its source")
