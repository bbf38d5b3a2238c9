"""Where the benchmark's data lives, and how each piece is found by name.

`BENCHMARK.json` (at the root of the checkout) names cells, configurations
and metrics. Everything that belongs to one of them sits in a file of its
own, found from the name alone, so a later PR adds files and manifest
entries and edits nothing:

  configuration   the manifest entry's `file` (benchmark/configs/<name>.json)
  traffic mix     benchmark/traffic/<traffic>.json: parameters for the one
                  general generator its `kind` names
  generator       benchmark/generators/<kind>.py: set-up, window, check
  metric          benchmark/metrics/<name>.py: `read(run)` -> number or None

Data files (manifest, configurations, traffic mixes) resolve against the
directory that holds the manifest, so a test can point `--manifest` at a
temporary tree; code (generators, metric readers) is always this package's.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
PACKAGE = os.path.basename(BENCH_DIR)
MANIFEST_NAME = "BENCHMARK.json"

# what a configuration file may hand to `vitax.config.Config`: the model's
# shape and the mesh. No performance knob (scan, remat, fused optimizer,
# batcher settings ...) is ever read from a file: a cell measures what the
# program's defaults give someone who names only the model.
SHAPE_KEYS = ("image_size", "patch_size", "embed_dim", "num_heads",
              "num_blocks", "mlp_ratio", "num_classes", "moe_experts",
              "moe_top_k", "moe_capacity_factor")
MESH_KEYS = ("dp_size", "fsdp_size", "tp_size", "sp_size", "pp_size",
             "ep_size")


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, path: str = ""):
        self.path = os.path.abspath(path or os.path.join(ROOT, MANIFEST_NAME))
        self.root = os.path.dirname(self.path)
        self.data = _read_json(self.path)

    def cell(self, name: str) -> dict:
        for entry in self.data["workloads"]:
            if entry["name"] == name:
                return entry
        known = [w["name"] for w in self.data["workloads"]]
        raise SystemExit(f"unknown workload {name!r}; {self.path} has {known}")

    def config(self, name: str) -> dict:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                return _read_json(os.path.join(self.root, entry["file"]))
        raise SystemExit(f"workload names configuration {name!r}, which "
                         f"{self.path} does not list")

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.root, PACKAGE, "traffic",
                                       f"{name}.json"))

    def metrics(self, section: str, cell_name: str) -> list:
        """The manifest's metrics of `section` (`end_to_end` / `per_layer`)
        that exist in this cell."""
        return [m for m in self.data[section]
                if cell_name in m.get("workloads", [cell_name])]


def config_kwargs(config: dict) -> dict:
    """The `Config` fields a configuration file sets: shape and mesh only."""
    return {k: config[k] for k in SHAPE_KEYS + MESH_KEYS if k in config}


def generator(kind: str):
    return importlib.import_module(f"{PACKAGE}.generators.{kind}")


def metric_reader(name: str):
    return importlib.import_module(f"{PACKAGE}.metrics.{name}")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip, by `device_kind`. A device that is not
    in the table is an error, not a default."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    for key, row in table["chips"].items():
        if key.lower() in device_kind.lower():
            return row
    raise SystemExit(f"benchmark/peaks.json has no entry for device kind "
                     f"{device_kind!r}: add it with its source")
