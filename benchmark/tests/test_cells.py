"""Every cell end to end at tiny shapes on the CPU (`--rehearse`), and a new
cell added with data files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf

RUN = os.path.join(mf.BENCH_DIR, "run.py")
MANIFEST = mf.Manifest()
TREE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tree")


def rehearse(workload, trace, manifest=""):
    cmd = [sys.executable, RUN, "--workload", workload, "--rehearse",
           "--seconds", "1", "--trace", str(trace), "--seed", "3"]
    if manifest:
        cmd += ["--manifest", manifest]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          cwd=mf.ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST.data["workloads"]])
def test_rehearsal(cell, trace):
    line = rehearse(cell, trace)
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
