"""The cell that runs the trainer's own loop, end to end at tiny shapes on
the CPU (`--rehearse`), and what its generator holds the program to.

`test_cells.py::test_rehearsal` runs every cell of the manifest and asserts
that no cell's process loads `vitax.train.loop`: true of the cells that
bypass the loop, and the opposite of what this cell is for. Its two cases
for this cell fail on that line until a `benchmark` PR, which may edit that
file, makes the assertion the resident kinds' own (PERF.md, section 7)."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import manifest as mf
from benchmark.generators import train_loop
from benchmark.tests.test_cells import rehearse

CELL = "l14_train_loop_fake"
MANIFEST = mf.Manifest()
# the per-layer metrics a CPU run can report: no device, so no device trace
ON_THE_CPU = {"step_hbm_gb", "compiles_in_window", "data_wait_pct",
              "loop_put_pct", "loop_dispatch_pct", "loop_fence_pct",
              "loop_host_pct"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_loop_cell_rehearses(trace, tmp_path):
    line = rehearse(CELL, trace, out_dir=str(tmp_path))
    with open(tmp_path / f"{CELL}.trace{trace}.seed3.json") as f:
        record = json.load(f)
    assert "vitax.train.loop" in record["program_modules"]
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0
    # interval 2 in rehearsal, one warm interval, one in the window
    assert line["attempted"] == 2 == record["records"]["steps"]
    rows = record["records"]["loop_marks"]
    assert [r[0] for r in rows] == [1, 2, 3, 4]
    assert record["records"]["window_open_t"] == rows[1][5]
    assert record["records"]["window_close_t"] == rows[3][5]
    assert line["checks"]["loss_rel_gap"] <= line["checks"]["loss_rtol"]
    want = ON_THE_CPU if trace else {"train_images_per_s_chip", "setup_s"}
    assert set(line["metrics"]) == want
    if trace:   # around the whole of train(), the host tracer off
        assert record["records"]["xplane_bytes"] > 0


def test_the_cell_sets_none_of_the_defaults_it_relies_on():
    cell = MANIFEST.cell(CELL)
    traffic = MANIFEST.traffic(cell["traffic"])
    config = MANIFEST.config(cell["config"])
    cfg = train_loop.build_config(MANIFEST.config_kwargs(config), traffic, 1,
                                  3_000_000_019)
    from vitax.config import Config
    named = {**MANIFEST.config_kwargs(config), "batch_size": 128,
             "seed": 3_000_000_019, "fake_data": True}
    for f in dataclasses.fields(Config):
        assert getattr(cfg, f.name) == named.get(f.name, f.default), f.name
    assert cfg.log_step_interval == 20 and "log_interval" not in traffic
    # a program whose default moved is refused, not steered
    traffic["loop_defaults"]["log_step_interval"] = 50
    with pytest.raises(SystemExit, match="log_step_interval"):
        train_loop.build_config(MANIFEST.config_kwargs(config), traffic, 1, 0)


def test_the_float8_reference_control_reaches_the_reference(tmp_path):
    """`control: float8_reference` in a copy of the traffic file: the loop's
    step 1 reads the same, the reference moves, and the gradient norm's gap
    grows many times over (on the chip, at the real size, over its limit:
    PERF.md section 6, PR 37)."""
    sound = rehearse(CELL, 0)["checks"]
    for rel in ("BENCHMARK.json", "benchmark/configs/vit_l14.json",
                "benchmark/traffic/loop_fake_b128.json"):
        os.makedirs(os.path.dirname(tmp_path / rel), exist_ok=True)
        shutil.copy(os.path.join(mf.ROOT, rel), tmp_path / rel)
    path = tmp_path / "benchmark/traffic/loop_fake_b128.json"
    traffic = json.loads(path.read_text())
    traffic["control"] = "float8_reference"
    path.write_text(json.dumps(traffic))
    control = rehearse(CELL, 0, manifest=str(tmp_path / "BENCHMARK.json"))
    control = control["checks"]
    assert control["loss_step1"] == sound["loss_step1"]
    assert control["grad_norm_reference"] != sound["grad_norm_reference"]
    assert control["grad_norm_rel_gap"] > 5 * sound["grad_norm_rel_gap"]


def run_with_keys(lowered, in_train, cache_on=True):
    import jax
    run = types.SimpleNamespace(
        failures=[], checks={}, traffic={"per_chip_batch": 128})
    run.check = lambda ok, what: ok or run.failures.append(what)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", cache_on)
    try:
        train_loop.check_step_is_the_lowered_one(run, lowered, in_train)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return run


@pytest.mark.parametrize("in_train, hit, fails", [
    # the loop's step came from the entry the lowered step's compile left
    ([("jit_init", "jit_init-1", False),
      ("jit_train_step", "jit_train_step-aa", True)], True, ""),
    # the same program, compiled anew (the entry could not be written)
    ([("jit_train_step", "jit_train_step-aa", False)], False, ""),
    # another batch shape, dtype, `Config` or text: another key
    ([("jit_train_step", "jit_train_step-bb", True)], True, "another"),
    # a second step program inside the run (a recompile)
    ([("jit_train_step", "jit_train_step-aa", True),
      ("jit_train_step", "jit_train_step-bb", False)], False, "another"),
    # `train()` compiled no step at all
    ([("jit_init", "jit_init-1", False)], False, "another"),
], ids=["loaded", "compiled_anew", "other_program", "recompiled", "none"])
def test_the_loops_step_is_held_to_the_lowered_one(in_train, hit, fails):
    lowered = [("jit_train_step", "jit_train_step-aa", False)]
    run = run_with_keys(lowered, in_train)
    assert run.checks["step_cache_key"] == "jit_train_step-aa"
    assert run.checks["loop_step_cache_hit"] is hit
    assert bool(run.failures) == bool(fails)
    assert all(fails in f for f in run.failures)
    # no key for the lowered step, with the cache on: nothing to hold it to
    assert "0 keys" in run_with_keys([], in_train).failures[0]
    # `--rehearse` turns the cache off: no keys, no verdict
    off = run_with_keys([], in_train, cache_on=False)
    assert not off.failures and not off.checks


def test_cache_keys_sees_a_miss_and_then_a_hit_under_one_key(tmp_path):
    """`CacheKeys` in a process of its own with a cache of its own: the same
    program from two `jit` objects is a miss and then a hit, one key."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "from benchmark.generators.train_loop import CacheKeys\n"
        "def make():\n"
        "    def step(x):\n        return (x @ x).sum()\n"
        "    return step\n"
        "with CacheKeys() as keys:\n"
        "    jax.jit(make())(jnp.ones((8, 8))).block_until_ready()\n"
        "    jax.jit(make())(jnp.ones((8, 8))).block_until_ready()\n"
        "print([k for k in keys.seen if k[0] == 'jit_step'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=mf.ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    seen = eval(proc.stdout.strip().splitlines()[-1])
    assert [hit for _, _, hit in seen] == [False, True]
    assert seen[0][1] == seen[1][1]
    assert "cache" not in proc.stderr.lower()   # nothing of it is printed
