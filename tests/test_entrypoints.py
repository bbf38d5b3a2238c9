"""Driver entry-point smoke tests: bench.py and __graft_entry__.py must keep
working — the round's benchmark and compile checks run through them.

Both run in subprocesses with JAX_PLATFORMS=cpu, the explicit CPU pin that
lets bench.py run without a TPU (without it a chipless run must fail)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, extra_env=None, timeout=1500):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.update(extra_env or {})
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
def test_bench_prints_one_json_line():
    r = _run([sys.executable, "bench.py", "--preset", "tiny", "--batch_size", "8",
              "--steps", "2", "--warmup", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    # the four contract keys and the device block must be present
    assert {"metric", "value", "unit", "vs_baseline",
            "platform", "device_kind", "n_devices"} <= set(result)
    assert result["unit"] == "images/sec/chip"
    assert result["value"] > 0
    assert (result["platform"], result["n_devices"]) == ("cpu", 8)
    assert result["mfu"] is None  # a CPU run reports no MFU
    assert result["knobs"]["batch_per_chip"] == 1  # global 8 over 8 devices


def test_bench_refuses_to_run_without_a_tpu():
    """No TPU and no explicit JAX_PLATFORMS=cpu pin: JAX falls back to the
    CPU silently, bench.py must not — non-zero exit, no result line."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run([sys.executable, "bench.py", "--preset", "tiny"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert r.stdout.strip() == ""


def test_compile_cache_placed_from_outside(tmp_path):
    """vitax.platform.setup_compile_cache: JAX_COMPILATION_CACHE_DIR wins and
    nothing is set in code; without it the fixed in-checkout directory."""
    code = ("import jax\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "from vitax.platform import setup_compile_cache\n"
            "print(setup_compile_cache())\n"
            "print(before == jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    outside = str(tmp_path / "placed")
    for env_dir, want in ((outside, outside),
                          (None, os.path.join(REPO, ".jax_cache"))):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        returned, untouched, configured = r.stdout.split()
        assert returned == configured == want
        assert (untouched == "True") == bool(env_dir)


def test_spawning_parents_never_initialize_a_backend():
    """A chip belongs to one process at a time: the supervisor and the fleet
    replica manager start children that need it, so importing and using the
    parents' own code paths must leave JAX's backends uninitialized."""
    code = ("import tempfile\n"
            "import vitax.supervise as sup\n"
            "import vitax.serve.fleet.replica as rep\n"
            "import vitax.serve.fleet.__main__\n"
            "d = tempfile.mkdtemp()\n"
            "sup.run_progress(d, ''); sup.checkpoint_topology(d)\n"
            "sup.Supervisor(['true'], ckpt_dir=d)\n"
            "rep.ReplicaManager()\n"
            "from jax._src import xla_bridge\n"
            "print('initialized', xla_bridge.backends_are_initialized())\n")
    r = _run([sys.executable, "-c", code], timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "initialized False" in r.stdout


@pytest.mark.slow
def test_graft_dryrun_multichip():
    r = _run([sys.executable, "-c",
              "import __graft_entry__ as g; g.dryrun_multichip(8)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip ok" in r.stdout


@pytest.mark.slow
def test_graft_entry_compiles_single_chip():
    r = _run([sys.executable, "-c", (
        "import jax, __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn).lower(*args).compile()(*args)\n"
        "print('entry ok', out.shape)\n")],
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "entry ok" in r.stdout


def test_apply_ladder_picks_measured_winners(tmp_path, monkeypatch):
    """tools/apply_ladder.py closes the measure->tune loop: ladder rows +
    the stored default-config baseline in, per-preset knob winners out
    (TUNED.json), which bench.py's default_* functions then consult — the
    chip watcher can flip defaults to measured winners autonomously.
    Safety rules under test: never flip away from an UNMEASURED current
    default; ignore errored/truncated/non-knob rows; small wins below
    min_gain don't flip; a policy win flips the policy."""
    import json
    import importlib

    def knobs(sb, su, rw, policy, batch):
        # per-chip batch must equal the preset's default (train_presets(1))
        # or the row is deliberately non-comparable to the current default
        return {"scan_blocks": sb, "scan_unroll": su, "remat_window": rw,
                "remat_policy": policy, "batch_per_chip": batch}

    ladder = tmp_path / "ladder.jsonl"
    rows = [
        # l14 code default is the unrolled path: measure it, then beat it
        {"args": "--preset l14",
         "result": {"value": 250.0,
                    "knobs": knobs(False, 1, 0, "dots_attn_saveable", 32)}},
        {"args": "--preset l14 --remat_window 8",
         "result": {"value": 280.0,
                    "knobs": knobs(True, 1, 8, "dots_attn_saveable", 32)}},
        # b16: alternative beats the measured default by < min_gain -> keep
        {"args": "--preset b16 --no_scan_blocks",
         "result": {"value": 100.0,
                    "knobs": knobs(False, 1, 0, "dots_attn_saveable", 64)}},
        # 10b_slice: a policy-only win must flip the policy along (the
        # family code default is window-2 — the round-4 ladder — so the default
        # and alternative rows both carry it)
        {"args": "--preset 10b_slice --remat_policy dots_saveable",
         "result": {"value": 130.0,
                    "knobs": knobs(True, 1, 2, "dots_saveable", 64)}},
        # ignored rows: truncated, errored-with-positive-value, non-knob
        {"args": "--preset l14 --scan_unroll", "result": {"value": 999.0}},
        {"args": "--preset l14 --remat_window 16",
         "result": {"value": 999.0, "error": "watchdog killed",
                    "knobs": knobs(True, 1, 16, "dots_attn_saveable", 32)}},
        {"args": "--preset tiny --batch_size 8", "result": {"value": 999.0}},
    ]
    ladder.write_text("\n".join(json.dumps(r) for r in rows) + "\n")

    sys.path.insert(0, str(REPO))
    sys.path.insert(0, str(os.path.join(REPO, "tools")))
    apply_ladder = importlib.import_module("apply_ladder")
    baselines = {
        # b16's stored row IS its current default (scan path), measured
        "b16": {"images_per_sec_chip": 99.0, "scan_blocks": True,
                "scan_unroll": 1, "remat_window": 0,
                "remat_policy": "dots_attn_saveable"},
        # 10b_slice default (scan, window-2, none_saveable) measured at 116
        "10b_slice": {"images_per_sec_chip": 116.0, "scan_blocks": True,
                      "scan_unroll": 1, "remat_window": 2,
                      "remat_policy": "none_saveable"},
        # tiny default measured — but tiny has no eligible ladder rows
        "tiny": {"images_per_sec_chip": 3827.0, "scan_blocks": True,
                 "scan_unroll": 1, "remat_window": 0,
                 "remat_policy": "dots_attn_saveable"},
    }
    base_file = tmp_path / "BASELINE_MEASURED.json"
    base_file.write_text(json.dumps(baselines))
    out = tmp_path / "TUNED.json"
    monkeypatch.setattr(apply_ladder, "REPO", str(tmp_path))
    import bench
    monkeypatch.setattr(bench, "TUNED_FILE", str(out))  # pre-flip: absent
    monkeypatch.setattr(sys, "argv",
                        ["apply_ladder", "--ladder", str(ladder),
                         "--out", str(out)])
    apply_ladder.main()

    tuned = json.loads(out.read_text())
    # l14: windowed row beats the measured unrolled default (280 > 250*1.02)
    assert tuned["l14"]["remat_window"] == 8
    assert tuned["l14"]["scan_blocks"] is True
    # b16: 100.0 < 1.02 * 99.0 -> no entry, default stands
    assert "b16" not in tuned
    # tiny: default measured, no alternatives -> no entry
    assert "tiny" not in tuned
    # 10b_slice: the policy win rides into TUNED (window-2 rides along)
    assert tuned["10b_slice"]["remat_policy"] == "dots_saveable"
    assert tuned["10b_slice"]["remat_window"] == 2

    # bench.py defaults consult TUNED.json
    assert bench.default_remat_window("l14") == 8
    assert bench.default_scan_blocks("l14") is True
    assert bench.default_scan_blocks("b16") is True   # untouched fallback
    assert bench.default_remat_policy("10b_slice") == "dots_saveable"
    assert bench.default_remat_policy("l14") == "dots_attn_saveable"
    # explicit knob A/Bs pin the pre-TUNED policy
    assert bench.default_remat_policy("10b_slice",
                                      allow_tuned=False) == "none_saveable"
