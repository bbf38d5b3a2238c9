"""Autotuner + perf-regression CI tests (vitax/tune/, tools/autotune.py,
tools/perf_gate.py, vitax/telemetry/schema.py).

Fast tier: the compile-only cost model's ranking pins, successive-halving
budget math, trial-JSONL schema round-trips, preset apply semantics, and the
perf_gate pass/fail/exit-code contract on synthetic trajectories — all pure
host-side code, no compiles. Slow tier: the off-TPU degradation path end to
end — `tools/autotune.py --compile_only` must produce a deterministic ranked
shortlist and a committable preset that `bench.py --preset_file` reproduces
knob-for-knob."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from vitax.config import Config  # noqa: E402
from vitax.telemetry.schema import (  # noqa: E402
    validate_autotune_trial, validate_bench_file, validate_bench_payload,
    validate_trials_file)
from vitax.tune.cost import analytic_cost, check_ranking  # noqa: E402
from vitax.tune.driver import (  # noqa: E402
    TrialLog, plan_successive_halving, run_search)
from vitax.tune.knobs import (  # noqa: E402
    KNOB_PAYLOAD_KEYS, add_knob_args, knob_payload)
from vitax.tune.preset import (  # noqa: E402
    apply_preset_to_args, config_defaults_from_preset, load_preset,
    make_preset, preset_path, save_preset)
from vitax.tune.space import candidate_space, rank_serve_geometries  # noqa: E402

import perf_gate  # noqa: E402  (tools/perf_gate.py)

TINY_KW = dict(image_size=224, patch_size=16, embed_dim=192, num_heads=3,
               num_blocks=12)


def _tiny_cfg(n_dev=1, **over):
    kw = dict(TINY_KW, num_classes=1000, warmup_steps=0,
              batch_size=32 * n_dev)
    kw.update(over)
    return Config(**kw).validate()


def _tiny_knobs(n_dev=1, **over):
    return knob_payload(_tiny_cfg(n_dev, **over), n_dev)


# ---------------------------------------------------------------- cost model

def test_cost_model_ranking_pins_all_green():
    """The compile-only cost model must order every known-ordered knob pair
    correctly (the perf_gate --check_ranking CI arm)."""
    results = check_ranking()
    assert len(results) >= 5
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


def test_gather_overlap_off_never_outranks_auto_on_zero3():
    """The ISSUE's named example, pinned directly (not only through the
    KNOWN_ORDERED_PAIRS table)."""
    base = dict(TINY_KW, num_classes=1000, warmup_steps=0,
                batch_size=32 * 8, fsdp_size=-1, scan_blocks=True,
                grad_ckpt=True, remat_policy="none_saveable")
    auto = Config(**base, gather_overlap="auto").validate()
    off = Config(**base, gather_overlap="off").validate()
    c_auto = analytic_cost(auto, 8, 197.0)
    c_off = analytic_cost(off, 8, 197.0)
    assert c_auto["overlap_active"]
    assert not c_off["overlap_active"]
    assert c_auto["sec_per_image_chip"] <= c_off["sec_per_image_chip"]


def test_analytic_cost_fields():
    c = analytic_cost(_tiny_cfg(8), 8, 197.0)
    for key in ("step_s", "sec_per_image_chip", "recompute_flops",
                "gather_bytes", "reduce_bytes", "live_bytes_estimate"):
        assert key in c and c[key] >= 0, key
    assert c["step_s"] > 0


# ------------------------------------------------------- successive halving

def test_plan_halving_exact_budget_when_min_not_binding():
    plan = plan_successive_halving(8, 800, min_steps=5)
    assert plan == [(8, 25), (4, 50), (2, 100), (1, 200)]
    assert sum(n * s for n, s in plan) == 800


def test_plan_halving_min_steps_floor():
    plan = plan_successive_halving(8, 240, min_steps=10)
    assert plan[0] == (8, 10)  # 240/4 rounds // 8 = 7 -> clamped to 10
    assert [n for n, _ in plan] == [8, 4, 2, 1]
    assert all(s >= 10 for _, s in plan)


def test_plan_halving_single_candidate_gets_whole_budget():
    assert plan_successive_halving(1, 100, min_steps=10) == [(1, 100)]


def test_plan_halving_rejects_bad_args():
    with pytest.raises(AssertionError):
        plan_successive_halving(0, 100)
    with pytest.raises(AssertionError):
        plan_successive_halving(4, 100, eta=1)


# ------------------------------------------------------ trial JSONL schema

def test_trial_log_roundtrip_validates(tmp_path):
    path = str(tmp_path / "trials.jsonl")
    log = TrialLog(path)
    knobs = _tiny_knobs()
    log.write("tiny", "cpu:1", "analytic", knobs, rank=0,
              cost={"step_s": 0.1})
    log.write("tiny", "cpu:1", "compile", knobs, compile_s=1.5,
              compile={"live_bytes": 123})
    log.write("tiny", "cpu:1", "measure", knobs, pruned_by="halving",
              round=0)
    log.close()
    assert validate_trials_file(path) == []
    recs = [json.loads(line) for line in open(path)]
    assert [r["trial_id"] for r in recs] == [0, 1, 2]
    assert all(r["kind"] == "autotune_trial" and r["schema"] == 1
               for r in recs)


def test_trials_file_rejects_non_monotone_and_corrupt(tmp_path):
    knobs = _tiny_knobs()

    def rec(tid):
        return json.dumps({"schema": 1, "kind": "autotune_trial",
                           "trial_id": tid, "time": 1.0,
                           "model_preset": "tiny", "topology": "cpu:1",
                           "phase": "analytic", "knobs": knobs,
                           "pruned_by": None})

    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(rec(0) + "\n" + rec(2) + "\n" + rec(1) + "\n")
    errs = validate_trials_file(path)
    assert any("not monotone" in e for e in errs)

    path2 = str(tmp_path / "corrupt.jsonl")
    with open(path2, "w") as f:
        f.write(rec(0) + "\n{not json\n")
    assert any("invalid JSON" in e for e in validate_trials_file(path2))


def test_validate_autotune_trial_rejects_bad_records():
    knobs = _tiny_knobs()
    good = {"schema": 1, "kind": "autotune_trial", "trial_id": 0,
            "time": 1.0, "model_preset": "tiny", "topology": "cpu:1",
            "phase": "analytic", "knobs": knobs, "pruned_by": None}
    assert validate_autotune_trial(good) == []
    assert validate_autotune_trial({**good, "phase": "searching"})
    assert validate_autotune_trial({**good, "pruned_by": "vibes"})
    assert validate_autotune_trial({**good, "trial_id": True})
    assert validate_autotune_trial({**good, "schema": 2})
    missing = {k: v for k, v in good.items() if k != "pruned_by"}
    assert validate_autotune_trial(missing)
    incomplete = dict(good, knobs={"batch_per_chip": 32})
    assert validate_autotune_trial(incomplete)


def test_validate_bench_payload_contract():
    good = {"metric": "images/sec/chip (ViT-tiny, train step)",
            "value": 100.0, "unit": "images/sec/chip", "vs_baseline": None,
            "knobs": _tiny_knobs()}
    assert validate_bench_payload(good) == []
    assert validate_bench_payload({k: v for k, v in good.items()
                                   if k != "vs_baseline"})
    assert validate_bench_payload({**good, "value": "fast"})
    assert validate_bench_payload({**good, "knobs": [1, 2]})


def test_bench_trajectory_files_validate(tmp_path):
    """The driver-round wrapper files pass the schema validator (the
    lint.sh / perf_gate --validate guard, run in-process) in each of the
    three forms the record has held: a payload older than the knobs object,
    a measured payload with knobs and the device block, an outage round —
    and any still committed in the repo do too."""
    import glob
    rounds = [
        _bench_round(2, 163.02),
        _bench_round(4, 252.63, dict(_tiny_knobs(), batch_per_chip=32)),
        _bench_round(5, 0.0, error="backend unavailable after 8 probes"),
    ]
    rounds[1]["parsed"].update(platform="tpu", device_kind="TPU v5 lite",
                               n_devices=1)
    for rec in rounds:
        path = tmp_path / f"BENCH_r{rec['n']:02d}.json"
        path.write_text(json.dumps(rec))
    files = (sorted(glob.glob(str(tmp_path / "BENCH_r*.json")))
             + sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))))
    assert len(files) >= 3
    for path in files:
        assert validate_bench_file(path) == [], path
    # the device block is typed: a count that is not an integer is refused
    bad = _bench_round(6, 1.0)
    bad["parsed"].update(platform="tpu", device_kind="TPU v5 lite",
                         n_devices="one")
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    assert validate_bench_file(str(tmp_path / "bad.json"))


# -------------------------------------------------------- candidate space

def test_candidate_space_deterministic_and_valid():
    kw = dict(TINY_KW)
    a, inv_a = candidate_space("tiny", 8, kw)
    b, inv_b = candidate_space("tiny", 8, kw)
    assert a == b and inv_a == inv_b
    assert len(a) > 50
    for cand in a[:5]:
        Config(**cand).validate()


def test_serve_geometry_ranking_deterministic():
    r1 = rank_serve_geometries()
    r2 = rank_serve_geometries()
    assert r1 == r2
    assert r1[0]["serve_max_batch"] >= 1
    assert r1 == sorted(r1, key=lambda r: (r["score"], r["serve_max_batch"],
                                           r["max_batch_wait_ms"]))


# ------------------------------------------------- run_search (off-TPU path)

def _search(tmp_path, n_dev, tag):
    log = TrialLog(str(tmp_path / f"trials_{tag}.jsonl"))
    try:
        return run_search("tiny", f"cpu:{n_dev}", dict(TINY_KW), n_dev, log,
                          peak_tflops=1.0, max_candidates=48, shortlist=4,
                          compile_top=0, measure=False,
                          log_fn=lambda *_: None)
    finally:
        log.close()


def test_run_search_deterministic_across_runs_and_topologies(tmp_path):
    """The off-TPU degradation contract: same ranked shortlist on repeat
    runs, for more than one topology, with schema-valid trial logs."""
    for n_dev in (1, 8):
        r1 = _search(tmp_path, n_dev, f"{n_dev}a")
        r2 = _search(tmp_path, n_dev, f"{n_dev}b")
        assert [e["knobs"] for e in r1["ranked"]] == \
               [e["knobs"] for e in r2["ranked"]]
        assert r1["winner"]["knobs"] == r2["winner"]["knobs"]
        assert len(r1["ranked"]) == 4
        errs = validate_trials_file(str(tmp_path / f"trials_{n_dev}a.jsonl"))
        assert errs == []


def test_run_search_trial_log_covers_all_candidates(tmp_path):
    r = _search(tmp_path, 1, "cov")
    path = str(tmp_path / "trials_cov.jsonl")
    recs = [json.loads(line) for line in open(path)]
    assert len(recs) == r["n_candidates"]  # every candidate logged
    pruned = [x for x in recs if x["pruned_by"] == "cost_rank"]
    assert len(pruned) == r["n_candidates"] - len(r["ranked"])


# ----------------------------------------------------------------- presets

def test_preset_emit_load_bitwise(tmp_path):
    knobs = _tiny_knobs()
    preset = make_preset("tiny", "cpu:1", knobs,
                         serve={"serve_max_batch": 8,
                                "max_batch_wait_ms": 5.0},
                         source={"mode": "compile_only"})
    path = save_preset(preset_path(str(tmp_path), "tiny", "cpu:1"), preset)
    assert path.endswith("tiny_cpu-1.json")
    loaded = load_preset(path)
    assert loaded == preset
    # byte-stable on re-save (sort_keys + fixed indent)
    with open(path, "rb") as f:
        first = f.read()
    save_preset(path, loaded)
    with open(path, "rb") as f:
        assert f.read() == first


def test_load_preset_rejects_malformed(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"kind": "other"}))
    with pytest.raises(ValueError):
        load_preset(str(p))
    p.write_text(json.dumps({"kind": "vitax_preset", "schema": 1,
                             "knobs": {"batch_per_chip": 8}}))
    with pytest.raises(ValueError):
        load_preset(str(p))


def test_apply_preset_explicit_cli_wins():
    import argparse
    knobs = _tiny_knobs(remat_policy="none_saveable",
                        param_gather_dtype="float32")
    preset = make_preset("tiny", "cpu:1", knobs)
    parser = add_knob_args(argparse.ArgumentParser())
    # explicit --remat_policy must survive the preset; everything else fills
    args = parser.parse_args(["--remat_policy", "dots_saveable"])
    applied = apply_preset_to_args(preset, args, n_dev=4)
    assert args.remat_policy == "dots_saveable"
    assert "remat_policy" not in applied
    assert args.batch_size == knobs["batch_per_chip"] * 4
    assert args.param_gather_dtype == "float32"
    assert args.gather_overlap == knobs["gather_overlap"]


def test_config_defaults_from_preset_clamps_sentinels():
    knobs = _tiny_knobs()
    knobs = dict(knobs, scan_unroll=0, remat_window=-1)
    preset = make_preset("tiny", "cpu:1", knobs,
                         serve={"serve_max_batch": 16,
                                "max_batch_wait_ms": 2.0})
    d = config_defaults_from_preset(preset)
    assert d["scan_unroll"] == 1 and d["remat_window"] == 0
    assert d["serve_max_batch"] == 16
    assert "batch_size" not in d  # per-chip batch never maps blind


# --------------------------------------------------------------- perf gate

def _bench_round(n, value, knobs=None, error=None):
    parsed = {"metric": f"images/sec/chip (ViT-l14, train step, TPU v5 lite,"
                        f" mfu=0.5, step_time=1ms, remat=x)",
              "value": value, "unit": "images/sec/chip", "vs_baseline": None}
    if knobs:
        parsed["knobs"] = knobs
    if error:
        parsed["error"] = error
    return {"n": n, "cmd": "bench", "rc": 0, "tail": "", "parsed": parsed}


def test_perf_gate_passes_then_fails_on_regression(tmp_path):
    root = str(tmp_path)
    knobs = _tiny_knobs()
    with open(os.path.join(root, "BENCH_r01.json"), "w") as f:
        json.dump(_bench_round(1, 100.0, knobs), f)
    with open(os.path.join(root, "BENCH_r02.json"), "w") as f:
        json.dump(_bench_round(2, 99.0, knobs), f)
    assert perf_gate.main(["--root", root, "--json"]) == 0

    # an outage round must be skipped, not treated as a 100% regression
    with open(os.path.join(root, "BENCH_r03.json"), "w") as f:
        json.dump(_bench_round(3, 0.0, error="backend unavailable"), f)
    assert perf_gate.main(["--root", root, "--json"]) == 0

    # >5% below best -> exit 1, and the --json contract names the series
    with open(os.path.join(root, "BENCH_r04.json"), "w") as f:
        json.dump(_bench_round(4, 80.0, knobs), f)
    assert perf_gate.main(["--root", root, "--json"]) == 1
    # a looser threshold passes again
    assert perf_gate.main(["--root", root, "--threshold_pct", "25"]) == 0


def test_perf_gate_json_contract(tmp_path, capsys):
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_r01.json"), "w") as f:
        json.dump(_bench_round(1, 100.0), f)
    rc = perf_gate.main(["--root", root, "--json"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert out["kind"] == "perf_gate" and out["ok"] is True
    assert out["series"][0]["model"] == "l14"
    assert out["series"][0]["best"] == 100.0


def test_perf_gate_folds_autotune_trials(tmp_path):
    """A measured autotune trial extends the trajectory: a later slow trial
    for the same (preset, topology) trips the gate."""
    root = str(tmp_path)
    knobs = _tiny_knobs()
    trials = os.path.join(root, "trials.jsonl")
    base = {"schema": 1, "kind": "autotune_trial", "time": 1.0,
            "model_preset": "tiny", "topology": "cpu:1",
            "phase": "measure", "knobs": knobs, "pruned_by": None}
    with open(trials, "w") as f:
        f.write(json.dumps({**base, "trial_id": 0,
                            "images_per_sec_chip": 100.0}) + "\n")
        f.write(json.dumps({**base, "trial_id": 1,
                            "images_per_sec_chip": 50.0}) + "\n")
    assert perf_gate.main(["--root", root, "--trials", trials,
                           "--json"]) == 1
    assert perf_gate.main(["--root", root, "--trials", trials,
                           "--threshold_pct", "60"]) == 0


def test_perf_gate_validate_catches_bad_trials(tmp_path):
    root = str(tmp_path)
    trials = os.path.join(root, "trials.jsonl")
    with open(trials, "w") as f:
        f.write(json.dumps({"schema": 1, "kind": "autotune_trial",
                            "trial_id": 0}) + "\n")
    assert perf_gate.main(["--root", root, "--trials", trials,
                           "--validate", "--json"]) == 1


def test_perf_gate_check_ranking_green_at_head(tmp_path):
    assert perf_gate.main(["--root", str(tmp_path), "--check_ranking",
                           "--json"]) == 0


def test_perf_gate_passes_on_committed_trajectory():
    """HEAD must be green: the repo's own BENCH files + ranking pins."""
    assert perf_gate.main(["--root", REPO, "--trials", "--validate",
                           "--check_ranking", "--json"]) == 0


# ------------------------------------------------- end-to-end (subprocess)

def _run(cmd, timeout=1500, n_dev=8):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}")
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
def test_autotune_compile_only_end_to_end(tmp_path):
    """The acceptance path: off-TPU `tools/autotune.py --compile_only`
    emits a deterministic ranked shortlist + schema-valid trial JSONL +
    committable presets across 2 topologies, and `bench.py --preset_file`
    reproduces the winning knob set exactly."""
    def go(tag):
        trials = str(tmp_path / f"trials_{tag}.jsonl")
        pdir = str(tmp_path / f"presets_{tag}")
        r = _run([sys.executable, "tools/autotune.py", "--preset", "tiny",
                  "--topologies", "cpu:1", "cpu:8", "--compile_only",
                  "--max_candidates", "24", "--shortlist", "4",
                  "--trials", trials, "--presets_dir", pdir, "--json"])
        assert r.returncode == 0, r.stderr[-2000:]
        summaries = [json.loads(line) for line in r.stdout.splitlines()
                     if line.startswith("{")]
        assert [s["topology"] for s in summaries] == ["cpu:1", "cpu:8"]
        assert validate_trials_file(trials) == []
        return summaries, pdir

    s1, pdir1 = go("a")
    s2, _ = go("b")
    # deterministic: identical shortlists and winners run-to-run
    assert [s["shortlist"] for s in s1] == [s["shortlist"] for s in s2]
    assert [s["winner_knobs"] for s in s1] == [s["winner_knobs"] for s in s2]

    preset_file = os.path.join(pdir1, "tiny_cpu-1.json")
    preset = load_preset(preset_file)
    assert preset["knobs"] == s1[0]["winner_knobs"]
    assert set(preset["knobs"]) == set(KNOB_PAYLOAD_KEYS)

    # one forced host device so the CPU step stays affordable; the preset
    # stores per-chip batch, so the payload's resolved knobs must equal the
    # preset's knobs EXACTLY
    r = _run([sys.executable, "bench.py", "--preset", "tiny",
              "--preset_file", preset_file, "--steps", "2", "--warmup", "1"],
             timeout=1500, n_dev=1)
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads(r.stdout.strip().splitlines()[-1])
    assert "error" not in payload, payload
    assert payload["knobs"] == preset["knobs"]


@pytest.mark.slow
def test_train_entrypoint_accepts_preset_file(tmp_path):
    """python -m vitax.train --preset_file: preset knobs become parser
    defaults; explicit flags still win (checked via a dry parse)."""
    knobs = _tiny_knobs(remat_policy="dots_saveable")
    preset = make_preset("tiny", "cpu:1", knobs)
    pfile = save_preset(str(tmp_path / "p.json"), preset)
    r = _run([sys.executable, "-c", (
        "from vitax.config import parse_config\n"
        f"cfg = parse_config(['--fake_data', '--preset_file', {pfile!r},\n"
        "                    '--remat_window', '0'])\n"
        "print('remat', cfg.remat_policy, cfg.remat_window)\n")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "remat dots_saveable 0" in r.stdout
