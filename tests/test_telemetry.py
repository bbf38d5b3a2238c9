"""vitax.telemetry tier-1 tests: analytic FLOPs model (closed-form), JSONL
sink round-trip, recorder fail-soft, watchdog fire/silence, telemetry-off
step-program identity, the instrumented train smoke, and
tools/metrics_report.py --json.
"""

import json
import math
import os
import subprocess
import sys
import time

import pytest

import jax

from vitax.config import Config
from vitax.telemetry import (
    REQUIRED_STEP_KEYS, SCHEMA_VERSION, Watchdog, build_recorder,
    detect_peak_tflops, make_tensorboard_sink, model_flops_per_image)
from vitax.telemetry.flops import mfu as mfu_of
from vitax.utils.metrics import SmoothedValue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=16, dtype="float32", lr=1e-3, warmup_steps=2,
        clip_grad_norm=1.0, seed=0,
    )
    base.update(kw)
    return Config(**base).validate()


# --- satellite: SmoothedValue.get_latest on an empty window ---

def test_get_latest_empty_returns_nan():
    sv = SmoothedValue(window_size=3)
    assert math.isnan(sv.get_latest())  # raised IndexError before
    sv.update(3.5)
    assert sv.get_latest() == 3.5
    sv.reset()
    assert math.isnan(sv.get_latest())


# --- analytic FLOPs model: closed-form checks ---

def test_flops_closed_form_dense():
    cfg = tiny_cfg()
    d, L, n, h = 32, 2, 4, 128  # embed, blocks, patches (16/8)^2, mlp hidden
    per_token = 2 * (3 * d * d + d * d) + 2 * (d * h + h * d)
    attn = 2 * 2 * n * n * d
    fwd = L * (per_token * n + attn)
    fwd += 2 * n * (3 * 8 ** 2) * d          # patchify
    fwd += 2 * d * cfg.num_classes           # head
    assert model_flops_per_image(cfg) == pytest.approx(3.0 * fwd)


def test_flops_closed_form_moe_top_k():
    cfg = tiny_cfg(moe_experts=4, moe_top_k=2)
    d, L, n, h = 32, 2, 4, 128
    per_token = (2 * (3 * d * d + d * d)          # qkv + proj
                 + 2 * 2 * (d * h + h * d)        # top-2 expert MLPs
                 + 2 * d * 4)                     # router logits
    attn = 2 * 2 * n * n * d
    fwd = L * (per_token * n + attn) + 2 * n * (3 * 8 ** 2) * d + 2 * d * 4
    assert model_flops_per_image(cfg) == pytest.approx(3.0 * fwd)
    # top-2 MoE does strictly more useful work per image than dense
    assert model_flops_per_image(cfg) > model_flops_per_image(tiny_cfg())


def test_flops_invariant_under_grad_accum():
    # accumulation reshapes where samples flow, not the per-step FLOPs
    assert model_flops_per_image(tiny_cfg()) == model_flops_per_image(
        tiny_cfg(grad_accum_steps=4))


def test_peak_tflops_table_and_override():
    assert detect_peak_tflops("TPU v5e") == 197.0
    assert detect_peak_tflops("TPU v4") == 275.0
    assert detect_peak_tflops("cpu") is None  # a CPU run reports no MFU
    with pytest.raises(ValueError, match="no peak TFLOP/s known"):
        detect_peak_tflops("unknown accelerator")  # an error, not a default
    assert detect_peak_tflops("TPU v5e", override=300.0) == 300.0  # --peak_tflops
    assert detect_peak_tflops("unknown accelerator", override=300.0) == 300.0


def test_mfu_bounds():
    cfg = tiny_cfg()
    assert mfu_of(cfg, sec_per_iter=0.0, n_devices=8, peak_tflops_per_chip=1.0) == 0.0
    v = mfu_of(cfg, sec_per_iter=1.0, n_devices=8, peak_tflops_per_chip=1.0)
    assert 0.0 < v <= 1.0
    assert mfu_of(cfg, sec_per_iter=1.0, n_devices=8,
                  peak_tflops_per_chip=None) is None


# --- config validation of the new flags ---

def test_validate_rejects_bad_telemetry_flags():
    with pytest.raises(AssertionError):
        tiny_cfg(profile_num_steps=0)
    with pytest.raises(AssertionError):
        tiny_cfg(profile_start_step=-1)
    with pytest.raises(AssertionError):
        tiny_cfg(hang_timeout_s=-1.0)
    with pytest.raises(AssertionError):
        tiny_cfg(peak_tflops=-5.0)
    with pytest.raises(AssertionError):
        tiny_cfg(tensorboard=True)  # needs --metrics_dir


# --- recorder + JSONL sink round-trip ---

def test_jsonl_roundtrip(tmp_path):
    cfg = tiny_cfg(metrics_dir=str(tmp_path / "m"))
    rec = build_recorder(cfg, n_devices=8, device_kind="TPU v5e", rank=0)
    assert rec is not None
    for i in range(1, 4):
        rec.record_step(step=i, epoch=1, step_in_epoch=i, loss=2.0 - 0.1 * i,
                        lr=1e-3, sec_per_iter=0.5, data_wait_s=0.01,
                        grad_norm=1.5)
    rec.event("hang", stalled_s=12.0, stacks="fake")
    rec.close()

    lines = (tmp_path / "m" / "metrics.jsonl").read_text().splitlines()
    records = [json.loads(ln) for ln in lines]  # every line must parse
    steps = [r for r in records if "kind" not in r]
    events = [r for r in records if r.get("kind") == "hang"]
    assert len(steps) == 3 and len(events) == 1
    for r in steps:
        assert set(REQUIRED_STEP_KEYS) <= set(r), r
        assert r["schema"] == SCHEMA_VERSION
        assert 0.0 < r["mfu"] <= 1.0
    assert [r["step"] for r in steps] == sorted(r["step"] for r in steps)
    assert steps[0]["images_per_sec"] == pytest.approx(16 / 0.5)
    assert steps[0]["tokens_per_sec"] == pytest.approx(16 * 4 / 0.5)


def test_recorder_none_when_off_or_nonzero_rank(tmp_path):
    assert build_recorder(tiny_cfg(), 8, "cpu", rank=0) is None  # no dir
    cfg = tiny_cfg(metrics_dir=str(tmp_path / "m"))
    assert build_recorder(cfg, 8, "cpu", rank=1) is None  # rank 0 owns records


def test_recorder_fail_soft_on_unwritable_dir(tmp_path, capsys):
    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    cfg = tiny_cfg(metrics_dir=str(blocker / "sub"))  # mkdir will fail
    assert build_recorder(cfg, 8, "cpu", rank=0) is None  # warned, no raise
    assert "not" in capsys.readouterr().err.lower()


def test_tensorboard_sink_degrades_without_package(tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "tensorboard", None)
    monkeypatch.setitem(sys.modules, "tensorboard.summary", None)
    assert make_tensorboard_sink(str(tmp_path / "tb")) is None


def test_tensorboard_sink_writes_events(tmp_path):
    pytest.importorskip("tensorboard")
    sink = make_tensorboard_sink(str(tmp_path / "tb"))
    assert sink is not None
    sink.write({"schema": 1, "step": 1, "loss": 2.0, "mfu": 0.1})
    sink.write({"schema": 1, "kind": "hang", "rank": 0})  # events: TB no-op
    sink.close()
    files = os.listdir(tmp_path / "tb")
    assert any("tfevents" in f for f in files), files


# --- watchdog ---

def test_watchdog_fires_on_stall():
    fired = []
    wd = Watchdog(timeout_s=0.15, on_fire=fired.append, rank=3,
                  poll_s=0.02).start()
    try:
        time.sleep(0.6)  # never petted
        assert wd.fire_count == 1, "must fire once per stall, not per poll"
        payload = fired[0]
        assert payload["stalled_s"] >= 0.15
        assert "vitax-watchdog" in payload["stacks"]  # all-thread dump
        assert "MainThread" in payload["stacks"]
        wd.pet()  # progress re-arms it
        time.sleep(0.4)
        assert wd.fire_count == 2
    finally:
        wd.stop()


def test_watchdog_silent_on_healthy_loop(capsys):
    wd = Watchdog(timeout_s=0.3, poll_s=0.02).start()
    try:
        for _ in range(30):
            wd.pet()
            time.sleep(0.02)
    finally:
        wd.stop()
    assert wd.fire_count == 0
    assert "watchdog" not in capsys.readouterr().err


# --- step program identity + host-side work counts ---

def _lowered_step(cfg):
    from tests.test_train_smoke import build_train_objects, random_batch
    mesh, state, step_fn, _ = build_train_objects(cfg)
    batch = random_batch(cfg, mesh)
    return step_fn.lower(state, batch, jax.random.key(0)).as_text()


def test_telemetry_off_traces_identical_step_program(devices8):
    """--metrics_dir / --hang_timeout_s / --peak_tflops are host-side only:
    the lowered step program must be bit-identical with telemetry on or off
    (the acceptance pin against new device ops / extra syncs)."""
    off = _lowered_step(tiny_cfg())
    on = _lowered_step(tiny_cfg(metrics_dir="/tmp/vitax_metrics_identity_test",
                                hang_timeout_s=300.0, peak_tflops=197.0))
    assert off == on


def test_a_live_recorder_counts_compiles_and_changes_no_program(
        tmp_path, devices8):
    """The recorder's one `jax.monitoring` listener (the step records'
    `compiles`) sees the lowering and moves nothing in it: the same text
    with a recorder alive as with none, and no listener left after close."""
    from jax._src import monitoring
    without = _lowered_step(tiny_cfg())
    listeners = len(monitoring.get_event_duration_listeners())
    cfg = tiny_cfg(metrics_dir=str(tmp_path / "m"))
    rec = build_recorder(cfg, n_devices=8, device_kind="cpu", rank=0)
    assert rec.compiles == 0
    assert _lowered_step(cfg) == without
    traced = rec.compiles
    assert traced > 0                       # the step's trace, at the least
    r = rec.record_step(step=1, epoch=1, step_in_epoch=1, loss=1.0, lr=1e-3,
                        sec_per_iter=0.5, data_wait_s=0.0,
                        loop_marks=[[1, 10.0, 10.1, 10.2, 10.3, 10.4]])
    assert r["compiles"] == traced
    assert r["loop_marks"] == [[1, 10.0, 10.1, 10.2, 10.3, 10.4]]
    rec.close()
    rec.close()                             # twice is harmless
    assert len(monitoring.get_event_duration_listeners()) == listeners


def test_step_metrics_carry_work_counts(devices8):
    from tests.test_train_smoke import build_train_objects, random_batch
    cfg = tiny_cfg()
    mesh, state, step_fn, _ = build_train_objects(cfg)
    _, metrics = step_fn(state, random_batch(cfg, mesh), jax.random.key(0))
    # host-side statics (no device ops): batch images, patches per image
    assert metrics["images"] == cfg.batch_size
    assert metrics["tokens"] == cfg.batch_size * cfg.num_patches


# --- instrumented train smoke: the acceptance JSONL contract ---

def _smoke_cfg(tmp_path, **kw):
    base = dict(
        fake_data=True, num_epochs=1, steps_per_epoch=3, log_step_interval=1,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=99,
        test_epoch_interval=99, num_workers=2, eval_max_batches=1,
        metrics_dir=str(tmp_path / "metrics"), hang_timeout_s=120.0,
    )
    base.update(kw)
    return tiny_cfg(**base)


def test_train_smoke_emits_jsonl_and_report(tmp_path, devices8):
    from vitax.train.loop import train
    train(_smoke_cfg(tmp_path))

    path = tmp_path / "metrics" / "metrics.jsonl"
    records = [json.loads(ln) for ln in path.read_text().splitlines()]
    steps = [r for r in records if "kind" not in r]
    events = [r for r in records if "kind" in r]
    assert len(steps) == 3  # log_step_interval=1 -> one record per step
    for r in steps:
        for key in ("step", "loss", "sec_per_iter", "data_wait_s", "mfu",
                    "mem_used_bytes"):
            assert key in r, (key, r)
        assert r["schema"] == SCHEMA_VERSION
        assert r["mfu"] is None  # a CPU run: the key is there, null
        assert r["data_wait_s"] >= 0.0
        assert r["sec_per_iter"] > 0.0
    assert [r["step"] for r in steps] == [1, 2, 3]  # monotonic global steps
    # the watchdog observed the whole healthy run and never fired
    assert not [e for e in events if e.get("kind") == "hang"]
    assert any(e.get("kind") == "run_start" for e in events)

    # metrics_report --json over the run: the CI summary contract
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         str(path), "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout)
    assert summary["records"] == 3
    assert summary["hang_events"] == 0
    assert summary["mfu_last"] is None
    assert summary["sec_per_iter_p50"] > 0
    assert summary["sec_per_iter_p95"] >= summary["sec_per_iter_p50"]
    assert summary["data_wait_fraction"] is not None
    assert len(summary["loss_curve"]) == 3


def test_profile_window_configurable(tmp_path, devices8):
    """--profile_start_step/--profile_num_steps move the trace window (the
    hardcoded steps-3..7 satellite); a window starting at step 0 still
    produces trace artifacts on a 2-step run (the old constants could not)."""
    from vitax.train.loop import train
    prof_dir = str(tmp_path / "trace")
    train(_smoke_cfg(tmp_path, steps_per_epoch=2, profile_dir=prof_dir,
                     profile_start_step=0, profile_num_steps=2,
                     metrics_dir="", hang_timeout_s=0.0))
    found = [f for _, _, fs in os.walk(prof_dir) for f in fs]
    assert any(f.endswith((".pb", ".json.gz", ".trace.json.gz"))
               for f in found), found


# --- metrics_report over a synthetic run (accelerator-free) ---

def test_metrics_report_synthetic(tmp_path):
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for i in range(1, 21):
            f.write(json.dumps({
                "schema": 1, "time": 1000.0 + i, "step": i, "epoch": 1,
                "step_in_epoch": i, "loss": 3.0 - 0.1 * i, "lr": 1e-3,
                "sec_per_iter": 0.5 + (0.5 if i == 20 else 0.0),
                "images_per_sec": 32.0, "tokens_per_sec": 8192.0,
                "data_wait_s": 0.05, "mfu": 0.4, "mem_used_bytes": 123456,
                "mem_peak_bytes": 234567,
                # a second of wall time a step: wait 0.05, put 0.1, dispatch
                # 0.05, fence 0.7 (1.2 at step 20), host 0.1 to the next row;
                # something compiled before steps 1 and 12
                "compiles": 100 + (7 if i >= 12 else 0),
                "loop_marks": [[i] + [1000.0 + i + t for t in (
                    0.0, 0.05, 0.15, 0.2, 1.4 if i == 20 else 0.9)]],
                }) + "\n")
        f.write(json.dumps({"schema": 1, "kind": "hang", "rank": 0,
                            "stalled_s": 99.0, "stacks": "..."}) + "\n")
        f.write("{corrupt json\n")

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    summary = metrics_report.summarize(str(path))
    assert summary["records"] == 20
    assert summary["corrupt_lines"] == 1
    assert summary["hang_events"] == 1
    assert summary["sec_per_iter_p50"] == pytest.approx(0.5)
    assert summary["sec_per_iter_p95"] > 0.5  # the slow tail is visible
    assert summary["data_wait_fraction"] == pytest.approx(
        (19 * 0.1 + 0.05) / 20)
    assert summary["loss_first"] == pytest.approx(2.9)
    assert summary["loss_last"] == pytest.approx(1.0)
    assert summary["mem_peak_bytes"] == 234567
    phases = summary["loop_phases"]
    assert list(phases) == ["wait", "put", "dispatch", "fence", "host"]
    assert phases["wait"]["p50"] == pytest.approx(0.05)
    assert phases["fence"]["p50"] == pytest.approx(0.7)
    assert phases["fence"]["p95"] > 0.7         # step 20's long fence
    assert phases["host"]["p50"] == pytest.approx(0.1)
    # 19 closed rows of 1.0 s and the last, open after its fence, of 1.4
    assert phases["put"]["share"] == pytest.approx(20 * 0.1 / 20.4, abs=1e-5)
    assert sum(v["share"] for v in phases.values()) == pytest.approx(
        1.0, abs=1e-5)
    assert summary["compile_steps"] == [1, 12]

    # human mode renders without crashing and flags the hang
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         str(path)], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "watchdog hang events: 1" in r.stdout
    assert "loop fence: p50 0.7000s" in r.stdout
    assert "compiled before the records of steps: [1, 12]" in r.stdout

    # empty file -> exit 2 (CI must notice a run that recorded nothing)
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         str(empty), "--json"], capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
