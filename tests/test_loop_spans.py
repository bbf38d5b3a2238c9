"""The train loop's own timeline (vitax/train/loop.py, module docstring):
every iteration stamps five `time.time()` marks, step records carry them as
`loop_marks` with the recorder's cumulative `compiles`, and a profile trace
carries a step marker an iteration.

One tiny real run of `train()` with telemetry on, a loader made to stall
before one batch and a delay planted at the loop's fault hook; every
statement about its records is one case of one parametrised test.
"""

import glob
import json
import os
import sys

import pytest

import jax
import jax.numpy as jnp

from vitax.telemetry import (
    LOOP_MARKS, LOOP_PHASES, REQUIRED_STEP_KEYS, phase_intervals)
from tests.test_telemetry import tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# long enough that half of it stands clear of what can land in any phase of
# a busy test machine: a full collection of a test worker's heap (0.13-0.2 s)
# and a tiny step's fence with six workers on the cores (0.3 s seen)
STALL_S = 1.5
LOADER_STALL_AT = 4     # the producer stalls before the fourth batch
STEP_DELAY_AT = 5       # the fault hook sleeps after step 5's dispatch
LOGGED = [1, 2, 4, 6]   # the first step, then every second


def loop_cfg(tmp, **kw):
    base = dict(
        fake_data=True, num_epochs=2, log_step_interval=2, max_steps=6,
        ckpt_dir=str(tmp / "ckpt"), ckpt_epoch_interval=99,
        test_epoch_interval=99, num_workers=2,
        metrics_dir=str(tmp / "metrics"))
    base.update(kw)
    return tiny_cfg(**base)


def step_records(metrics_dir):
    with open(os.path.join(metrics_dir, "metrics.jsonl"),
              encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r for r in records if "kind" not in r]


def phases_of(rows):
    """{step: {phase: seconds}}; `host` closes on the next row's t_next, so
    the last row has none."""
    out = {}
    for step, phase, a, b in phase_intervals(rows):
        out.setdefault(step, {})[phase] = b - a
    return out


@pytest.fixture(scope="module")
def run(devices8, tmp_path_factory):
    """(cfg, step records, rows of all their loop_marks) of one run."""
    from vitax.train.loop import train
    tmp = tmp_path_factory.mktemp("loop_spans")
    plan = [{"site": "loader", "at": LOADER_STALL_AT, "action": "stall",
             "seconds": STALL_S},
            {"site": "step", "at": STEP_DELAY_AT, "action": "hang",
             "seconds": STALL_S}]
    cfg = loop_cfg(tmp, fault_plan=json.dumps(plan), prefetch_batches=1)
    train(cfg)
    records = step_records(cfg.metrics_dir)
    rows = [row for r in records for row in r["loop_marks"]]
    return cfg, records, rows


def check_records_carry_their_rows(cfg, records, rows):
    assert LOOP_MARKS == ("step", "t_next", "t_got", "t_batch", "t_dispatch",
                          "t_fence")
    assert [r["step"] for r in records] == LOGGED
    for r in records:
        assert set(REQUIRED_STEP_KEYS) <= set(r)
        assert all(len(row) == len(LOOP_MARKS) for row in r["loop_marks"])
        assert r["loop_marks"][-1][0] == r["step"]      # its own row last
    # one row an iteration since the record before: 1, 1, 2, 2
    assert [len(r["loop_marks"]) for r in records] == [1, 1, 2, 2]
    assert [row[0] for row in rows] == [1, 2, 3, 4, 5, 6]


def check_marks_tile_the_threads_time(cfg, records, rows):
    """Non-decreasing within a row and from a row's t_fence to the next
    row's t_next, across records too: `host` is all that lies between, so
    the five phases leave no hole."""
    stamps = [t for row in rows for t in row[1:]]
    assert all(a <= b for a, b in zip(stamps, stamps[1:]))
    spans = phases_of(rows)
    covered = sum(sum(p.values()) for p in spans.values())
    assert covered == pytest.approx(rows[-1][5] - rows[0][1])
    # the record is written inside its own step's `host` phase
    for r, after in zip(records, records[1:]):
        assert r["loop_marks"][-1][5] <= r["time"] <= after["loop_marks"][0][1]


def check_fence_only_where_one_is_taken(cfg, records, rows):
    spans = phases_of(rows)
    for step in (3, 5):                     # t_fence IS t_dispatch there
        assert spans[step]["fence"] == 0.0
    for step in LOGGED:
        assert spans[step]["fence"] > 0.0


def check_a_stalled_loader_shows_in_wait(cfg, records, rows):
    spans = phases_of(rows)
    assert spans[LOADER_STALL_AT]["wait"] >= 0.5 * STALL_S
    for phase in ("put", "dispatch", "fence", "host"):
        assert spans[LOADER_STALL_AT][phase] < 0.5 * STALL_S, phase
    assert all(spans[s]["wait"] < 0.5 * STALL_S
               for s in spans if s != LOADER_STALL_AT)
    # data_wait_s is the mean `wait` of the record's own rows: one clock
    for r in records:
        waits = [row[2] - row[1] for row in r["loop_marks"]]
        assert r["data_wait_s"] == pytest.approx(sum(waits) / len(waits))
    assert records[2]["data_wait_s"] >= 0.25 * STALL_S     # steps 3 and 4


def check_a_delay_at_the_fault_hook_shows_in_host(cfg, records, rows):
    spans = phases_of(rows)
    assert spans[STEP_DELAY_AT]["host"] >= STALL_S
    for phase in ("wait", "put", "dispatch", "fence"):
        assert spans[STEP_DELAY_AT][phase] < 0.5 * STALL_S, phase


def check_compiles_rise_over_the_first_step_and_stay(cfg, records, rows):
    counts = [r["compiles"] for r in records]
    assert counts[0] > 0                    # the step program, at the least
    assert counts == [counts[0]] * len(counts)
    # the compile is the first step's `dispatch`
    spans = phases_of(rows)
    assert spans[1]["dispatch"] > max(spans[s]["dispatch"] for s in (2, 3, 4))


def check_the_report_reads_the_marks(cfg, records, rows):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    summary = metrics_report.summarize(
        os.path.join(cfg.metrics_dir, "metrics.jsonl"))
    assert tuple(summary["loop_phases"]) == LOOP_PHASES
    assert sum(v["share"] for v in summary["loop_phases"].values()) \
        == pytest.approx(1.0, abs=1e-5)
    assert summary["loop_phases"]["wait"]["p95"] >= 0.25 * STALL_S
    assert summary["compile_steps"] == [1]


def check_step_one_is_the_assembled_states(cfg, records, rows):
    """`train()` starts from the state `Geometry.assemble` gives for the
    same `cfg`: the benchmark's loop cell feeds that state to its reference
    and holds the loop's step-1 record to it."""
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    from vitax.programs.builder import Geometry, build_program
    geom = Geometry.assemble(cfg, materialize=True)
    sh = NamedSharding(geom.mesh, batch_pspec())
    s = cfg.image_size
    batch = {"image": jax.device_put(
                 jnp.zeros((cfg.batch_size, s, s, 3), jnp.float32), sh),
             "label": jax.device_put(
                 jnp.zeros((cfg.batch_size,), jnp.int32), sh)}
    _, metrics = build_program("train", geom)(
        geom.state, batch, jax.random.key(cfg.seed + 1))
    assert float(metrics["loss"]) == pytest.approx(records[0]["loss"],
                                                   rel=1e-6)
    assert float(metrics["grad_norm"]) == pytest.approx(
        records[0]["grad_norm"], rel=1e-5)


STATEMENTS = [check_records_carry_their_rows,
              check_marks_tile_the_threads_time,
              check_fence_only_where_one_is_taken,
              check_a_stalled_loader_shows_in_wait,
              check_a_delay_at_the_fault_hook_shows_in_host,
              check_compiles_rise_over_the_first_step_and_stay,
              check_the_report_reads_the_marks,
              check_step_one_is_the_assembled_states]


@pytest.mark.parametrize("statement", STATEMENTS,
                         ids=[s.__name__[6:] for s in STATEMENTS])
def test_loop_span_records(run, statement):
    statement(*run)


def test_a_trace_carries_a_step_marker_an_iteration(devices8, tmp_path):
    """--profile_dir: the batch fetch and the dispatch of every traced
    iteration run under `StepTraceAnnotation("train", step_num=<the global
    step>)`, so the profiler's step view is not empty; without a recorder
    the marks are taken and nothing is written."""
    from jax.profiler import ProfileData
    from vitax.train.loop import train
    cfg = loop_cfg(tmp_path, metrics_dir="", max_steps=4,
                   profile_dir=str(tmp_path / "trace"), profile_start_step=1,
                   profile_num_steps=2)
    train(cfg)
    assert not os.path.exists(tmp_path / "metrics")
    found = glob.glob(os.path.join(cfg.profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    steps = sorted(
        dict(ev.stats).get("step_num")
        for plane in ProfileData.from_file(found[0]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name == "train")
    # the trace opens after step 1 and closes with step 3's fence
    assert steps == [2, 3]
