"""Schema validation for the perf-data files CI folds into a trajectory:
bench result payloads (BENCH_r*.json / bench.jsonl) and autotune trial
JSONL (kind:"autotune_trial").

Validators return a list of error strings (empty = valid) instead of
raising, so tools/perf_gate.py --validate and tools/lint.sh can report every
problem in one pass. The contracts guarded here:

  - bench payload: the ONE JSON line bench.py prints — metric/value/unit/
    vs_baseline always present, with the device block (platform,
    device_kind, n_devices) typed where present; a measured payload carries
    the full resolved `knobs` object (KNOB_PAYLOAD_KEYS) so the trajectory
    can tell whether two numbers are comparable. Historical payloads
    predate the knobs object; absence is legal, a *malformed* knobs
    object is not.
  - autotune trial: schema 1, monotone trial ids within a file, phase and
    pruned_by drawn from closed vocabularies, knobs complete.
"""

from __future__ import annotations

import json
from typing import List, Optional

from vitax.tune.knobs import KNOB_PAYLOAD_KEYS

TRIAL_PHASES = ("analytic", "compile", "measure")
PRUNED_BY_VALUES = (None, "invalid", "cost_rank", "hbm", "hbm_estimate",
                    "compile_error", "halving", "run_error")

_KNOB_TYPES = {
    "batch_per_chip": int,
    "remat_policy": str,
    "scan_blocks": bool,
    "scan_unroll": int,
    "remat_window": int,
    "grad_ckpt": bool,
    "use_flash_attention": bool,
    "grad_accum_steps": int,
    "param_gather_dtype": (str, type(None)),
    "grad_reduce_dtype": str,
    "gather_overlap": str,
    "fused_optimizer": str,
}

_NUM = (int, float)


def _typecheck(errs: List[str], where: str, obj: dict, key: str, types,
               required: bool = True) -> None:
    if key not in obj:
        if required:
            errs.append(f"{where}: missing required key {key!r}")
        return
    val = obj[key]
    # bool is an int subclass; an int-typed knob must not accept True
    if types is int and isinstance(val, bool):
        errs.append(f"{where}: {key!r} must be int, got bool")
        return
    if not isinstance(val, types):
        tname = getattr(types, "__name__", str(types))
        errs.append(f"{where}: {key!r} must be {tname}, "
                    f"got {type(val).__name__}")


def validate_knobs(knobs, where: str = "knobs",
                   require_all: bool = True) -> List[str]:
    """The resolved-knob payload (KNOB_PAYLOAD_KEYS, vitax/tune/knobs.py)."""
    errs: List[str] = []
    if not isinstance(knobs, dict):
        return [f"{where}: knobs must be an object, "
                f"got {type(knobs).__name__}"]
    for key in KNOB_PAYLOAD_KEYS:
        _typecheck(errs, where, knobs, key, _KNOB_TYPES[key],
                   required=require_all)
    return errs


def validate_bench_payload(payload, where: str = "bench") -> List[str]:
    """The bench.py single-JSON-line contract (and BENCH_r*.json "parsed")."""
    errs: List[str] = []
    if not isinstance(payload, dict):
        return [f"{where}: payload must be an object, "
                f"got {type(payload).__name__}"]
    _typecheck(errs, where, payload, "metric", str)
    _typecheck(errs, where, payload, "value", _NUM)
    _typecheck(errs, where, payload, "unit", str)
    if "vs_baseline" not in payload:
        errs.append(f"{where}: missing required key 'vs_baseline'")
    elif payload["vs_baseline"] is not None and not isinstance(
            payload["vs_baseline"], _NUM):
        errs.append(f"{where}: 'vs_baseline' must be number or null")
    if isinstance(payload.get("value"), _NUM) and payload["value"] < 0:
        errs.append(f"{where}: 'value' must be >= 0")
    _typecheck(errs, where, payload, "error", str, required=False)
    # the device the number came from, as JAX reported it (every payload
    # bench.py prints carries the block; older records predate it). A CPU
    # run's mfu is null.
    _typecheck(errs, where, payload, "platform", str, required=False)
    _typecheck(errs, where, payload, "device_kind", str, required=False)
    _typecheck(errs, where, payload, "n_devices", int, required=False)
    _typecheck(errs, where, payload, "mfu", (*_NUM, type(None)),
               required=False)
    if "knobs" in payload:
        errs.extend(validate_knobs(payload["knobs"], f"{where}.knobs",
                                   require_all=False))
    return errs


def validate_bench_round(obj, where: str = "BENCH") -> List[str]:
    """One BENCH_rNN.json trajectory entry (driver wrapper + parsed line)."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return [f"{where}: must be an object, got {type(obj).__name__}"]
    _typecheck(errs, where, obj, "n", int)
    _typecheck(errs, where, obj, "cmd", str)
    _typecheck(errs, where, obj, "rc", int)
    parsed = obj.get("parsed")
    if parsed is not None:
        errs.extend(validate_bench_payload(parsed, f"{where}.parsed"))
    return errs


def validate_autotune_trial(rec, where: str = "trial") -> List[str]:
    """One kind:"autotune_trial" record (vitax/tune/driver.py TrialLog)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"{where}: must be an object, got {type(rec).__name__}"]
    if rec.get("schema") != 1:
        errs.append(f"{where}: schema must be 1, got {rec.get('schema')!r}")
    if rec.get("kind") != "autotune_trial":
        errs.append(f"{where}: kind must be 'autotune_trial', "
                    f"got {rec.get('kind')!r}")
    _typecheck(errs, where, rec, "trial_id", int)
    if isinstance(rec.get("trial_id"), int) and rec["trial_id"] < 0:
        errs.append(f"{where}: trial_id must be >= 0")
    _typecheck(errs, where, rec, "time", _NUM)
    _typecheck(errs, where, rec, "model_preset", str)
    _typecheck(errs, where, rec, "topology", str)
    if rec.get("phase") not in TRIAL_PHASES:
        errs.append(f"{where}: phase must be one of {TRIAL_PHASES}, "
                    f"got {rec.get('phase')!r}")
    if "pruned_by" not in rec:
        errs.append(f"{where}: missing required key 'pruned_by'")
    elif rec["pruned_by"] not in PRUNED_BY_VALUES:
        errs.append(f"{where}: pruned_by {rec['pruned_by']!r} not in "
                    f"{PRUNED_BY_VALUES}")
    errs.extend(validate_knobs(rec.get("knobs"), f"{where}.knobs"))
    for key in ("compile_s", "step_time_s", "images_per_sec_chip", "mfu"):
        _typecheck(errs, where, rec, key, _NUM, required=False)
    for key in ("rank", "round", "steps"):
        _typecheck(errs, where, rec, key, int, required=False)
    for key in ("cost", "compile", "mem"):
        _typecheck(errs, where, rec, key, dict, required=False)
    return errs


def validate_trials_file(path: str,
                         max_errors: int = 50) -> List[str]:
    """Validate an autotune trial JSONL file: every line parses, every
    record passes validate_autotune_trial, trial ids strictly increase."""
    errs: List[str] = []
    last_id: Optional[int] = None
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            if not line.strip():
                continue
            where = f"{path}:{lineno}"
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"{where}: invalid JSON ({e})")
                continue
            errs.extend(validate_autotune_trial(rec, where))
            tid = rec.get("trial_id")
            if isinstance(tid, int) and not isinstance(tid, bool):
                if last_id is not None and tid <= last_id:
                    errs.append(f"{where}: trial_id {tid} not monotone "
                                f"(previous {last_id})")
                last_id = tid
            if len(errs) >= max_errors:
                errs.append(f"{path}: stopping after {max_errors} errors")
                break
    return errs


def validate_bench_file(path: str) -> List[str]:
    """Validate one BENCH_rNN.json trajectory file."""
    try:
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    return validate_bench_round(obj, path)
