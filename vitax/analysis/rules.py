"""Declarative SPMD program-invariant rules.

Every perf win in this repo is a property of the *lowered program* — bf16
collectives (PR 2), prefetch-slot gathers (PR 3), host-side-only telemetry
(PR 4), zero-recompile serve buckets (PR 5), buffer donation — so a future
refactor can silently regress any of them without a unit test noticing. Each
rule here turns one such folklore invariant into a checkable gate (the Error
Prone model: bug patterns as compile-time checks), run over the programs
`build_train_program` / `build_serve_program` lower across the parallelism
arms (tools/check_invariants.py is the CLI/CI entry).

A rule is declarative data: (id, severity, kinds, applies_to(config),
check(program, config) -> findings). `applies_to` filters by configuration
(e.g. the collective-dtype rule only binds when the bf16 comm-cast policy is
active); `check` parses the program artifacts via vitax.analysis.hlo. Rules
never mutate the program; findings carry enough detail for a CI log to be
actionable without rerunning.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from vitax.analysis import hlo
from vitax.config import Config

SEVERITIES = ("ERROR", "WARN")


@dataclasses.dataclass
class Finding:
    """One rule violation in one program."""
    rule: str
    severity: str
    arm: str
    message: str
    details: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Program:
    """One lowered program plus the artifacts the rules parse.

    kind "train": `mlir` (lowered StableHLO, always present),
    `partitioned_hlo` (post-SPMD-partitioning dump; "" on single-device
    meshes where the partitioner never runs), and `jaxpr` (traced-jaxpr
    text, captured only on fused-optimizer arms — interpret-mode Pallas
    leaves no custom-call marker in MLIR, so VTX-R008 reads the jaxpr).
    kind "serve": a warmed-up InferenceEngine (the AOT bucket invariants
    are runtime properties of the executable set, not of any one module's
    text)."""
    kind: str                     # "train" | "serve"
    arm: str
    config: Config
    mlir: str = ""
    partitioned_hlo: str = ""
    jaxpr: str = ""
    mesh_shape: Dict[str, int] = dataclasses.field(default_factory=dict)
    n_state_leaves: int = 0
    engine: Any = None
    # scenario freeze evidence (vitax/programs/builder.py freeze_report,
    # captured on probe/distill arms): '/'-joined param paths the task
    # freezes, and the param subpath of every optimizer moment (mu/nu) leaf
    # that exists in the abstract opt_state — VTX-R010's inputs
    frozen_paths: Tuple[str, ...] = ()
    opt_moment_paths: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str                       # stable VTX-Rnnn code (CI contract)
    name: str                     # kebab-case human handle
    severity: str                 # ERROR fails CI; WARN is advisory
    kinds: Tuple[str, ...]        # program kinds the rule reads
    description: str
    applies_to: Callable[[Config], bool]
    check: Callable[[Program, Config], List[Finding]]

    def applicable(self, program: Program) -> bool:
        return (program.kind in self.kinds
                and self.applies_to(program.config))


RULES: List[Rule] = []


def rule(id: str, name: str, severity: str, kinds: Tuple[str, ...],
         description: str, applies_to: Callable[[Config], bool] = lambda cfg: True):
    """Register a check function as a Rule."""
    assert severity in SEVERITIES, severity

    def wrap(fn: Callable[[Program, Config], List[Finding]]) -> Rule:
        r = Rule(id=id, name=name, severity=severity, kinds=tuple(kinds),
                 description=description, applies_to=applies_to, check=fn)
        assert all(existing.id != id for existing in RULES), f"duplicate {id}"
        RULES.append(r)
        return r

    return wrap


def _finding(r: Rule, program: Program, message: str, **details) -> Finding:
    return Finding(rule=r.id, severity=r.severity, arm=program.arm,
                   message=message, details=details)


def large_param_threshold_bytes(cfg: Config) -> int:
    """Size above which a replicated parameter is a sharding regression: one
    f32 block matmul matrix (embed_dim^2 * 4). Everything the fsdp axis is
    meant to shard is at least this big; everything legitimately replicated
    (LN scales, cls token, small pos embeds, the step counter) is far
    smaller."""
    return cfg.embed_dim * cfg.embed_dim * 4


# --- built-in rules ---------------------------------------------------------


@rule("VTX-R001", "no-host-transfer-in-step", "ERROR", ("train",),
      "the compiled train step must not move data to the host: no outfeed/"
      "infeed/send/recv, no host-callback custom-calls (a stray jax.debug."
      "print or io_callback serializes every step on a device->host sync; "
      "telemetry is host-side by contract, PR 4)")
def check_no_host_transfer(program: Program, cfg: Config) -> List[Finding]:
    r = NO_HOST_TRANSFER
    ops = (hlo.host_transfer_ops(program.partitioned_hlo)
           if program.partitioned_hlo
           else hlo.mlir_host_transfer_ops(program.mlir))
    return [
        _finding(r, program,
                 f"host transfer in compiled step: {o['op']} ({o['detail']})",
                 instruction=o["line"])
        for o in ops
    ]


@rule("VTX-R002", "donation-honored", "ERROR", ("train",),
      "donate_argnums on the train state must survive to the executable: "
      "every state leaf aliased input->output (a dropped donation doubles "
      "the optimizer-state footprint silently)")
def check_donation(program: Program, cfg: Config) -> List[Finding]:
    r = DONATION_HONORED
    out: List[Finding] = []
    args = hlo.mlir_main_args(program.mlir)
    donated = [a for a in args if a["donated_to"] is not None]
    if len(donated) < program.n_state_leaves:
        out.append(_finding(
            r, program,
            f"only {len(donated)} of {program.n_state_leaves} state buffers "
            f"are marked donated in the lowered program (donate_argnums "
            f"dropped or not set)",
            donated=len(donated), expected=program.n_state_leaves))
    if program.partitioned_hlo:
        aliases = hlo.input_output_aliases(program.partitioned_hlo)
        if len(aliases) < program.n_state_leaves:
            out.append(_finding(
                r, program,
                f"compiler honored only {len(aliases)} of "
                f"{program.n_state_leaves} donations (input_output_alias "
                f"header) — XLA refused aliasing for the rest",
                aliased=len(aliases), expected=program.n_state_leaves))
    return out


@rule("VTX-R003", "collective-dtype-policy", "ERROR", ("train",),
      "under the bf16 comm-precision policy every block-sized param "
      "all-gather must move bf16 (and block-sized grad reductions bf16 when "
      "--grad_reduce_dtype bfloat16): an f32 collective doubles wire bytes "
      "— the PR 2 win regressing silently",
      applies_to=lambda cfg: cfg.comm_cast_active)
def check_collective_dtype(program: Program, cfg: Config) -> List[Finding]:
    r = COLLECTIVE_DTYPE
    if not program.partitioned_hlo:
        return []  # single-device program: no collectives to police
    out: List[Finding] = []
    rows = hlo.collect_collectives(program.partitioned_hlo)
    block_numel = cfg.embed_dim * cfg.embed_dim  # smallest block matmul param
    for row in rows:
        if (row["op"] == "all-gather" and row["dtype"] == "f32"
                and row["numel"] >= block_numel):
            out.append(_finding(
                r, program,
                f"f32 block-param all-gather under the bf16 gather policy: "
                f"{row['count']}x {row['shape']} ({row['bytes']:,} B/step)",
                collective=row))
        if (cfg.grad_reduce_dtype == "bfloat16"
                and row["op"] in ("reduce-scatter", "all-reduce")
                and row["dtype"] == "f32" and row["numel"] >= block_numel):
            out.append(_finding(
                r, program,
                f"f32 block-sized grad {row['op']} under --grad_reduce_dtype "
                f"bfloat16: {row['count']}x {row['shape']} "
                f"({row['bytes']:,} B/step)",
                collective=row))
    return out


def _overlap_requested(cfg: Config) -> bool:
    """Config-only restriction of sharding.gather_overlap_active: `on`, or
    `auto` with every config-side precondition met (the mesh-side fsdp>1
    condition is re-checked in the rule body against program.mesh_shape)."""
    mode = getattr(cfg, "gather_overlap", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return (cfg.reshard_after_forward and not cfg.run_without_fsdp
            and cfg.scan_blocks and cfg.grad_ckpt
            and cfg.remat_policy == "none_saveable"
            and getattr(cfg, "pp_size", 1) == 1)


@rule("VTX-R004", "gather-overlap-structure", "ERROR", ("train",),
      "with --gather_overlap active, every per-iteration forward all-gather "
      "must sit on the scan carry's prefetch slot (reach the while body ROOT "
      "through layout plumbing only) — a use-site gather means the double "
      "buffering silently degraded to the serial schedule (PR 3); and no "
      "block-sized synchronous reduce in a scan body under --gather_overlap "
      "— on a mesh that shards over fsdp alone every block matrix's gradient "
      "leaves through the ring's collective-permutes (PR 50)",
      applies_to=_overlap_requested)
def check_gather_overlap(program: Program, cfg: Config) -> List[Finding]:
    r = GATHER_OVERLAP
    fsdp = program.mesh_shape.get("fsdp", 1)
    if fsdp <= 1:
        return []  # nothing to overlap on an unsharded fsdp axis
    verdict = hlo.overlap_verdict(
        program.partitioned_hlo,
        min_reduce_numel=cfg.embed_dim * cfg.embed_dim // fsdp)
    per_body = verdict["per_iteration_gather_count"]
    if not per_body:
        return [_finding(r, program,
                         "no while-loop body with gathers found — the "
                         "overlap schedule did not lower to a scanned "
                         "program at all", verdict=verdict)]
    # the first while body in program order is the forward scan
    fwd = next(iter(per_body))
    n_gathers = per_body[fwd]
    on_slot = verdict["prefetch_slot_by_body"].get(fwd, 0)
    if n_gathers == 0:
        return [_finding(r, program,
                         f"forward scan body {fwd} issues no per-iteration "
                         "gathers — ZeRO-3 per-block gathers were hoisted "
                         "or lost", verdict=verdict)]
    if on_slot != n_gathers:
        return [_finding(
            r, program,
            f"{n_gathers - on_slot} of {n_gathers} forward in-loop gathers "
            f"are use-site gathers (not on the prefetch slot): the overlap "
            f"schedule regressed to serial gather-then-compute",
            verdict=verdict)]
    # the sum over any other axis (dp, tp) is the partitioner's, and a
    # synchronous reduce of a shard is what it makes of one
    fsdp_alone = all(size == 1 for axis, size in program.mesh_shape.items()
                     if axis != "fsdp")
    if fsdp_alone and verdict["sync_block_reduces"]:
        return [_finding(
            r, program,
            f"{verdict['sync_block_reduces']} block-sized synchronous "
            f"reduce(s) in a scan body ({verdict['ring_permutes']} ring "
            f"permutes): a block matrix's gradient left the ring for the "
            f"compiler's reduce-scatter, which no TPU schedule overlaps",
            verdict=verdict)]
    return []


@rule("VTX-R005", "no-replicated-large-params", "ERROR", ("train",),
      "under fsdp arms no state buffer above one block-matrix in size may "
      "lower fully replicated: a replicated 10B tree is an instant HBM OOM "
      "at flagship scale and a silent memory regression at any scale",
      applies_to=lambda cfg: not cfg.run_without_fsdp and cfg.fsdp_size != 1)
def check_no_replicated_large_params(program: Program, cfg: Config) -> List[Finding]:
    r = NO_REPLICATED_LARGE
    if program.mesh_shape.get("fsdp", 1) <= 1:
        return []  # the resolved mesh has no sharding capacity to demand
    threshold = large_param_threshold_bytes(cfg)
    out: List[Finding] = []
    for a in hlo.mlir_main_args(program.mlir):
        if a["donated_to"] is None:
            continue  # donated args are exactly the state buffers
        if a["bytes"] >= threshold and hlo.sharding_is_replicated(a["sharding"]):
            out.append(_finding(
                r, program,
                f"state buffer arg{a['index']} ({a['dtype']}{a['shape']}, "
                f"{a['bytes']:,} B) lowers fully replicated under an fsdp "
                f"mesh (sharding={a['sharding']})",
                arg=a, threshold_bytes=threshold))
    return out


@rule("VTX-R006", "serve-no-recompile", "ERROR", ("serve",),
      "steady-state serving must never compile: after warmup, compile count "
      "== bucket count, mixed-size traffic reuses the AOT executables, and "
      "a bucket executable rejects shapes it was not compiled for (PR 5)")
def check_serve_no_recompile(program: Program, cfg: Config) -> List[Finding]:
    r = SERVE_NO_RECOMPILE
    import numpy as np
    eng = program.engine
    out: List[Finding] = []
    expected = len(eng.buckets)
    if eng.compile_count != expected:
        out.append(_finding(
            r, program,
            f"compile_count {eng.compile_count} != bucket count {expected} "
            f"after warmup",
            compile_count=eng.compile_count, buckets=list(eng.buckets)))
    s = cfg.image_size
    before = eng.compile_count
    # mixed-size traffic: exact smallest, exact largest, and one off-bucket
    # size that must pad rather than compile
    sizes = sorted({1, eng.buckets[-1], min(3, eng.buckets[-1])})
    for n in sizes:
        eng.predict(np.zeros((n, s, s, 3), np.uint8))
    if eng.compile_count != before:
        out.append(_finding(
            r, program,
            f"serving traffic of sizes {sizes} triggered "
            f"{eng.compile_count - before} recompile(s)",
            sizes=sizes, compiles=eng.compile_count - before))
    # the AOT executables must reject unseen shapes instead of silently
    # recompiling for them
    b0 = eng.buckets[0]
    try:
        import jax
        bad = np.zeros((b0, s + 1, s + 1, 3), np.uint8)
        # the tree the bucket was compiled for, so that the shape alone is
        # what it can reject (a quantized engine's call raises either way)
        eng._compiled[b0](
            eng.compute_params, jax.device_put(bad, eng._batch_shardings[b0]))
        out.append(_finding(
            r, program,
            f"bucket-{b0} executable accepted an unseen input shape "
            f"{bad.shape} — recompiles are not structurally impossible"))
    except Exception:  # vtx: ignore[VTX106] rejection IS the pass condition of this probe
        pass
    return out


# how each QUANT_DTYPES entry spells in the lowered StableHLO arg table and
# as a device-resident numpy dtype (R007 audits both representations)
QUANT_MLIR_DTYPES = {"int8": "i8", "float8_e4m3": "f8E4M3"}


def _quant_np_dtype(quant_dtype: str):
    import numpy as np
    if quant_dtype == "int8":
        return np.dtype(np.int8)
    import ml_dtypes
    return np.dtype(ml_dtypes.float8_e4m3)


@rule("VTX-R007", "quant-weights-resident", "ERROR", ("serve",),
      "a quantized serve program must hold its matmul weights AT THE QUANT "
      "DTYPE: every manifested leaf int8/fp8 on device, the lowered program "
      "taking exactly one quant-dtype argument per scaled leaf, and no "
      "floating weight argument at or above block-matrix size (a dequant "
      "hoisted out of jit materializes the f32 copy the quantized export "
      "exists to avoid — 4x the HBM, silently)",
      applies_to=lambda cfg: bool(getattr(cfg, "serve_quant_dtype", "")))
def check_quant_weights_resident(program: Program, cfg: Config) -> List[Finding]:
    r = QUANT_WEIGHTS_RESIDENT
    import numpy as np
    eng = program.engine
    out: List[Finding] = []
    scales = getattr(eng, "scales", {})
    if not scales:
        return [_finding(
            r, program,
            f"--serve_quant_dtype {cfg.serve_quant_dtype} but the engine "
            f"carries no quant scales — serving full-precision weights")]
    want = cfg.serve_quant_dtype
    want_np = _quant_np_dtype(want)
    want_mlir = QUANT_MLIR_DTYPES[want]
    # (1) device residency: every scaled leaf must actually be the quant
    # dtype — a float leaf paired with a scale is a dequant that happened
    # at load time
    from vitax.checkpoint.consolidate import flatten_tree
    for key, leaf in flatten_tree(eng.params).items():
        if key in scales and np.dtype(leaf.dtype) != want_np:
            out.append(_finding(
                r, program,
                f"scaled leaf {key} is resident as {leaf.dtype}, not {want} "
                f"— dequantized outside the jitted program",
                key=key, dtype=str(leaf.dtype)))
    # (2) the lowered program's weight operands: one quant-dtype argument
    # per scaled leaf, and no block-sized floating argument (pos_embed and
    # LN leaves sit far below the threshold at every geometry; uint8 images
    # lower as ui8, which never collides with i8)
    mlir = eng.lower_bucket_mlir(eng.buckets[-1])
    args = hlo.mlir_main_args(mlir)
    n_q = sum(1 for a in args if a["dtype"] == want_mlir)
    if n_q != len(scales):
        out.append(_finding(
            r, program,
            f"lowered program has {n_q} {want_mlir} arguments for "
            f"{len(scales)} scaled leaves — quantized weights are not "
            f"entering the program as {want}",
            quant_args=n_q, scaled_leaves=len(scales)))
    threshold = large_param_threshold_bytes(cfg)
    for a in args:
        if a["dtype"] in ("f32", "f64", "bf16", "f16") and a["bytes"] >= threshold:
            out.append(_finding(
                r, program,
                f"block-sized floating argument arg{a['index']} "
                f"({a['dtype']}{a['shape']}, {a['bytes']:,} B) in the "
                f"quantized serve program — a materialized dequantized "
                f"weight",
                arg=a, threshold_bytes=threshold))
    return out


def _fused_active(cfg: Config) -> bool:
    """Config-side gate for VTX-R008: the resolved --fused_optimizer policy
    (lazy import — rules.py stays importable without pulling in jax)."""
    from vitax.ops.fused_optimizer import fused_optimizer_active
    return fused_optimizer_active(cfg)


@rule("VTX-R008", "fused-optimizer-lowered", "ERROR", ("train",),
      "with the fused optimizer active the traced train step must actually "
      "launch the fused AdamW Pallas kernel AND leave no post-clip "
      "param-sized f32 temporary chain: sqrt / select_n equations at "
      "parameter size outside the kernel are the optax adamw / per-leaf "
      "clip tell-tales of the one-pass update silently regressing to the "
      "tree-of-ops chain (same perf-properties-are-CI discipline as "
      "R004/R007)",
      applies_to=_fused_active)
def check_fused_optimizer(program: Program, cfg: Config) -> List[Finding]:
    r = FUSED_OPTIMIZER
    from vitax.ops.fused_optimizer import FUSED_KERNEL_NAME
    if not program.jaxpr:
        return [_finding(
            r, program,
            "fused-optimizer arm lowered without a traced-jaxpr artifact — "
            "the rule has nothing to audit (build_train_program captures "
            "Program.jaxpr whenever the fused policy resolves on)")]
    out: List[Finding] = []
    n_launches = program.jaxpr.count(FUSED_KERNEL_NAME)
    if n_launches == 0:
        out.append(_finding(
            r, program,
            f"traced train step contains no {FUSED_KERNEL_NAME} pallas_call "
            f"— the fused optimizer did not enter the compiled program",
            kernel=FUSED_KERNEL_NAME))
    min_elems = large_param_threshold_bytes(cfg) // 4  # f32 elements
    for row in hlo.jaxpr_oversized_eqns(program.jaxpr, min_elems):
        out.append(_finding(
            r, program,
            f"param-sized f32 {row['op']} over [{row['shape']}] "
            f"({row['numel']:,} elems) outside the fused kernel — an "
            f"optimizer temporary the one-pass update should have "
            f"eliminated",
            eqn=row, min_elems=min_elems))
    return out


def _fused_dequant_cfg(cfg: Config) -> bool:
    """Config-side gate for VTX-R009: the resolved --fused_dequant policy
    (lazy import, same shape as VTX-R008's gate)."""
    from vitax.ops.dequant_matmul import fused_dequant_active
    return (bool(getattr(cfg, "serve_quant_dtype", ""))
            and fused_dequant_active(cfg))


@rule("VTX-R009", "fused-dequant-lowered", "ERROR", ("serve",),
      "with the fused dequant-matmul active the traced serve program must "
      "actually launch the Pallas kernel AND materialize no weight-sized "
      "float tensor sourced from a quantized dtype outside it: a top-level "
      "i8/fp8 -> f32 convert at block size is a dequantized weight round-"
      "tripping through HBM — the fusion silently regressing to the "
      "convert+dot chain (the serve twin of VTX-R008)",
      applies_to=_fused_dequant_cfg)
def check_fused_dequant(program: Program, cfg: Config) -> List[Finding]:
    r = FUSED_DEQUANT
    from vitax.ops.dequant_matmul import DEQUANT_KERNEL_NAME
    eng = program.engine
    jaxpr = eng.trace_bucket_jaxpr(eng.buckets[-1])
    out: List[Finding] = []
    n_launches = jaxpr.count(DEQUANT_KERNEL_NAME)
    if n_launches == 0:
        out.append(_finding(
            r, program,
            f"traced serve program contains no {DEQUANT_KERNEL_NAME} "
            f"pallas_call — the fused dequant-matmul did not enter the "
            f"compiled program",
            kernel=DEQUANT_KERNEL_NAME))
    min_elems = large_param_threshold_bytes(cfg) // 4  # f32 elements
    # the patchify conv kernel is the one quantized leaf no Dense site
    # consumes: it legitimately dequantizes in-graph (XLA fuses the convert
    # into the conv's operand read) and is exempt by its exact shape
    p = cfg.patch_size
    exempt = ((p, p, 3, cfg.embed_dim),)
    for row in hlo.jaxpr_quant_dequant_converts(jaxpr, min_elems, exempt):
        out.append(_finding(
            r, program,
            f"weight-sized dequant outside the fused kernel: "
            f"{row['src_dtype']} -> f32 over {row['shape']} "
            f"({row['numel']:,} elems) at the top level of the serve "
            f"program",
            eqn=row, min_elems=min_elems))
    return out


def _frozen_task(cfg: Config) -> bool:
    """Config-side gate for VTX-R010: scenarios that freeze parameters."""
    return getattr(cfg, "task", "train") in ("probe", "distill")


@rule("VTX-R010", "frozen-params-not-updated", "ERROR", ("train",),
      "a scenario that freezes parameters (--task probe: the backbone; "
      "--task distill: the whole teacher tower) must not give any frozen "
      "leaf optimizer moments — optax.masked drops masked-out positions to "
      "leafless MaskedNodes, so a frozen leaf acquiring a mu/nu slot means "
      "the mask silently stopped covering it and AdamW is stepping a "
      "'frozen' parameter; distill programs must additionally carry the "
      "teacher forward under stop_gradient in the traced jaxpr",
      applies_to=_frozen_task)
def check_frozen_not_updated(program: Program, cfg: Config) -> List[Finding]:
    r = FROZEN_NOT_UPDATED
    out: List[Finding] = []
    if not program.frozen_paths:
        out.append(_finding(
            r, program,
            "frozen-scenario program carries no frozen-path evidence — "
            "build_train_program captures freeze_report() on probe/distill "
            "arms; nothing to audit",
            task=getattr(cfg, "task", "train")))
        return out
    frozen = program.frozen_paths
    for m in program.opt_moment_paths:
        if any(m == f or m.startswith(f + "/") for f in frozen):
            out.append(_finding(
                r, program,
                f"optimizer moment exists for frozen leaf {m!r}: the "
                f"freeze mask does not cover it and AdamW will step it",
                moment_path=m))
    if getattr(cfg, "task", "train") == "distill":
        if not program.jaxpr:
            out.append(_finding(
                r, program,
                "distill arm lowered without a traced-jaxpr artifact — "
                "the teacher's stop_gradient marker cannot be audited"))
        elif "stop_gradient" not in program.jaxpr:
            out.append(_finding(
                r, program,
                "distill step's traced jaxpr contains no stop_gradient — "
                "the teacher tower is not severed from autodiff and "
                "teacher cotangents may be computed"))
    return out


NO_HOST_TRANSFER = RULES[0]
DONATION_HONORED = RULES[1]
COLLECTIVE_DTYPE = RULES[2]
GATHER_OVERLAP = RULES[3]
NO_REPLICATED_LARGE = RULES[4]
SERVE_NO_RECOMPILE = RULES[5]
QUANT_WEIGHTS_RESIDENT = RULES[6]
FUSED_OPTIMIZER = RULES[7]
FUSED_DEQUANT = RULES[8]
FROZEN_NOT_UPDATED = RULES[9]


def rules_for(program: Program) -> List[Rule]:
    return [r for r in RULES if r.applicable(program)]


def run_rules(program: Program) -> Tuple[List[str], List[Finding]]:
    """Run every applicable rule over one program.

    Returns (rule ids run, findings). An empty findings list from a rule
    means the invariant holds in this program."""
    ran, findings = [], []
    for r in rules_for(program):
        ran.append(r.id)
        findings.extend(r.check(program, program.config))
    return ran, findings


# --- program builders (the parallelism arms the CI gate lowers) -------------

# Small geometry, CPU-loweable on the 8-virtual-device mesh. batch_size 64
# keeps B*N above the GSPMD partial-dot threshold (see
# tests/test_gather_overlap.py geometry note) so the arms exercise the real
# weight-gather strategies the rules police.
BASE_GEOMETRY = dict(
    image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
    num_classes=4, batch_size=64, warmup_steps=2,
)

# arm name -> Config overrides on top of BASE_GEOMETRY. dtype defaults to
# bfloat16, so the bf16 comm-cast policy (and with it VTX-R003) is active on
# every fsdp arm; "dp" pins float32 as the no-policy baseline.
TRAIN_ARMS: Dict[str, dict] = {
    "dp": dict(run_without_fsdp=True, dtype="float32"),
    "zero2": dict(reshard_after_forward=False),
    "zero3": dict(gather_overlap="off"),
    "zero3_overlap": dict(gather_overlap="on"),
    "accum": dict(batch_size=128, grad_accum_steps=2),
    "moe": dict(moe_experts=4, gather_overlap="off"),
    # forced fused optimizer (interpret-mode Pallas on CPU) — the arm that
    # activates VTX-R008 and captures the traced-jaxpr artifact
    "fused": dict(gather_overlap="off", fused_optimizer="on"),
    # scenario arms (vitax/programs/registry.py): the probe's masked-frozen
    # backbone and the distill two-tower step, lowered through the unified
    # builder (vitax/programs/builder.py) — the arms that activate VTX-R010
    "probe": dict(task="probe", gather_overlap="off"),
    "distill": dict(task="distill", gather_overlap="off"),
}

SERVE_ARM = "serve"
# quantized serving: same geometry with the params int8-quantized in memory
# (vitax/serve/quant.py quantize_params_for_serve); runs R006 (the AOT
# contract is dtype-blind) plus R007
SERVE_QUANT_ARM = "serve_quant"
# the fp8 weight arm: same machinery with float8_e4m3 leaves — R007's
# residency/arg checks are dtype-keyed, so the arm pins the second
# QUANT_DTYPES slot end to end
SERVE_FP8_ARM = "serve_fp8"
# int8 weights + dynamic activation quant + forced fused dequant-matmul
# (interpret-mode Pallas on CPU) — the serve twin of the "fused" train arm;
# activates VTX-R009 and reads the traced-jaxpr artifact
SERVE_ACTQUANT_ARM = "serve_actquant"
SERVE_ARMS = (SERVE_ARM, SERVE_QUANT_ARM, SERVE_FP8_ARM, SERVE_ACTQUANT_ARM)
ALL_ARMS = tuple(TRAIN_ARMS) + SERVE_ARMS
# the lint.sh / pre-push subset: one train arm covering R001-R005 (the
# overlap arm applies every train rule), the fused arm for R008, the
# scenario arms for R010, plus the serve arms for R006/R007 (all quant
# dtypes) and R009 (forced fused)
FAST_ARMS = ("zero3_overlap", "fused", "probe", "distill") + SERVE_ARMS


def arm_config(arm: str, **overrides) -> Config:
    kw = dict(BASE_GEOMETRY)
    if arm == SERVE_ARM:
        kw.update(serve_max_batch=4)
    elif arm == SERVE_QUANT_ARM:
        kw.update(serve_max_batch=4, serve_quant_dtype="int8")
    elif arm == SERVE_FP8_ARM:
        kw.update(serve_max_batch=4, serve_quant_dtype="float8_e4m3")
    elif arm == SERVE_ACTQUANT_ARM:
        kw.update(serve_max_batch=4, serve_quant_dtype="int8",
                  serve_act_quant="int8", fused_dequant="on")
    else:
        kw.update(TRAIN_ARMS[arm])
    kw.update(overrides)
    return Config(**kw).validate()


def build_train_program(cfg: Config, arm: str = "custom",
                        donate: bool = True) -> Program:
    """Lower the scenario's step program for `cfg` through the builder
    (vitax/programs/builder.py: the program the trainer runs) and capture
    the rule artifacts, among them the freeze-report evidence VTX-R010
    reads on the arms that freeze parameters."""
    from vitax.programs import builder as B
    lowered, n_state_leaves = B.lower_step(cfg, donate=donate)
    frozen_paths, opt_moment_paths = (B.freeze_report(cfg)
                                      if _frozen_task(cfg) else ((), ()))
    # the traced-jaxpr artifact only exists where a rule reads it
    jaxpr = (B.step_jaxpr(cfg)
             if (_fused_active(cfg) or cfg.task == "distill") else "")
    return Program(
        kind="train", arm=arm, config=cfg,
        mlir=lowered.as_text(),
        partitioned_hlo=hlo.capture_partitioned(lowered),
        jaxpr=jaxpr,
        mesh_shape=dict(B.Geometry.from_config(cfg).mesh.shape),
        n_state_leaves=n_state_leaves,
        frozen_paths=frozen_paths,
        opt_moment_paths=opt_moment_paths,
    )


def build_serve_program(cfg: Config, arm: str = SERVE_ARM) -> Program:
    """Build and warm an InferenceEngine over randomly-initialized sharded
    params (the AOT bucket invariants do not depend on the weights)."""
    import jax
    import jax.numpy as jnp

    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import init_sharded_params
    from vitax.serve.engine import InferenceEngine, _build_model

    mesh = build_mesh(cfg)
    # init always uses the plain-Dense model: a QuantDense model cannot
    # init (its act path asserts int8 weights), and the param paths are
    # identical, so the quant-aware engine model binds the same tree
    init_model = _build_model(cfg, mesh, quantized=False)
    model = _build_model(cfg, mesh)
    sample_b = mesh.shape["dp"] * mesh.shape["fsdp"]
    sample = jnp.zeros((sample_b, cfg.image_size, cfg.image_size, 3),
                       jnp.float32)
    params, _ = init_sharded_params(
        lambda rng: init_model.init(rng, sample, True),
        jax.random.key(cfg.seed), cfg, mesh)
    scales, quant_dtype = None, ""
    if getattr(cfg, "serve_quant_dtype", ""):
        # in-memory quantization — the arm exercises the quantized serve
        # program without a checkpoint on disk (random weights: the
        # residency and AOT invariants do not depend on the values)
        from vitax.serve.quant import quantize_params_for_serve
        params, scales = quantize_params_for_serve(
            params, cfg, mesh, dtype=cfg.serve_quant_dtype)
        quant_dtype = cfg.serve_quant_dtype
    engine = InferenceEngine(cfg, mesh, model, params,
                             scales=scales, quant_dtype=quant_dtype)
    engine.warmup()
    return Program(kind="serve", arm=arm, config=cfg,
                   mesh_shape=dict(mesh.shape), engine=engine)


def build_program(arm: str, **overrides) -> Program:
    cfg = arm_config(arm, **overrides)
    if arm in SERVE_ARMS:
        return build_serve_program(cfg, arm=arm)
    return build_train_program(cfg, arm=arm)
