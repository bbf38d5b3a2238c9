"""Parsers over lowered/partitioned XLA programs.

Generalized from the terse-HLO parser that grew inside tools/comm_audit.py
(PRs 2-3) into the shared module every program-invariant rule builds on
(vitax/analysis/rules.py, tools/check_invariants.py; comm_audit now imports
from here).

Two program artifacts, two parsers:

- the **post-`spmd-partitioning` HLO text** (captured via a per-compile
  `xla_dump_to`): collectives with dtype/shape/bytes, while-loop bodies and
  their op inventories, the prefetch-slot overlap verdict, host-transfer ops,
  and the module-header `input_output_alias` donation map. This stage — not
  the final executable — is the backend-independent ground truth: XLA:CPU's
  float normalization rewrites every bf16 collective as f32-wrapped-in-
  converts in the final module, so the final CPU HLO can never show a bf16
  gather no matter what the program asked for.

- the **StableHLO MLIR text** (`lowered.as_text()`): per-argument shardings
  (`mhlo.sharding`) and donation (`tf.aliasing_output`) straight off the
  `@main` signature — available without compiling, and the only artifact
  that still names which arguments are which.
"""

from __future__ import annotations

import collections
import glob
import math
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

# `= bf16[2,32,128]{...} all-gather(` — dtype, shape, op from a partitioned-HLO
# instruction line. `-start` variants cover async collectives; `-done` halves
# carry no shape of their own and are skipped.
COLLECTIVE_RE = re.compile(
    r"= (\w+)\[([\d,]*)\][^ ]* "
    r"((?:all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?)\(")

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f16": 2, "bf16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8,
}


def collect_collectives(hlo_text: str) -> List[dict]:
    """Parse a partitioned-HLO module into aggregated collective rows.

    Returns a list of dicts {op, dtype, shape, count, numel, bytes} where
    `bytes` is count * output-shape bytes. Output-shape bytes is the honest
    per-step proxy for wire traffic: an all-gather's output is the gathered
    tensor every participant materializes, an all-reduce/reduce-scatter's
    output is what the reduction moves. (Exact wire bytes carry an extra
    (n-1)/n ring factor that is identical across policies and so cancels in
    every ratio this parser is used for.)
    """
    rows = collections.Counter()
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, shape_s, op = m.groups()
        shape = tuple(int(d) for d in shape_s.split(",") if d)
        rows[(op.replace("-start", ""), dtype, shape)] += 1
    out = []
    for (op, dtype, shape), count in sorted(rows.items()):
        numel = 1
        for d in shape:
            numel *= d
        out.append({
            "op": op, "dtype": dtype, "shape": list(shape), "count": count,
            "numel": numel,
            "bytes": count * numel * DTYPE_BYTES.get(dtype, 4),
        })
    return out


def summarize(rows: List[dict]) -> dict:
    """Totals per op kind, split by element type."""
    totals: dict = {}
    for r in rows:
        slot = totals.setdefault(r["op"], {"count": 0, "bytes": 0, "by_dtype": {}})
        slot["count"] += r["count"]
        slot["bytes"] += r["bytes"]
        d = slot["by_dtype"].setdefault(r["dtype"], {"count": 0, "bytes": 0})
        d["count"] += r["count"]
        d["bytes"] += r["bytes"]
    return totals


def gather_bytes(rows: List[dict], dtype: Optional[str] = None,
                 min_numel: int = 0) -> int:
    """Total all-gather bytes, optionally filtered by dtype / operand size."""
    return sum(r["bytes"] for r in rows
               if r["op"] == "all-gather"
               and (dtype is None or r["dtype"] == dtype)
               and r["numel"] >= min_numel)


def reduce_bytes(rows: List[dict], dtype: Optional[str] = None,
                 min_numel: int = 0) -> int:
    """Total reduce-scatter + all-reduce bytes, same filters as gather_bytes."""
    return sum(r["bytes"] for r in rows
               if r["op"] in ("reduce-scatter", "all-reduce")
               and (dtype is None or r["dtype"] == dtype)
               and r["numel"] >= min_numel)


# ops a value may pass through on its way to the while body's ROOT tuple and
# still count as "sitting on the carry": layout/dtype plumbing, not compute.
# A gather whose result reaches ROOT only through these feeds the next
# iteration's prefetch slot; a gather consumed by a dot/fusion first is a
# use-site gather.
TRIVIAL_OPS = frozenset({
    "copy", "convert", "bitcast", "bitcast-convert", "reshape", "transpose",
    "get-tuple-element", "tuple", "optimization-barrier", "all-gather-done",
})

# `  ROOT name = type op(a, b), attrs...` — name, op, operand list of one
# instruction line. Handles both dump styles: the verbose one (`%name = f32[2]
# add(%a, %b)`) and the terse one XLA emits for pass dumps (`add.3 = f32[2]
# add(p.1, p.2)`); the type may itself be a parenthesised tuple, so the op is
# "the first bare word directly followed by ( after the =".
INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\((.*)$")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
# `bf16[5120,1280]` in an instruction's type, tuple or not
_ARRAY_TYPE_RE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
# the synchronous reduces a scan body can hold: the ops themselves (their
# `-start` halves are the asynchronous form) and the TPU compiler's fused
# all-reduce + dynamic-slice, `fusion(...), kind=kCustom,
# calls=%all-reduce-scatter.N`
SYNC_REDUCE_OPS = frozenset({"all-reduce", "reduce-scatter"})
_REDUCE_FUSION_RE = re.compile(r"calls=%?all-reduce-scatter")
RING_PERMUTE_OPS = frozenset({"collective-permute", "collective-permute-start"})


def split_computations(hlo_text: str) -> Dict[str, List[str]]:
    """Split an HLO module dump into {computation_name: [instruction lines]}.

    Computation headers sit at column 0 and end with `{`: terse style is
    `region_0.574_spmd {` / `ENTRY main.1234_spmd {`, verbose style is
    `%fused (p: f32[2]) -> f32[2] {`. Instruction lines are indented and
    contain `=`, which the header pattern excludes."""
    comps: Dict[str, List[str]] = {}
    name, lines = None, []
    header = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\b[^=]*{\s*$")
    for line in hlo_text.splitlines():
        if name is None:
            # a tuple type of more than five elements prints `/*index=5*/`
            # marks, whose `=` is no instruction's
            m = header.match(_COMMENT_RE.sub("", line))
            if m:
                name, lines = m.group(1), []
        elif line.startswith("}"):
            comps[name] = lines
            name = None
        else:
            lines.append(line)
    return comps


def while_bodies(hlo_text: str) -> List[str]:
    """Names of every while-loop body computation, in program order.

    First-occurrence order = program order of the while ops: the forward
    scan's body comes before the backward's, so consumers can key on the
    first entry for forward-schedule invariants."""
    return list(dict.fromkeys(re.findall(r"body=%?([\w.\-]+)", hlo_text)))


def parse_instructions(lines: List[str]) -> Tuple[Dict[str, Tuple[str, List[str]]], Optional[str]]:
    """Parse one computation's instruction lines into
    ({name: (op, [operand names])}, root_name)."""
    instrs: Dict[str, Tuple[str, List[str]]] = {}
    root = None
    for line in lines:
        m = INSTR_RE.match(line)
        if not m:
            continue
        iname, op, rest = m.groups()
        # operand names: %refs up to the closing paren of the operand
        # list (metadata/attrs after it may hold %refs to computations)
        depth, end = 1, len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        instrs[iname] = (op, _OPERAND_RE.findall(rest[:end]))
        if line.lstrip().startswith("ROOT"):
            root = iname
    return instrs, root


def instruction_types(lines: List[str]) -> Dict[str, Tuple[str, str]]:
    """{name: (type text, attribute text)} of one computation's instruction
    lines: what stands between `=` and the op, and what follows the operand
    list's opening parenthesis (operands, then `kind=`, `calls=` ...)."""
    out: Dict[str, Tuple[str, str]] = {}
    for line in lines:
        line = _COMMENT_RE.sub("", line)
        m = INSTR_RE.match(line)
        if m:
            head = line[:m.start(2)]
            out[m.group(1)] = (head[head.index("=") + 1:], m.group(3))
    return out


_CALL_RE = re.compile(r"\scall\(.*\bto_apply=%?([\w.\-]+)")


def _with_called(comps: Dict[str, List[str]],
                 lines: List[str]) -> List[List[str]]:
    """A computation's lines and, after them, those of every computation a
    `call` in it applies, transitively."""
    out, seen, todo = [], set(), [lines]
    while todo:
        cur = todo.pop()
        out.append(cur)
        for line in cur:
            m = _CALL_RE.search(line)
            if m and m.group(1) not in seen and m.group(1) in comps:
                seen.add(m.group(1))
                todo.append(comps[m.group(1)])
    return out


def while_body_op_inventory(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per while-loop body: {op name: count} over its instructions — the
    cheap structural fingerprint of what a scan iteration executes."""
    comps = split_computations(hlo_text)
    out: Dict[str, Dict[str, int]] = {}
    for body in while_bodies(hlo_text):
        lines = comps.get(body)
        if lines is None:
            continue
        instrs, _ = parse_instructions(lines)
        counter: collections.Counter = collections.Counter(
            op for op, _ in instrs.values())
        out[body] = dict(counter)
    return out


def matrix_numel(type_text: str) -> int:
    """Elements of the largest MATRIX in an instruction's type: an array with
    at least two dimensions above 1 (a bias or a norm scale, stacked or not,
    is a vector and counts 0)."""
    best = 0
    for _, dims in _ARRAY_TYPE_RE.findall(type_text):
        sizes = [int(d) for d in dims.split(",") if d]
        if sum(1 for d in sizes if d > 1) >= 2:
            best = max(best, math.prod(sizes))
    return best


def _is_sync_block_reduce(name: str, op: str, operands: List[str],
                          types: Dict[str, Tuple[str, str]],
                          min_numel: int) -> bool:
    """Whether one instruction is a synchronous reduce (SYNC_REDUCE_OPS, or
    the fused all-reduce-scatter) of a matrix of at least `min_numel`
    elements: the larger of its own type's and its operands' (the fused
    form's result is the shard, its operand the whole matrix)."""
    own_type, attrs = types[name]
    if not (op in SYNC_REDUCE_OPS
            or (op == "fusion" and _REDUCE_FUSION_RE.search(attrs))):
        return False
    largest = max([matrix_numel(own_type)]
                  + [matrix_numel(types[o][0]) for o in operands if o in types])
    return largest >= max(min_numel, 1)   # a vector (0) never counts


def overlap_verdict(hlo_text: str, min_reduce_numel: int = 0) -> dict:
    """Structural check of the --gather_overlap schedule.

    Locates every while-loop body in the partitioned module and, per body,
    counts its all-gathers and how many of them sit ON THE PREFETCH SLOT:
    their result reaches the body's ROOT tuple (the carry for the next
    iteration) through nothing but layout/dtype plumbing (TRIVIAL_OPS).
    Use-site gathers — what the plain ZeRO-3 scan has — are consumed by a
    convolution/dot/fusion before any carry, so they never qualify.

    The backward's half (the gradient ring, sharding.ring_weight_grad): per
    body, `sync_block_reduces` counts the synchronous reduces (SYNC_REDUCE_OPS
    and the fused all-reduce-scatter) of a matrix of at least
    `min_reduce_numel` elements; the caller passes a block matrix's shard, so
    a bias or a norm scale never counts. `ring_permutes` counts the body's
    collective-permutes (`-start` where the compiler made a pair of it). The
    plain schedule has one such reduce a block matrix and no permute; the
    ring fsdp - 1 permutes a matrix and no reduce.

    Returns {gathers_in_scan_body, prefetch_slot_gathers,
    per_iteration_gather_count: {body: count}, prefetch_slot_by_body,
    sync_block_reduces, sync_block_reduces_by_body, ring_permutes,
    ring_permutes_by_body} — the `--json` overlap verdict the tier-1 suite
    asserts on (gather count unchanged between off and on; prefetch-slot
    gathers appear only under on)."""
    comps = split_computations(hlo_text)
    bodies = while_bodies(hlo_text)

    per_body = {}
    slot_by_body = {}
    reduces_by_body = {}
    permutes_by_body = {}
    for body in bodies:
        lines = comps.get(body)
        if lines is None:
            continue
        instrs, root = parse_instructions(lines)
        reduces_by_body[body] = permutes_by_body[body] = 0
        # the body and what it `call`s: before the backend inlines it, a
        # shard_map's body is a computation of its own
        for called in _with_called(comps, lines):
            c_instrs, _ = parse_instructions(called)
            types = instruction_types(called)
            reduces_by_body[body] += sum(
                _is_sync_block_reduce(n, op, operands, types, min_reduce_numel)
                for n, (op, operands) in c_instrs.items())
            permutes_by_body[body] += sum(
                1 for op, _ in c_instrs.values() if op in RING_PERMUTE_OPS)
        gathers = {n for n, (op, _) in instrs.items()
                   if op in ("all-gather", "all-gather-start")}
        per_body[body] = len(gathers)
        slot_by_body[body] = 0
        if root is None or not gathers:
            continue
        on_slot = set()
        seen = set()
        frontier = [root]
        while frontier:
            n = frontier.pop()
            if n in seen or n not in instrs:
                continue
            seen.add(n)
            op, operands = instrs[n]
            if op in ("all-gather", "all-gather-start"):
                on_slot.add(n)
                continue  # the gather IS the slot value; don't look past it
            if n == root or op in TRIVIAL_OPS:
                frontier.extend(operands)
        slot_by_body[body] = len(on_slot)

    return {
        "gathers_in_scan_body": sum(per_body.values()),
        "prefetch_slot_gathers": sum(slot_by_body.values()),
        "per_iteration_gather_count": per_body,
        "prefetch_slot_by_body": slot_by_body,
        "sync_block_reduces": sum(reduces_by_body.values()),
        "sync_block_reduces_by_body": reduces_by_body,
        "ring_permutes": sum(permutes_by_body.values()),
        "ring_permutes_by_body": permutes_by_body,
    }


# --- host transfers ---------------------------------------------------------

# custom-call targets that move data to (or synchronize with) the host: the
# Python callback family (io_callback / pure_callback / jax.debug.print all
# lower to these) on CPU/GPU; outfeed/infeed are the TPU-side carriers.
_HOST_CALLBACK_TARGET_RE = re.compile(
    r'custom_call_target="([^"]*callback[^"]*)"')
_HOST_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(outfeed|infeed|send|send-done|recv|recv-done)\(")
_MLIR_HOST_RE = re.compile(
    r"stablehlo\.(outfeed|infeed|send|recv)\b|"
    r"stablehlo\.custom_call\s+@(\S*callback\S*)\(")


def host_transfer_ops(hlo_text: str) -> List[dict]:
    """Every host-transfer op in a partitioned-HLO module: outfeed / infeed /
    send / recv instructions and custom-calls into the host-callback family.

    Returns [{op, detail, line}] where `line` is the stripped instruction
    text (truncated) for the finding message."""
    out = []
    for i, line in enumerate(hlo_text.splitlines(), 1):
        m = _HOST_OP_RE.search(line)
        if m:
            out.append({"op": m.group(1), "detail": m.group(1),
                        "line": line.strip()[:160]})
            continue
        m = _HOST_CALLBACK_TARGET_RE.search(line)
        if m:
            out.append({"op": "custom-call", "detail": m.group(1),
                        "line": line.strip()[:160]})
    return out


def mlir_host_transfer_ops(mlir_text: str) -> List[dict]:
    """Host-transfer ops in a StableHLO module (the pre-compile view — works
    on single-device programs the partitioner never touches)."""
    out = []
    for line in mlir_text.splitlines():
        m = _MLIR_HOST_RE.search(line)
        if m:
            op = m.group(1) or "custom_call"
            out.append({"op": op, "detail": m.group(2) or m.group(1),
                        "line": line.strip()[:160]})
    return out


# --- donation (input_output_alias) ------------------------------------------

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(may-alias|must-alias)\)")


def input_output_aliases(hlo_text: str) -> List[dict]:
    """Parse the module-header `input_output_alias={ {out}: (param, {idx},
    kind), ... }` donation map from a partitioned-HLO dump.

    Returns [{output_index, parameter, kind}] — one entry per aliased
    (donated and actually reused) buffer. An empty list under donate_argnums
    means XLA dropped every donation (shape/dtype mismatch or a backend that
    refuses aliasing) — exactly the regression the donation rule exists to
    catch."""
    header = hlo_text.splitlines()[0] if hlo_text else ""
    key = "input_output_alias={"
    start = header.find(key)
    if start < 0:
        return []
    # the alias map nests braces ({ {0}: (0, {}, may-alias), ... }): scan to
    # the balancing close instead of regexing across nesting
    i = start + len(key)
    depth, j = 1, i
    while j < len(header) and depth:
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
        j += 1
    out = []
    for om in _ALIAS_ENTRY_RE.finditer(header[i:j - 1]):
        out.append({
            "output_index": tuple(int(x) for x in om.group(1).split(",") if x.strip()),
            "parameter": int(om.group(2)),
            "kind": om.group(3),
        })
    return out


# --- MLIR @main argument table ----------------------------------------------

# dtype tail: lowercase+digits (f32, i8, bf16), an optional uppercase suffix
# for the fp8 family (f8E4M3, f8E5M2, f8E4M3FN), or the braceless i1
_MLIR_TYPE_RE = re.compile(
    r"tensor<([x\d]*?)(?:x)?([a-z]+\d+(?:[A-Z][A-Z0-9]*)?|i1)>")
_MLIR_SHARDING_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_MLIR_DONOR_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
# Shardy's form of the same (jax 0.9 lowers with it): the argument carries
# `sdy.sharding = #sdy.sharding<@mesh, [{}, {"fsdp"}]>` (one brace group a
# dimension, the mesh axes that shard it inside) and the module declares
# `sdy.mesh @mesh = <["dp"=1, "fsdp"=8, ...]>`
_SDY_SHARDING_RE = re.compile(r"sdy\.sharding\s*=\s*#sdy\.sharding<@\w+,\s*"
                              r"(\[.*?\])\s*(?:,[^>]*)?>")
_SDY_MESH_AXIS_RE = re.compile(r'"(\w+)"=(\d+)')

_MLIR_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "f8E4M3": 1, "f8E5M2": 1, "f8E4M3FN": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
}


def mlir_main_args(mlir_text: str) -> List[dict]:
    """Argument table of the StableHLO `@main` signature.

    Returns [{index, dtype, shape, numel, bytes, sharding, donated_to}] in
    argument order. `sharding` is the raw OpSharding string ("{replicated}",
    "{devices=[1,8]<=[8]}", ...), Shardy's per-dimension axis lists
    (`[{}, {"fsdp"}]`, axes of one device left out), or None when
    unannotated; `donated_to` is
    the flat output index the buffer is donated to (`tf.aliasing_output`)
    or None for non-donated args. This is the only artifact where donation
    and sharding are still attached to *arguments* rather than anonymous
    parameter numbers."""
    m = re.search(r"func\.func\s+public\s+@main\s*\((.*?)\)\s*->", mlir_text,
                  re.DOTALL)
    if not m:
        return []
    # split the signature on argument boundaries: everything between
    # `%argN:` and the next `%argM:` (type + attr dict) belongs to arg N —
    # sidesteps brace-matching the attr dict, whose sharding strings nest
    # braces inside quotes
    parts = re.split(r"%arg(\d+)\s*:", m.group(1))
    # an axis of one device shards nothing: left out of a Shardy sharding,
    # so that the string names only what really splits the array
    mesh = re.search(r"sdy\.mesh\s+@\w+\s*=\s*<\[(.*?)\]>", mlir_text)
    unit_axes = re.compile("|".join(
        rf'"{a}",?\s*' for a, n in _SDY_MESH_AXIS_RE.findall(
            mesh.group(1) if mesh else "") if int(n) == 1) or "$^")
    out = []
    for i in range(1, len(parts) - 1, 2):
        idx = int(parts[i])
        body = parts[i + 1]
        tm = _MLIR_TYPE_RE.search(body)
        shape: Tuple[int, ...] = ()
        dtype = "?"
        if tm:
            shape = tuple(int(d) for d in tm.group(1).split("x") if d)
            dtype = tm.group(2)
        sm = _MLIR_SHARDING_RE.search(body)
        sharding = sm.group(1) if sm else None
        sdy = _SDY_SHARDING_RE.search(body)
        if sharding is None and sdy:
            sharding = unit_axes.sub("", sdy.group(1))
        dm = _MLIR_DONOR_RE.search(body)
        numel = 1
        for d in shape:
            numel *= d
        out.append({
            "index": idx, "dtype": dtype, "shape": list(shape),
            "numel": numel,
            "bytes": numel * _MLIR_DTYPE_BYTES.get(dtype, 4),
            "sharding": sharding,
            "donated_to": int(dm.group(1)) if dm else None,
        })
    return out


def sharding_is_replicated(sharding: Optional[str]) -> bool:
    """Whether an OpSharding string places the value on every device whole.

    None (unannotated) counts as replicated: GSPMD's default for an
    unconstrained input is replication, which is precisely the silent
    regression the large-param rule hunts."""
    if sharding is None:
        return True
    s = sharding.strip()
    if s.startswith("["):   # Shardy's per-dimension axis lists
        return '"' not in s
    if "replicated" in s or "maximal" in s:
        return "devices=" not in s
    # "{devices=[1,1,8]<=[8] last_tile_dim_replicate}" with ALL non-trailing
    # tile dims 1 is also full replication
    m = re.search(r"devices=\[([\d,]+)\]", s)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        if "last_tile_dim_replicate" in s:
            dims = dims[:-1]
        return all(d == 1 for d in dims)
    return False


# --- program capture --------------------------------------------------------


def capture_partitioned(lowered, module_hint: str = "train_step") -> str:
    """Compile a `jax.stages.Lowered` with a per-compile dump and return the
    HLO module text right after the SPMD partitioner.

    Why this stage and not the final executable: backend simplification
    passes may rewrite collective element types after SPMD partitioning.
    XLA:CPU's float normalization in particular rewrites every bf16
    collective as an f32 collective wrapped in converts, so the final CPU
    HLO can never show a bf16 gather no matter what the program asked for.
    The post-`spmd-partitioning` module is the backend-independent ground
    truth for what dtype each collective moves.

    Returns "" for single-device programs (the partitioner never runs, so
    there is no dump — and no collectives to audit either)."""
    dump_dir = tempfile.mkdtemp(prefix="vitax_analysis_hlo_")
    try:
        lowered.compile(
            compiler_options={"xla_dump_to": dump_dir,
                              "xla_dump_hlo_pass_re": ".*partitioning"})
        dumps = glob.glob(os.path.join(dump_dir, "*after_spmd-partitioning*"))
        preferred = [f for f in dumps if module_hint in os.path.basename(f)]
        if not preferred:  # fall back to the largest module (the step)
            preferred = sorted(dumps, key=os.path.getsize)[-1:]
        if not preferred:
            import jax
            if len(jax.devices()) == 1:  # vtx: ignore[VTX104] analysis tool probing whatever backend is live
                return ""
            raise RuntimeError(
                f"no post-partitioning HLO dump appeared in {dump_dir}; "
                "this XLA build may not honour per-compile xla_dump_to")
        with open(preferred[0], encoding="utf-8") as f:
            return f.read()
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)


def lower_train_step(cfg, max_iteration: int = 10_000, donate: bool = True):
    """AOT-lower the step program for `cfg` on the current backend: the
    builder's `lower_step` (vitax/programs/builder.py), i.e. the program the
    trainer runs. Returns (lowered, n_state_leaves)."""
    from vitax.programs.builder import lower_step
    return lower_step(cfg, max_iteration, donate)


def train_step_jaxpr(cfg, max_iteration: int = 10_000) -> str:
    """Trace the step program for `cfg` and return its closed jaxpr as text
    (the builder's `step_jaxpr`).

    The jaxpr — not StableHLO — is the artifact the fused-optimizer rule
    (VTX-R008) reads: Pallas interpret mode (the only lowering available
    off-TPU in this jax) leaves no custom-call marker in MLIR, but every
    `pallas_call` jaxpr equation prints the kernel function's name, and the
    surrounding equations still show any param-sized post-clip temporaries
    the fusion was supposed to eliminate."""
    from vitax.programs.builder import step_jaxpr
    return step_jaxpr(cfg, max_iteration)


# `c:f32[256,96] = sqrt b` — binder dtype/shape and primitive name of a jaxpr
# equation, for the ops VTX-R008 bans at param size outside the fused kernel
JAXPR_EQN_RE = re.compile(r":f32\[([\d,]*)\] = (sqrt|select_n)\b")


def strip_bracketed(text: str, marker: str) -> str:
    """Remove every `marker[...]` block (bracket-matched, nests fine) from
    jaxpr text — used to drop `pallas_call[...]` equation params, whose
    embedded kernel jaxpr would otherwise alias the ops the fused-optimizer
    rule scans for OUTSIDE the kernel."""
    out = []
    i = 0
    while True:
        j = text.find(marker + "[", i)
        if j < 0:
            out.append(text[i:])
            return "".join(out)
        out.append(text[i:j + len(marker)])
        k = j + len(marker)
        depth = 0
        while k < len(text):
            if text[k] == "[":
                depth += 1
            elif text[k] == "]":
                depth -= 1
                if depth == 0:
                    break
            k += 1
        i = k + 1


def jaxpr_oversized_eqns(jaxpr_text: str, min_elems: int) -> List[dict]:
    """Equations (sqrt / select_n, the optax adamw + clip tell-tales) whose
    f32 output has >= min_elems elements, AFTER stripping pallas_call params.
    Returns rows {op, shape, numel} for the rule's finding details."""
    stripped = strip_bracketed(jaxpr_text, "pallas_call")
    rows = []
    for m in JAXPR_EQN_RE.finditer(stripped):
        dims, op = m.group(1), m.group(2)
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        if numel >= min_elems:
            rows.append({"op": op, "shape": dims, "numel": numel})
    return rows


# eqn params that embed sub-jaxprs with their OWN variable namespaces: strip
# them before building a var -> dtype map, or an inner binder reusing an
# outer name would mislabel operands (the scan body restarts at `a`)
JAXPR_SUBJAXPR_MARKERS = (
    "pallas_call", "scan", "while", "cond", "remat2",
    "custom_vjp_call_jaxpr", "custom_vjp_call", "custom_jvp_call",
    "pjit", "shard_map")

# `a:i8[2,32,96]` — any binder (lambda header or eqn output), dtype + dims
_JAXPR_BINDER_RE = re.compile(r"(\w+):([a-z][a-z0-9_]*)\[([\d,]*)\]")
# `c:f32[2,32,96] = convert_element_type[new_dtype=float32 ...] a`
_JAXPR_CONVERT_RE = re.compile(
    r"\w+:f32\[([\d,]*)\] = convert_element_type\[[^\]]*\]\s+(\w+)")


def jaxpr_quant_dequant_converts(jaxpr_text: str, min_elems: int,
                                 exempt_shapes=()) -> List[dict]:
    """Weight-sized dequantizations OUTSIDE the fused kernel: f32
    `convert_element_type` equations whose operand is a quantized-dtype var
    (i8 / f8_*; u8 is excluded — uint8 images legitimately convert) with
    >= min_elems elements, after stripping every sub-jaxpr body. The
    VTX-R009 tell-tale: a fused serve program dequantizes weight blocks only
    inside pallas_call, so any such convert at the top level is a weight
    tensor round-tripping through HBM in float. `exempt_shapes` (dim tuples)
    skips the sites allowed to dequant in-graph — the patchify conv kernel,
    which no Dense-site kernel consumes. Returns rows {src_dtype, shape,
    numel} for the rule's finding details."""
    text = jaxpr_text
    for marker in JAXPR_SUBJAXPR_MARKERS:
        text = strip_bracketed(text, marker)
    dtypes = {}
    for m in _JAXPR_BINDER_RE.finditer(text):
        dtypes.setdefault(m.group(1), m.group(2))
    exempt = {tuple(s) for s in exempt_shapes}
    rows = []
    for m in _JAXPR_CONVERT_RE.finditer(text):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        src = dtypes.get(m.group(2), "")
        if not src.startswith(("i8", "f8")):
            continue
        numel = 1
        for d in dims:
            numel *= d
        if numel >= min_elems and dims not in exempt:
            rows.append({"src_dtype": src, "shape": list(dims),
                         "numel": numel})
    return rows


def partitioned_hlo_text(cfg, max_iteration: int = 10_000) -> str:
    """AOT-lower the train step for `cfg` and return the post-partitioning
    HLO module text (the tools/comm_audit.py entry point, kept here so the
    audit and the invariant verifier share one lowering path)."""
    lowered, _ = lower_train_step(cfg, max_iteration=max_iteration)
    return capture_partitioned(lowered)
