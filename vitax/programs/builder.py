"""The program builder: one way to assemble a program from a `Config`.

`Geometry.assemble(cfg, ...)` is the only place that writes out

    mesh -> kernels -> model (both activation anchors) ->
    the scenario's optimizer -> train state (abstract or live) -> specs

and `build_program(task, geom)` the only place that turns such a geometry
into a jitted program. Everything that needs a program goes through the two:

- the training loop (vitax/train/loop.py) assembles with the live loader's
  `max_iteration` and `materialize=resume_epoch <= 0`, then builds its step,
  eval and optimizer-probe programs;
- the analysis arms and AOT surfaces (vitax/analysis/hlo.py, rules.py,
  `lower_step` / `step_jaxpr` / `freeze_report` below, chip_smoke.py) use
  `Geometry.from_config(cfg)`: the memoized `assemble(materialize=False)`,
  so an arm's lower + jaxpr + freeze-report probes share one traced stack;
- tools that compile for a described topology (tools/aot_topology.py) pass
  `devices=` and `force_tpu_kernels=`;
- the serve engine (vitax/serve/engine.py) takes the model half alone,
  `build_model_for(cfg, mesh, ...)`, which `assemble` itself calls: the one
  door for `quant_matmul` and the anchors; `build_engine(cfg, ...)` is the
  registry's engine constructor (scenario validation before checkpoint IO).

The activation anchors are sharding rules and live with them
(vitax/parallel/sharding.py). The scenario registry (programs/registry.py)
names which tasks each --task may build; unknown combinations fail here with
the scenario's program set. The benchmark's generators assemble here too
(since PR 31); tests/test_assembly.py holds this module to the written-out
form, text for text, and the generators to this module.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from vitax.config import Config
from vitax.models import build_model
from vitax.models.decoder import build_decoder
from vitax.parallel.mesh import Mesh, batch_pspec, build_mesh
from vitax.parallel.rules import _leaf_path_names
from vitax.parallel.sharding import (moe_dispatch_sharding, shardings_of,
                                     token_sharding)
from vitax.programs.kernels import choose_kernels
from vitax.programs.registry import Scenario, get_scenario
from vitax.programs.workloads import load_teacher_params, make_distill_step
from vitax.train.state import make_train_state
from vitax.train.step import make_eval_step, make_train_step

PyTree = Any

# program kinds build_program understands (each scenario declares a subset)
PROGRAM_KINDS = ("train", "eval", "distill", "serve_bucket")


def build_model_for(cfg: Config, mesh: Mesh, force_tpu_kernels: bool = False,
                    quant_matmul: Optional[Callable] = None):
    """The model every program runs: the kernels chosen for this config and
    mesh (vitax/programs/kernels.py: `choose_kernels`), and BOTH activation
    anchors (token sharding on any multi-device mesh, the MoE dispatch
    sharding iff the model has experts). `force_tpu_kernels` selects the TPU
    kernels off the TPU (a compile for a described topology; interpret mode
    on the CPU); `quant_matmul` (serving only) swaps every Dense site for
    QuantDense. `cfg.model_family` picks the module: the ViT, or the token
    decoder (vitax/models/decoder.py)."""
    kernels = choose_kernels(cfg, mesh, force_tpu_kernels)
    if cfg.decoder:
        assert quant_matmul is None, "the decoder has no quantized arm"
        return build_decoder(cfg, kernels=kernels,
                             token_sharding=token_sharding(cfg, mesh))
    return build_model(
        cfg,
        attention_impl=kernels.attention,
        token_sharding=token_sharding(cfg, mesh),
        moe_dispatch_sharding=moe_dispatch_sharding(cfg, mesh),
        quant_matmul=quant_matmul)


@dataclasses.dataclass
class Geometry:
    """Everything a program is built against: the resolved mesh / model /
    optimizer / spec stack for one Config. Made by `Geometry.assemble`;
    built programs are cached on the geometry they were built against."""
    cfg: Config
    mesh: Any
    model: Any
    tx: Any
    schedule: Any
    state_specs: PyTree
    abstract_state: Optional[PyTree] = None   # ShapeDtypeStruct TrainState
    # what assemble made: the live TrainState under materialize=True, else
    # `abstract_state` itself. A caller that keeps the geometry takes it
    # out (a train step donates the state it is given)
    state: Optional[PyTree] = None
    max_iteration: int = 10_000
    _programs: Dict[Tuple, Any] = dataclasses.field(default_factory=dict)

    @property
    def scenario(self) -> Scenario:
        return get_scenario(self.cfg.task)

    @classmethod
    def assemble(cls, cfg: Config, max_iteration: int = 10_000,
                 devices: Optional[Sequence[jax.Device]] = None,
                 materialize: bool = False,
                 rng: Optional[jax.Array] = None,
                 force_tpu_kernels: bool = False) -> "Geometry":
        """The one assembly of a program's stack from a `Config`.

        max_iteration      length of the lr schedule (the loop: from the
                           live loader)
        devices            the mesh's devices (default: all attached; a
                           described topology's, or a subset)
        materialize, rng   True: `state` is born sharded on the devices from
                           `rng` (default `key(cfg.seed)`); False: `state` is
                           the abstract state, costing no device memory (a
                           restore target, an AOT lowering)
        force_tpu_kernels  see `build_model_for`

        The model is traced once for the abstract state; a live state's
        abstract twin is read off its arrays, not traced again."""
        mesh = build_mesh(cfg, devices)
        model = build_model_for(cfg, mesh, force_tpu_kernels)
        tx, schedule = get_scenario(cfg.task).make_optimizer(
            cfg, max_iteration)
        state, sspecs, _ = make_train_state(
            cfg, model, tx, mesh,
            jax.random.key(cfg.seed) if rng is None else rng,
            materialize=materialize)
        abstract = state if not materialize else jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            state, shardings_of(mesh, sspecs))
        return cls(cfg=cfg, mesh=mesh, model=model, tx=tx, schedule=schedule,
                   state_specs=sspecs, abstract_state=abstract, state=state,
                   max_iteration=max_iteration)

    @classmethod
    def from_config(cls, cfg: Config, max_iteration: int = 10_000) -> "Geometry":
        """`assemble(cfg, max_iteration)` (abstract state), memoized per
        (cfg, max_iteration) so one arm's several probes trace the stack
        once and share its built programs."""
        key = (dataclasses.astuple(cfg), max_iteration)
        if key not in _GEOMETRY_CACHE:
            _GEOMETRY_CACHE[key] = cls.assemble(cfg, max_iteration)
        return _GEOMETRY_CACHE[key]


# from_config's geometries — Config is a flat dataclass of scalars/strings,
# so astuple is hashable
_GEOMETRY_CACHE: Dict[Tuple, Geometry] = {}


def build_program(task: str, geom: Geometry, donate: bool = True,
                  bucket: Optional[int] = None, engine=None):
    """Build (or fetch from the geometry's cache) one program.

    task        one of PROGRAM_KINDS, and a member of the scenario's declared
                program set (registry.py) — the registry is the contract for
                what each --task may assemble
    donate      train/distill only: donate the state buffers (production);
                False builds the analysis negative arm
    bucket      serve_bucket only: the batch bucket to lower
    engine      serve_bucket only: the InferenceEngine holding the params
                (serve programs are bound to concrete weights, not abstract
                geometry — build one with build_engine; never cached here)
    """
    scenario = geom.scenario
    if task not in PROGRAM_KINDS:
        raise ValueError(
            f"unknown program kind {task!r}; builder knows {PROGRAM_KINDS}")
    if task not in scenario.programs:
        raise ValueError(
            f"--task {scenario.name} does not build {task!r} programs "
            f"(declared set: {scenario.programs}; vitax/programs/registry.py)")

    if task == "serve_bucket":
        assert engine is not None and bucket is not None, (
            "serve_bucket programs are built on an InferenceEngine: pass "
            "engine=build_engine(cfg, ...) and bucket=<batch size>")
        lowered, _ = engine._lower_bucket(bucket)
        return lowered

    key = (task, donate)
    if key in geom._programs:
        return geom._programs[key]

    cfg, mesh, model = geom.cfg, geom.mesh, geom.model
    if task == "train":
        program = make_train_step(cfg, model, geom.tx, mesh,
                                  geom.state_specs, donate=donate,
                                  schedule=geom.schedule)
    elif task == "eval":
        program = make_eval_step(cfg, model, mesh, geom.state_specs)
    else:  # distill
        if cfg.teacher_npz:
            teacher = load_teacher_params(cfg, mesh)
        else:
            # no file: lower against the ABSTRACT teacher (analysis arms,
            # AOT probes)
            assert geom.abstract_state is not None, (
                "--task distill needs --teacher_npz to build a runnable "
                "program (abstract lowering needs Geometry.assemble)")
            teacher = geom.abstract_state.params
        program = make_distill_step(cfg, model, geom.tx, mesh,
                                    geom.state_specs, teacher,
                                    donate=donate, schedule=geom.schedule)

    geom._programs[key] = program
    return program


def build_engine(cfg: Config, npz: str = "", epoch: Optional[int] = None):
    """The registry's serving-engine constructor: scenario-checked, then the
    engine source is picked exactly like vitax.serve.__main__ historically
    did — a consolidated npz export (quantized exports load their int8
    leaves as int8, the arbiter's warm-on-borrowed-host path) or the latest/
    requested Orbax epoch checkpoint."""
    scenario = get_scenario(cfg.task)
    assert "serve_bucket" in scenario.programs, (
        f"--task {scenario.name} declares no serving programs "
        f"(vitax/programs/registry.py)")
    from vitax.serve.engine import InferenceEngine
    if npz:
        return InferenceEngine.from_npz(cfg, npz)
    return InferenceEngine.from_checkpoint(cfg, cfg.ckpt_dir, epoch)


# --- AOT / analysis surfaces -------------------------------------------------
# The scenario's step program lowered against abstract arguments: what the
# invariant arms (vitax/analysis/), tools/comm_audit.py and chip_smoke.py read.


def abstract_batch(cfg: Config, mesh: Mesh) -> Dict[str, jax.ShapeDtypeStruct]:
    """The step's batch as shapes, sharded as the loader shards it: the
    dense model's images, or the decoder's packed documents."""
    sh = NamedSharding(mesh, batch_pspec())
    if cfg.decoder:
        rows = jax.ShapeDtypeStruct((cfg.batch_size, cfg.pack_tokens),
                                    jnp.int32, sharding=sh)
        return {"tokens": rows, "segment_ids": rows, "positions": rows}
    return {
        "image": jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.image_size, cfg.image_size, 3),
            jnp.float32, sharding=sh),
        "label": jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32,
                                      sharding=sh),
    }


def _build_step(cfg: Config, max_iteration: int, donate: bool):
    """(step, (state, batch, rng) abstract args, n_state_leaves) for the
    scenario's step program, for any --task."""
    geom = Geometry.from_config(cfg, max_iteration=max_iteration)
    step = build_program(geom.scenario.step_program, geom, donate=donate)
    args = (geom.abstract_state, abstract_batch(cfg, geom.mesh),
            jax.random.key(cfg.seed + 1))
    return step, args, len(jax.tree_util.tree_leaves(geom.abstract_state))


def lower_step(cfg: Config, max_iteration: int = 10_000, donate: bool = True):
    """AOT-lower the scenario's step program on the current backend.

    Returns (lowered, n_state_leaves): the `jax.stages.Lowered` step and the
    number of TrainState leaves (the donation rule's expected aliased-buffer
    count). `donate=False` builds the same program without donate_argnums —
    the deliberately-broken arm the donation rule's negative test compiles."""
    step, args, n_state_leaves = _build_step(cfg, max_iteration, donate)
    return step.lower(*args), n_state_leaves


def step_jaxpr(cfg: Config, max_iteration: int = 10_000) -> str:
    """Traced jaxpr text of the scenario's step program (the VTX-R008 /
    VTX-R010 artifact — stop_gradient and pallas_call markers survive only
    here, not in StableHLO)."""
    step, args, _ = _build_step(cfg, max_iteration, donate=True)
    return str(step.trace(*args).jaxpr)


def freeze_report(cfg: Config,
                  max_iteration: int = 10_000) -> Tuple[Tuple[str, ...],
                                                        Tuple[str, ...]]:
    """(frozen_param_paths, optimizer_moment_paths) for the scenario, read
    off the ABSTRACT state — the VTX-R010 evidence.

    frozen paths: '/'-joined param-tree paths the scenario freezes ("head"
    excluded for probe; every teacher leaf, prefixed "teacher/", for
    distill). moment paths: the param subpath of every mu/nu leaf that
    EXISTS in the optimizer state — optax.masked replaces masked-out
    positions with leafless MaskedNodes, so a frozen leaf acquiring moments
    shows up here as a path collision."""
    geom = Geometry.from_config(cfg, max_iteration=max_iteration)
    param_paths = [
        "/".join(_leaf_path_names(path))
        for path, _ in jax.tree_util.tree_leaves_with_path(
            geom.abstract_state.params)
    ]

    task = cfg.task
    if task == "probe":
        frozen = tuple(p for p in param_paths
                       if "head" not in p.split("/"))
    elif task == "distill":
        frozen = tuple("teacher/" + p for p in param_paths)
    else:
        frozen = ()

    moments = []
    for path, _ in jax.tree_util.tree_leaves_with_path(
            geom.abstract_state.opt_state):
        names = _leaf_path_names(path)
        for marker in ("mu", "nu"):
            if marker in names:
                moments.append("/".join(names[names.index(marker) + 1:]))
                break
    return frozen, tuple(sorted(set(moments)))
