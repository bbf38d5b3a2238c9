"""Scenario registry: `--task` name -> programs, optimizer, validator, rules.

ROADMAP item 3 ("one build_program(task, geometry) entry; scenarios become
registry entries"). A Scenario is declarative data: which step program the
training loop runs, which programs the task may build, how its optimizer is
assembled, and a SELF-CONTAINED validator holding the task's pairwise flag
checks — `config.py:validate` dispatches here instead of accreting another
block per workload, so adding a scenario touches this file, not the shared
validator.

This module is deliberately jax-free (it is imported from Config.validate,
which tools call before any backend setup): optimizers and sharding tables
are reached through lazy imports at use time.

The registry entries:

    train     the reference pretraining loop (vitax/train/loop.py)
    finetune  warm start from a consolidated npz export (--init_npz), head
              re-initialized for a new --num_classes (--reinit_head or a
              shape mismatch), optional --backbone_lr_mult update scaling
    probe     linear probe: backbone frozen via optax masking (updates
              set_to_zero; optimizer moments exist for the head ONLY), the
              classifier head trained as usual
    distill   knowledge distillation: a frozen teacher (--teacher_npz,
              engine-style eval forward under stop_gradient) and the student
              train step in ONE jitted program; loss = (1-alpha)*CE +
              alpha*KL(teacher||student) at --distill_temp

How to add a workload: write a validator + optimizer builder (or reuse), add
a Scenario below, and (if it needs a new step program) teach
vitax/programs/builder.py:build_program the new task name. The analysis arms
(vitax/analysis/rules.py) and `--task` choices pick it up from SCENARIOS.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One registry entry: everything a workload declares about itself."""
    name: str
    description: str
    step_program: str            # builder task the training loop steps with
    programs: Tuple[str, ...]    # program kinds build_program accepts for it
    make_optimizer: Callable     # (cfg, max_iteration) -> (tx, schedule)
    validate: Callable           # (cfg) -> None; raises on bad flag combos

    def sharding_rules(self):
        """The declarative path->PartitionSpec table this scenario shards
        with (vitax/parallel/rules.py). One shared table today; a scenario
        needing a different layout overrides this."""
        from vitax.parallel.rules import RULE_TABLE
        return RULE_TABLE


# --- optimizer builders (lazy: registry stays importable without jax) -------


def _train_optimizer(cfg, max_iteration: int):
    from vitax.train.state import build_optimizer
    return build_optimizer(cfg, max_iteration)


def _finetune_optimizer(cfg, max_iteration: int):
    from vitax.programs.workloads import make_finetune_optimizer
    return make_finetune_optimizer(cfg, max_iteration)


def _probe_optimizer(cfg, max_iteration: int):
    from vitax.programs.workloads import make_probe_optimizer
    return make_probe_optimizer(cfg, max_iteration)


# --- validators: the task-specific pairwise checks, absorbed from
# config.py:validate's growth path. Each sees a fully type-checked Config and
# raises AssertionError with an actionable message, exactly like validate().


def _validate_train(cfg) -> None:
    assert not cfg.init_npz, (
        "--init_npz is a finetune/probe warm-start flag; --task train "
        "initializes from seed (use --task finetune to resume params from "
        "a consolidated export)")
    assert not cfg.teacher_npz, (
        "--teacher_npz is a distillation flag; use --task distill")
    assert not cfg.reinit_head, (
        "--reinit_head only applies to --task finetune (train initializes "
        "every leaf fresh anyway)")
    assert cfg.backbone_lr_mult == 1.0, (
        f"--backbone_lr_mult {cfg.backbone_lr_mult} only applies to "
        f"--task finetune; train updates every leaf at the schedule lr")


def _validate_finetune(cfg) -> None:
    assert cfg.init_npz, (
        "--task finetune resumes params from a consolidated export: pass "
        "--init_npz <file> (produce one with vitax.checkpoint.consolidate)")
    assert not cfg.teacher_npz, (
        "--teacher_npz is a distillation flag; use --task distill")
    assert cfg.pp_size <= 1, (
        "--task finetune runs the non-pipelined step; restore with "
        "--pp_size 1 (the consolidated export is topology-free)")
    assert cfg.backbone_lr_mult >= 0, (
        f"--backbone_lr_mult must be >= 0, got {cfg.backbone_lr_mult} "
        f"(0 freezes the backbone — consider --task probe, which also "
        f"drops the backbone optimizer moments)")
    if cfg.backbone_lr_mult != 1.0:
        assert cfg.fused_optimizer != "on", (
            "--fused_optimizer on is incompatible with --backbone_lr_mult: "
            "the fused clip+AdamW kernel applies one lr to every leaf "
            "(vitax/ops/fused_optimizer.py); the optax path handles the "
            "masked scaling")


def _validate_probe(cfg) -> None:
    assert not cfg.teacher_npz, (
        "--teacher_npz is a distillation flag; use --task distill")
    assert cfg.pp_size <= 1, (
        "--task probe runs the non-pipelined step; use --pp_size 1")
    assert cfg.fused_optimizer != "on", (
        "--fused_optimizer on is incompatible with --task probe: the fused "
        "clip+AdamW kernel updates every leaf in place, but the probe "
        "freezes the backbone via optax masking (VTX-R010 pins that frozen "
        "leaves receive no optimizer moments)")
    assert cfg.backbone_lr_mult == 1.0, (
        "--backbone_lr_mult has no effect under --task probe (the backbone "
        "is frozen outright); use --task finetune for a reduced backbone lr")


def _validate_distill(cfg) -> None:
    # --teacher_npz itself is enforced at program-build time, not here: the
    # analysis arms lower the distill program against an ABSTRACT teacher
    # with no file on disk (vitax/programs/builder.py)
    assert not cfg.init_npz, (
        "--init_npz warm starts are not wired for --task distill (the "
        "student trains from seed); distill from a finetuned teacher via "
        "--teacher_npz instead")
    assert not cfg.reinit_head, (
        "--reinit_head only applies to --task finetune")
    assert cfg.backbone_lr_mult == 1.0, (
        "--backbone_lr_mult only applies to --task finetune")
    assert cfg.pp_size <= 1, (
        "--task distill runs the non-pipelined two-tower step; use "
        "--pp_size 1")
    assert cfg.moe_experts == 0, (
        "--task distill does not support MoE models yet: the teacher "
        "forward would need the aux-loss plumbing threaded through the "
        "frozen tower")
    assert cfg.grad_accum_steps <= 1, (
        "--grad_accum_steps > 1 is not wired for --task distill: the "
        "two-tower step computes teacher logits once per loader batch")
    assert cfg.reshard_after_forward, (
        "--no_reshard_after_forward (ZeRO-2) is not wired for --task "
        "distill: the step-top gather path covers the student tower only")


SCENARIOS = {
    "train": Scenario(
        name="train",
        description="reference pretraining loop (CE over labels)",
        step_program="train",
        programs=("train", "eval", "serve_bucket"),
        make_optimizer=_train_optimizer,
        validate=_validate_train,
    ),
    "finetune": Scenario(
        name="finetune",
        description="fine-tune from a consolidated npz export "
                    "(--init_npz; head re-init, --backbone_lr_mult)",
        step_program="train",
        programs=("train", "eval", "serve_bucket"),
        make_optimizer=_finetune_optimizer,
        validate=_validate_finetune,
    ),
    "probe": Scenario(
        name="probe",
        description="linear probe: frozen backbone (optax-masked), "
                    "head-only optimizer state",
        step_program="train",
        programs=("train", "eval", "serve_bucket"),
        make_optimizer=_probe_optimizer,
        validate=_validate_probe,
    ),
    "distill": Scenario(
        name="distill",
        description="knowledge distillation: frozen teacher "
                    "(--teacher_npz) + student in one jitted program",
        step_program="distill",
        programs=("distill", "eval", "serve_bucket"),
        make_optimizer=_train_optimizer,  # plain AdamW over the student
        validate=_validate_distill,
    ),
}

TASKS = tuple(SCENARIOS)


def get_scenario(task: str) -> Scenario:
    """Resolve a --task name; unknown names fail with the valid set."""
    if task not in SCENARIOS:
        raise ValueError(
            f"unknown --task {task!r}; registered scenarios: "
            f"{', '.join(sorted(SCENARIOS))} (vitax/programs/registry.py)")
    return SCENARIOS[task]
