"""Scenario registry + the one program builder.

`registry` maps --task names to Scenario declarations (programs, optimizer,
validator, sharding rules); `builder` assembles a Config's stack once
(`Geometry.assemble`) and turns (task, geometry) into jitted/AOT programs,
cached on the geometry; `workloads` holds the finetune /
linear-probe / distillation ingredients the scenarios are spent on.
"""

from vitax.programs.registry import SCENARIOS, TASKS, Scenario, get_scenario

__all__ = [
    "SCENARIOS",
    "TASKS",
    "Scenario",
    "get_scenario",
    # heavy (jax-importing) surfaces are reached via their modules:
    #   vitax.programs.builder   Geometry (.assemble, .from_config),
    #                            build_model_for, build_program, build_engine,
    #                            lower_step, step_jaxpr, freeze_report
    #   vitax.programs.workloads masks, optimizers, warm starts, distill step
]
