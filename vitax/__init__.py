"""vitax — a TPU-native (JAX/XLA) framework for training large Vision Transformers.

Built from scratch with the capability surface of ronghanghu/vit_10b_fsdp_example
(see SURVEY.md): FSDP/ZeRO-3 sharded training of 10B+ ViTs on TPU pods, activation
checkpointing, sharded checkpoint save/resume + consolidation, fake-data and pure-DP
baseline modes, and the reference's exact CLI flag surface — expressed TPU-first as
sharding declarations over a `jax.sharding.Mesh` compiled by GSPMD, not as module
wrappers over a lazy-tensor runtime.

Package map:
  config        CLI + typed config (reference run_vit_training.py:327-363 parity)
  models        Flax ViT (patchify, attention, MLP, scanned+remat blocks)
  ops           TPU kernels (Pallas flash attention) + reference implementations
  parallel      mesh construction, sharding rules (FSDP/DP/TP/SP), ring attention
  data          host input pipeline (fake data, ImageFolder, transforms, prefetch)
  train         train state, jitted step functions, epoch loop, LR schedule
  checkpoint    Orbax sharded save/restore + consolidation
  utils         metrics, logging, profiling
  distributed   multi-host runtime (init, barriers, host reductions)
"""

__version__ = "0.1.0"

# The sharding-must-not-change-the-math contract (param init DRAWS equal
# under every mesh shape: tests/test_train_smoke.py::
# test_dp_fsdp_zero2_equivalence and every sp/tp/pp equivalence test) rests
# on jax_threefry_partitionable, which is jax's default.
