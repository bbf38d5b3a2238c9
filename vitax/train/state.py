"""Train state and optimizer construction.

Replaces the reference's (model, optimizer, lr_scheduler) triple
(reference run_vit_training.py:228-240) with one immutable pytree carried
through the jitted step: {step, params, opt_state}. The LR schedule is a pure
function of `step`, so there is no separate scheduler state to checkpoint —
`step` alone reproduces it (reference save_ckpt's lr_scheduler entry,
utils.py:31, collapses to this).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from vitax.config import Config
from vitax.parallel.mesh import Mesh
from vitax.parallel.sharding import (
    jit_init_sharded,
    param_specs,
    shardings_of,
    state_specs_like,
)
from vitax.train.schedule import warmup_cosine_schedule

PyTree = Any


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array          # scalar int32 — optimizer step counter
    params: PyTree           # flax variables dict {"params": ...}
    opt_state: PyTree        # optax state (AdamW moments inherit param sharding)


def build_optimizer(cfg: Config, max_iteration: int) -> Tuple[optax.GradientTransformation, Callable]:
    """AdamW + global-norm clip + warmup-cosine, matching the reference:
    - clip BEFORE the update (reference clips grads then steps,
      run_vit_training.py:266-278); clipping by *global* norm of sharded grads
      is exact under jit — the norm is computed with a compiled all-reduce,
      which is what FSDP's model.clip_grad_norm_ does by hand (run_vit_training.py:270).
      The clip itself is applied in the train step (vitax/train/step.py),
      bitwise-reproducing optax.clip_by_global_norm's formula off the SAME
      global-norm reduction that feeds the grad_norm metric — one norm pass
      per step instead of two. The chain keeps an optax.identity() in the
      clip's historical slot so the opt_state tree (and with it state_specs,
      checkpoints, and donation) is unchanged: both lower to EmptyState.
    - AdamW betas (0.9, 0.999), eps 1e-8, weight decay on ALL params
      (torch.optim.AdamW semantics, reference run_vit_training.py:237)
    """
    schedule = warmup_cosine_schedule(cfg.lr, cfg.warmup_steps, max_iteration)
    parts = []
    if cfg.clip_grad_norm > 0:
        parts.append(optax.identity())
    parts.append(
        optax.adamw(schedule, weight_decay=cfg.weight_decay, **ADAMW_HPARAMS))
    return optax.chain(*parts), schedule


# torch.optim.AdamW defaults (reference run_vit_training.py:237); the startup
# optimizer dump (train/loop.py) prints from this same dict
ADAMW_HPARAMS = dict(b1=0.9, b2=0.999, eps=1e-8)


def make_train_state(
    cfg: Config,
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    materialize: bool = True,
) -> Tuple[TrainState, PyTree, PyTree]:
    """Create the train state born sharded: params AND AdamW moments are
    materialized directly into their shards — no host or device ever holds the
    full 10B tree (the shard_on_cpu capability, done the XLA way).

    With materialize=False, returns the *abstract* state (ShapeDtypeStructs
    carrying target shardings) — the restore target for checkpoint resume,
    costing no device memory.

    Returns (state, state_specs, param_specs).
    """
    # sample batch must divide evenly over the (dp, fsdp) batch axes — the
    # attention shard_map paths trace through init
    sample_b = mesh.shape["dp"] * mesh.shape["fsdp"]
    if cfg.decoder:
        from vitax.models.decoder import sample_documents
        sample = sample_documents(cfg, sample_b)
    else:
        from vitax.models.vit import sample_input
        sample = sample_input(cfg, sample_b)

    def init_fn(rng):
        params = model.init(rng, sample, True)
        opt_state = tx.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    abstract = jax.eval_shape(init_fn, rng)
    pspecs = param_specs(abstract.params, cfg, mesh)
    sspecs = state_specs_like(abstract, pspecs)
    shardings = shardings_of(mesh, sspecs)
    if materialize:
        state = jit_init_sharded(init_fn, rng, shardings, cfg.shard_on_cpu)
    else:
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, shardings)
    return state, sspecs, pspecs
