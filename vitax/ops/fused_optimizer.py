"""Fused clip+AdamW optimizer: one Pallas pass over the FSDP-sharded state.

The optax chain (`optax.chain(clip_by_global_norm, adamw)`) walks the full
param tree several times per step — a norm pass, a clip-scale pass, then ~10
elementwise HLO ops per leaf for the moment / bias-correction / decay /
param-step math — materializing multiple param-sized f32 temporaries exactly
where ZeRO-3 sharding is supposed to keep per-chip optimizer traffic minimal
(at 10B scale each avoided full-tree pass is ~40 GB of HBM per step).

This module replaces phase 2 of that pipeline with ONE kernel launch per
same-shape/dtype leaf group:

- **Phase 1** (plain jnp, fused by XLA with the grad tree): the single
  squared-norm reduction over all grad leaves. It emits the one clip scalar
  AND the `grad_norm` metric — the duplicated `optax.global_norm` the old
  step paid for the metric falls out for free.
- **Phase 2** (`fused_adamw_kernel`): per leaf, a Pallas kernel reads
  (param, grad, mu, nu) blocks plus the (clip_scale, lr, bias-correction)
  scalars from SMEM and writes (param, mu, nu) in place via
  `input_output_aliases` — clip-multiply, moment update, bias correction,
  decoupled weight decay, and the parameter step in a single pass over each
  element. Leaves sharing (2-D shape, dtype) share one compiled kernel (the
  blocks-stacked leaves are already grouped by construction), cached in
  `_pallas_leaf_call`.

Sharding: each leaf runs under `shard_map` with its own state spec, so every
chip touches only its FSDP shard — ZeRO semantics, `state_specs`, and the
donation contract are unchanged (the update is elementwise, so shard-local
math IS the global math once the clip scalar is computed globally).

Numerics match optax's `chain(clip_by_global_norm, adamw)` op-for-op (same
formulas, same operand order — see `_make_kernel`); the only intentional
deviation is the clip: optax scales per element as `(g / norm) * max_norm`,
the kernel multiplies by the precomputed scalar `max_norm / norm` (one
rounding each, ~1 ulp apart, and bit-identical whenever the clip does not
trigger). Off-TPU the kernel runs in Pallas interpret mode, exactly like
`vitax/ops/attention.py`; `VITAX_FORCE_MOSAIC=1` forces real Mosaic lowering
for AOT TPU-target compiles (tools/aot_topology.py).

The compiled-program invariant lives in vitax/analysis/rules.py VTX-R008:
interpret-mode Pallas leaves no custom-call marker in StableHLO, so the rule
reads the traced jaxpr, where every launch keeps `FUSED_KERNEL_NAME`.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from vitax.ops.common import interpret as _interpret
from vitax.parallel.mesh import shard_map

PyTree = Any

# the pallas_call `name=`: the jaxpr marker VTX-R008 greps for (one occurrence
# per launch) and the custom call's op_name in compiled HLO (chip_smoke.py)
FUSED_KERNEL_NAME = "fused_adamw_kernel"

# per-operand f32 block budget: 64K elements x 4 B x ~7 live buffers
# (p/g/mu/nu in + p/mu/nu out) ~ 1.8 MB of VMEM per grid step
_BLOCK_ELEMS = 64 * 1024


def fused_optimizer_active(cfg) -> bool:
    """Resolve --fused_optimizer {auto,on,off} for this process.

    `auto` engages the fused path exactly when the Pallas kernels lower to
    real Mosaic (TPU backend, or VITAX_FORCE_MOSAIC=1 for AOT TPU-target
    compiles) — mirroring the attention kernels' `_interpret()` policy, so
    default CPU programs stay on the reference optax chain. `on` forces the
    fused path anywhere (interpret mode off-TPU — the CI equivalence arms).

    Scenario exemptions (vitax/programs/registry.py): the fused kernel
    bypasses the optax chain and steps EVERY leaf at the schedule lr, so it
    cannot express the probe's masked-frozen backbone or the finetune
    backbone-lr multiplier — those tasks stay on optax regardless of mode
    (their validators reject an explicit `on`)."""
    task = getattr(cfg, "task", "train")
    if task == "probe":
        return False
    if task == "finetune" and getattr(cfg, "backbone_lr_mult", 1.0) != 1.0:
        return False
    mode = getattr(cfg, "fused_optimizer", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return not _interpret()


def _as_2d(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """Collapse a leaf shape to (rows, last-dim) for the kernel grid. The
    reshape is row-major contiguous — a bitcast to XLA, so it does not break
    the in-place aliasing chain."""
    if not shape:
        return (1, 1)
    n = shape[-1]
    m = 1
    for d in shape[:-1]:
        m *= d
    return (m, n)


def _make_kernel(b1: float, b2: float, eps: float, wd: float):
    def fused_adamw_kernel(scal_ref, p_ref, g_ref, mu_ref, nu_ref,
                           po_ref, muo_ref, nuo_ref):
        # scal (SMEM): [clip_scale, lr, 1-b1^t, 1-b2^t] — the only values
        # that vary per step; the hparams are compile-time constants
        s = scal_ref[0, 0]
        lr = scal_ref[0, 1]
        bc1 = scal_ref[0, 2]
        bc2 = scal_ref[0, 3]
        g = g_ref[...] * s
        # operand order matches optax.scale_by_adam's update_moment exactly
        mu = (1.0 - b1) * g + b1 * mu_ref[...]
        nu = (1.0 - b2) * (g * g) + b2 * nu_ref[...]
        upd = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps) + wd * p_ref[...]
        po_ref[...] = p_ref[...] + (-lr) * upd
        muo_ref[...] = mu
        nuo_ref[...] = nu
    return fused_adamw_kernel


def _block_shape(m: int, n: int, itemsize: int = 4) -> Tuple[int, int]:
    """(rows, lanes) block for an (m, n) leaf view that the TPU lowering
    accepts at ANY shape: each block dim is either the whole array dim or a
    multiple of the (sublane, 128) tile. Whole rows while a sublane tile of
    them fits the element budget; wider leaves (10B-width fc1/qkv: last dim
    20480/15360) tile the lane dimension too — with a divisor of n where one
    exists, so no grid step runs on a masked partial block."""
    sublane = 8 * (4 // itemsize)  # f32 (8, 128) tile; bf16 packs (16, 128)
    max_bn = _BLOCK_ELEMS // sublane
    bn = n
    if n > max_bn:
        bn = max_bn - max_bn % 128
        for c in range(bn, 127, -128):
            if n % c == 0:
                bn = c
                break
    # a block narrower than a lane tile still occupies whole 128-lane tiles
    bm = min(m, _BLOCK_ELEMS // (-(-bn // 128) * 128))
    if bm < m:
        bm -= bm % sublane
    return bm, bn


@functools.lru_cache(maxsize=None)
def _pallas_leaf_call(shape2d: Tuple[int, int], dtype: str,
                      hparams: Tuple[float, float, float, float],
                      interpret: bool):
    """One pallas_call per (2-D shape, dtype, hparams) leaf *group* — every
    leaf sharing these reuses the cached kernel (and XLA dedups the compiled
    custom-call). Writes (param, mu, nu) onto their input buffers via
    input_output_aliases."""
    m, n = shape2d
    bm, bn = _block_shape(m, n, jnp.dtype(dtype).itemsize)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _make_kernel(*hparams),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),  # scal (1, 4)
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.dtype(dtype))] * 3,
        # param <- param, mu <- mu, nu <- nu (operand 0 is the SMEM scalars)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        name=FUSED_KERNEL_NAME,
        interpret=interpret,
    )


def _local_leaf_update(p, g, mu, nu, scal, *, hparams, interpret):
    """Shard-local fused update for one leaf (runs inside shard_map on
    multi-device meshes, so shapes here are the LOCAL shard shapes)."""
    m, n = _as_2d(p.shape)
    call = _pallas_leaf_call((m, n), str(p.dtype), hparams, interpret)
    po, muo, nuo = call(scal, p.reshape(m, n), g.reshape(m, n),
                        mu.reshape(m, n), nu.reshape(m, n))
    return po.reshape(p.shape), muo.reshape(p.shape), nuo.reshape(p.shape)


def find_adam_state(opt_state) -> optax.ScaleByAdamState:
    """Locate the single ScaleByAdamState in an optax chain state tree."""
    found: List[optax.ScaleByAdamState] = []

    def walk(s):
        if isinstance(s, optax.ScaleByAdamState):
            found.append(s)
        elif isinstance(s, tuple) and not hasattr(s, "_fields"):
            for x in s:
                walk(x)

    walk(opt_state)
    assert len(found) == 1, (
        f"expected exactly one ScaleByAdamState in the optimizer state, "
        f"found {len(found)} — the fused path only replaces the "
        f"clip+AdamW chain built by vitax.train.state.build_optimizer")
    return found[0]


def _rebuild_opt_state(s, new_adam: optax.ScaleByAdamState):
    """Reassemble the optax chain state: the AdamW moments swap in, and any
    other counted state (ScaleByScheduleState) increments exactly as its
    optax update_fn would — structure, dtypes, and sharding unchanged."""
    if isinstance(s, optax.ScaleByAdamState):
        return new_adam
    if isinstance(s, tuple) and hasattr(s, "_fields"):
        if "count" in s._fields:
            return s._replace(count=optax.safe_int32_increment(s.count))
        return s
    if isinstance(s, tuple):
        return tuple(_rebuild_opt_state(x, new_adam) for x in s)
    return s


def fused_clip_adamw(
    grads: PyTree,
    opt_state: PyTree,
    params: PyTree,
    *,
    grad_norm: jax.Array,
    schedule,
    clip_norm: float,
    weight_decay: float,
    b1: float,
    b2: float,
    eps: float,
    mesh=None,
    param_specs: Optional[PyTree] = None,
    interpret: Optional[bool] = None,
) -> Tuple[PyTree, PyTree]:
    """One-pass fused clip+AdamW update. Returns (new_params, new_opt_state)
    — a drop-in replacement for `tx.update` + `optax.apply_updates` on the
    chain built by vitax.train.state.build_optimizer, preserving the optax
    state structure (counts incremented, mu/nu replaced in place).

    `grad_norm` is the phase-1 global norm of `grads` (the caller computes it
    once and reuses it for the metric); `schedule` is the pure lr schedule
    evaluated at the pre-increment step count, exactly where optax's
    scale_by_schedule reads it. With `mesh`/`param_specs` set, every leaf
    updates under shard_map on its own spec — shard-local, no collectives."""
    if interpret is None:
        interpret = _interpret()
    adam = find_adam_state(opt_state)
    count_inc = optax.safe_int32_increment(adam.count)
    lr = jnp.asarray(schedule(adam.count), jnp.float32)
    bc1 = jnp.asarray(1 - b1 ** count_inc, jnp.float32)
    bc2 = jnp.asarray(1 - b2 ** count_inc, jnp.float32)
    if clip_norm and clip_norm > 0:
        clip_scale = jnp.where(grad_norm < clip_norm, jnp.float32(1.0),
                               clip_norm / grad_norm).astype(jnp.float32)
    else:
        clip_scale = jnp.float32(1.0)
    scal = jnp.stack([clip_scale, lr, bc1, bc2]).reshape(1, 4)

    hparams = (float(b1), float(b2), float(eps), float(weight_decay))
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    mu_leaves = treedef.flatten_up_to(adam.mu)
    nu_leaves = treedef.flatten_up_to(adam.nu)
    specs = (treedef.flatten_up_to(param_specs) if param_specs is not None
             else [None] * len(p_leaves))

    sharded = mesh is not None and mesh.size > 1
    new_p, new_mu, new_nu = [], [], []
    for p, g, mu, nu, spec in zip(p_leaves, g_leaves, mu_leaves, nu_leaves,
                                  specs):
        fn = functools.partial(_local_leaf_update, hparams=hparams,
                               interpret=bool(interpret))
        if sharded and spec is not None:
            fn = shard_map(fn, mesh,
                           in_specs=(spec, spec, spec, spec, P()),
                           out_specs=(spec, spec, spec))
        po, muo, nuo = fn(p, g.astype(p.dtype), mu, nu, scal)
        new_p.append(po)
        new_mu.append(muo)
        new_nu.append(nuo)

    new_adam = optax.ScaleByAdamState(
        count=count_inc,
        mu=jax.tree_util.tree_unflatten(treedef, new_mu),
        nu=jax.tree_util.tree_unflatten(treedef, new_nu))
    return (jax.tree_util.tree_unflatten(treedef, new_p),
            _rebuild_opt_state(opt_state, new_adam))
