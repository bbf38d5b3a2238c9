"""Device mesh construction.

TPU-native replacement for the reference's process-per-core world
(xmp.spawn, reference run_vit_training.py:364): one process per host, all
devices arranged in a 6-axis `jax.sharding.Mesh`:

  axes = ("dp", "fsdp", "tp", "sp", "pp", "ep")

- "dp":   pure data parallelism (params replicated across it)
- "fsdp": ZeRO-3 axis — params/grads/optimizer state sharded across it, and it
          also carries batch parallelism (the reference's single 'data' axis)
- "tp":   tensor parallelism (attention heads / MLP hidden sharded)
- "sp":   sequence/context parallelism (ring attention over the token axis)
- "pp":   pipeline parallelism (GPipe stages over the stacked layer axis —
          vitax/parallel/pipeline.py; composes with dp, fsdp/ZeRO-3, and
          tp/sp — the latter ride as GSPMD-auto axes inside the body)
- "ep":   expert parallelism (vitax/models/moe.py) — carries batch like dp,
          and MoE expert weights shard their leading (E, ...) dim across it;
          GSPMD inserts the batch<->expert all-to-alls from the specs

The reference's FSDP corresponds to mesh shape (1, n_devices, 1, 1); its
--run_without_fsdp DP baseline to (n_devices, 1, 1, 1). GSPMD emits the
all-gather / reduce-scatter / all-reduce collectives over ICI from the sharding
specs alone (SURVEY.md section 2.4).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from vitax.config import Config

MESH_AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


def shard_map(f, mesh, in_specs, out_specs, check_vma=False, axis_names=None):
    """jax.shard_map with vitax's default: replication (vma) checking off
    unless a site asks for it (the pipeline's partial-manual tp path does).
    `axis_names` names the manual axes; None = every mesh axis."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def resolve_mesh_shape(cfg: Config, n_devices: Optional[int] = None) -> Tuple[int, ...]:
    """Resolve (dp, fsdp, tp, sp, pp, ep) against the device count. One axis may be
    -1 (= all remaining devices). `--run_without_fsdp` forces everything onto dp
    (the reference's pure-DP baseline, run_vit_training.py:171-172). Pipeline
    parallelism (pp > 1) composes with dp, fsdp (ZeRO-3 gathers run
    just-in-time inside the pipeline body), and tp/sp (GSPMD-auto axes
    inside the body — see vitax/parallel/pipeline.py; MoE under pp remains
    tp-free, enforced by Config.validate)."""
    n = n_devices if n_devices is not None else jax.device_count()
    dp, fsdp, tp, sp = cfg.dp_size, cfg.fsdp_size, cfg.tp_size, cfg.sp_size
    pp = getattr(cfg, "pp_size", 1)
    ep = getattr(cfg, "ep_size", 1)

    if cfg.run_without_fsdp:
        if fsdp not in (-1, 1):
            raise ValueError("--run_without_fsdp is incompatible with --fsdp_size > 1")
        fsdp = 1
        if dp == 1 and tp == 1 and sp == 1 and pp == 1 and ep == 1:
            dp = -1  # default DP baseline: all devices data-parallel

    if pp > 1:
        # fsdp composes: ZeRO-3 shards are gathered just-in-time inside the
        # pipeline body (vitax/parallel/pipeline.py). With --fsdp_size 1 the
        # remaining devices default to carrying the batch on dp; an explicit
        # --dp_size -1 wins over fsdp's -1 default (round-2 CLI behavior).
        if fsdp == 1 and dp == 1:
            dp = -1
        elif dp == -1 and fsdp == -1:
            fsdp = 1

    sizes = [dp, fsdp, tp, sp, pp, ep]
    n_auto = sum(1 for s in sizes if s == -1)
    if n_auto > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {sizes}")
    fixed = int(np.prod([s for s in sizes if s != -1]))
    if n_auto == 1:
        if n % fixed != 0:
            raise ValueError(f"device count {n} not divisible by fixed mesh axes {sizes}")
        sizes[sizes.index(-1)] = n // fixed
    elif fixed != n:
        raise ValueError(f"mesh {sizes} does not cover {n} devices")
    return tuple(sizes)  # type: ignore[return-value]


def build_mesh(cfg: Config, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the 6-axis mesh. Device order follows jax.devices(), which on TPU
    reflects physical torus coordinates — keeping the fastest-varying axis
    ("sp", then "tp") on the closest ICI neighbors."""
    devices = list(devices) if devices is not None else jax.devices()  # vtx: ignore[VTX104] mesh wants real devices
    shape = resolve_mesh_shape(cfg, len(devices))
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


BATCH_AXES = ("dp", "fsdp", "ep")  # mesh axes that carry the global batch


def batch_pspec(sp_shard_tokens: bool = False) -> P:
    """PartitionSpec for a (B, ...) batch: batch over dp+fsdp+ep.

    The reference shards the global batch across all ranks
    (DistributedSampler, run_vit_training.py:62-64); here the same statement is
    one PartitionSpec. With sequence parallelism the token axis of activations
    is additionally sharded over "sp" (handled inside the model/step, not on the
    raw image batch). "ep" carries batch too — expert parallelism is data
    parallelism whose MoE expert weights are sharded instead of replicated.
    """
    del sp_shard_tokens
    return P(BATCH_AXES)
