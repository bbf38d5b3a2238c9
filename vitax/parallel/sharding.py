"""Sharding rules: FSDP/ZeRO-3 as PartitionSpec assignment, not module wrappers.

This module is the TPU-native core replacing XlaFullyShardedDataParallel
(reference run_vit_training.py:13,177-181; SURVEY.md section 2.2 row 1):

- ZeRO-3  = every parameter (and its grad and AdamW moments) carries a
  PartitionSpec placing one dim on the "fsdp" mesh axis. GSPMD then emits the
  per-block all-gather before use and reduce-scatter of grads — the exact
  collectives the reference gets from nested FSDP wrapping, but scheduled by
  the XLA compiler with compute/communication overlap.
- ZeRO-2  = `--no_reshard_after_forward`: params are gathered once per step
  (see `gather_over_fsdp`) and stay live through backward; grads/opt state stay
  sharded.
- DP      = `--run_without_fsdp`: params replicated, batch sharded; the grad
  all-reduce the reference does manually (xm.reduce_gradients,
  run_vit_training.py:273) falls out of GSPMD.
- TP      = name-based rules sharding attention heads / MLP hidden over "tp"
  (capability the reference lacks; mesh axis reserved in SURVEY.md section 2.3).
- `--flatten_parameters` is accepted but a no-op: flattening exists in torch FSDP
  to amortize many small all-gathers; under GSPMD the compiler already fuses and
  schedules collectives, so there is nothing to flatten.

Sharded init (`init_sharded_params`) jits the initializer with output shardings
so a 10B+ model is *born sharded* — no host or device ever materializes the full
parameter tree. This subsumes the reference's `--shard_on_cpu` workaround
(run_vit_training.py:175-181, pytorch/xla#3992); with `--shard_on_cpu` we instead
init on host CPU and device_put shard-by-shard, which is the literal equivalent.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vitax.config import Config
from vitax.parallel.mesh import BATCH_AXES, shard_map

PyTree = Any

# Parameters consumed at float32 by the model: the head Dense ("head + loss in
# float32", vitax/models/vit.py), the MoE router Dense (vitax/models/moe.py),
# and every LayerNorm's scale/bias (flax normalizes in f32 and folds the scale
# in BEFORE casting the output to the compute dtype, so LN params never pass
# through a bf16 cast). Downcasting them would change the math — f32(bf16(w))
# != w — so the comm cast skips any leaf under these names. All are O(d) or
# O(d*num_classes): their f32 gathers are noise next to the O(d^2) block
# matrices the policy targets.
KEEP_F32_PARAMS = ("head", "router", "norm", "norm1", "norm2")


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
        else:
            names.append(str(p))
    return tuple(names)


# TP rules: (predicate on path names) -> dim sharded over "tp".
# Column-parallel: qkv and fc1 shard their *output* dim; row-parallel: proj and
# fc2 shard their *input* dim (Megatron layout: one all-reduce per pair, here
# inserted automatically by GSPMD).
def _tp_dim(names: Tuple[str, ...], ndim: int, last_two: Tuple[int, int]) -> Optional[int]:
    in_dim, out_dim = last_two
    if "qkv" in names or "fc1" in names:
        return out_dim if names[-1] == "kernel" else (ndim - 1)  # bias: its only dim
    if "proj" in names and "attn" in names and names[-1] == "kernel":
        return in_dim
    if "fc2" in names and names[-1] == "kernel":
        return in_dim
    return None


def param_pspec(
    path,
    shape: Tuple[int, ...],
    cfg: Config,
    mesh_shape: Tuple[int, ...],  # (dp, fsdp, tp, sp, pp, ep)
    scanned: bool,
) -> P:
    """Assign a PartitionSpec to one parameter.

    Strategy: apply the TP rule (if tp > 1), then FSDP-shard the largest
    remaining dim divisible by the fsdp axis size. The leading stacked-layers
    dim of scanned block params is never sharded over fsdp (lax.scan slices it
    per iteration; sharding it would serialize a gather per layer) — but under
    pipeline parallelism it IS the partitioned dim: each "pp" stage holds its
    own contiguous slice of layers (vitax/parallel/pipeline.py).
    """
    _, fsdp, tp, _, pp, ep = mesh_shape
    ndim = len(shape)
    names = _path_names(path)
    spec: list = [None] * ndim

    is_scanned_block = scanned and "blocks" in names
    first_shardable = 1 if is_scanned_block else 0

    if pp > 1 and is_scanned_block:
        assert shape[0] % pp == 0, (
            f"pp: stacked layer dim {shape[0]} of {names} not divisible by "
            f"pp={pp}")
        spec[0] = "pp"

    if ep > 1 and "moe" in names and names[-1] in ("w1", "b1", "w2", "b2"):
        # expert weights: the (E, ...) experts dim shards over "ep" (the
        # GShard layout — vitax/models/moe.py); router params follow the
        # default rules like any dense weight
        e_dim = first_shardable
        assert shape[e_dim] % ep == 0, (
            f"ep: experts dim {e_dim} of {names} {shape} not divisible by "
            f"ep={ep}")
        spec[e_dim] = "ep"
        first_shardable = e_dim + 1  # fsdp picks among the remaining dims

    if tp > 1:
        tp_dim = _tp_dim(names, ndim, (ndim - 2, ndim - 1))
        if tp_dim is not None and tp_dim >= first_shardable:
            assert shape[tp_dim] % tp == 0, (
                f"TP: dim {tp_dim} of {names} {shape} not divisible by tp={tp}")
            spec[tp_dim] = "tp"

    if fsdp > 1 and not cfg.run_without_fsdp:
        # largest free dim divisible by fsdp size (ZeRO-3 shards every param;
        # small indivisible params stay replicated, matching FSDP's handling of
        # leftover/root params)
        candidates = [
            (shape[d], d) for d in range(first_shardable, ndim)
            if spec[d] is None and shape[d] % fsdp == 0 and shape[d] >= fsdp
        ]
        if candidates:
            _, d = max(candidates)
            spec[d] = "fsdp"

    return P(*spec)


def param_specs(abstract_params: PyTree, cfg: Config, mesh: Mesh) -> PyTree:
    """PartitionSpec tree matching an (abstract) parameter tree.

    Routed through the declarative rule table (vitax/parallel/rules.py,
    scalax `TreePathShardingRule` style). `param_pspec` above remains the
    reference dispatcher the table is pinned against leaf-for-leaf across
    the dp/zero2/zero3/tp/pp/ep arms (tests/test_programs.py)."""
    from vitax.parallel import rules as _rules

    return _rules.specs_from_rules(abstract_params, cfg, mesh)


def state_specs_like(abstract_state: PyTree, params_specs: PyTree) -> PyTree:
    """Spec tree for a TrainState-like pytree: leaves under a `mu`/`nu` (AdamW
    moments) or `params` subtree inherit the matching parameter's spec; scalars
    and everything else are replicated.

    This is how optimizer-state sharding (ZeRO-1) 'falls out' of param sharding
    (SURVEY.md section 2.3): AdamW moments are param-shaped pytrees, so they
    reuse the param specs leaf-for-leaf.
    """
    flat_specs = {
        _path_names(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(params_specs)[0]
    }

    def assign(path, leaf):
        names = _path_names(path)
        for marker in ("mu", "nu", "params"):
            if marker in names:
                # exact-path match: the subpath after the marker must name a
                # parameter (mu/nu ARE param-shaped trees; `params` in the
                # state is the param tree itself). Suffix matching is a
                # silent-misplacement landmine with colliding leaf names.
                sub = names[names.index(marker) + 1:]
                spec = flat_specs.get(sub)
                if spec is None:
                    raise ValueError(
                        f"state leaf {'/'.join(names)}: no parameter at "
                        f"subpath {'/'.join(sub) or '<root>'} — cannot infer "
                        "its sharding (new optimizer state needs an explicit "
                        "rule here)")
                if len(leaf.shape) != len(spec):
                    raise ValueError(
                        f"state leaf {'/'.join(names)} has rank "
                        f"{len(leaf.shape)} but the parameter spec at "
                        f"{'/'.join(sub)} is rank {len(spec)} — non-param-"
                        "shaped aux state (e.g. factored moments) needs an "
                        "explicit sharding rule")
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(assign, abstract_state)


def shardings_of(mesh: Mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def token_sharding(cfg: Config, mesh: Mesh) -> Optional[NamedSharding]:
    """(B, N, D) activation sharding: batch over (dp, fsdp, ep), tokens over
    sp. Anchors GSPMD propagation; None on single-device meshes."""
    if mesh.size == 1:
        return None
    sp = mesh.shape.get("sp", 1)
    token_axis = "sp" if (sp > 1 and cfg.num_patches % sp == 0) else None
    return NamedSharding(mesh, P(BATCH_AXES, token_axis, None))


def moe_dispatch_sharding(cfg: Config,
                          mesh: Mesh) -> Optional[NamedSharding]:
    """(E, B, C, D) dispatched-tensor sharding for the MoE einsums: experts
    over "ep", batch over the data axes. The explicit anchor makes GSPMD
    lower dispatch/combine to all-to-alls instead of the partitioner's
    involuntary full rematerialization. None when dense or single-device."""
    if cfg.moe_experts == 0 or mesh.size == 1:
        return None
    ep = mesh.shape.get("ep", 1)
    return NamedSharding(
        mesh, P("ep" if ep > 1 else None, ("dp", "fsdp"), None, None))


def gather_over_fsdp(specs: PyTree) -> PyTree:
    """ZeRO-2 view of the param specs: drop the "fsdp" placement (params fully
    gathered over fsdp for the whole step), keep TP placements. Used when
    `--no_reshard_after_forward` is set (reference run_vit_training.py:358,174)."""
    def strip(spec: P) -> P:
        return P(*[None if axis == "fsdp" else axis for axis in spec])
    return jax.tree.map(strip, specs, is_leaf=lambda x: isinstance(x, P))


def gather_overlap_active(cfg: Config, mesh: Mesh) -> bool:
    """Resolve --gather_overlap {auto,off,on} against the actual mesh.

    `on` is taken at its word (Config.validate already rejected structurally
    impossible configs; on a mesh without an fsdp axis the prefetch constraints
    degenerate to no-ops and the schedule is merely pointless, not wrong).
    `auto` engages only where the schedule both applies and preserves the
    requested semantics: ZeRO-3 per-block gathers, the scanned stacked tree,
    per-block remat with none_saveable (the overlap backward re-gathers and
    recomputes each block — exactly those semantics), no pipeline, and an
    fsdp axis that actually shards (otherwise there is nothing to overlap)."""
    mode = getattr(cfg, "gather_overlap", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return (cfg.reshard_after_forward
            and not cfg.run_without_fsdp
            and cfg.scan_blocks
            and cfg.grad_ckpt
            and cfg.remat_policy == "none_saveable"
            and getattr(cfg, "pp_size", 1) == 1
            and not getattr(cfg, "packed", False)  # no packed arm of the schedule
            and mesh.shape.get("fsdp", 1) > 1)


def prefetch_gather(stacked: PyTree, start, length: int,
                    mesh: Mesh, block_specs: PyTree) -> PyTree:
    """Explicitly all-gather `length` layers of the stacked block-param tree
    over the "fsdp" axis, starting at layer `start` (a traced scalar is fine).

    This is the collective the double-buffered scan schedule issues one
    iteration ahead of use (--gather_overlap): slicing the stacked (L, ...)
    leaves first and constraining the slice to the fsdp-stripped layout makes
    GSPMD emit the gather HERE — on the prefetch slot feeding the scan carry —
    instead of at the parameter use sites inside the next block's matmuls.
    Composes with the comm-precision cast (cast_to_compute): the cast runs on
    the sharded stacked tree before the forward, so under the bf16 policy the
    prefetched gather moves bf16 bytes (KEEP_F32_PARAMS leaves gather f32,
    as at the use sites). The same call re-gathers a group in the schedule's
    backward; the gradients of what it gathered come back sharded, through
    `ring_weight_grad` below (the block matrices) and the compiler's small
    reduces (biases, norm scales).

    `block_specs` is the PartitionSpec tree of the stacked block params (the
    `state_specs.params["params"]["blocks"]` subtree); the returned tree holds
    (length, ...) leaves gathered over fsdp with every other placement (tp,
    ep) intact."""
    # specs lead the tree.maps: P is a tuple subclass and must be the
    # is_leaf-guarded first tree (see vitax/parallel/pipeline.py)
    is_spec = lambda x: isinstance(x, P)
    sharded = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                           block_specs, is_leaf=is_spec)
    gathered = jax.tree.map(
        lambda spec: NamedSharding(
            mesh, P(*[None if ax == "fsdp" else ax for ax in spec])),
        block_specs, is_leaf=is_spec)

    def leaf(sh_in, sh_out, x):
        s = jax.lax.dynamic_slice_in_dim(x, start, length, axis=0)
        # pin the slice to the stacked tree's own (fsdp-sharded) layout
        # first: without this GSPMD back-propagates the replicated
        # constraint through the dynamic_slice and hoists the all-gather
        # ABOVE it — gathering the entire (L, ...) stack every iteration
        # instead of one group's slice
        s = jax.lax.with_sharding_constraint(s, sh_in)
        return jax.lax.with_sharding_constraint(s, sh_out)

    return jax.tree.map(leaf, sharded, gathered, stacked)


def ring_order(mesh: Mesh) -> Tuple[int, ...]:
    """The "fsdp" indices in the order a ring over that axis visits them.

    `build_mesh` lays devices out in `jax.devices()` order, and on a v5e
    2 x 2 that order is (0,0) (1,0) (0,1) (1,1): a ring in index order makes
    two of its four hops across the diagonal, two links each. Where the
    devices say where they sit (`coords`), the ring walks from index 0 to
    the nearest chip not yet visited (0 1 3 2 on the 2 x 2: every hop one
    link); where they do not (the CPU's devices), index order."""
    line = np.moveaxis(mesh.devices, mesh.axis_names.index("fsdp"), 0)
    line = line.reshape(line.shape[0], -1)[:, 0]
    coords = [getattr(d, "coords", None) for d in line]
    if any(c is None for c in coords):
        return tuple(range(len(line)))
    order = [0]
    while len(order) < len(line):
        here = coords[order[-1]]
        order.append(min(
            (i for i in range(len(line)) if i not in order),
            key=lambda i: sum(abs(a - b) for a, b in zip(here, coords[i]))))
    return tuple(order)


def ring_dim(spec: P, shape: Tuple[int, int], mesh: Mesh) -> Optional[int]:
    """The dimension of an (in, out) kernel that `ring_weight_grad` cuts in
    chunks: the one its spec places on "fsdp", if the axis shards and
    divides it. None: the leaf keeps the plain product."""
    fsdp = mesh.shape.get("fsdp", 1)
    dims = [d for d, ax in enumerate(spec) if ax == "fsdp"]
    if fsdp == 1 or len(dims) != 1 or shape[dims[0]] % fsdp:
        return None
    return dims[0]


def ring_weight_grad(x: jax.Array, dy: jax.Array, mesh: Mesh, spec: P,
                     dtype: Any) -> jax.Array:
    """A matmul site's kernel gradient, x^T dy summed over every chip's rows,
    computed and reduce-scattered over "fsdp" in one ring ("collective
    matmul"): the ZeRO-3 backward of --gather_overlap.

    The whole product followed by GSPMD's reduce-scatter lowers on the TPU to
    a fused all-reduce + dynamic-slice that no compiler option runs
    asynchronously (PERF.md, PR 50): a seventh of the step's busy time with
    the MXU idle. Here the dimension `spec` shards on "fsdp" (columns of dy
    for qkv, proj and fc1; columns of x for fc2) is cut in fsdp chunks. Chip
    i starts with its partial product of chunk i - 1; each of the fsdp - 1
    steps after it sends the running sum one chip down the ring
    (`ppermute`, the collective the TPU compiler does overlap) and adds the
    partial product of the next chunk up, so chip i ends with chunk i summed
    over all chips: the shard it owns, in the stacked tree's own layout.
    Half of every chunk goes round the ring each way (`ring_order` says
    which chips are neighbours), where a chunk halves. Partial products
    accumulate in float32 as the MXU gives them and the incoming sum is
    added in float32; the wire and the result are `dtype` (what the plain
    schedule reduces in: the gathered kernel's dtype).

    x (B, ..., in) and dy (B, ..., out) are a site's rows with the batch over
    BATCH_AXES. Manual on "fsdp" alone: a "tp" / "ep" placement and the sum
    over "dp" stay the partitioner's. Where `ring_dim` finds nothing to cut,
    or the batch does not split over dp x fsdp, the plain product."""
    n, dp = mesh.shape.get("fsdp", 1), mesh.shape.get("dp", 1)
    dim = ring_dim(spec, (x.shape[-1], dy.shape[-1]), mesh)
    rows = tuple(range(x.ndim - 1))
    if dim is None or x.shape[0] % (dp * n):
        return jax.lax.dot_general(x, dy, ((rows, rows), ((), ()))).astype(dtype)

    order = ring_order(mesh)
    place = np.argsort(order)        # a chip's index -> its place in the ring

    def ring(x, dy):
        x, dy = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
        me = jnp.asarray(place)[jax.lax.axis_index("fsdp")]
        cut = x if dim == 0 else dy
        width = cut.shape[-1] // n
        # both directions at once where a chunk halves: a link carries half
        # the bytes each way (one way left 6.7% of the four-chip cell's
        # window waiting on a hop, both ways 0.9%: PERF.md, PR 50)
        ways = (1, -1) if width % 2 == 0 else (1,)
        part = width // len(ways)

        def partial(owner, lane):
            chunk = jax.lax.dynamic_slice_in_dim(
                cut, owner * width + lane * part, part, 1)
            a, b = (chunk, dy) if dim == 0 else (x, chunk)
            return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        sums = []
        for lane, way in enumerate(ways):
            hop = [(order[j], order[(j + way) % n]) for j in range(n)]
            owner = jnp.asarray(order)[(me - way) % n]
            acc = partial(owner, lane).astype(dtype)
            for step in range(1, n):
                arrived = jax.lax.ppermute(acc, "fsdp", hop)
                owner = jnp.asarray(order)[(me - way * (1 + step)) % n]
                # a chunk's product waits for the sum it is added to: left
                # free, the compiler runs every product at the top of the
                # scan body and keeps them in float32 until their turn
                # (six [5120, 5120] in the four-chip cell, 0.65 GB, and a
                # slower step)
                owner, arrived = jax.lax.optimization_barrier((owner, arrived))
                acc = (partial(owner, lane)
                       + arrived.astype(jnp.float32)).astype(dtype)
            sums.append(acc)
        return jnp.concatenate(sums, dim)

    # the batch dim as (dp, fsdp, rest): the layout BATCH_AXES gives it
    # already, so each chip rings the rows it holds and nothing moves
    split = lambda a: a.reshape(dp, n, a.shape[0] // (dp * n), *a.shape[1:])
    grad = shard_map(
        ring, mesh, in_specs=(P(None, "fsdp"), P(None, "fsdp")),
        out_specs=P(*("fsdp" if d == dim else None for d in range(2))),
        axis_names={"fsdp"})(split(x), split(dy))
    return jax.lax.with_sharding_constraint(grad, NamedSharding(mesh, spec))


def ring_dot_general(mesh: Mesh, spec: P) -> Callable:
    """The `dot_general` of an `nn.Dense` site whose (in, out) kernel carries
    `spec`: forward and the rows' cotangent are `jax.lax.dot_general`'s, the
    kernel's cotangent comes from `ring_weight_grad`. A kernel with nothing
    for the ring to cut keeps `jax.lax.dot_general` whole."""

    def dot_general(lhs, rhs, dimension_numbers, precision=None,
                    preferred_element_type=None):
        def plain(lhs, rhs):
            return jax.lax.dot_general(
                lhs, rhs, dimension_numbers, precision=precision,
                preferred_element_type=preferred_element_type)

        if ring_dim(spec, rhs.shape, mesh) is None:
            return plain(lhs, rhs)

        @jax.custom_vjp
        def dot(lhs, rhs):
            return plain(lhs, rhs)

        def fwd(lhs, rhs):
            return plain(lhs, rhs), (lhs, rhs)

        def bwd(res, dy):
            lhs, rhs = res
            _, rows_vjp = jax.vjp(lambda l: plain(l, rhs), lhs)
            return (*rows_vjp(dy),
                    ring_weight_grad(lhs, dy, mesh, spec, rhs.dtype))

        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)

    return dot_general


def cast_to_compute(
    params: PyTree,
    dtype: Any = jnp.bfloat16,
    shardings: Optional[PyTree] = None,
    grad_reduce_dtype: Any = jnp.float32,
    keep_f32: Tuple[str, ...] = KEEP_F32_PARAMS,
) -> PyTree:
    """Downcast the param tree to the compute dtype *while still sharded*.

    The point: flax's `promote_dtype` casts params at the use site — *after*
    GSPMD has gathered them — so every FSDP all-gather moves f32 bytes even in
    a bf16 run. Casting each shard first commutes with the gather (a gather
    rearranges bits, a cast maps them elementwise), so applying the model with
    the pre-cast tree is bitwise-identical to gather-then-cast while every
    param collective (ZeRO-3 per-block gathers, the ZeRO-2 step-top gather,
    pipeline in-body gathers) moves half the bytes.

    Each cast leaf is a `custom_vjp` convert:

    - forward: `astype(dtype)` + re-anchor to the leaf's own NamedSharding (the
      cast must not perturb GSPMD's placement of the downstream gather);
    - backward: upcast the cotangent to f32 and pin it to the shard layout —
      with `grad_reduce_dtype=float32` the convert runs *before* the sharded
      anchor, so the grad reduce-scatter / all-reduce happens on f32 bits
      (exact current numerics); with bfloat16 the anchor is applied to the
      bf16 cotangent first, pinning the reduction on bf16 bits (2x fewer grad
      bytes, an opt-in precision trade).

    Leaves consumed at f32 by the model (`keep_f32`: head, router) and non-f32
    leaves pass through untouched. `shardings` must mirror `params`
    (leaf-for-leaf NamedShardings) or be None (no re-anchor; single-device).
    """
    cdtype = jnp.dtype(dtype)
    reduce_bf16 = jnp.dtype(grad_reduce_dtype) == jnp.bfloat16

    def leaf_fn(path, x, sh):
        names = _path_names(path)
        if x.dtype != jnp.float32 or any(k in names for k in keep_f32):
            return x

        def _fwd_impl(v):
            y = v.astype(cdtype)
            if sh is not None:
                y = jax.lax.with_sharding_constraint(y, sh)
            return y

        @jax.custom_vjp
        def cast(v):
            return _fwd_impl(v)

        def fwd(v):
            return _fwd_impl(v), None

        def bwd(_, g):
            if reduce_bf16 and sh is not None:
                g = jax.lax.with_sharding_constraint(g, sh)
            g = g.astype(jnp.float32)
            if not reduce_bf16 and sh is not None:
                g = jax.lax.with_sharding_constraint(g, sh)
            return (g,)

        cast.defvjp(fwd, bwd)
        return cast(x)

    if shardings is None:
        return jax.tree_util.tree_map_with_path(
            lambda p, x: leaf_fn(p, x, None), params)
    return jax.tree_util.tree_map_with_path(leaf_fn, params, shardings)


class CommPrecision:
    """Resolved comm-precision policy for one (cfg, mesh, param-spec) triple.

    Built by `make_comm_precision` only when the policy is active
    (cfg.comm_cast_active); callers hold `Optional[CommPrecision]` and treat
    None as "f32 collectives, pre-PR program".

    `cast` downcasts the tree (see `cast_to_compute`); apply it *inside* the
    differentiated function where possible so the convert-vjp upcasts and pins
    the cotangent at the cast boundary. `finalize_grads` is the explicit
    equivalent for the path that casts outside autodiff (ZeRO-2's step-top
    gather): it upcasts any bf16 grad leaf to f32,
    pinning the reduction dtype the same way. It is a no-op on f32 leaves, so
    applying it unconditionally after any grad path is safe.
    """

    def __init__(self, cfg: Config, mesh: Mesh, params_specs: PyTree):
        self.dtype = jnp.dtype(cfg.dtype)
        self.reduce_bf16 = cfg.grad_reduce_dtype == "bfloat16"
        self.grad_reduce_dtype = (
            jnp.bfloat16 if self.reduce_bf16 else jnp.float32)
        self.shardings = shardings_of(mesh, params_specs)

    def cast(self, params: PyTree) -> PyTree:
        return cast_to_compute(
            params, self.dtype, self.shardings, self.grad_reduce_dtype)

    def finalize_grads(self, grads: PyTree) -> PyTree:
        def leaf(g, sh):
            if g.dtype != self.dtype:
                return g
            if self.reduce_bf16:
                g = jax.lax.with_sharding_constraint(g, sh)
            return g.astype(jnp.float32)
        return jax.tree.map(leaf, grads, self.shardings)


def make_comm_precision(
    cfg: Config, mesh: Mesh, params_specs: PyTree,
) -> Optional[CommPrecision]:
    """CommPrecision when the bf16 comm-cast policy is active, else None."""
    if not cfg.comm_cast_active:
        return None
    return CommPrecision(cfg, mesh, params_specs)


def jit_init_sharded(
    init_fn: Callable[[jax.Array], PyTree],
    rng: jax.Array,
    shardings: PyTree,
    shard_on_cpu: bool = False,
) -> PyTree:
    """Run an initializer so its outputs are born sharded.

    Default path: `jax.jit(init_fn, out_shardings=...)` — XLA materializes each
    array already laid out across the mesh; peak memory per device is the shard
    size, not the full model (SURVEY.md section 7 'hard parts' #1).

    `shard_on_cpu` path: run the initializer on host CPU, then `device_put`
    leaf-by-leaf to the target sharding (each host slices out only its
    addressable shards). Literal equivalent of FSDP's CPU-side shard
    construction (reference run_vit_training.py:175-181, pytorch/xla#3992).
    """
    if shard_on_cpu:
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            host_tree = jax.jit(init_fn)(jax.device_put(rng, cpu))
        # device_put of a host array can be zero-copy ADOPTED by the CPU
        # backend, leaving the params backed by malloc-heap memory that the
        # donating train step later reuses in place (same hazard as
        # checkpoint/peer.assemble_state). Launder each leaf through a jitted
        # on-device copy so the returned tree is backed by fresh XLA-owned
        # buffers, exactly like the jit-init path below.
        placed = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x).copy(), s),
            host_tree, shardings)
        return jax.tree.map(jax.jit(jnp.copy), placed)
    return jax.jit(init_fn, out_shardings=shardings)(rng)


def init_sharded_params(
    init_fn: Callable[[jax.Array], PyTree],
    rng: jax.Array,
    cfg: Config,
    mesh: Mesh,
) -> Tuple[PyTree, PyTree]:
    """Initialize parameters directly into their FSDP/TP shards."""
    abstract = jax.eval_shape(init_fn, rng)
    specs = param_specs(abstract, cfg, mesh)
    params = jit_init_sharded(init_fn, rng, shardings_of(mesh, specs), cfg.shard_on_cpu)
    return params, specs
