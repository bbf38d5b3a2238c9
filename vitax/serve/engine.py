"""InferenceEngine: checkpoint -> jitted eval-mode forward over bucketed batches.

The inference half of the stack (ROADMAP north star: serve heavy traffic).
Params come from either source the training side produces:

- `from_checkpoint`: a sharded Orbax epoch checkpoint (vitax/checkpoint/
  orbax_io.py) restored straight into the serving mesh layout — the abstract
  target tree carries the same param_specs shardings training used, so a
  checkpoint written on one topology serves on another;
- `from_npz`: a consolidated single-file export (vitax/checkpoint/
  consolidate.py), restored to the exact param tree via the shared
  flatten_tree/unflatten_tree key convention, then device_put per-shard.

The forward is eval-mode only (det=True: no dropout, no loss, no optimizer
state — the restored opt_state is dropped on the floor so a 10B serve fits
in a third of the training footprint) and is AOT-compiled once per
power-of-two batch bucket (1, 2, 4, ..., serve_max_batch) at startup
(`warmup`). Requests are padded to the next bucket, so steady-state traffic
executes precompiled programs only: `compile_count` is exactly
len(bucket_sizes) after warmup and never moves again — recompiles are
structurally impossible because `predict` calls AOT executables, which
reject any shape they were not compiled for (tests/test_serve.py pins this).

A batch goes through the engine in two phases, split where the host would
block. `dispatch(images)` does all the host's work — fault hook, bucket
choice, padding, `device_put`, the compiled call — and returns a `Dispatched`
handle at once: the device runs the batch (behind the one before it, if that
is still running) while the caller does something else. `handle.result()`
blocks on the outputs, fetches the top-k and cuts the padding off;
`handle.done()` says without blocking whether they are ready. The handle
carries its own batch's two marks, `t_dispatch` (`device_put` returned) and
`t_wait` (`result()` began to block): with two batches in flight, marks kept
on the engine would be the other batch's. `predict(images)` is
`dispatch(images).result()`. The batcher (vitax/serve/batcher.py) keeps at
most one batch queued on the device behind the one that runs.

The bucket programs do not take `engine.params`: they take the engine's
COMPUTE TREE (`compute_params`), the same tree with float32 leaves cast to
the model's compute dtype that the forward would cast before their first use
(vitax/models/vit.py `cast_before_use`: Dense/Conv kernels and biases of the
`dtype=self.dtype` sites, MoE expert weights, pos_embed). Served weights
never change, so that cast is loop-invariant over requests: `warmup` runs it
once, before the first bucket compiles, and a pre-cast leaf costs the
per-batch program neither a weight-sized `convert` nor a weight-sized
temporary. The outputs are bit-identical to the forward on `engine.params`.
`params` stays what the caller handed in, resident and untouched: the served
model's source of truth. So a pre-cast leaf is resident twice, and the engine
takes copies, largest leaf first, only while `params`, the scales and the
copies together stay within WEIGHTS_MEMORY_SHARE of a device's memory; a
leaf left out is cast inside the program, as all were before. On a float32
config, and on a quantized engine (int8/fp8 leaves enter the program as they
are), the compute tree IS `params`: no copy, no extra program. Accounting,
also on /metrics and the `serve_start` event:

  precast_leaves   leaves the compute tree holds as its own cast copy
                   (0 on the float32 and quantized paths)
  precast_bytes    logical bytes of those copies
  param_bytes()    everything a warmed engine keeps resident: `params`, the
                   quant scales, and the pre-cast copies
  weights_dtype    dtype of `params` (the quant dtype when quantized), not
                   of the compute tree
"""

from __future__ import annotations

import math
import time
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from vitax import faults
from vitax.config import Config
from vitax.models.vit import cast_before_use
from vitax.parallel.mesh import BATCH_AXES, Mesh, batch_pspec, build_mesh
from vitax.programs.builder import Geometry, build_model_for
from vitax.utils.logging import master_print


def bucket_sizes(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two buckets 1, 2, 4, ..., max_batch (validate() guarantees
    max_batch is itself a power of two)."""
    sizes = []
    b = 1
    while b <= max_batch:
        sizes.append(b)
        b *= 2
    return tuple(sizes)


def next_bucket(n: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket holding n requests (n must fit the largest bucket)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"batch of {n} exceeds the largest bucket {buckets[-1]} "
        f"(--serve_max_batch); the batcher never emits this")


# The share of a device's memory the engine will fill with weights: `params`,
# the quant scales and the compute tree's pre-cast copies. The rest stays free
# for the bucket programs' temporaries, for what else the process runs on the
# device beside serving (an eval-mode forward over `params`, as chip_smoke.py
# and the benchmark's reference check run, wants a layer or two of float32
# weights at a time) and for fragmentation.
WEIGHTS_MEMORY_SHARE = 0.8


def device_memory_limit(mesh: Mesh) -> Optional[int]:
    """`bytes_limit` of the smallest device of the mesh, or None where a
    device reports none: the CPU backend, a described (not attached) chip."""
    limits = []
    for device in mesh.devices.flat:
        try:
            stats = device.memory_stats() or {}
        except jax.errors.JaxRuntimeError:  # "only supported for addressable"
            stats = {}
        if "bytes_limit" not in stats:
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits)


def _quant_model_mode(cfg: Config) -> bool:
    """Whether serving uses the QuantDense model (vitax/models/vit.py):
    quantized weights with activation quant and/or the fused dequant-matmul
    engaged. Weight-only serving with the fused kernel off keeps the PR-14
    `dequantize_tree` path — same jit signature either way, so VTX-R007's
    arg pins hold for both."""
    from vitax.ops.dequant_matmul import fused_dequant_active
    if not getattr(cfg, "serve_quant_dtype", ""):
        return False
    if getattr(cfg, "moe_experts", 0) > 0:
        return False
    return (getattr(cfg, "serve_act_quant", "off") != "off"
            or fused_dequant_active(cfg))


def _build_model(cfg: Config, mesh: Mesh, quantized: bool = True):
    """The model the training loop builds (vitax/programs/builder.py
    `build_model_for`: attention core and activation anchors included), so
    serving runs the forward graph eval ran — except under quant-model mode
    (quantized=True and _quant_model_mode), where every Dense site becomes
    QuantDense consuming the quantized kernel + merged qscale directly.
    quantized=False forces the plain model (full-precision param sources:
    from_checkpoint, param init in the invariant arms)."""
    quant_matmul = None
    if quantized and _quant_model_mode(cfg):
        from vitax.ops.dequant_matmul import make_quant_matmul
        quant_matmul = make_quant_matmul(cfg)
    return build_model_for(cfg, mesh, quant_matmul=quant_matmul)


class Dispatched:
    """One batch between `InferenceEngine.dispatch` and its answers: the
    compiled call's outputs, still on the device, and the batch's own marks
    (`time.time()`): `t_dispatch` when `device_put` returned, `t_wait` when
    `result()` began to block (None until then)."""

    __slots__ = ("_outputs", "_n", "t_dispatch", "t_wait")

    def __init__(self, outputs, n: int, t_dispatch: float):
        self._outputs = outputs       # (top_i, top_p) of the padded bucket
        self._n = n                   # real rows
        self.t_dispatch = t_dispatch
        self.t_wait = None

    def done(self) -> bool:
        """Whether `result()` would return without waiting for the device."""
        return all(out.is_ready() for out in self._outputs)

    def result(self) -> Tuple[np.ndarray, np.ndarray]:
        """Block on the outputs; (top-k ids (n, k) int32, probs (n, k)
        float32) of the real rows."""
        self.t_wait = time.time()
        top_i, top_p = self._outputs
        return np.asarray(top_i)[:self._n], np.asarray(top_p)[:self._n]


class InferenceEngine:
    """Bucketed eval-mode forward: uint8 (B, H, W, 3) images -> top-k.

    Thread-compatible by design: `dispatch`, the handles' `result` and
    `predict` are called from the batcher's single worker thread;
    construction/warmup happen before the server accepts traffic.
    """

    def __init__(self, cfg: Config, mesh: Mesh, model, params,
                 scales: Optional[Dict[str, jax.Array]] = None,
                 quant_dtype: str = ""):
        assert getattr(cfg, "pp_size", 1) == 1, (
            "serving v1 runs the non-pipelined forward; restore a pp "
            "checkpoint with --pp_size 1 (Orbax reshards on load)")
        assert bool(scales) == bool(quant_dtype), (
            "quantized engines carry both scales and quant_dtype")
        self.cfg = cfg
        self.mesh = mesh
        self.model = model
        self.params = params
        # quantized serving: int8 leaves stay int8 on device; scales is the
        # flat {param_key: float32 per-output-channel scale} side table
        # (replicated — O(out_channels)), and the jitted predict dequantizes
        # at use so XLA fuses the convert into the matmul (vitax/serve/
        # quant.py). Empty scales = plain full-precision engine.
        self.scales: Dict[str, jax.Array] = scales or {}
        self.quant_dtype = quant_dtype
        # tier-2 quant accounting (reported on /metrics, aggregated by the
        # fleet router, scraped by serve_bench): dynamic activation quant
        # mode and whether the Pallas fused dequant-matmul is engaged
        from vitax.ops.dequant_matmul import fused_dequant_active
        quantized = bool(self.scales)
        self.act_quant = (getattr(cfg, "serve_act_quant", "off")
                          if quantized else "off")
        self.fused_dequant = bool(quantized and fused_dequant_active(cfg))
        self._quant_model = quantized and _quant_model_mode(cfg)
        self.topk = min(cfg.serve_topk, cfg.num_classes)
        self.buckets = bucket_sizes(cfg.serve_max_batch)
        self.compile_count = 0          # warmup compiles; pinned by tests
        # readiness vs liveness: the HTTP server is LIVE as soon as it binds
        # (healthz answers), but READY only once every AOT bucket is compiled
        # and exercised — a fleet router must not dispatch to a warming
        # replica (vitax/serve/fleet/replica.py keys off healthz "ready")
        self.ready = False
        self._compiled: Dict[int, jax.stages.Compiled] = {}
        self._batch_shardings: Dict[int, NamedSharding] = {}
        # batch-carrying device count: buckets divisible by it shard the
        # batch; smaller buckets replicate (tiny inputs, sharded params)
        self._batch_devices = 1
        for ax in BATCH_AXES:
            self._batch_devices *= mesh.shape.get(ax, 1)
        from vitax.parallel.sharding import param_specs, shardings_of
        self._param_shardings = shardings_of(
            mesh, param_specs(params, cfg, mesh))
        # the compute tree's plan, from what is in sight here (the model's
        # compute dtype, the scales, each leaf's role, dtype and shard, the
        # devices' memory). Nothing is cast yet: `compute_params` runs the
        # one program, at warmup
        self._precast = self._plan_precast()
        picked = [v for v, m in zip(jax.tree.leaves(params), self._precast)
                  if m]
        self.precast_leaves = len(picked)
        self.precast_bytes = sum(
            int(v.size) * jnp.dtype(model.dtype).itemsize for v in picked)
        self._compute_params = None if picked else params

    def _plan_precast(self):
        """One bool a leaf of `params`, in `jax.tree.leaves` order: whether
        the compute tree holds that leaf as its own cast copy. Candidates
        are the float32 leaves the forward casts before use; none on a
        float32 config or a quantized engine. Largest first, a candidate is
        taken while the weights resident on one device stay within
        WEIGHTS_MEMORY_SHARE of the device's memory; where no limit is
        known, all are."""
        flat = jax.tree_util.tree_flatten_with_path(self.params)[0]
        take = [False] * len(flat)
        dtype = jnp.dtype(self.model.dtype)
        if self.scales or dtype == jnp.float32:
            return take
        shard_elems = [math.prod(sh.shard_shape(v.shape)) for (_, v), sh in
                       zip(flat, jax.tree.leaves(self._param_shardings))]
        limit = device_memory_limit(self.mesh)
        room = math.inf if limit is None else (
            WEIGHTS_MEMORY_SHARE * limit
            - sum(n * v.dtype.itemsize for n, (_, v) in zip(shard_elems, flat)))
        candidates = [i for i, (path, v) in enumerate(flat)
                      if v.dtype == jnp.float32 and cast_before_use(path)]
        for i in sorted(candidates, key=lambda i: -shard_elems[i]):
            cost = shard_elems[i] * dtype.itemsize
            if cost <= room:
                take[i] = True
                room -= cost
        return take

    # --- accounting (reported on /metrics and by serve_bench) -------------

    @property
    def quantized(self) -> bool:
        return bool(self.scales)

    @property
    def weights_dtype(self) -> str:
        """Dtype of the matmul weights as resident on device: the quant
        dtype for a quantized engine, else the dtype of the largest leaf
        (LN/bias stragglers don't get to name a bf16 or f32 tree)."""
        if self.scales:
            return self.quant_dtype
        largest = max(jax.tree.leaves(self.params), key=lambda v: v.size)
        return str(largest.dtype)

    def param_bytes(self) -> int:
        """Device-resident parameter footprint of the warmed engine: weight
        leaves, the quant scale side table and the compute tree's pre-cast
        copies, logical (unsharded) bytes — the per-replica HBM number the
        fleet density math runs on."""
        total = sum(int(v.nbytes) for v in jax.tree.leaves(self.params))
        total += sum(int(v.nbytes) for v in self.scales.values())
        return total + self.precast_bytes

    @property
    def compute_params(self):
        """The tree every bucket program is lowered against and called with:
        `params` with the planned leaves cast to the model's compute dtype,
        in the same param_specs layout (a sharded serve mesh keeps its
        shards); every other leaf is the very array `params` holds. Made on
        first use — `warmup` asks before the first bucket compiles — and
        kept. Over abstract params (ShapeDtypeStruct leaves: the analysis
        and AOT arms) it is `jax.eval_shape` of the same cast, nothing runs.
        `params` itself when there is nothing to cast."""
        if self._compute_params is None:
            dtype = self.model.dtype
            leaves, treedef = jax.tree.flatten(self.params)
            mask = self._precast
            shardings = jax.tree.leaves(self._param_shardings)
            cast = jax.jit(
                lambda xs: [x.astype(dtype) for x in xs],
                out_shardings=[s for s, m in zip(shardings, mask) if m])
            picked = [x for x, m in zip(leaves, mask) if m]
            abstract = isinstance(picked[0], jax.ShapeDtypeStruct)
            done = iter(jax.eval_shape(cast, picked) if abstract
                        else cast(picked))
            self._compute_params = treedef.unflatten(
                [next(done) if m else x for x, m in zip(leaves, mask)])
        return self._compute_params

    # --- constructors -----------------------------------------------------

    @classmethod
    def from_checkpoint(cls, cfg: Config, ckpt_dir: Optional[str] = None,
                        epoch: Optional[int] = None) -> "InferenceEngine":
        """Restore params from a sharded Orbax epoch checkpoint (epoch None =
        latest) directly into the serving mesh layout."""
        from vitax.checkpoint.orbax_io import latest_epoch, restore_state
        ckpt_dir = ckpt_dir or cfg.ckpt_dir
        if epoch is None:
            epoch = latest_epoch(ckpt_dir)
            assert epoch is not None, f"no epoch checkpoint under {ckpt_dir}"
        # the abstract TrainState is the restore target (no device
        # materialization); the optimizer exists only to shape it — its
        # restored moments are dropped immediately below
        geom = Geometry.assemble(cfg, max_iteration=1)
        mesh, model = geom.mesh, geom.model
        state = restore_state(ckpt_dir, epoch, geom.abstract_state)
        engine = cls(cfg, mesh, model, state.params)
        del state  # opt_state/step freed: serving holds params only
        master_print(f"serve: params from Orbax checkpoint "
                     f"{ckpt_dir} epoch {epoch}")
        return engine

    @classmethod
    def from_npz(cls, cfg: Config, path: str) -> "InferenceEngine":
        """Restore params from a consolidated .npz export
        (vitax/checkpoint/consolidate.py) — the exact tree comes back through
        the shared flatten/unflatten key convention, then every leaf is
        device_put into its param_specs shard layout.

        A `__quant__`-manifested export loads its int8 leaves AS INT8 on
        device (param_pspec keys off path+shape, so the shard layout is the
        f32 one) with the scale side table replicated; the file's manifest
        is authoritative. --serve_quant_dtype only ASSERTS the expectation —
        pointing a quantized config at an unquantized export fails loudly
        instead of silently serving 4x the HBM."""
        from vitax.checkpoint.consolidate import load_npz_raw, unflatten_tree
        from vitax.parallel.sharding import param_specs, shardings_of
        mesh = build_mesh(cfg)
        model = _build_model(cfg, mesh)
        flat, scales, manifest = load_npz_raw(path)
        want = getattr(cfg, "serve_quant_dtype", "")
        if want and not manifest:
            raise ValueError(
                f"--serve_quant_dtype {want} but {path} has no __quant__ "
                f"manifest; re-export with consolidate.py --dtype {want}")
        params = unflatten_tree(flat)
        shardings = shardings_of(mesh, param_specs(params, cfg, mesh))
        params = jax.tree.map(jax.device_put, params, shardings)
        quant_dtype = ""
        if manifest:
            from vitax.serve.quant import scale_shardings
            quant_dtype = sorted(set(manifest.values()))[0]
            sc_sh = scale_shardings(scales, mesh)
            scales = {k: jax.device_put(v, sc_sh[k])
                      for k, v in scales.items()}
        else:
            scales = {}
        master_print(f"serve: params from consolidated export {path}"
                     + (f" (quantized: {quant_dtype}, "
                        f"{len(scales)} scaled leaves)" if manifest else ""))
        return cls(cfg, mesh, model, params, scales=scales,
                   quant_dtype=quant_dtype)

    # --- compilation ------------------------------------------------------

    def _batch_sharding(self, bucket: int) -> NamedSharding:
        if bucket % self._batch_devices == 0:
            return NamedSharding(self.mesh, batch_pspec())
        return NamedSharding(self.mesh, P())  # replicate sub-mesh buckets

    def _predict_fn(self):
        model, k = self.model, self.topk

        def forward(params, images):
            from vitax.train.step import prepare_images
            logits = model.apply(params, prepare_images(images), True)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            top_p, top_i = jax.lax.top_k(probs, k)
            return top_i.astype(jnp.int32), top_p

        if not self.scales:
            return forward

        if self._quant_model:
            def predict_quant_model(params, scales, images):
                # QuantDense mode: Dense-site kernels stay quantized all the
                # way into the matmul (fused Pallas kernel and/or int8 x
                # int8 dots — vitax/ops/dequant_matmul.py); their scales
                # merge into the tree as sibling qscale leaves, and only
                # the non-site leaves (the patchify conv) dequantize
                # in-place. VTX-R009 pins the result on the traced jaxpr.
                from vitax.serve.quant import (
                    dense_site_kind, dequantize_tree, merge_quant_scales)
                site = {k: s for k, s in scales.items()
                        if dense_site_kind(k)}
                rest = {k: s for k, s in scales.items()
                        if not dense_site_kind(k)}
                p = dequantize_tree(params, rest)
                return forward(merge_quant_scales(p, site), images)

            return predict_quant_model

        def predict_quant(params, scales, images):
            # dequant INSIDE the jitted program: int8 weights enter as
            # program arguments, `(w * scale).astype(f32)` fuses into each
            # consuming matmul, and no f32 weight tensor outlives the call
            # (VTX-R007 pins this on the lowered args)
            from vitax.serve.quant import dequantize_tree
            return forward(dequantize_tree(params, scales), images)

        return predict_quant

    def _lower_bucket(self, bucket: int):
        """Lower (but do not compile) the predict program for one bucket —
        shared by warmup compilation and the analysis rules, which inspect
        the StableHLO without disturbing compile_count. Lowered against the
        compute tree, in the layout of `params`."""
        batch_sh = self._batch_sharding(bucket)
        params, param_sh = self.compute_params, self._param_shardings
        s = self.cfg.image_size
        images = jax.ShapeDtypeStruct((bucket, s, s, 3), jnp.uint8,
                                      sharding=batch_sh)
        if self.scales:
            scale_sh = {k: NamedSharding(self.mesh, P())
                        for k in self.scales}
            fn = jax.jit(self._predict_fn(),
                         in_shardings=(param_sh, scale_sh, batch_sh),
                         out_shardings=None)
            lowered = fn.lower(params, self.scales, images)
        else:
            fn = jax.jit(self._predict_fn(),
                         in_shardings=(param_sh, batch_sh),
                         out_shardings=None)
            lowered = fn.lower(params, images)
        return lowered, batch_sh

    def lower_bucket_mlir(self, bucket: int) -> str:
        """StableHLO text of one bucket's predict program (no compile, no
        compile_count movement) — the VTX-R007 artifact."""
        lowered, _ = self._lower_bucket(bucket)
        return lowered.as_text()

    def trace_bucket_jaxpr(self, bucket: int) -> str:
        """Traced jaxpr text of one bucket's predict program — the VTX-R009
        artifact. Interpret-mode Pallas leaves no custom-call marker in
        StableHLO (the VTX-R008 lesson), so the fused-dequant rule reads the
        jaxpr, where every launch keeps DEQUANT_KERNEL_NAME in its
        pallas_call params and every convert_element_type is visible."""
        s = self.cfg.image_size
        images = jax.ShapeDtypeStruct((bucket, s, s, 3), jnp.uint8)
        fn = self._predict_fn()
        if self.scales:
            jaxpr = jax.make_jaxpr(fn)(self.params, self.scales, images)
        else:
            jaxpr = jax.make_jaxpr(fn)(self.compute_params, images)
        return str(jaxpr)

    def _compile_bucket(self, bucket: int) -> jax.stages.Compiled:
        lowered, batch_sh = self._lower_bucket(bucket)
        compiled = lowered.compile()
        self.compile_count += 1
        self._batch_shardings[bucket] = batch_sh
        return compiled

    def warmup(self) -> Dict[int, float]:
        """Make the compute tree, then AOT-compile every bucket and run each
        once (first execution pays allocator/transfer setup). Returns
        {bucket: seconds} for the log."""
        t0 = time.time()
        jax.block_until_ready(self.compute_params)
        cast_s = time.time() - t0
        timings = {}
        s = self.cfg.image_size
        for b in self.buckets:
            t0 = time.time()
            self._compiled[b] = self._compile_bucket(b)
            self._run(b, np.zeros((b, s, s, 3), np.uint8), b).result()
            timings[b] = time.time() - t0
        self.ready = True
        master_print(
            "serve: warmup compiled buckets "
            + ", ".join(f"{b}:{t:.2f}s" for b, t in timings.items())
            + f"; pre-cast {self.precast_leaves} leaves "
              f"({self.precast_bytes:,} B) in {cast_s:.2f}s")
        return timings

    # --- inference --------------------------------------------------------

    def _run(self, bucket: int, images: np.ndarray, n: int) -> Dispatched:
        """Put one padded batch on the device and call its bucket's program;
        returns without waiting for either."""
        batch = jax.device_put(images, self._batch_shardings[bucket])
        t_dispatch = time.time()
        if self.scales:
            out = self._compiled[bucket](self.params, self.scales, batch)
        else:
            out = self._compiled[bucket](self.compute_params, batch)
        return Dispatched(out, n, t_dispatch)

    def dispatch(self, images: np.ndarray) -> Dispatched:
        """(n, H, W, 3) uint8 -> handle of the batch, which the device now
        runs or holds queued; nothing here waits for it. Pads to the next
        bucket; the padded rows' outputs are discarded by `result()`. Only
        precompiled buckets execute — an unseen shape raises instead of
        silently recompiling."""
        faults.fire("engine_predict")
        n = images.shape[0]
        bucket = next_bucket(n, self.buckets)
        assert bucket in self._compiled, (
            f"bucket {bucket} not warmed up — call warmup() before serving")
        if n < bucket:
            padded = np.zeros((bucket,) + images.shape[1:], images.dtype)
            padded[:n] = images
            images = padded
        return self._run(bucket, images, n)

    def predict(self, images: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(n, H, W, 3) uint8 -> (top-k class ids (n, k) int32,
        top-k probs (n, k) float32): `dispatch`, then wait for the answers."""
        return self.dispatch(images).result()
