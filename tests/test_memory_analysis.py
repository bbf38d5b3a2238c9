"""HLO-level proof of ZeRO-3 memory behavior (VERDICT round-1 item 2).

The whole scan+GSPMD design bets that XLA keeps per-layer all-gathers INSIDE
the scan's while loop instead of hoisting a full-model gather before it — the
property nested FSDP wrapping guarantees by construction in the reference
(run_vit_training.py:177-181; SURVEY.md section 7 hard-part #2). These tests
discharge that bet from the compiled (optimized, SPMD-partitioned) HLO of the
real ViT-L/14 train step on the 8-device mesh:

1. per-device argument memory is shard-bound (== global state / 8);
2. transient (temp) memory is far below full-model size — no hoisted gather;
3. every all-gather's output is per-layer/activation sized, never the stacked
   24-block parameter tensor;
4. the block-weight all-gathers carry `while/body` scope metadata in both the
   forward and the rematted backward scan — they run once per layer step,
   inside the loop.

Plus a 10B-shape (BASELINE config 4) eval_shape + AOT lowering smoke: the
flagship config traces and lowers without materializing anything.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from vitax.config import Config
from vitax.models import build_model, count_params
from vitax.parallel.mesh import batch_pspec, build_mesh
from vitax.train.state import build_optimizer, make_train_state
from vitax.train.step import make_train_step


def _lower_train_step(cfg, n_steps_sched=100, n_devices=None):
    mesh = build_mesh(cfg, devices=jax.devices()[:n_devices]
                      if n_devices else None)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=n_steps_sched)
    state, sspecs, _ = make_train_state(
        cfg, model, tx, mesh, jax.random.key(0), materialize=False)
    step = make_train_step(cfg, model, tx, mesh, sspecs)
    sh = NamedSharding(mesh, batch_pspec())
    batch = {
        "image": jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.image_size, cfg.image_size, 3),
            jnp.float32, sharding=sh),
        "label": jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32, sharding=sh),
    }
    return state, step.lower(state, batch, jax.random.key(0))


def _state_bytes(abstract_state) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(abstract_state))


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
                "u8": 1, "s8": 1, "f64": 8, "s64": 8, "u64": 8}


def _shape_bytes(shape_str: str) -> int:
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


@pytest.fixture(scope="module")
def l14(devices8):
    """Compiled ViT-L/14 FSDP train step (the BASELINE config-3 shape) on the
    8-device mesh, with its abstract state."""
    cfg = Config(image_size=224, patch_size=14, embed_dim=1024, num_heads=16,
                 num_blocks=24, num_classes=1000, batch_size=8,
                 warmup_steps=0).validate()
    state, lowered = _lower_train_step(cfg)
    compiled = lowered.compile()
    return cfg, state, compiled


def test_per_device_state_is_shard_bound(l14):
    """Each device's input (params + both AdamW moments + batch shard) must be
    ~1/8 of the global state — ZeRO-1/2/3 all hold simultaneously."""
    cfg, state, compiled = l14
    ma = compiled.memory_analysis()
    global_bytes = _state_bytes(state)
    batch_bytes = cfg.batch_size * cfg.image_size ** 2 * 3 * 4
    bound = global_bytes / 8 + batch_bytes
    assert ma.argument_size_in_bytes < bound * 1.10, (
        f"per-device args {ma.argument_size_in_bytes/1e6:.0f} MB exceed the "
        f"shard-bound {bound/1e6:.0f} MB — state is not fully sharded")


def test_temp_memory_is_not_model_bound(l14):
    """Transient memory must stay far below the full parameter tensor: a
    hoisted whole-model all-gather would show up here at >= 1.2 GB."""
    cfg, state, compiled = l14
    ma = compiled.memory_analysis()
    full_param_bytes = count_params_bytes(cfg)
    assert ma.temp_size_in_bytes < 0.5 * full_param_bytes, (
        f"temp {ma.temp_size_in_bytes/1e6:.0f} MB vs full params "
        f"{full_param_bytes/1e6:.0f} MB — looks like a hoisted full gather")


def count_params_bytes(cfg) -> int:
    from vitax.models.vit import expected_param_count
    return expected_param_count(cfg) * 4  # f32 master params


def test_no_all_gather_is_stack_sized(l14):
    """Every all-gather output must be per-layer/per-activation sized; the
    stacked (24, ...) block parameters must never be gathered whole."""
    cfg, state, compiled = l14
    txt = compiled.as_text()
    ags = re.findall(r"= (\S+) all-gather\(", txt)
    assert ags, "no all-gathers found — sharding did not engage"
    # largest legitimate gather: one layer's fc weights gathered as activations
    # (B, N, mlp_hidden) f32 = 8*256*4096*4 = 33.5 MB; the stacked fc1 kernel
    # would be 24*1024*4096*4 = 402 MB
    per_layer_bound = 64 * 1024 * 1024
    sizes = sorted((_shape_bytes(s) for s in ags), reverse=True)
    assert sizes[0] < per_layer_bound, (
        f"largest all-gather is {sizes[0]/1e6:.0f} MB — full-stack gather "
        "(ZeRO-3 memory bet violated)")


def _hlo_computations(txt: str) -> dict:
    """Parse compiled HLO text into {computation_name: [instruction lines]}.
    Computation definitions start at column 0 as `%name (params) -> type {`
    (optionally prefixed with ENTRY)."""
    comps = {}
    name = None
    for line in txt.splitlines():
        m = re.match(r"(?:ENTRY\s+)?(%[\w.\-]+)\s*\(", line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            if line.startswith("}"):
                name = None
            else:
                comps[name].append(line)
    return comps


def _while_body_names(txt: str) -> set:
    """Computation names referenced as `body=` by while ops — the structural
    (metadata-independent) definition of 'inside the scan loop'."""
    return set(re.findall(r"body=(%[\w.\-]+)", txt))


def _check_block_gathers_inside_loop(txt: str) -> None:
    """Assert the ZeRO-3 scheduling property from compiled HLO structure:
    block-weight all-gathers live inside while-loop bodies (fwd AND rematted
    bwd), and no gather outside a loop body touches the stacked block params.

    Loop membership is STRUCTURAL (the gather's enclosing computation is some
    while op's `body=`), not an op_name substring match. op_name metadata is
    still used to classify fwd vs rematted-bwd and to name outside gathers —
    so its presence is asserted first: if XLA ever stops emitting it, this
    fails loudly instead of silently green-lighting a regression."""
    comps = _hlo_computations(txt)
    bodies = _while_body_names(txt)
    assert bodies, "no while loops found in compiled HLO — scan disappeared"

    in_loop, outside = [], []
    for cname, lines in comps.items():
        for line in lines:
            if re.search(r"= \S+ all-gather", line):
                (in_loop if cname in bodies else outside).append(line)
    assert in_loop, "no all-gathers inside any while body — ZeRO-3 bet violated"

    def op_name(line):
        m = re.search(r'op_name="([^"]*)"', line)
        return m.group(1) if m else ""

    in_scopes = [op_name(l) for l in in_loop]
    out_scopes = [op_name(l) for l in outside]
    # metadata guard: every gather must carry a real op_name before we trust
    # any classification built on it
    assert all(in_scopes) and all(out_scopes), (
        f"all-gather missing op_name metadata — cannot verify scheduling; "
        f"in-loop: {in_scopes}, outside: {out_scopes}")

    fwd = [s for s in in_scopes if "blocks" in s and "transpose" not in s]
    bwd = [s for s in in_scopes if "blocks" in s and "transpose" in s]
    assert fwd, f"no forward in-loop block gathers; in-loop scopes: {in_scopes}"
    assert bwd, f"no rematted-backward in-loop block gathers; in-loop scopes: {in_scopes}"
    for s in out_scopes:
        assert "blocks" not in s, (
            f"block-parameter all-gather hoisted out of the scan loop: {s}")


def test_block_all_gathers_are_inside_scan_loop(l14):
    """The block-weight gathers run once per layer step inside the scan's
    while loop — forward and rematted backward — never hoisted whole."""
    cfg, state, compiled = l14
    _check_block_gathers_inside_loop(compiled.as_text())


def test_scope_check_fails_when_metadata_stripped(l14):
    """Negative control: with op_name metadata stripped from the HLO the
    checker must FAIL (not silently pass) — the round-2 weakness where the
    `outside` check green-lit metadata-free text."""
    cfg, state, compiled = l14
    txt = re.sub(r',?\s*op_name="[^"]*"', "", compiled.as_text())
    with pytest.raises(AssertionError, match="op_name"):
        _check_block_gathers_inside_loop(txt)


@pytest.mark.slow
@pytest.mark.parametrize("scan_unroll", [1, 4])
def test_10b_shape_traces_and_lowers(devices8, scan_unroll):
    """BASELINE config 4 (the 10.078B flagship): eval_shape the sharded state,
    AOT-lower AND compile the full train step on the 8-mesh — no array is ever
    materialized — then assert the ZeRO-3 memory bet AT FLAGSHIP SHAPE from
    the compiled memory analysis: per-device arguments are exactly the
    1/8 state shard (15.12 GB of the 120.94 GB global f32 state) and temps
    stay far below the full 40.3 GB parameter tensor (no hoisted whole-model
    gather).

    Parametrized over --scan_unroll because a K-block scan window all-gathers
    K blocks' params at once (K x 314.6M x 4 B here) — the wgrad-fusion
    throughput lever must not silently regress the flagship memory story,
    including the structural per-block-gather-inside-the-loop property."""
    cfg = Config(image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
                 num_blocks=32, num_classes=1000, batch_size=8,
                 warmup_steps=0, scan_unroll=scan_unroll).validate()
    state, lowered = _lower_train_step(cfg)
    from vitax.models.vit import expected_param_count
    n = sum(x.size for x in jax.tree.leaves(state.params))
    assert n == expected_param_count(cfg) == 10_077_917_160
    txt = lowered.as_text()
    assert "stablehlo.while" in txt  # the 32-block scan survived lowering

    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    global_bytes = _state_bytes(state)
    batch_bytes = cfg.batch_size * cfg.image_size ** 2 * 3 * 4
    assert ma.argument_size_in_bytes < (global_bytes / 8 + batch_bytes) * 1.05, (
        f"10B per-device args {ma.argument_size_in_bytes/1e9:.2f} GB exceed "
        f"the shard bound {global_bytes/8/1e9:.2f} GB")
    full_param_bytes = count_params_bytes(cfg)  # 40.3 GB f32
    assert ma.temp_size_in_bytes < 0.5 * full_param_bytes, (
        f"10B temps {ma.temp_size_in_bytes/1e9:.2f} GB look like a hoisted "
        f"whole-model gather (full params {full_param_bytes/1e9:.1f} GB)")
    # and the structural scheduling property holds at this scale too
    _check_block_gathers_inside_loop(compiled.as_text())


@pytest.mark.slow
def test_60b_shape_readiness(devices8):
    """BASELINE config 5 (60B-class, reference README.md:122 "e.g. 60B"):

    1. eval_shape the full train state at 8192-dim/80-block (~64.5B params) —
       nothing materializes;
    2. every >=2D parameter's spec actually shards over a virtual 256-way fsdp
       axis (v5p-256), and the per-device state bytes fit v5p HBM (95 GB) with
       a large margin;
    3. the shard_on_cpu (host-offload) init path's host-RAM requirement is
       computed and sane to document;
    4. the train step AOT-lowers end-to-end at this shape on the test mesh.
    """
    from vitax.models.vit import expected_param_count
    from vitax.parallel.sharding import param_pspec, state_specs_like
    from vitax.parallel.sharding import _path_names

    cfg = Config(image_size=224, patch_size=14, embed_dim=8192, num_heads=64,
                 num_blocks=80, num_classes=1000, batch_size=8,
                 warmup_steps=0).validate()

    state, lowered = _lower_train_step(cfg)
    n = sum(x.size for x in jax.tree.leaves(state.params))
    assert n == expected_param_count(cfg)
    assert n > 60e9, f"{n/1e9:.1f}B params is not 60B-class"
    assert "stablehlo.while" in lowered.as_text()  # 80-block scan intact
    # compile on the 8-mesh and confirm the per-device shard bound holds at
    # this scale too (args == global state / 8; nothing materializes)
    ma = lowered.compile().memory_analysis()
    global_bytes = _state_bytes(state)
    batch_bytes = cfg.batch_size * cfg.image_size ** 2 * 3 * 4
    assert ma.argument_size_in_bytes < (global_bytes / 8 + batch_bytes) * 1.05

    # --- virtual v5p-256: specs computed analytically, no 256 devices needed
    VIRT = (1, 256, 1, 1, 1, 1)  # (dp, fsdp, tp, sp, pp, ep)
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    pspecs = {}
    for path, leaf in flat:
        spec = param_pspec(path, leaf.shape, cfg, VIRT, cfg.scan_blocks)
        pspecs[_path_names(path)] = spec
        if leaf.ndim >= 2:  # every matrix/stacked tensor must shard
            assert "fsdp" in tuple(spec), (
                f"{_path_names(path)} {leaf.shape} unsharded at fsdp=256")

    def shard_bytes(leaf, spec):
        denom = 1
        for axis in tuple(spec):
            if axis == "fsdp":
                denom *= 256
        return leaf.size * leaf.dtype.itemsize / denom

    # state = f32 params + AdamW mu + nu (all param-shaped, same specs —
    # state_specs_like) + scalar step
    params_tree = state.params
    spec_tree = jax.tree_util.tree_map_with_path(
        lambda path, leaf: pspecs[_path_names(path)], params_tree)
    state_specs = state_specs_like(state, spec_tree)
    per_device = sum(
        shard_bytes(leaf, spec) for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(state)[0],
            jax.tree_util.tree_flatten_with_path(
                state_specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))[0]))
    V5P_HBM = 95e9
    assert per_device < 0.10 * V5P_HBM, (
        f"per-device 60B state {per_device/1e9:.1f} GB leaves too little HBM "
        "headroom for activations/temps on v5p")

    # --- shard_on_cpu path: full f32 params materialize in host RAM first
    # (reference run_vit_training.py:175-181 semantics; README.md:122 tcmalloc
    # note); born-sharded init needs none.
    host_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(state.params))
    assert 2.3e11 < host_bytes < 3.0e11  # ~258 GB — host-RAM sized, not HBM


@pytest.mark.slow
def test_10b_slice_fits_single_chip_hbm(devices8):
    """The 10B block at depth 2 (the shape of the `vit10b_d2` benchmark
    configuration) keeps "params+moments+activations under 16 GB HBM" on one
    v5e chip — asserted from the compiled single-device step's memory
    analysis instead of a comment.

    Resident bytes = arguments (params + mu + nu + batch) + temps
    (activations, grads, stacking buffers) + any output bytes NOT aliased
    back onto donated inputs — so the check also fails if state donation
    ever breaks (vitax/train/step.py donate_argnums).

    Caveat: this compiles on the CPU test backend with the dense jnp
    attention; TPU layout padding and Pallas scratch can shift temps by some
    margin — the compile for a described chip (benchmark/size_cells.py) and
    the cell's run are the ground truth, this test is the regression guard
    (it caught a depth-4 shape overflowing by 9+ GB). The dense-attention
    divergence is why the batch is the flagship's pod operating point
    (8/chip, the reference's per-core batch) rather than the cell's 64,
    whose dense-path CPU estimate inflates to ~29 GB of score tensors the
    Pallas kernel never materializes."""
    cfg = Config(image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
                 num_blocks=2, batch_size=8, num_classes=1000, warmup_steps=0,
                 # the HBM byte thresholds below were measured under this
                 # policy: pinned, so a change of default cannot silently
                 # change what this guard holds
                 remat_policy="none_saveable", fsdp_size=1).validate()
    state, lowered = _lower_train_step(cfg, n_devices=1)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    unaliased_out = ma.output_size_in_bytes - ma.alias_size_in_bytes
    resident = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + unaliased_out)
    V5E_HBM = 16e9
    assert resident < V5E_HBM, (
        f"10b_slice single-chip resident {resident/1e9:.2f} GB exceeds v5e "
        f"HBM (args {ma.argument_size_in_bytes/1e9:.2f} + temps "
        f"{ma.temp_size_in_bytes/1e9:.2f} + unaliased out "
        f"{unaliased_out/1e9:.2f} — small metrics outputs are expected here; "
        f"a STATE-SIZED value (~{_state_bytes(state)/1e9:.1f} GB) means "
        f"donation broke)")
    # arguments alone are the f32 state: params + 2 AdamW moments + batch
    assert ma.argument_size_in_bytes > 0.9 * _state_bytes(state)


@pytest.mark.slow
def test_10b_shape_lowers_under_pipeline_fsdp(devices8):
    """The flagship composes with pipeline parallelism for pods: the full
    10.078B shape AOT-lowers and compiles on a pp2 x fsdp4 mesh (16 layers
    per stage, ZeRO-3 shards gathered just-in-time inside the GPipe body —
    vitax/parallel/pipeline.py), with the same per-device memory bet: the
    compiled arguments are one (pp x fsdp)-shard of the state, and temps
    stay far below the whole 40.3 GB parameter tensor. Guards the real
    hazard this test caught: XLA LICM hoisting the per-block gathers out of
    the layer scan, materializing the whole stage (28.7 GB vs 12.6 GB
    temps)."""
    cfg = Config(image_size=224, patch_size=14, embed_dim=5120, num_heads=32,
                 num_blocks=32, num_classes=1000, batch_size=8,
                 warmup_steps=0, pp_size=2, fsdp_size=4, dp_size=1,
                 remat_policy="none_saveable").validate()
    state, lowered = _lower_train_step(cfg)
    from vitax.models.vit import expected_param_count
    n = sum(x.size for x in jax.tree.leaves(state.params))
    assert n == expected_param_count(cfg) == 10_077_917_160

    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    global_bytes = _state_bytes(state)
    batch_bytes = cfg.batch_size * cfg.image_size ** 2 * 3 * 4
    # blocks shard over pp AND fsdp (8-way for block state); embed/head
    # shard over fsdp only (4-way) — bound by the looser 4-way shard plus
    # slack rather than exactly global/8
    assert ma.argument_size_in_bytes < (global_bytes / 4 + batch_bytes) * 1.05, (
        f"10B pp x fsdp per-device args {ma.argument_size_in_bytes/1e9:.2f} "
        f"GB exceed the 4-way shard bound {global_bytes/4/1e9:.2f} GB")
    full_param_bytes = count_params_bytes(cfg)  # 40.3 GB f32
    assert ma.temp_size_in_bytes < 0.5 * full_param_bytes, (
        f"10B pp temps {ma.temp_size_in_bytes/1e9:.2f} GB look like a "
        f"hoisted whole-model gather ({full_param_bytes/1e9:.1f} GB full)")


@pytest.mark.slow
def test_topology_aot_kernel_true_smoke():
    """Round-5 capability pin: the FULL train step with REAL Mosaic kernels
    (VITAX_FORCE_MOSAIC, not interpret mode) AOT-compiles against a real
    TPU topology target with no hardware attached — the mechanism behind
    AOT_TOPOLOGY.json's flagship rows (tools/aot_topology.py). Runs in a
    subprocess (libtpu allows one process; skip cleanly on lock contention
    with a concurrent topology compile)."""
    import subprocess

    code = """
import os, sys
sys.path.insert(0, '.')
import jax, jax.numpy as jnp
from jax.experimental import topologies
from vitax.config import Config
from vitax.programs.builder import Geometry, abstract_batch, build_program

td = topologies.get_topology_desc('v5e:2x4', 'tpu')
cfg = Config(image_size=224, patch_size=16, embed_dim=128, num_heads=2,
             num_blocks=2, num_classes=16, batch_size=16,
             fsdp_size=-1).validate()
geom = Geometry.assemble(cfg, max_iteration=10, devices=list(td.devices),
                         force_tpu_kernels=True)
assert geom.model.attention_impl is not None, 'kernel selection bailed'
step = build_program('train', geom)
state, batch = geom.abstract_state, abstract_batch(cfg, geom.mesh)
key = jax.eval_shape(lambda: jax.random.key(0))
compiled = step.lower(state, batch, key).compile()
ma = compiled.memory_analysis()
assert ma.argument_size_in_bytes > 0
print('AOT_OK', ma.temp_size_in_bytes)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", VITAX_FORCE_MOSAIC="1")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    if r.returncode != 0 and "libtpu_lockfile" in (r.stderr or ""):
        pytest.skip("libtpu lockfile held by a concurrent topology compile")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "AOT_OK" in r.stdout, r.stdout
