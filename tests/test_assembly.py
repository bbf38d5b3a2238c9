"""The one assembly path (vitax/programs/builder.py) held to its written-out
form, and the layering around it.

- `written_out` below is the ONE oracle copy of mesh -> attention core ->
  model (both anchors) -> optimizer -> state -> step. `Geometry.assemble` +
  `build_program` must lower to its text on every arm;
- the activation anchors are on the model exactly when they should be (the
  drift this file exists to stop: hand-written copies that dropped one);
- every consumer of the train program (the loop, builder.lower_step,
  analysis/hlo.lower_train_step) lowers the same text;
- nothing below the trainer imports the trainer (or tools / the benchmark);
- `Config` defaults are the one knob mechanism, and they are what the
  benchmark's cells run (PERF.md section 4).
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from vitax.config import Config, build_parser, parse_config
from vitax.models import build_model
from vitax.ops.attention import make_attention_impl
from vitax.parallel.mesh import batch_pspec, build_mesh
from vitax.parallel.sharding import moe_dispatch_sharding, token_sharding
from vitax.programs import builder
from vitax.programs.registry import get_scenario
from vitax.programs.workloads import make_distill_step
from vitax.train.state import make_train_state
from vitax.train.step import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_ITERATION = 100


def tiny_cfg(**kw):
    base = dict(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                num_blocks=2, num_classes=4, batch_size=16, dtype="float32",
                lr=1e-3, warmup_steps=2, clip_grad_norm=1.0, seed=0)
    base.update(kw)
    return Config(**base).validate()


def packed_cfg():
    return Config(embed_dim=64, num_heads=4, num_blocks=2, mlp_dim=100,
                  patch_size=4, num_classes=10, pack_tokens=128,
                  pack_images=4, max_image_tokens=64, pos_grid=8,
                  batch_size=8, dtype="float32", warmup_steps=2).validate()


# arm -> Config (8 virtual CPU devices): every layout, step variant and
# scenario the builder assembles
ARMS = {
    "zero3": lambda: tiny_cfg(),
    "zero2": lambda: tiny_cfg(reshard_after_forward=False),
    "dp": lambda: tiny_cfg(run_without_fsdp=True),
    "accum4": lambda: tiny_cfg(grad_accum_steps=4, batch_size=32),
    "tp2": lambda: tiny_cfg(tp_size=2, fsdp_size=4),
    "sp_ring": lambda: tiny_cfg(sp_size=2, fsdp_size=4),
    "sp_ulysses": lambda: tiny_cfg(sp_size=2, fsdp_size=4,
                                   sp_impl="ulysses"),
    "pp_gpipe": lambda: tiny_cfg(pp_size=2, dp_size=2, fsdp_size=2),
    "moe_ep": lambda: tiny_cfg(moe_experts=4, ep_size=2, dp_size=2,
                               fsdp_size=2),
    "moe_top2": lambda: tiny_cfg(moe_experts=4, moe_top_k=2),
    "packed": packed_cfg,
    "probe": lambda: tiny_cfg(task="probe", init_npz="/x.npz"),
    "finetune": lambda: tiny_cfg(task="finetune", init_npz="/x.npz",
                                 backbone_lr_mult=0.1),
    "distill": lambda: tiny_cfg(task="distill", gather_overlap="off"),
}


def written_out(cfg, max_iteration):
    """The assembly, written out: the oracle `Geometry.assemble` and
    `build_program` are held to. Returns (mesh, abstract state, step)."""
    mesh = build_mesh(cfg)
    model = build_model(
        cfg, attention_impl=make_attention_impl(cfg, mesh),
        token_sharding=token_sharding(cfg, mesh),
        moe_dispatch_sharding=moe_dispatch_sharding(cfg, mesh))
    tx, schedule = get_scenario(cfg.task).make_optimizer(cfg, max_iteration)
    state, specs, _ = make_train_state(cfg, model, tx, mesh,
                                       jax.random.key(cfg.seed),
                                       materialize=False)
    if cfg.task == "distill":  # the scenario's step program
        step = make_distill_step(cfg, model, tx, mesh, specs, state.params,
                                 schedule=schedule)
    else:
        step = make_train_step(cfg, model, tx, mesh, specs,
                               schedule=schedule)
    return mesh, state, step


def abstract_batch(cfg, mesh):
    sh = NamedSharding(mesh, batch_pspec())

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    if not cfg.packed:
        return builder.abstract_batch(cfg, mesh)
    r, t, s = cfg.batch_size, cfg.pack_tokens, cfg.pack_images
    return {"patches": shaped((r, t, 3 * cfg.patch_size ** 2), jnp.uint8),
            "segment_ids": shaped((r, t), jnp.int32),
            "positions": shaped((r, t, 2), jnp.int32),
            "grid_hw": shaped((r, s, 2), jnp.int32),
            "label": shaped((r, s), jnp.int32),
            "label_mask": shaped((r, s), jnp.float32)}


def lower(step, state, cfg, mesh) -> str:
    return step.lower(state, abstract_batch(cfg, mesh),
                      jax.random.key(cfg.seed + 1)).as_text()


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_assemble_matches_written_out(devices8, arm):
    cfg = ARMS[arm]()
    mesh, state, step = written_out(cfg, MAX_ITERATION)
    geom = builder.Geometry.assemble(cfg, MAX_ITERATION)
    assert geom.mesh == mesh and geom.state is geom.abstract_state
    assert (jax.tree.structure(geom.abstract_state)
            == jax.tree.structure(state))
    program = builder.build_program(geom.scenario.step_program, geom)
    assert (lower(program, geom.abstract_state, cfg, geom.mesh)
            == lower(step, state, cfg, mesh))


ANCHOR_ARMS = {
    # arm -> (config overrides, devices, token anchor?, dispatch anchor?)
    "dense_8dev": (dict(), 8, True, False),
    "moe_8dev": (dict(moe_experts=4, ep_size=2, dp_size=2, fsdp_size=2), 8,
                 True, True),
    "dense_1dev": (dict(fsdp_size=1), 1, False, False),
    "moe_1dev": (dict(moe_experts=4, fsdp_size=1), 1, False, False),
}


@pytest.mark.parametrize("arm", sorted(ANCHOR_ARMS))
def test_model_anchors(devices8, arm):
    """The token anchor on any multi-device mesh, the dispatch anchor iff
    the model has experts, none on one device — on the model of every door
    (the builder's, the geometry's, the serve engine's)."""
    from vitax.serve.engine import _build_model
    kw, n_dev, want_token, want_dispatch = ANCHOR_ARMS[arm]
    cfg = tiny_cfg(**kw)
    devices = devices8[:n_dev]
    mesh = build_mesh(cfg, devices)
    models = [builder.build_model_for(cfg, mesh),
              builder.Geometry.assemble(cfg, devices=devices).model,
              _build_model(cfg, mesh)]
    for model in models:
        assert (model.token_sharding is not None) == want_token
        assert (model.moe_dispatch_sharding is not None) == want_dispatch
        assert model.token_sharding == token_sharding(cfg, mesh)
        assert (model.moe_dispatch_sharding
                == moe_dispatch_sharding(cfg, mesh))


class _Assembled(Exception):
    """Stops `train()` once it has built its step program."""


def _loop_geometry(cfg, monkeypatch):
    """The geometry the training loop itself assembles for `cfg`."""
    from vitax.train import loop
    seen = []

    def record(task, geom, **kw):
        seen.append(geom)
        raise _Assembled

    monkeypatch.setattr(loop, "build_program", record)
    with pytest.raises(_Assembled):
        loop.train(cfg)
    return seen[0]


@pytest.mark.parametrize("consumer", ["loop", "lower_step", "hlo"])
def test_consumers_share_one_program(devices8, consumer, monkeypatch,
                                     tmp_path):
    """On the MoE arm (where the analysis once dropped the dispatch anchor)
    every consumer lowers the written-out program."""
    from vitax.analysis import hlo
    cfg = tiny_cfg(moe_experts=4, ep_size=2, dp_size=2, fsdp_size=2,
                   fake_data=True, num_epochs=1, steps_per_epoch=5,
                   ckpt_dir=str(tmp_path))
    max_iteration = cfg.steps_per_epoch * cfg.num_epochs
    mesh, state, step = written_out(cfg, max_iteration)
    want = lower(step, state, cfg, mesh)
    assert "sharding_constraint" in want or "@Sharding" in want
    if consumer == "loop":
        geom = _loop_geometry(cfg, monkeypatch)
        assert geom.max_iteration == max_iteration and geom.state is None
        got = lower(builder.build_program("train", geom),
                    geom.abstract_state, cfg, geom.mesh)
    elif consumer == "lower_step":
        got = builder.lower_step(cfg, max_iteration)[0].as_text()
    else:
        got = hlo.lower_train_step(cfg, max_iteration)[0].as_text()
    assert got == want


def test_assemble_traces_once_and_memoizes(devices8, monkeypatch):
    """One `make_train_state` per assembly, live or abstract: a live state's
    abstract twin is read off its arrays; `from_config` assembles once per
    (cfg, max_iteration)."""
    calls = []

    def counting(*args, **kw):
        calls.append(kw.get("materialize", True))
        return make_train_state(*args, **kw)

    monkeypatch.setattr(builder, "make_train_state", counting)
    cfg = tiny_cfg(seed=7)
    live = builder.Geometry.assemble(cfg, MAX_ITERATION, materialize=True)
    assert calls == [True]
    _, abstract, _ = written_out(cfg, MAX_ITERATION)
    assert jax.tree.map(lambda a: (a.shape, a.dtype, a.sharding),
                        live.abstract_state) == jax.tree.map(
        lambda a: (a.shape, a.dtype, a.sharding), abstract)
    assert np.isfinite(float(jnp.sum(
        live.state.params["params"]["head"]["kernel"])))
    a = builder.Geometry.from_config(cfg, MAX_ITERATION)
    assert builder.Geometry.from_config(cfg, MAX_ITERATION) is a
    assert calls == [True, False]
    assert builder.Geometry.from_config(cfg, MAX_ITERATION + 1) is not a


# --- layering ----------------------------------------------------------------

LOWER_PACKAGES = ("ops", "models", "parallel", "telemetry", "programs",
                  "serve", "analysis", "checkpoint", "data")
FORBIDDEN_ROOTS = ("tools", "bench", "benchmark")


def _imports(path):
    """Every module name a file imports, `from a import b` as both a and
    a.b (b may be a module)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


@pytest.mark.parametrize("package", LOWER_PACKAGES)
def test_layering(package):
    """Nothing below the trainer imports the trainer's loop, a tool or the
    benchmark: the arrows point down."""
    bad = []
    root = os.path.join(REPO, "vitax", package)
    assert os.path.isdir(root), root
    for dirpath, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            for mod in _imports(path):
                if (mod == "vitax.train.loop"
                        or mod.startswith("vitax.train.loop.")
                        or mod.split(".")[0] in FORBIDDEN_ROOTS):
                    bad.append(f"{os.path.relpath(path, REPO)}: {mod}")
    assert not bad, "\n".join(bad)


def test_the_loops_sharding_aliases_are_gone():
    """`vitax/train/loop.py` re-exported the two anchors for a harness that
    assembled by hand (PERF.md section 7 (n)); nothing imports them now."""
    from vitax.train import loop
    assert not hasattr(loop, "_token_sharding")
    assert not hasattr(loop, "_moe_dispatch_sharding")
    with open(loop.__file__, encoding="utf-8") as f:
        source = f.read()
    assert "token_sharding" not in source
    assert "moe_dispatch_sharding" not in source


BENCH = os.path.join(REPO, "benchmark")
ASSEMBLERS = sorted(
    os.path.join("generators", name)
    for name in os.listdir(os.path.join(BENCH, "generators"))
    if name.startswith(("train_", "serve_")))


@pytest.mark.parametrize("path", ASSEMBLERS + ["harness.py"])
def test_the_benchmark_builds_through_the_one_assembly(path):
    """The benchmark's generators are callers of `Geometry.assemble` like
    the loop (section 7 (o)): each builds its program there, and neither they
    nor the harness write the assembly out by hand. One generator runs the
    trainer's loop, `train_loop.py`, the cell that is for it (PR 37); the
    others keep it out of their process."""
    with open(os.path.join(BENCH, path), encoding="utf-8") as f:
        source = f.read()
    for by_hand in ("build_model(", "build_decoder(", "make_train_state(",
                    "make_attention_impl("):
        assert by_hand not in source, (path, by_hand)
    if path != "harness.py":
        assert "Geometry.assemble(" in source, path
        assert ("vitax.train.loop" in source) == (
            path == os.path.join("generators", "train_loop.py")), path


# --- one knob mechanism: Config defaults --------------------------------------


def test_no_preset_flag():
    """The flag that installed an autotune preset as parser defaults is gone
    (spelled in two pieces: the tree is grepped for the whole word)."""
    flag = "--preset" "_file"
    assert flag not in build_parser().format_help()
    with pytest.raises(SystemExit):
        parse_config([flag, "x.json"])


def test_perf_defaults_are_what_the_cells_run():
    """No benchmark file sets a performance knob: every cell runs these
    (PERF.md section 4). A change of default is a change of every cell."""
    cfg = parse_config([])
    assert (cfg.scan_blocks, cfg.grad_ckpt, cfg.remat_policy) == (
        True, True, "none_saveable")
    assert (cfg.scan_unroll, cfg.remat_window) == (1, 0)
    assert cfg.use_flash_attention is True
    assert (cfg.fused_optimizer, cfg.gather_overlap) == ("auto", "auto")
    assert (cfg.serve_max_batch, cfg.max_batch_wait_ms) == (8, 5.0)
    from vitax.serve.engine import bucket_sizes
    assert tuple(bucket_sizes(cfg.serve_max_batch)) == (1, 2, 4, 8)
