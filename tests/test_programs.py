"""Scenario registry tests: the unified program builder (vitax/programs/),
the declarative sharding-rule table (vitax/parallel/rules.py), and the three
transfer workloads (finetune / probe / distill) it carries.

Three pin families live here:

- rule-table parity: `rules.rule_pspec` reproduces the reference dispatcher
  `sharding.param_pspec` leaf-for-leaf on real model trees across the
  dp / zero2 / zero3 / tp / pp / ep arms;
- bitwise identity: the builder's train / eval / serve-bucket programs lower
  to the same bytes as the pre-registry direct assembly paths
  (analysis/hlo.py, train/step.py, serve/engine.py);
- workload semantics: warm-start key discipline, the probe's head-only
  optimizer state and bitwise-frozen backbone, the distill program's
  single-jit teacher+student with decreasing loss, and the VTX-R010
  frozen-params invariant over both scenario arms.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from vitax.checkpoint.consolidate import flatten_tree, save_npz
from vitax.config import Config, parse_config
from vitax.models import build_model
from vitax.parallel import rules as prules
from vitax.parallel.mesh import build_mesh
from vitax.parallel.sharding import param_pspec, param_specs
from vitax.programs import TASKS, get_scenario
from vitax.programs import builder
from vitax.programs.workloads import warm_start_from_npz
from vitax.train.state import make_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**kw):
    base = dict(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                num_blocks=2, num_classes=4, batch_size=16, dtype="float32",
                lr=1e-3, warmup_steps=2, clip_grad_norm=1.0, seed=0)
    base.update(kw)
    return Config(**base).validate()


def abstract_params(cfg):
    model = build_model(cfg)
    x = jnp.zeros((2, cfg.image_size, cfg.image_size, 3))
    return jax.eval_shape(lambda r: model.init(r, x, True),
                          jax.random.key(0))


def random_batch(cfg, mesh, seed=0):
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(cfg.batch_size, cfg.image_size,
                              cfg.image_size, 3)).astype(np.float32)
    labels = rng.integers(0, cfg.num_classes,
                          size=(cfg.batch_size,)).astype(np.int32)
    sh = NamedSharding(mesh, batch_pspec())
    return {"image": jax.device_put(jnp.asarray(images), sh),
            "label": jax.device_put(jnp.asarray(labels), sh)}


def export_params_npz(cfg, path, seed=42):
    """Consolidated params-only npz from a fresh sharded init (the export
    vitax.checkpoint.consolidate would produce)."""
    from vitax.train.state import build_optimizer
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=10)
    state, _, _ = make_train_state(cfg, model, tx, mesh,
                                   jax.random.key(seed))
    flat = {k: np.asarray(v) for k, v in flatten_tree(state.params).items()}
    save_npz(path, flat)
    return flat


# --- declarative sharding rules (vitax/parallel/rules.py) --------------------


# the mesh/config arms the table is pinned against (mirrors the sharding and
# pipeline test configs; 8 virtual CPU devices)
PARITY_ARMS = {
    "dp": dict(run_without_fsdp=True),
    "zero2": dict(reshard_after_forward=False),
    "zero3": dict(),
    "tp": dict(tp_size=2, fsdp_size=4),
    "pp": dict(pp_size=2, dp_size=2, fsdp_size=2, grad_ckpt=True),
    "ep": dict(moe_experts=4, ep_size=2, dp_size=2, fsdp_size=2),
}


class TestRuleTable:
    @pytest.mark.parametrize("arm", sorted(PARITY_ARMS))
    def test_parity_with_param_pspec(self, devices8, arm):
        """The rule table reproduces the reference dispatcher leaf-for-leaf
        on the real model tree (satellite: pinned bitwise across arms)."""
        cfg = tiny_cfg(**PARITY_ARMS[arm])
        mesh = build_mesh(cfg)
        mesh_shape = tuple(mesh.shape[a] for a in prules.MESH_AXES)
        flat = jax.tree_util.tree_flatten_with_path(abstract_params(cfg))[0]
        assert flat
        for path, leaf in flat:
            names = prules._leaf_path_names(path)
            ref = param_pspec(path, leaf.shape, cfg, mesh_shape,
                              cfg.scan_blocks)
            got = prules.rule_pspec(names, leaf.shape, cfg, mesh_shape,
                                    cfg.scan_blocks)
            assert got == ref, (
                f"[{arm}] {'/'.join(names)} {leaf.shape}: "
                f"table says {got}, param_pspec says {ref}")

    def test_param_specs_routes_through_table(self, devices8):
        """The live spec constructor and the table agree tree-for-tree."""
        cfg = tiny_cfg(tp_size=2, fsdp_size=4)
        mesh = build_mesh(cfg)
        tree = abstract_params(cfg)
        via_live = param_specs(tree, cfg, mesh)
        via_table = prules.specs_from_rules(tree, cfg, mesh)
        assert jax.tree_util.tree_all(
            jax.tree.map(lambda a, b: a == b, via_live, via_table,
                         is_leaf=lambda x: isinstance(x, P)))

    def test_strict_match_raises_on_unknown_param(self):
        with pytest.raises(ValueError, match="Partition rule not found"):
            prules.match_rule("params/blocks/attn/mystery_weight")

    def test_scalar_exemption_skips_matching(self):
        """0-dim / size-1 leaves replicate without needing a rule — even a
        path no table entry matches."""
        cfg = tiny_cfg()
        shape6 = (1, 8, 1, 1, 1, 1)
        assert prules.rule_pspec(("params", "temperature"), (), cfg,
                                 shape6, False) == P()
        assert prules.rule_pspec(("params", "temperature"), (1, 1), cfg,
                                 shape6, False) == P(None, None)

    def test_rule_order_first_match_wins(self):
        assert prules.match_rule(
            "params/blocks/attn/qkv/kernel").name == "megatron-column-qkv-fc1"
        assert prules.match_rule(
            "params/blocks/attn/proj/kernel").name == "megatron-row-attn-proj"
        assert prules.match_rule(
            "params/blocks/moe/w1").name == "moe-expert-weights"
        assert prules.match_rule(
            "params/head/kernel").name == "dense-default"

    def test_describe_table_names_every_rule(self):
        text = prules.describe_table()
        for r in prules.RULE_TABLE:
            assert r.name in text


# --- scenario registry (vitax/programs/registry.py) --------------------------


class TestRegistry:
    def test_task_set(self):
        assert TASKS == ("train", "finetune", "probe", "distill")

    def test_unknown_task_raises_naming_valid_set(self):
        with pytest.raises(ValueError, match="train"):
            get_scenario("pretrain")

    def test_cli_task_flag_round_trips(self):
        cfg = parse_config(["--task", "probe", "--init_npz", "/x.npz",
                            "--image_size", "16", "--patch_size", "8",
                            "--embed_dim", "32", "--num_heads", "2",
                            "--num_blocks", "2", "--num_classes", "4"])
        assert cfg.task == "probe" and cfg.init_npz == "/x.npz"

    def test_validators_reject_bad_combos(self):
        # train must not carry transfer-source flags
        with pytest.raises(AssertionError):
            tiny_cfg(init_npz="/x.npz")
        # finetune requires a source export
        with pytest.raises(AssertionError):
            tiny_cfg(task="finetune")
        # probe cannot run the fused optimizer (masking happens in optax)
        with pytest.raises(AssertionError):
            tiny_cfg(task="probe", init_npz="/x.npz", fused_optimizer="on")
        # distill composes with dense models only
        with pytest.raises(AssertionError):
            tiny_cfg(task="distill", moe_experts=4, ep_size=2,
                     dp_size=2, fsdp_size=2)

    def test_builder_enforces_scenario_program_set(self, devices8):
        geom = builder.Geometry.from_config(tiny_cfg())
        with pytest.raises(ValueError, match="does not build"):
            builder.build_program("distill", geom)
        with pytest.raises(ValueError, match="unknown program kind"):
            builder.build_program("serve", geom)


# --- bitwise identity pins (satellite 1) -------------------------------------


class TestIdentityPins:
    def test_train_program_identical_to_hlo_path(self, devices8):
        """builder.lower_step == analysis/hlo.lower_train_step, byte for
        byte, at the HEAD train geometry (the refactor moved the assembly,
        not the program)."""
        from vitax.analysis import hlo
        cfg = tiny_cfg()
        ref, n_ref = hlo.lower_train_step(cfg)
        got, n_got = builder.lower_step(cfg)
        assert n_ref == n_got
        assert ref.as_text() == got.as_text()

    def test_eval_program_identical_to_direct_assembly(self, devices8):
        """build_program("eval") lowers to the same bytes as a direct
        make_eval_step call on the same geometry (loop.py's historical
        wiring), and the owned-geometry program cache returns one object."""
        from jax.sharding import NamedSharding
        from vitax.parallel.mesh import batch_pspec
        from vitax.train.step import make_eval_step
        cfg = tiny_cfg()
        geom = builder.Geometry.from_config(cfg)
        via_builder = builder.build_program("eval", geom)
        assert builder.build_program("eval", geom) is via_builder
        direct = make_eval_step(cfg, geom.model, geom.mesh, geom.state_specs)
        sh = NamedSharding(geom.mesh, batch_pspec())
        batch = {
            "image": jax.ShapeDtypeStruct(
                (cfg.batch_size, cfg.image_size, cfg.image_size, 3),
                jnp.float32, sharding=sh),
            "label": jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32,
                                          sharding=sh),
        }
        assert (via_builder.lower(geom.abstract_state, batch).as_text()
                == direct.lower(geom.abstract_state, batch).as_text())

    def test_serve_bucket_identical_to_direct_engine(self, devices8,
                                                     tmp_path):
        """build_engine routes to the same InferenceEngine; the lowered
        bucket module is byte-identical to the pre-registry from_npz path."""
        from vitax.serve.engine import InferenceEngine
        cfg = tiny_cfg()
        npz = str(tmp_path / "w.npz")
        export_params_npz(cfg, npz)
        via_builder = builder.build_engine(cfg, npz=npz)
        direct = InferenceEngine.from_npz(cfg, npz)
        assert (via_builder.lower_bucket_mlir(8)
                == direct.lower_bucket_mlir(8))


# --- warm start (finetune source discipline) ---------------------------------


@pytest.mark.slow
class TestWarmStart:
    def test_loads_backbone_bitwise(self, devices8, tmp_path):
        cfg = tiny_cfg()
        npz = str(tmp_path / "init.npz")
        flat_src = export_params_npz(cfg, npz, seed=42)
        cfg_ft = tiny_cfg(task="finetune", init_npz=npz)
        mesh = build_mesh(cfg_ft)
        from vitax.train.state import build_optimizer
        model = build_model(cfg_ft)
        tx, _ = build_optimizer(cfg_ft, max_iteration=10)
        state, _, _ = make_train_state(cfg_ft, model, tx, mesh,
                                       jax.random.key(7))
        state, info = warm_start_from_npz(cfg_ft, state, mesh)
        flat = {k: np.asarray(v)
                for k, v in flatten_tree(state.params).items()}
        assert set(flat) == set(flat_src)
        for k in flat_src:  # same num_classes: the head loads too
            assert np.array_equal(flat[k], flat_src[k]), k
        assert info["loaded"] == len(flat_src) and info["reinit"] == []

    def test_head_reinit_on_new_num_classes(self, devices8, tmp_path):
        npz = str(tmp_path / "init.npz")
        flat_src = export_params_npz(tiny_cfg(), npz, seed=42)
        cfg_ft = tiny_cfg(task="finetune", init_npz=npz, num_classes=7)
        mesh = build_mesh(cfg_ft)
        from vitax.train.state import build_optimizer
        model = build_model(cfg_ft)
        tx, _ = build_optimizer(cfg_ft, max_iteration=10)
        state, _, _ = make_train_state(cfg_ft, model, tx, mesh,
                                       jax.random.key(7))
        state, info = warm_start_from_npz(cfg_ft, state, mesh)
        assert info["reinit"] == ["params/head/bias", "params/head/kernel"]
        flat = {k: np.asarray(v)
                for k, v in flatten_tree(state.params).items()}
        assert flat["params/head/kernel"].shape == (32, 7)
        for k in flat_src:
            if "head" not in k.split("/"):
                assert np.array_equal(flat[k], flat_src[k]), k

    def test_loud_failures_on_key_mismatch(self, devices8, tmp_path):
        cfg = tiny_cfg()
        flat_src = export_params_npz(cfg, str(tmp_path / "ok.npz"))
        mesh = build_mesh(cfg)
        from vitax.train.state import build_optimizer
        model = build_model(cfg)
        tx, _ = build_optimizer(cfg, max_iteration=10)
        state, _, _ = make_train_state(cfg, model, tx, mesh,
                                       jax.random.key(7))

        unknown = dict(flat_src)
        unknown["params/extra/kernel"] = np.zeros((2, 2), np.float32)
        save_npz(str(tmp_path / "unknown.npz"), unknown)
        cfg_u = tiny_cfg(task="finetune",
                         init_npz=str(tmp_path / "unknown.npz"))
        with pytest.raises(ValueError, match="keys absent"):
            warm_start_from_npz(cfg_u, state, mesh)

        missing = {k: v for k, v in flat_src.items()
                   if k != "params/pos_embed"}
        save_npz(str(tmp_path / "missing.npz"), missing)
        cfg_m = tiny_cfg(task="finetune",
                         init_npz=str(tmp_path / "missing.npz"))
        with pytest.raises(ValueError, match="missing param"):
            warm_start_from_npz(cfg_m, state, mesh)

        wrong = dict(flat_src)
        wrong["params/pos_embed"] = np.zeros((1, 3, 32), np.float32)
        save_npz(str(tmp_path / "wrong.npz"), wrong)
        cfg_w = tiny_cfg(task="finetune",
                         init_npz=str(tmp_path / "wrong.npz"))
        with pytest.raises(ValueError, match="has shape"):
            warm_start_from_npz(cfg_w, state, mesh)


# --- workloads end-to-end (the acceptance runs) ------------------------------


def loop_cfg(**kw):
    base = dict(fake_data=True, num_epochs=1, steps_per_epoch=3,
                log_step_interval=1, ckpt_epoch_interval=99,
                test_epoch_interval=99, num_workers=2, eval_max_batches=1)
    base.update(kw)
    return tiny_cfg(**base)


@pytest.mark.slow
class TestWorkloadsE2E:
    def test_finetune_and_probe_full_loop(self, devices8, tmp_path):
        """--task finetune and --task probe through the real training loop
        on fake data: finetune re-initializes the head for a new
        --num_classes and trains 3 steps; the probe's backbone stays
        bitwise at the warm-start values while the head moves, and the
        optimizer state carries moments for the head ONLY."""
        from vitax.train.loop import train
        npz = str(tmp_path / "init.npz")
        flat_src = export_params_npz(tiny_cfg(), npz, seed=42)

        st = train(loop_cfg(task="finetune", init_npz=npz, num_classes=7,
                            ckpt_dir=str(tmp_path / "ft"), seed=1))
        assert int(jax.device_get(st.step)) == 3
        assert np.asarray(
            flatten_tree(st.params)["params/head/kernel"]).shape == (32, 7)

        st = train(loop_cfg(task="probe", init_npz=npz,
                            ckpt_dir=str(tmp_path / "pr"), seed=2))
        assert int(jax.device_get(st.step)) == 3
        flat = {k: np.asarray(v)
                for k, v in flatten_tree(st.params).items()}
        for k in flat_src:
            if "head" not in k.split("/"):
                assert np.array_equal(flat[k], flat_src[k]), (
                    f"probe moved frozen backbone leaf {k}")
        assert not np.array_equal(flat["params/head/kernel"],
                                  flat_src["params/head/kernel"])
        # head-only optimizer state, pinned by tree inspection
        moment_paths = [
            "/".join(prules._leaf_path_names(p))
            for p, _ in jax.tree_util.tree_leaves_with_path(st.opt_state)]
        moments = [p for p in moment_paths
                   if {"mu", "nu"} & set(p.split("/"))]
        assert moments, "probe opt_state carries no AdamW moments at all"
        assert all("head" in p.split("/") for p in moments), moments

    def test_distill_loss_decreases_single_program(self, devices8,
                                                   tmp_path):
        """--task distill: ONE jitted program holds the frozen teacher
        forward and the student update; on a fixed batch the combined
        CE+KL loss decreases, and the traced jaxpr carries the teacher
        under stop_gradient."""
        from vitax.programs.registry import get_scenario as scen
        from vitax.ops.attention import make_attention_impl
        from vitax.parallel.sharding import (
            moe_dispatch_sharding as _moe_dispatch_sharding,
            token_sharding as _token_sharding)
        npz = str(tmp_path / "teacher.npz")
        export_params_npz(tiny_cfg(), npz, seed=42)
        cfg = tiny_cfg(task="distill", teacher_npz=npz, lr=1e-2,
                       gather_overlap="off")
        mesh = build_mesh(cfg)
        model = build_model(
            cfg, attention_impl=make_attention_impl(cfg, mesh),
            token_sharding=_token_sharding(cfg, mesh),
            moe_dispatch_sharding=_moe_dispatch_sharding(cfg, mesh))
        tx, schedule = scen(cfg.task).make_optimizer(cfg, 100)
        state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                            jax.random.key(3))
        geom = builder.Geometry(cfg=cfg, mesh=mesh, model=model, tx=tx,
                                schedule=schedule, state_specs=sspecs)
        step = builder.build_program("distill", geom)
        batch = random_batch(cfg, mesh, seed=0)
        losses = []
        for i in range(10):
            state, metrics = step(state, batch, jax.random.key(i))
            losses.append(float(jax.device_get(metrics["loss"])))
        assert losses[-1] < losses[0], losses
        for key in ("ce", "kl", "teacher_top1", "student_top1"):
            assert key in metrics, key

    def test_distill_full_loop(self, devices8, tmp_path):
        from vitax.train.loop import train
        npz = str(tmp_path / "teacher.npz")
        export_params_npz(tiny_cfg(), npz, seed=42)
        st = train(loop_cfg(task="distill", teacher_npz=npz,
                            ckpt_dir=str(tmp_path / "kd"), seed=3,
                            gather_overlap="off"))
        assert int(jax.device_get(st.step)) == 3


# --- VTX-R010 + scenario analysis arms (satellite 2) -------------------------


class TestFrozenInvariant:
    def test_freeze_report_probe_and_distill(self, devices8):
        frozen_p, moments_p = builder.freeze_report(
            tiny_cfg(task="probe", init_npz="/x.npz"))
        assert frozen_p and all("head" not in f.split("/")
                                for f in frozen_p)
        assert sorted(moments_p) == ["params/head/bias",
                                     "params/head/kernel"]
        frozen_d, _ = builder.freeze_report(
            tiny_cfg(task="distill", gather_overlap="off"))
        assert frozen_d and all(f.startswith("teacher/") for f in frozen_d)

    def test_r010_negative_moment_on_frozen_leaf(self):
        """A mu/nu slot appearing under a frozen path is an ERROR finding —
        the mask silently stopped covering that leaf."""
        from vitax.analysis.rules import FROZEN_NOT_UPDATED, Program
        cfg = tiny_cfg(task="probe", init_npz="/x.npz")
        broken = Program(
            kind="train", arm="probe", config=cfg, mlir="m",
            frozen_paths=("params/blocks/attn/qkv/kernel",),
            opt_moment_paths=("params/blocks/attn/qkv/kernel",
                              "params/head/kernel"))
        findings = FROZEN_NOT_UPDATED.check(broken, cfg)
        assert findings and findings[0].severity == "ERROR"
        ok = Program(
            kind="train", arm="probe", config=cfg, mlir="m",
            frozen_paths=("params/blocks/attn/qkv/kernel",),
            opt_moment_paths=("params/head/kernel",))
        assert FROZEN_NOT_UPDATED.check(ok, cfg) == []

    def test_r010_distill_requires_stop_gradient_marker(self):
        from vitax.analysis.rules import FROZEN_NOT_UPDATED, Program
        cfg = tiny_cfg(task="distill", gather_overlap="off")
        no_marker = Program(kind="train", arm="distill", config=cfg,
                            mlir="m", jaxpr="add mul",
                            frozen_paths=("teacher/params/head/kernel",),
                            opt_moment_paths=())
        assert FROZEN_NOT_UPDATED.check(no_marker, cfg)
        with_marker = Program(kind="train", arm="distill", config=cfg,
                              mlir="m", jaxpr="stop_gradient add",
                              frozen_paths=("teacher/params/head/kernel",),
                              opt_moment_paths=())
        assert FROZEN_NOT_UPDATED.check(with_marker, cfg) == []

    @pytest.mark.slow
    def test_check_invariants_scenario_arms(self, devices8):
        # the same rules_ran pin also runs in-process above and in
        # tools/lint.sh's fast-arm subset; this is the CLI-contract mirror
        import json
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "check_invariants.py"),
             "--arms", "probe", "distill", "--json"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True and doc["errors"] == {}
        for arm_name in ("probe", "distill"):
            arm = doc["arms"][arm_name]
            assert arm["rules_ran"] == ["VTX-R001", "VTX-R002", "VTX-R003",
                                        "VTX-R005", "VTX-R010"]
            assert arm["findings"] == []
