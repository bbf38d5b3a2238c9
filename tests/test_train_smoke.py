"""End-to-end training smoke tests on the 8-virtual-device CPU mesh:
loss decreases under FSDP, DP-vs-FSDP equivalence (the property the reference's
A/B flag implies but never asserts — SURVEY.md section 4), ZeRO-2 equivalence,
max_steps stop, and eval.
"""

import numpy as np
import pytest

import jax
import os

from vitax.config import Config
from vitax.models import build_model
from vitax.parallel.mesh import build_mesh
from vitax.train.state import build_optimizer, make_train_state
from vitax.train.step import make_eval_step, make_train_step


def tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=16, dtype="float32", lr=1e-3, warmup_steps=2,
        clip_grad_norm=1.0, seed=0,
    )
    base.update(kw)
    return Config(**base).validate()


def random_batch(cfg, mesh, seed=0):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(cfg.batch_size, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    labels = (rng.integers(0, cfg.num_classes, size=(cfg.batch_size,))).astype(np.int32)
    sh = NamedSharding(mesh, batch_pspec())
    return {"image": jax.device_put(jnp.asarray(images), sh),
            "label": jax.device_put(jnp.asarray(labels), sh)}


def build_train_objects(cfg, max_iteration=100):
    """Build the full sharded training machinery exactly as the training loop
    does (attention impl + token sharding selection included)."""
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.sharding import token_sharding as _token_sharding
    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh),
                        token_sharding=_token_sharding(cfg, mesh))
    tx, _ = build_optimizer(cfg, max_iteration=max_iteration)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(cfg.seed))
    step_fn = make_train_step(cfg, model, tx, mesh, sspecs)
    eval_fn = make_eval_step(cfg, model, mesh, sspecs)
    return mesh, state, step_fn, eval_fn


def fresh(state):
    """A copy, placed as the original is, that a step may donate."""
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, state)


def run_steps(cfg, n_steps=8, seed=0, built=None):
    """`built`: what `build_train_objects(cfg)` gave, for a caller that runs
    one configuration more than once and compiles its step once (the state
    is donated: hand over `fresh(state)` for every run but the last)."""
    mesh, state, step_fn, _ = built or build_train_objects(cfg)
    rng = jax.random.key(cfg.seed + 1)
    losses = []
    for i in range(n_steps):
        batch = random_batch(cfg, mesh, seed=seed + i % 2)  # two alternating batches
        state, metrics = step_fn(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    return state, losses


def test_profile_trace_written(devices8, tmp_path):
    """--profile_dir captures a jax.profiler trace of steps 3-7 through the
    full loop (SURVEY.md section 5, tracing/profiling subsystem)."""
    import os
    from vitax.train.loop import train
    prof_dir = str(tmp_path / "trace")
    # the final-epoch save/eval clause still fires on num_epochs=1 — cap eval
    train(tiny_cfg(fake_data=True, num_epochs=1, steps_per_epoch=8,
                   profile_dir=prof_dir, log_step_interval=10,
                   ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=99,
                   test_epoch_interval=99, num_workers=2, eval_max_batches=1))
    found = [os.path.join(dp, f) for dp, _, fs in os.walk(prof_dir) for f in fs]
    assert any(f.endswith((".pb", ".json.gz", ".trace.json.gz")) for f in found), (
        f"no trace artifacts under {prof_dir}: {found}")


def test_fsdp_loss_decreases(devices8):
    _, losses = run_steps(tiny_cfg(), n_steps=10)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


def test_dp_fsdp_zero2_equivalence(devices8):
    """Same seed -> same loss trajectory across DP, ZeRO-3 and ZeRO-2 paths.
    This is the correctness property of sharded training: sharding must not
    change the math."""
    _, fsdp = run_steps(tiny_cfg(), n_steps=5)
    _, dp = run_steps(tiny_cfg(run_without_fsdp=True), n_steps=5)
    _, zero2 = run_steps(tiny_cfg(reshard_after_forward=False), n_steps=5)
    np.testing.assert_allclose(fsdp, dp, rtol=2e-4)
    np.testing.assert_allclose(fsdp, zero2, rtol=2e-4)


def test_no_grad_ckpt_equivalence(devices8):
    _, with_ckpt = run_steps(tiny_cfg(grad_ckpt=True), n_steps=4)
    _, without = run_steps(tiny_cfg(grad_ckpt=False), n_steps=4)
    np.testing.assert_allclose(with_ckpt, without, rtol=2e-4)


def test_grad_clipping_applied(devices8):
    """With a tiny clip norm, the update magnitude must shrink accordingly."""
    # warmup_steps=0: lr would be 0 at step 0 otherwise (schedule parity) and
    # no update would happen at all
    cfg_free = tiny_cfg(clip_grad_norm=0.0, warmup_steps=0)   # 0 disables clipping (reference :269)
    cfg_clip = tiny_cfg(clip_grad_norm=1e-4, warmup_steps=0)
    mesh = build_mesh(cfg_free)
    model = build_model(cfg_free)

    def one_update_norm(cfg):
        tx, _ = build_optimizer(cfg, max_iteration=100)
        state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
        step_fn = make_train_step(cfg, model, tx, mesh, sspecs)
        batch = random_batch(cfg, mesh)
        # state is donated to step_fn — snapshot params to host first
        old_params = jax.tree.map(lambda x: np.asarray(x), state.params)
        new_state, metrics = step_fn(state, batch, jax.random.key(1))
        import optax
        delta = jax.tree.map(lambda a, b: np.asarray(a) - b, new_state.params, old_params)
        return float(jax.device_get(optax.global_norm(delta))), float(
            jax.device_get(metrics["grad_norm"]))

    free_delta, free_gn = one_update_norm(cfg_free)
    clip_delta, clip_gn = one_update_norm(cfg_clip)
    assert free_gn > 1e-3  # unclipped grad norm is substantial
    # grad_norm metric reports the pre-clip norm in both cases
    np.testing.assert_allclose(free_gn, clip_gn, rtol=1e-4)
    assert clip_delta < free_delta  # clipped update is smaller


def test_eval_step_counts_correct(devices8):
    cfg = tiny_cfg()
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=10)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    eval_fn = make_eval_step(cfg, model, mesh, sspecs)
    batch = random_batch(cfg, mesh)
    counts = jax.device_get(eval_fn(state, batch))
    correct = int(counts["correct"])
    correct5 = int(counts["correct_top5"])
    assert 0 <= correct <= cfg.batch_size
    # top-5 dominates top-1; with num_classes=4 < 5, k clamps to 4 and
    # every sample's label is in the top-4 by construction
    assert correct <= correct5 <= cfg.batch_size
    assert correct5 == cfg.batch_size


def test_full_loop_fake_data(devices8, tmp_path):
    """The whole train() orchestration: fake data, 1 epoch of 3 steps, ckpt
    save, eval — BASELINE.json config 1 shape."""
    from vitax.train.loop import train
    cfg = tiny_cfg(
        fake_data=True, num_epochs=1, steps_per_epoch=3, log_step_interval=1,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=1,
        test_epoch_interval=1, num_workers=2, batch_size=16, eval_max_batches=4,
    )
    state = train(cfg)
    assert int(jax.device_get(state.step)) == 3
    import os
    assert os.path.isdir(os.path.join(str(tmp_path / "ckpt"), "epoch_1"))


def test_compile_cache_dir_populates(tmp_path):
    """The persistent compile cache, placed from outside through
    JAX_COMPILATION_CACHE_DIR (vitax/platform.py setup_compile_cache sets
    nothing in code then), keeps compiled step programs so restarts
    (launcher --restart, preemption resume) skip recompilation. Runs the
    REAL CLI in a subprocess: enabling the persistent cache mutates global
    jax.config and serializes executables, and doing that inside this
    process after ~200 suite tests aborted the interpreter twice (native
    crash in the cache write path with accumulated XLA state) — which is
    also why tests/conftest.py keeps the cache off in-process."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "xla_cache"
    # nothing of the caller's JAX or XLA settings reaches the child; one
    # device: the cache is what is pinned here, and eight virtual devices'
    # collectives time out at their rendezvous on a busy machine
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    # --eval_max_batches: the closing eval of the whole fake validation
    # split was 100 of this test's 120 seconds
    r = subprocess.run(
        [sys.executable, "run_vit_training.py", "--fake_data",
         "--image_size", "32", "--patch_size", "8", "--embed_dim", "32",
         "--num_heads", "4", "--num_blocks", "2", "--batch_size", "16",
         "--num_epochs", "1", "--steps_per_epoch", "2",
         "--log_step_interval", "1", "--test_epoch_interval", "10",
         "--eval_max_batches", "1",
         "--num_workers", "1", "--ckpt_dir", str(tmp_path / "ckpt")],
        cwd=repo, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert cache.is_dir() and os.listdir(cache), (
        "compile cache dir was never populated")


def test_sigterm_preemption_save(devices8, tmp_path):
    """SIGTERM mid-training -> committed checkpoint + clean exit + auto-resume
    (the preemption story the async checkpointer enables; vitax/train/preempt.py)."""
    import os
    import signal

    from vitax.train import preempt
    from vitax.train.loop import train

    preempt.reset()
    assert preempt.install()  # main thread in pytest
    # deliver a real SIGTERM; Python runs the handler at the next bytecode
    # boundary, so the flag is set before train() begins stepping
    os.kill(os.getpid(), signal.SIGTERM)
    try:
        cfg = tiny_cfg(
            fake_data=True, num_epochs=3, steps_per_epoch=50, log_step_interval=99,
            ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=99,
            test_epoch_interval=99, num_workers=2, eval_max_batches=1,
        )
        state = train(cfg)
        # exited after ONE step of epoch 1 (not 3 epochs x 50 steps)
        assert int(jax.device_get(state.step)) == 1
        assert os.path.isdir(os.path.join(str(tmp_path / "ckpt"), "epoch_1"))
        # train() restored the pre-install SIGTERM disposition on exit, so
        # post-training work (and this pytest process) keeps normal semantics
        assert signal.getsignal(signal.SIGTERM) is not preempt._handler
    finally:
        preempt.uninstall()
        preempt.reset()

    # auto-resume re-enters epoch 1 AT STEP 2 (step-granular: the sidecar
    # recorded 1 completed step) and finishes it under the new
    # steps_per_epoch=2, then runs epoch 2 in full
    cfg2 = tiny_cfg(
        fake_data=True, num_epochs=2, steps_per_epoch=2, log_step_interval=99,
        resume_epoch=-1, ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=99,
        test_epoch_interval=99, num_workers=2, eval_max_batches=1,
    )
    state2 = train(cfg2)
    # 1 saved + epoch-1's remaining 1 step + epoch-2's 2 steps
    assert int(jax.device_get(state2.step)) == 4


@pytest.mark.slow
def test_model_actually_learns(devices8):
    """Beyond loss-decreases: on a linearly-separable synthetic task (class =
    dominant color channel) the full sharded train step must reach high train
    accuracy from random init — end-to-end learning evidence (model + loss +
    optimizer + schedule + sharding all correct together), not just a falling
    scalar."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec

    cfg = tiny_cfg(num_classes=3, batch_size=32, lr=3e-3, warmup_steps=5)
    mesh, state, step_fn, eval_fn = build_train_objects(cfg, max_iteration=200)
    sh = NamedSharding(mesh, batch_pspec())

    def color_batch(seed):
        rng = np.random.default_rng(seed)
        labels = rng.integers(0, 3, size=(cfg.batch_size,))
        imgs = rng.normal(0, 0.3, size=(
            cfg.batch_size, cfg.image_size, cfg.image_size, 3))
        for i, c in enumerate(labels):
            imgs[i, :, :, c] += 2.0  # dominant channel = class
        return {"image": jax.device_put(jnp.asarray(imgs, jnp.float32), sh),
                "label": jax.device_put(jnp.asarray(labels, jnp.int32), sh)}

    rng_key = jax.random.key(1)
    for i in range(60):
        state, metrics = step_fn(state, color_batch(i), rng_key)

    # held-out batches (seeds never trained on)
    correct = sum(
        int(jax.device_get(eval_fn(state, color_batch(1000 + j))["correct"]))
        for j in range(4))
    accuracy = correct / (4 * cfg.batch_size)
    assert accuracy > 0.9, f"model failed to learn a separable task: {accuracy=}"
