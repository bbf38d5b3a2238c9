"""Fused clip+AdamW optimizer (vitax/ops/fused_optimizer.py).

Covers the ISSUE-15 acceptance bars: per-leaf kernel numerics against a
closed-form AdamW reference (zero-grad and all-zero-channel leaves
included), both clip branches, in-place aliasing (buffer identity through
jit donation), 3-step fused-vs-optax equivalence on all six parallelism
arms, the flag-off program identity, and the single-norm-reduction jaxpr
pin (the satellite fix: grad_norm is no longer re-reduced for the metric).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from vitax.config import Config
from vitax.ops.fused_optimizer import (FUSED_KERNEL_NAME, find_adam_state,
                                       fused_clip_adamw,
                                       fused_optimizer_active)
from vitax.train.state import ADAMW_HPARAMS

B1, B2, EPS = ADAMW_HPARAMS["b1"], ADAMW_HPARAMS["b2"], ADAMW_HPARAMS["eps"]


def closed_form_adamw(p, g, mu, nu, *, count, lr, wd, clip_scale=1.0):
    """Textbook clip+AdamW in fp64 — independent of both optax and the
    kernel's operand ordering; the shared ≤1e-6 oracle."""
    p, g, mu, nu = (np.asarray(x, np.float64) for x in (p, g, mu, nu))
    g = g * clip_scale
    mu2 = (1 - B1) * g + B1 * mu
    nu2 = (1 - B2) * g * g + B2 * nu
    t = count + 1
    upd = (mu2 / (1 - B1 ** t)) / (np.sqrt(nu2 / (1 - B2 ** t)) + EPS)
    return p - lr * (upd + wd * p), mu2, nu2


def run_fused(params, grads, mu, nu, *, count=0, lr=1e-3, wd=0.01,
              clip_norm=0.0):
    opt_state = (optax.ScaleByAdamState(
        count=jnp.int32(count), mu=mu, nu=nu),)
    gnorm = optax.global_norm(grads)
    new_p, new_s = jax.jit(lambda g, s, p, n: fused_clip_adamw(
        g, s, p, grad_norm=n, schedule=lambda c: lr, clip_norm=clip_norm,
        weight_decay=wd, b1=B1, b2=B2, eps=EPS))(grads, opt_state, params,
                                                 gnorm)
    adam = find_adam_state(new_s)
    return new_p, adam


def assert_tree_close(got, want, rtol=1e-6, atol=1e-8):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=rtol, atol=atol)


class TestKernelNumerics:
    def _tree(self, seed=0):
        # 3-D (ragged rows vs the 8-row tile), matrix, vector, scalar
        shapes = [(3, 37, 96), (257, 40), (33,), ()]
        ks = jax.random.split(jax.random.key(seed), 3 * len(shapes))
        params = {f"l{i}": jax.random.normal(ks[3 * i], s, jnp.float32)
                  for i, s in enumerate(shapes)}
        grads = {f"l{i}": jax.random.normal(ks[3 * i + 1], s, jnp.float32)
                 for i, s in enumerate(shapes)}
        mu = {f"l{i}": 0.1 * jax.random.normal(ks[3 * i + 2], s, jnp.float32)
              for i, s in enumerate(shapes)}
        nu = {k: v * v for k, v in mu.items()}
        return params, grads, mu, nu

    def test_matches_closed_form(self):
        params, grads, mu, nu = self._tree()
        new_p, adam = run_fused(params, grads, mu, nu, count=5)
        assert int(adam.count) == 6
        for k in params:
            want = closed_form_adamw(params[k], grads[k], mu[k], nu[k],
                                     count=5, lr=1e-3, wd=0.01)
            for g, w in zip((new_p[k], adam.mu[k], adam.nu[k]), want):
                # atol: one f32 ulp of the O(1) outputs — the oracle is
                # fp64, so near-zero elements differ by result rounding
                np.testing.assert_allclose(np.asarray(g, np.float64), w,
                                           rtol=1e-6, atol=2e-7)

    def test_zero_grads(self):
        params, _, mu, nu = self._tree(1)
        zeros = jax.tree.map(jnp.zeros_like, params)
        new_p, adam = run_fused(params, zeros, mu, nu)
        for k in params:
            want = closed_form_adamw(params[k], np.zeros(params[k].shape),
                                     mu[k], nu[k], count=0, lr=1e-3, wd=0.01)
            for g, w in zip((new_p[k], adam.mu[k], adam.nu[k]), want):
                np.testing.assert_allclose(np.asarray(g, np.float64), w,
                                           rtol=1e-6, atol=1e-8)
            assert np.all(np.isfinite(new_p[k]))

    def test_all_zero_channel(self):
        # a dead channel (grad AND moments zero) must step by pure weight
        # decay — no 0/0 from the sqrt(nu) denominator
        p = jnp.ones((16, 8), jnp.float32)
        g = jnp.ones((16, 8), jnp.float32).at[:, 3].set(0.0)
        mu = jnp.zeros((16, 8), jnp.float32)
        nu = jnp.zeros((16, 8), jnp.float32)
        new_p, adam = run_fused({"w": p}, {"w": g}, {"w": mu}, {"w": nu},
                                lr=1e-2, wd=0.1)
        assert np.all(np.isfinite(new_p["w"]))
        want = closed_form_adamw(p, g, mu, nu, count=0, lr=1e-2, wd=0.1)
        np.testing.assert_allclose(np.asarray(new_p["w"], np.float64),
                                   want[0], rtol=1e-6, atol=1e-8)
        # the dead channel moved by exactly -lr*wd*p
        np.testing.assert_allclose(
            np.asarray(new_p["w"][:, 3]), (1 - 1e-2 * 0.1) * np.ones(16),
            rtol=1e-6)


class TestClipBranches:
    def _setup(self, gscale):
        k1, k2 = jax.random.split(jax.random.key(2))
        params = {"w": jax.random.normal(k1, (64, 32), jnp.float32)}
        grads = {"w": gscale * jax.random.normal(k2, (64, 32), jnp.float32)}
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        return params, grads, mu, nu

    def test_clip_inactive_is_identity(self):
        params, grads, mu, nu = self._setup(1e-3)  # norm << 1
        assert float(optax.global_norm(grads)) < 1.0
        clipped, _ = run_fused(params, grads, mu, nu, clip_norm=1.0)
        unclipped, _ = run_fused(params, grads, mu, nu, clip_norm=0.0)
        assert_tree_close(clipped, unclipped, rtol=0, atol=0)

    def test_clip_active_scales(self):
        params, grads, mu, nu = self._setup(10.0)
        gnorm = float(optax.global_norm(grads))
        assert gnorm > 1.0
        new_p, adam = run_fused(params, grads, mu, nu, clip_norm=1.0)
        want = closed_form_adamw(params["w"], grads["w"], mu["w"], nu["w"],
                                 count=0, lr=1e-3, wd=0.01,
                                 clip_scale=1.0 / gnorm)
        np.testing.assert_allclose(np.asarray(new_p["w"], np.float64),
                                   want[0], rtol=1e-6, atol=1e-8)
        # the post-clip grad norm the moments saw is ~clip_norm
        np.testing.assert_allclose(
            float(np.sqrt(np.sum(np.square(
                np.asarray(adam.mu["w"]) / (1 - B1))))), 1.0, rtol=1e-5)

    def test_matches_optax_chain(self):
        # vs the actual optax chain the flag replaces, both branches
        for gscale in (1e-3, 10.0):
            params, grads, mu, nu = self._setup(gscale)
            new_p, adam = run_fused(params, grads, mu, nu, count=2,
                                    clip_norm=1.0)
            tx = optax.chain(optax.clip_by_global_norm(1.0),
                             optax.adamw(lambda c: 1e-3, weight_decay=0.01,
                                         **ADAMW_HPARAMS))
            opt_state = jax.tree.map(
                lambda x: x,
                (optax.EmptyState(),
                 (optax.ScaleByAdamState(count=jnp.int32(2), mu=mu, nu=nu),
                  optax.EmptyState(),
                  optax.ScaleByScheduleState(count=jnp.int32(2)))))
            want = jax.jit(lambda g, s, p: optax.apply_updates(
                p, tx.update(g, s, p)[0]))(grads, opt_state, params)
            assert_tree_close(new_p, want, rtol=1e-6, atol=1e-8)


class TestAliasing:
    def test_inplace_buffer_identity(self):
        """param/mu/nu outputs land on the donated input buffers — the
        input_output_aliases contract survives jit donation to the runtime
        (unsafe_buffer_pointer equality, not just program metadata)."""
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(p, mu, nu, g):
            opt_state = (optax.ScaleByAdamState(
                count=jnp.int32(0), mu=mu, nu=nu),)
            new_p, new_s = fused_clip_adamw(
                g, opt_state, p, grad_norm=optax.global_norm(g),
                schedule=lambda c: 1e-3, clip_norm=1.0, weight_decay=0.01,
                b1=B1, b2=B2, eps=EPS)
            adam = find_adam_state(new_s)
            return new_p, adam.mu, adam.nu

        k = jax.random.key(3)
        mk = lambda key: jax.device_put(  # noqa: E731
            jax.random.normal(key, (256, 128), jnp.float32))
        p, mu, nu, g = (mk(x) for x in jax.random.split(k, 4))
        donated = {x.unsafe_buffer_pointer() for x in (p, mu, nu)}
        outs = step(p, mu, nu, g)
        out_ptrs = {x.unsafe_buffer_pointer() for x in outs}
        assert out_ptrs <= donated, (
            f"outputs allocated fresh buffers: {out_ptrs - donated}")
        assert len(out_ptrs) == 3  # three distinct in-place destinations

    def test_aliasing_in_lowered_program(self):
        # structural check: the donated params carry tf.aliasing_output in
        # the lowered MLIR (the program-level half of the contract)
        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(p, g):
            opt_state = (optax.ScaleByAdamState(
                count=jnp.int32(0), mu=jnp.zeros_like(p),
                nu=jnp.zeros_like(p)),)
            new_p, _ = fused_clip_adamw(
                g, opt_state, p, grad_norm=optax.global_norm(g),
                schedule=lambda c: 1e-3, clip_norm=0.0, weight_decay=0.0,
                b1=B1, b2=B2, eps=EPS)
            return new_p

        x = jnp.ones((64, 64), jnp.float32)
        mlir = step.lower(x, x).as_text()
        assert "tf.aliasing_output" in mlir


# ---------------------------------------------------------------------------
# end-to-end train-step arms

# the six ISSUE-15 parallelism arms (CPU, 8 virtual devices)
EQUIV_ARMS = {
    "dp": dict(run_without_fsdp=True, dtype="float32"),
    "zero2": dict(reshard_after_forward=False),
    "zero3": dict(gather_overlap="off"),
    "zero3_overlap": dict(gather_overlap="on"),
    "accum2": dict(batch_size=128, grad_accum_steps=2, gather_overlap="off"),
    "bf16comm": dict(gather_overlap="off", param_gather_dtype="bfloat16",
                     grad_reduce_dtype="bfloat16"),
}

GEOMETRY = dict(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                num_blocks=2, num_classes=4, batch_size=64, warmup_steps=2)


def _build(cfg):
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step

    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh))
    tx, schedule = build_optimizer(cfg, max_iteration=100)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                        jax.random.key(0))
    step = make_train_step(cfg, model, tx, mesh, sspecs, schedule=schedule)
    return mesh, state, step


def _run_steps(arm_overrides, fused_mode, steps=3):
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec

    kw = dict(GEOMETRY)
    kw.update(arm_overrides)
    kw["fused_optimizer"] = fused_mode
    cfg = Config(**kw).validate()
    mesh, state, step = _build(cfg)
    sh = NamedSharding(mesh, batch_pspec())
    rng_img = np.random.default_rng(0)
    metrics = []
    for _ in range(steps):
        batch = {
            "image": jax.device_put(rng_img.standard_normal(
                (cfg.batch_size, cfg.image_size, cfg.image_size, 3),
                dtype=np.float32), sh),
            "label": jax.device_put(
                (np.arange(cfg.batch_size) % cfg.num_classes).astype(
                    np.int32), sh),
        }
        state, m = step(state, batch, jax.random.key(42))
        metrics.append({k: float(jax.device_get(m[k]))
                        for k in ("loss", "grad_norm")})
    return state, metrics


@pytest.mark.parametrize("arm", sorted(EQUIV_ARMS))
def test_fused_matches_optax_3_steps(arm):
    """≤1e-6-relative fused-vs-optax agreement after 3 real train steps on
    every parallelism arm (the ISSUE-15 acceptance bar). atol floors the
    comparison for near-zero elements, where an elementwise ratio would
    amplify 1-ulp XLA fusion reassociation into meaceless percentages."""
    s_fused, m_fused = _run_steps(EQUIV_ARMS[arm], "on")
    s_optax, m_optax = _run_steps(EQUIV_ARMS[arm], "off")
    for mf, mo in zip(m_fused, m_optax):
        assert mf["loss"] == pytest.approx(mo["loss"], rel=1e-6)
        assert mf["grad_norm"] == pytest.approx(mo["grad_norm"], rel=1e-6)
    assert_tree_close(s_fused.params, s_optax.params)
    adam_f = find_adam_state(s_fused.opt_state)
    adam_o = find_adam_state(s_optax.opt_state)
    assert int(adam_f.count) == int(adam_o.count) == 3
    assert_tree_close(adam_f.mu, adam_o.mu)
    assert_tree_close(adam_f.nu, adam_o.nu)
    # state tree structure (checkpoint/state_specs contract) unchanged
    assert (jax.tree_util.tree_structure(s_fused.opt_state)
            == jax.tree_util.tree_structure(s_optax.opt_state))


def _trace_text(cfg):
    from vitax.analysis.hlo import train_step_jaxpr
    return train_step_jaxpr(cfg, max_iteration=100)


def test_flag_off_program_identity():
    """--fused_optimizer off traces the SAME program as the CPU default
    (auto resolves off where the kernels would interpret): byte-identical
    jaxpr — the flag's off position cannot perturb production numerics."""
    kw = dict(GEOMETRY, gather_overlap="off")
    off = _trace_text(Config(**kw, fused_optimizer="off").validate())
    auto = _trace_text(Config(**kw, fused_optimizer="auto").validate())
    assert not fused_optimizer_active(
        Config(**kw, fused_optimizer="auto").validate())
    assert off == auto
    assert FUSED_KERNEL_NAME not in off


def test_fused_on_enters_program():
    kw = dict(GEOMETRY, gather_overlap="off")
    on = _trace_text(Config(**kw, fused_optimizer="on").validate())
    assert FUSED_KERNEL_NAME in on


def test_single_norm_reduction_in_jaxpr():
    """Satellite regression pin: ONE scalar sqrt (the global-norm
    reduction) in the traced step on BOTH paths — the old program paid a
    second full-tree norm pass for the grad_norm metric."""
    kw = dict(GEOMETRY, gather_overlap="off")
    for mode in ("off", "on"):
        text = _trace_text(Config(**kw, fused_optimizer=mode).validate())
        if mode == "on":
            from vitax.analysis.hlo import strip_bracketed
            text = strip_bracketed(text, "pallas_call")
        scalar_sqrts = re.findall(r":f32\[\] = sqrt\b", text)
        assert len(scalar_sqrts) == 1, (mode, len(scalar_sqrts))


def test_fused_requires_schedule():
    from vitax.parallel.mesh import build_mesh
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl

    cfg = Config(**dict(GEOMETRY, gather_overlap="off",
                        fused_optimizer="on")).validate()
    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh))
    tx, _ = build_optimizer(cfg, max_iteration=100)
    _, sspecs, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0),
                                    materialize=False)
    with pytest.raises(ValueError, match="schedule"):
        make_train_step(cfg, model, tx, mesh, sspecs)
