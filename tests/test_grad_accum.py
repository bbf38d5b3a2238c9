"""Gradient-accumulation microbatching (--grad_accum_steps, PR 1) on the
8-virtual-device CPU mesh at fp32: K=4 must reproduce the K=1 trajectory
(losses AND final params) on the dense, MoE-aux, ZeRO-2, and remat-window
paths — accumulation must not change the math, only the peak memory.
Plus validate()-rejection cases and the K=1 no-scan-wrapper guarantee.
"""

import jax
import numpy as np
import pytest

from tests.test_train_smoke import (build_train_objects, fresh, random_batch,
                                    run_steps, tiny_cfg)


def _params_close(a, b, rtol=1e-5, atol=1e-6):
    flat_a = jax.tree.leaves(a)
    flat_b = jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def _equivalence(cfg_kw, n_steps=3):
    # batch 32 so the K=4 microbatch (8) still covers the 8 batch devices
    state_1, losses_1 = run_steps(tiny_cfg(batch_size=32, **cfg_kw),
                                  n_steps=n_steps)
    state_k, losses_k = run_steps(tiny_cfg(batch_size=32, grad_accum_steps=4,
                                           **cfg_kw), n_steps=n_steps)
    assert all(np.isfinite(losses_k))
    np.testing.assert_allclose(losses_k, losses_1, rtol=1e-5)
    _params_close(state_k.params, state_1.params)


def test_dense_equivalence(devices8):
    """Manual fp32 accumulation: exact vs K=1 by linearity of the gradient
    in the per-sample loss mean."""
    _equivalence({})


def test_moe_equivalence(devices8):
    """The load-balance aux couples microbatches (full-batch ingredient
    means before the frac*prob product) — the through-scan objective must
    still match K=1 exactly, not just approximately."""
    _equivalence(dict(moe_experts=4))


def test_moe_remat_window_equivalence(devices8):
    """MoE + --remat_window under accumulation: the windowed forward's raw
    aux-ingredient stacks feed the through-scan objective."""
    _equivalence(dict(moe_experts=4, remat_window=2))


def test_zero2_equivalence(devices8):
    """ZeRO-2: the step-top full gather is scan-invariant (one gather, K
    reuses) and grads accumulate at the SHARDED layout."""
    _equivalence(dict(reshard_after_forward=False))


def test_remat_window_equivalence(devices8):
    _equivalence(dict(remat_window=2))


def test_dropout_deterministic_per_microbatch(devices8):
    """Under dropout each microbatch folds its index into the step rng:
    the K>1 trajectory is deterministic given the seed, and differs from
    K=1 (different masks — by design, not a bug)."""
    kw = dict(att_dropout=0.1, mlp_dropout=0.1, pos_dropout=0.1)
    cfg = tiny_cfg(grad_accum_steps=2, **kw)
    mesh, state, step_fn, eval_fn = build_train_objects(cfg)
    _, a = run_steps(cfg, n_steps=2,
                     built=(mesh, fresh(state), step_fn, eval_fn))
    _, b = run_steps(cfg, n_steps=2, built=(mesh, state, step_fn, eval_fn))
    np.testing.assert_array_equal(a, b)
    _, base = run_steps(tiny_cfg(**kw), n_steps=2)
    assert all(np.isfinite(a))
    assert not np.allclose(a, base, rtol=1e-6)


def test_k1_compiles_without_scan_wrapper(devices8):
    """grad_accum_steps=1 must trace the exact pre-accumulation program: no
    accumulation while-loop in the lowered step (scan_blocks/remat off so
    the only possible loop would be the accumulation scan), while K=2
    introduces one."""
    def lowered_text(cfg):
        mesh, state, step_fn, _ = build_train_objects(cfg)
        batch = random_batch(cfg, mesh)
        return step_fn.lower(state, batch, jax.random.key(0)).as_text()

    base = dict(scan_blocks=False, grad_ckpt=False)
    assert "stablehlo.while" not in lowered_text(tiny_cfg(**base))
    assert "stablehlo.while" in lowered_text(
        tiny_cfg(grad_accum_steps=2, **base))


def test_validate_rejects_bad_accum():
    with pytest.raises(AssertionError, match="grad_accum_steps"):
        tiny_cfg(grad_accum_steps=0)
    with pytest.raises(AssertionError, match="not divisible"):
        tiny_cfg(grad_accum_steps=3)  # 16 % 3 != 0
    with pytest.raises(AssertionError, match="pipeline already microbatches"):
        tiny_cfg(grad_accum_steps=2, pp_size=2)


def test_validate_rejects_bad_dropout_rates():
    # rate >= 1 would turn the kernels' 1/(1-rate) rescale into inf/NaN
    for kw in (dict(att_dropout=1.0), dict(pos_dropout=-0.1),
               dict(mlp_dropout=1.5)):
        with pytest.raises(AssertionError, match="must be in"):
            tiny_cfg(**kw)
    tiny_cfg(att_dropout=0.0, mlp_dropout=0.999)  # boundary values pass
