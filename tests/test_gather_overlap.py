"""Overlapped ZeRO-3 gather schedule (--gather_overlap) correctness.

The double-buffered prefetch schedule (vitax/models/vit.py:make_overlap_forward
+ vitax/parallel/sharding.py:prefetch_gather) must be a pure SCHEDULING change:
same collectives, same math, different placement. These tests pin that down:

- bitwise loss equality over 3 steps, on vs off, across the zero3 /
  zero3+bf16-gather / grad-accum arms;
- `off` dispatches to the exact pre-overlap forward (identical jaxpr);
- Config.validate rejects `on` under pipeline parallelism;
- the comm_audit structural verdict: per-iteration forward gather count
  unchanged, and under `on` every in-loop forward gather sits on the scan
  carry's prefetch slot instead of a parameter use site.

Geometry note: the bitwise arms use batch_size=64 (B*N=320 tokens). At the
smoke default of 16, B*N=80 < 4*embed_dim=128 and GSPMD partitions the MLP as
activation-gather + hidden-sharded partial dot + all-reduce — the baseline
never gathers the MLP weights, so a weight-gather schedule cannot match its
accumulation order bitwise. Above that threshold the baseline flips to plain
use-site weight gathers and bitwise equality is well-defined.
"""

import numpy as np
import pytest

import jax

from vitax.config import Config

from tests.test_train_smoke import build_train_objects, random_batch, tiny_cfg


def _run_losses(cfg, n_steps=3):
    mesh, state, step_fn, _ = build_train_objects(cfg)
    rng = jax.random.key(cfg.seed + 1)
    losses = []
    for i in range(n_steps):
        batch = random_batch(cfg, mesh, seed=i % 2)
        state, metrics = step_fn(state, batch, rng)
        losses.append(jax.device_get(metrics["loss"]))
    return np.asarray(losses)


OVERLAP_ARMS = {
    # plain ZeRO-3, f32 end to end
    "zero3": dict(batch_size=64),
    # bf16 compute + bf16 gather policy: the prefetched slices go through
    # cast_to_compute exactly like use-site gathers do
    "zero3_bf16_gather": dict(batch_size=64, dtype="bfloat16",
                              param_gather_dtype="bfloat16"),
    # in-step gradient accumulation: the overlap forward runs inside the
    # accum microbatch scan (microbatches of 64 stay above the GSPMD
    # MLP-strategy threshold)
    "accum2": dict(batch_size=128, grad_accum_steps=2, dtype="bfloat16"),
}


@pytest.mark.parametrize("arm", sorted(OVERLAP_ARMS))
def test_overlap_bitwise_vs_off(devices8, arm):
    """`on` must produce bit-identical losses to `off` over 3 steps (2 full
    optimizer updates): the schedule moves gathers, not math."""
    kw = OVERLAP_ARMS[arm]
    off = _run_losses(tiny_cfg(gather_overlap="off", **kw))
    on = _run_losses(tiny_cfg(gather_overlap="on", **kw))
    assert np.array_equal(off, on), (
        f"{arm}: overlap changed the numerics: off={off!r} on={on!r}")


@pytest.mark.parametrize("arm_kw", [
    dict(),                          # zero3
    dict(reshard_after_forward=False),  # zero2
    dict(run_without_fsdp=True),     # pure DP
], ids=["zero3", "zero2", "dp"])
def test_off_traces_identical_program(devices8, arm_kw):
    """gather_overlap=off must trace the exact pre-overlap forward — the
    dispatch in vitax/train/step.py:_forward_fn may not wrap or perturb the
    program in any way (same jaxpr as a direct model.apply closure)."""
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import token_sharding as _token_sharding
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import _forward_fn

    cfg = tiny_cfg(gather_overlap="off", **arm_kw)
    mesh = build_mesh(cfg)
    model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh),
                        token_sharding=_token_sharding(cfg, mesh))
    tx, _ = build_optimizer(cfg, max_iteration=10)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                        jax.random.key(0))
    images = random_batch(cfg, mesh)["image"]

    dispatched = _forward_fn(cfg, model, mesh, sspecs)
    direct = lambda p, x: model.apply(p, x, True)
    jaxpr_dispatched = str(jax.make_jaxpr(
        lambda p, x: dispatched(p, x, True))(state.params, images))
    jaxpr_direct = str(jax.make_jaxpr(direct)(state.params, images))
    assert jaxpr_dispatched == jaxpr_direct


def test_overlap_auto_selection(devices8):
    """auto == on exactly when the schedule is sound: ZeRO-3 + scanned
    blocks + full remat, no pipeline, sharded fsdp axis."""
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import gather_overlap_active

    zero3 = tiny_cfg()  # gather_overlap defaults to auto
    assert gather_overlap_active(zero3, build_mesh(zero3))
    zero2 = tiny_cfg(reshard_after_forward=False)
    assert not gather_overlap_active(zero2, build_mesh(zero2))
    dp = tiny_cfg(run_without_fsdp=True)
    assert not gather_overlap_active(dp, build_mesh(dp))
    off = tiny_cfg(gather_overlap="off")
    assert not gather_overlap_active(off, build_mesh(off))


def test_overlap_rejects_pipeline():
    """The prefetch carry threads through the single layer scan; under
    pp_size>1 blocks live on pipeline stages and the schedule is undefined —
    validate() must reject the combination outright."""
    with pytest.raises(AssertionError):
        Config(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
               num_blocks=4, num_classes=4, batch_size=16,
               pp_size=2, gather_overlap="on").validate()


def test_comm_audit_overlap_verdict(devices8):
    """Structural HLO check via tools/comm_audit.py: the per-iteration
    forward gather count is unchanged between off and on, and under `on`
    every forward in-loop gather feeds the scan carry (prefetch slot) while
    under `off` none do."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.comm_audit import audit_config

    base = dict(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                num_blocks=2, num_classes=4, batch_size=64, warmup_steps=2)
    off = audit_config(Config(**base, gather_overlap="off").validate())["overlap"]
    on = audit_config(Config(**base, gather_overlap="on").validate())["overlap"]

    # the first while body in program order is the forward scan
    off_fwd_body = next(iter(off["per_iteration_gather_count"]))
    on_fwd_body = next(iter(on["per_iteration_gather_count"]))
    off_fwd = off["per_iteration_gather_count"][off_fwd_body]
    on_fwd = on["per_iteration_gather_count"][on_fwd_body]

    # 12 block-param leaves -> 12 gathers per iteration, both schedules
    assert off_fwd == on_fwd > 0, (off, on)
    # off: all use-site (consumed by compute); on: all on the prefetch slot
    assert off["prefetch_slot_gathers"] == 0, off
    assert on["prefetch_slot_by_body"][on_fwd_body] == on_fwd, on
