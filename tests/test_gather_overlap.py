"""Overlapped ZeRO-3 schedule (--gather_overlap) correctness.

The forward's double-buffered prefetch (vitax/models/vit.py:make_overlap_forward
+ vitax/parallel/sharding.py:prefetch_gather) is a pure SCHEDULING change: the
same gathers, the same math, placed elsewhere. The backward's weight gradients
(sharding.py:ring_weight_grad, PR 50) are the same sum of the same per-chip
partial products, added in the ring's order instead of the compiler's
reduce-scatter's, from float32 partials that are no longer rounded first.
These tests pin that down:

- step 1's loss bitwise on vs off (the forward is untouched) and three steps'
  losses equal within summation order, across the zero3 / zero3+bf16-gather /
  grad-accum arms;
- `ring_weight_grad` against x^T dy on integer-valued inputs (exact in any
  order) over ring lengths, both sharded dimensions and both wire dtypes; its
  result in the leaf's own layout; the plain product where fsdp does not
  divide; one step's gradient tree on vs off, leaf by leaf;
- `off` dispatches to the exact pre-overlap forward (identical jaxpr);
- Config.validate rejects `on` under pipeline parallelism;
- the comm_audit structural verdict: per-iteration forward gather count
  unchanged, under `on` every in-loop forward gather sits on the scan
  carry's prefetch slot instead of a parameter use site, and the backward
  body holds the ring's permutes and no block-sized synchronous reduce.

Geometry note: the loss arms use batch_size=64 (B*N=320 tokens). At the
smoke default of 16, B*N=80 < 4*embed_dim=128 and GSPMD partitions the MLP as
activation-gather + hidden-sharded partial dot + all-reduce — the baseline
never gathers the MLP weights, so a weight-gather schedule cannot match its
accumulation order bitwise. Above that threshold the baseline flips to plain
use-site weight gathers and the forward's bitwise equality is well-defined.
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


# three steps' losses, on against off: the float32 arm to float32's summation
# order; the bf16 arms to a quarter of a bf16 ulp (2**-8 is one), which is what
# two updates from gradients that differ by a rounding of their sums can move
LOSS_RTOL = {"zero3": 1e-6, "zero3_bf16_gather": 2.0 ** -10,
             "accum2": 2.0 ** -10}


@pytest.mark.parametrize("arm", sorted(OVERLAP_ARMS))
def test_overlap_bitwise_vs_off(devices8, arm):
    """Step 1's loss is bit-identical on vs off: the schedule moves the
    forward's gathers, not its math. Steps 2 and 3 (after two optimizer
    updates) agree within summation order: the ring adds the same partial
    weight gradients in another order."""
    kw = OVERLAP_ARMS[arm]
    off = _run_losses(tiny_cfg(gather_overlap="off", **kw))
    on = _run_losses(tiny_cfg(gather_overlap="on", **kw))
    assert off[0] == on[0], (
        f"{arm}: overlap changed the forward: off={off!r} on={on!r}")
    np.testing.assert_allclose(on, off, rtol=LOSS_RTOL[arm], atol=0,
                               err_msg=f"{arm}: off={off!r} on={on!r}")


def _fsdp_mesh(fsdp):
    from vitax.parallel.mesh import build_mesh
    return build_mesh(tiny_cfg(dp_size=8 // fsdp, fsdp_size=fsdp))


def _integer_rows(mesh, width_in, width_out, dtype):
    """(x, dy, x^T dy): small whole numbers, so every partial sum is exact in
    float32 and in bfloat16 alike, whatever the order of the additions."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    rng = np.random.default_rng(0)
    x = rng.integers(-2, 3, (16, 3, width_in)).astype(np.float32)
    dy = rng.integers(-2, 3, (16, 3, width_out)).astype(np.float32)
    sh = NamedSharding(mesh, batch_pspec())
    return (jax.device_put(jnp.asarray(x, dtype), sh),
            jax.device_put(jnp.asarray(dy, dtype), sh),
            np.einsum("bni,bno->io", x, dy))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("fsdp", [2, 4, 8])
def test_ring_weight_grad_exact(devices8, fsdp, dim, dtype):
    """The ring's sum is x^T dy, over ring lengths 2, 4 and 8 (beside dp 4, 2
    and 1), a kernel sharded on its rows and on its columns, a float32 and a
    bfloat16 wire; the result has the wire's dtype and the leaf's layout.
    A chunk of even width goes round in two halves, one each way (24 rows
    over 8 chips are chunks of 3: one way)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from vitax.parallel.sharding import ring_weight_grad

    mesh = _fsdp_mesh(fsdp)
    x, dy, want = _integer_rows(mesh, 24, 16, dtype)
    spec = P("fsdp", None) if dim == 0 else P(None, "fsdp")
    ring = jax.jit(lambda a, b: ring_weight_grad(a, b, mesh, spec, x.dtype))
    got = ring(x, dy)
    assert got.dtype == x.dtype
    assert got.sharding.is_equivalent_to(NamedSharding(mesh, spec), 2)
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)
    # fsdp - 1 hops a direction, and the sum over fsdp is no reduce of the
    # compiler's
    text = ring.lower(x, dy).compile().as_text()
    ways = 2 if ((24, 16)[dim] // fsdp) % 2 == 0 else 1
    assert text.count(" collective-permute(") + text.count(
        " collective-permute-start(") == ways * (fsdp - 1), text
    assert "reduce-scatter" not in text


def test_ring_weight_grad_falls_back(devices8):
    """A dimension fsdp does not divide, a leaf with no fsdp dimension and a
    batch that does not split over the chips keep the plain product: the same
    values, no permute in the program."""
    from jax.sharding import PartitionSpec as P
    from vitax.parallel.sharding import ring_dim, ring_weight_grad

    mesh = _fsdp_mesh(8)
    x, dy, want = _integer_rows(mesh, 24, 12, "float32")   # 12 % 8 != 0
    assert ring_dim(P(None, "fsdp"), (24, 12), mesh) is None
    assert ring_dim(P(None, None), (24, 16), mesh) is None
    assert ring_dim(P("fsdp", None), (24, 16), mesh) == 0
    for spec, a, b in [(P(None, "fsdp"), x, dy), (P(None, None), x, dy),
                       (P("fsdp", None), x[:12], dy[:12])]:
        plain = jax.jit(
            lambda a, b: ring_weight_grad(a, b, mesh, spec, "float32"))
        assert "ppermute" not in str(jax.make_jaxpr(plain)(a, b))
        got = np.asarray(plain(a, b))
        np.testing.assert_array_equal(
            got, want if a is x else np.einsum(
                "bni,bno->io", np.asarray(a), np.asarray(b)))


@pytest.mark.parametrize("mesh_kw", [dict(), dict(dp_size=2, fsdp_size=4)],
                         ids=["fsdp8", "dp2_fsdp4"])
def test_ring_gradient_tree_vs_off(devices8, mesh_kw):
    """One step's gradient tree, on against off, leaf by leaf: every block
    matrix's gradient comes out of the ring in the stacked tree's layout and
    equals the plain schedule's within float32 summation order, on a ring of
    8 and on a ring of 4 beside a dp of 2 (whose sum the partitioner adds)."""
    import jax.numpy as jnp
    from vitax.models import build_model
    from vitax.ops.attention import make_attention_impl
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import token_sharding as _token_sharding
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import _forward_fn

    def grads(mode):
        cfg = tiny_cfg(gather_overlap=mode, batch_size=64, **mesh_kw)
        mesh = build_mesh(cfg)
        model = build_model(cfg, attention_impl=make_attention_impl(cfg, mesh),
                            token_sharding=_token_sharding(cfg, mesh))
        tx, _ = build_optimizer(cfg, max_iteration=10)
        state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                            jax.random.key(0))
        forward = _forward_fn(cfg, model, mesh, sspecs)
        batch = random_batch(cfg, mesh)

        def loss(params):
            logp = jax.nn.log_softmax(forward(params, batch["image"], True))
            return -jnp.mean(jnp.take_along_axis(
                logp, batch["label"][:, None], axis=1))

        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(state.params))
        placed = jax.tree.map(lambda p: p.sharding, state.params)
        return jax.jit(jax.grad(loss))(state.params), jaxpr, placed

    off, off_jaxpr, _ = grads("off")
    on, on_jaxpr, placed = grads("on")
    assert "ppermute" in on_jaxpr and "ppermute" not in off_jaxpr
    flat_off = jax.tree_util.tree_flatten_with_path(off)[0]
    flat_on = jax.tree_util.tree_flatten_with_path(on)[0]
    assert [p for p, _ in flat_on] == [p for p, _ in flat_off]
    kernels = 0
    for (path, a), (_, b), sh in zip(flat_on, flat_off,
                                    jax.tree.leaves(placed)):
        names = [k.key for k in path]
        if "blocks" in names and names[-1] == "kernel":
            # a ring's result is born in its parameter's layout
            assert a.sharding.is_equivalent_to(sh, a.ndim), path
            kernels += 1
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6 * scale,
            err_msg=jax.tree_util.keystr(path))
    assert kernels == 4


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

    # the backward body (the second while in program order). off: the four
    # block matrices' gradients each leave through a synchronous reduce of
    # the partitioner's; on: through fsdp - 1 = 7 ring permutes each and
    # direction (every chunk here has an even width: both directions), and
    # no block-sized synchronous reduce is left in any scan body
    off_bwd_body = list(off["sync_block_reduces_by_body"])[1]
    on_bwd_body = list(on["ring_permutes_by_body"])[1]
    assert off["sync_block_reduces_by_body"][off_bwd_body] == 4, off
    assert off["ring_permutes"] == 0, off
    assert on["sync_block_reduces"] == 0, on
    assert on["ring_permutes_by_body"][on_bwd_body] == 2 * 4 * 7, on
    assert on["ring_permutes"] == 2 * 4 * 7, on
