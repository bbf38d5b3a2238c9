"""Mixture-of-Experts + expert parallelism on the 8-virtual-device CPU mesh:
single-expert equivalence with the dense Mlp, routing/capacity semantics, the
load-balance aux loss, expert param sharding over "ep", and full train-step
trajectory equivalence between ep-sharded and data-parallel meshes —
mirrors the pp/sp suites for the last parallelism axis (vitax/models/moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vitax.config import Config
from vitax.models import build_model
from vitax.models.moe import MoeMlp
from vitax.models.vit import Mlp
from vitax.parallel.mesh import build_mesh


def moe_cfg(**kw):
    base = dict(image_size=32, patch_size=8, embed_dim=32, num_heads=4,
                num_blocks=2, num_classes=4, batch_size=16, dtype="float32",
                moe_experts=4, ep_size=2, dp_size=2, fsdp_size=2,
                warmup_steps=0)
    base.update(kw)
    return Config(**base).validate()


def test_single_expert_equals_dense_mlp():
    """E=1 with capacity >= N degenerates to the dense Mlp: the router's
    softmax over one expert gates everything at 1.0, so output must equal
    Mlp with the same (unstacked) weights."""
    d, h, n = 16, 32, 8
    moe = MoeMlp(num_experts=1, hidden_dim=h, out_dim=d,
                 capacity_factor=1.0, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, n, d), jnp.float32)
    params = jax.jit(moe.init)(jax.random.key(1), x)
    dense = Mlp(hidden_dim=h, out_dim=d, dtype=jnp.float32)
    dense_params = {"params": {
        "fc1": {"kernel": params["params"]["w1"][0],
                "bias": params["params"]["b1"][0]},
        "fc2": {"kernel": params["params"]["w2"][0],
                "bias": params["params"]["b2"][0]},
    }}
    got = jax.jit(moe.apply)(params, x)
    want = jax.jit(dense.apply)(dense_params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_routing_and_capacity_drop():
    """Tokens route to their argmax expert weighted by the gate; tokens past
    the static capacity are dropped (zero MoE output -> residual passthrough
    at the block level)."""
    d, e, n = 8, 2, 4
    moe = MoeMlp(num_experts=e, hidden_dim=8, out_dim=d,
                 capacity_factor=0.5, dtype=jnp.float32)  # C = ceil(.5*4/2)=1
    x = jax.random.normal(jax.random.key(2), (1, n, d), jnp.float32)
    params = jax.jit(moe.init)(jax.random.key(3), x)
    # force ALL tokens to expert 0: bias the router hard
    params["params"]["router"]["bias"] = jnp.array([10.0, -10.0])
    params["params"]["router"]["kernel"] = jnp.zeros((d, e))
    out = jax.jit(moe.apply)(params, x)
    # capacity 1: only the FIRST token gets expert compute; rest are dropped
    assert not np.allclose(np.asarray(out[0, 0]), 0.0)
    np.testing.assert_allclose(np.asarray(out[0, 1:]), 0.0, atol=1e-7)


def test_aux_loss_uniform_router_is_one():
    """Switch aux loss = E * sum_e(frac_e * prob_e); a perfectly uniform
    router gives E * E * (1/E * 1/E) = 1 in expectation. With a zero router
    (all logits equal) prob_e = 1/E exactly; argmax ties resolve to expert 0
    so frac = onehot(0) and the loss is still exactly 1.0."""
    d, e = 8, 4
    moe = MoeMlp(num_experts=e, hidden_dim=8, out_dim=d, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(4), (2, 8, d), jnp.float32)
    params = jax.jit(moe.init)(jax.random.key(5), x)
    params["params"]["router"]["kernel"] = jnp.zeros((d, e))
    params["params"]["router"]["bias"] = jnp.zeros((e,))
    _, cols = jax.jit(lambda p, x: moe.apply(
        p, x, mutable=["intermediates"]))(params, x)
    moe_cols = cols["intermediates"]["moe_frac_tokens"], \
        cols["intermediates"]["moe_mean_prob"]
    (frac,), (prob,) = moe_cols
    aux = e * jnp.sum(frac * prob)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)


def test_top2_routing_hand_case():
    """Top-2: both chosen experts contribute with renormalized gates; second
    choices queue behind ALL first choices of that expert for capacity
    (GShard order). Hand-verifiable 2-expert case with identity-ish experts."""
    d, e, n = 4, 2, 2
    moe = MoeMlp(num_experts=e, hidden_dim=4, out_dim=d, top_k=2,
                 capacity_factor=float(n), dtype=jnp.float32)  # C = n: no drops
    x = jax.random.normal(jax.random.key(6), (1, n, d), jnp.float32)
    params = jax.jit(moe.init)(jax.random.key(7), x)
    # router: token probs fixed at [0.75, 0.25] for every token
    params["params"]["router"]["kernel"] = jnp.zeros((d, e))
    params["params"]["router"]["bias"] = jnp.log(jnp.array([3.0, 1.0]))
    out = jax.jit(moe.apply)(params, x)

    # expected: renormalized gates 0.75/0.25; expert e applies its own MLP
    def expert(i, v):
        p = params["params"]
        h = v @ p["w1"][i] + p["b1"][i]
        h = jax.nn.gelu(h, approximate=False)
        return h @ p["w2"][i] + p["b2"][i]

    want = 0.75 * expert(0, x) + 0.25 * expert(1, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_top2_second_choice_capacity_queue():
    """First choices rank before ALL second choices for capacity (GShard
    order — the count1 offset in vitax/models/moe.py): token 0 first-chooses
    expert 0 while token 1 second-chooses it; at capacity 1, token 1's
    second choice must lose the slot to token 0's first choice EVEN THOUGH
    either alone would fit. Symmetrically for expert 1. Dropping the offset
    (plain per-choice cumsum) would instead keep both second choices and
    make this fail."""
    d, e, n = 4, 2, 2
    moe = MoeMlp(num_experts=e, hidden_dim=4, out_dim=d, top_k=2,
                 capacity_factor=1.0, dtype=jnp.float32)  # C = ceil(2/2) = 1
    # token 0 = +e1 basis, token 1 = -e1: router kernel [s, -s] makes token
    # 0's probs [.75, .25] (first choice expert 0) and token 1's [.25, .75]
    x = jnp.zeros((1, n, d)).at[0, 0, 0].set(1.0).at[0, 1, 0].set(-1.0)
    params = jax.jit(moe.init)(jax.random.key(9), x)
    s = float(np.log(3.0) / 2.0)
    params["params"]["router"]["kernel"] = jnp.zeros((d, e)).at[0, 0].set(
        s).at[0, 1].set(-s)
    params["params"]["router"]["bias"] = jnp.zeros((e,))
    out = jax.jit(moe.apply)(params, x)

    def expert(i, v):
        p = params["params"]
        h = v @ p["w1"][i] + p["b1"][i]
        h = jax.nn.gelu(h, approximate=False)
        return h @ p["w2"][i] + p["b2"][i]

    # each token keeps only its FIRST choice (gate .75); its second choice
    # was evicted by the other token's first choice
    want0 = 0.75 * expert(0, x[:, 0])
    want1 = 0.75 * expert(1, x[:, 1])
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(want0[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[0, 1]), np.asarray(want1[0]),
                               rtol=1e-5, atol=1e-6)


def test_top2_train_step_ep_matches_dp(devices8):
    """Top-2 trajectories must be mesh-invariant too (ep-sharded == dp)."""
    from tests.test_train_smoke import run_steps

    cfg_ep = moe_cfg(moe_top_k=2)
    cfg_dp = moe_cfg(moe_top_k=2, ep_size=1, dp_size=2, fsdp_size=-1)
    _, losses_ep = run_steps(cfg_ep, n_steps=3)
    _, losses_dp = run_steps(cfg_dp, n_steps=3)
    assert all(np.isfinite(losses_ep))
    np.testing.assert_allclose(losses_ep, losses_dp, rtol=2e-4)


def test_expert_param_sharding(devices8):
    """Expert weights carry "ep" on the experts dim (after the stacked layer
    dim under scan); the router and dense params never do."""
    from vitax.parallel.sharding import param_specs

    cfg = moe_cfg()
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    abstract = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 32, 32, 3), jnp.float32), True),
        jax.random.key(0))
    specs = param_specs(abstract, cfg, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    saw_expert = saw_router = False
    for path, spec in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        if "moe" in names and names[-1] in ("w1", "b1", "w2", "b2"):
            assert spec[1] == "ep", (names, spec)  # dim 0 is the scan axis
            saw_expert = True
        else:
            assert "ep" not in tuple(spec), (names, spec)
            if "router" in names:
                saw_router = True
    assert saw_expert and saw_router


def test_moe_train_step_ep_matches_dp(devices8):
    """Full MoE train step on the dp2 x fsdp2 x ep2 mesh must match the
    dp-only (ep=1) trajectory — expert sharding must not change the math.
    Also checks the aux loss actually moved the objective (loss differs from
    a moe_aux_weight=0 run)."""
    from tests.test_train_smoke import run_steps

    cfg_ep = moe_cfg(grad_ckpt=True)
    cfg_dp = moe_cfg(grad_ckpt=True, ep_size=1, dp_size=2, fsdp_size=-1)
    _, losses_ep = run_steps(cfg_ep, n_steps=4)
    _, losses_dp = run_steps(cfg_dp, n_steps=4)
    assert all(np.isfinite(losses_ep))
    np.testing.assert_allclose(losses_ep, losses_dp, rtol=2e-4)

    _, losses_noaux = run_steps(moe_cfg(grad_ckpt=True, moe_aux_weight=0.0),
                                n_steps=2)
    assert abs(losses_noaux[0] - losses_ep[0]) > 1e-5, (
        "aux loss had no effect on the objective")


def test_moe_loss_decreases(devices8):
    from tests.test_train_smoke import run_steps

    _, losses = run_steps(moe_cfg(), n_steps=8)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f"MoE loss did not fall: {losses}"


def test_moe_config_validation():
    with pytest.raises(AssertionError):  # ep needs experts
        moe_cfg(moe_experts=0)
    with pytest.raises(AssertionError):  # experts % ep
        moe_cfg(moe_experts=3)
    # moe + pp with ep=1 is supported (v2: aux ingredients ride the pipeline)
    moe_cfg(ep_size=1, pp_size=2, fsdp_size=1, dp_size=4)
    # moe + pp with ep>1 is supported (v3: manual all-to-all dispatch inside
    # the pipeline body)
    moe_cfg(ep_size=2, pp_size=2, fsdp_size=1, dp_size=2)


@pytest.mark.slow
def test_moe_ep_partitioner_has_no_involuntary_remat():
    """The ep-sharded mesh must compile without GSPMD's "Involuntary full
    rematerialization" fallback (VERDICT r3 item 4: the replicate-then-
    repartition path costs real HBM bandwidth on a pod). The warning is
    emitted by XLA's C++ logging, so it is captured from a subprocess's
    stderr. Guarded by the activation anchors in vitax/models/vit.py
    (block-entry carry, qkv output, pooled head input) and moe.py
    (dispatch/combine + token re-anchor)."""
    import os
    import subprocess
    import sys

    code = (
        "import sys; sys.path.insert(0, '.')\n"
        "import __graft_entry__ as g\n"
        "mesh, losses = g._dryrun_one(8, 1, moe_experts=4, dp_size=2,\n"
        "                             fsdp_size=-1, ep_size=2)\n"
        "print('ok', mesh, losses)\n"
    )
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=480, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ok" in r.stdout, r.stdout
    assert "Involuntary full rematerialization" not in r.stderr, (
        "GSPMD fell back to replicate-then-repartition under the ep mesh:\n"
        + "\n".join(l for l in r.stderr.splitlines() if "Involuntary" in l))


def test_moe_eval_step(devices8):
    """Eval under --moe_experts (VERDICT r3 weak #7): the eval step routes
    through the plain forward where the aux-loss sows are silently inert
    (no mutable collection) — it must still produce the same correct-count
    as an explicit argmax over model.apply logits."""
    from jax.sharding import NamedSharding
    from vitax.parallel.mesh import batch_pspec
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_eval_step

    cfg = moe_cfg()
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=10)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                        jax.random.key(0))
    eval_step = make_eval_step(cfg, model, mesh, sspecs)

    sh = NamedSharding(mesh, batch_pspec())
    rng = np.random.default_rng(0)
    batch = {
        "image": jax.device_put(jnp.asarray(rng.normal(
            size=(cfg.batch_size, cfg.image_size, cfg.image_size, 3)),
            jnp.float32), sh),
        "label": jax.device_put(jnp.asarray(rng.integers(
            0, cfg.num_classes, size=(cfg.batch_size,)), jnp.int32), sh),
    }
    correct = int(jax.device_get(eval_step(state, batch)["correct"]))

    logits = jax.jit(model.apply, static_argnums=2)(
        state.params, batch["image"], True)
    want = int(jnp.sum(jnp.argmax(logits, -1) == batch["label"]))
    assert correct == want, (correct, want)
    assert 0 <= correct <= cfg.batch_size
