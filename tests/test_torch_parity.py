"""Forward parity against a PyTorch re-implementation of the reference model.

The reference composes timm 0.4.12 PatchEmbed/Block into its ViT
(/root/reference/run_vit_training.py:99-162); vitax claims architecture
parity via a closed-form param count and init statistics (tests/test_model.py).
This test goes further: it re-implements the reference's MODEL MATH in plain
PyTorch (torch is available CPU-only; timm itself is not installed), loads
the IDENTICAL weights from the vitax/Flax parameter tree, and requires the
logits to agree — which pins patchify layout, pre-norm order, qkv packing,
softmax axis, LN epsilons (1e-5 blocks / 1e-6 final), exact-GELU, mean-pool,
and the head, not just parameter counts. (Original re-implementation from
the architecture facts in vitax/models/vit.py's docstring — not a copy of
the reference's code.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from vitax.config import Config
from vitax.models import build_model


def torch_forward(p, images, *, patch_size, num_heads, num_blocks):
    """Reference-math forward in torch.float64 on the Flax param tree `p`
    (unstacked, scan_blocks=False layout: blocks_0, blocks_1, ...)."""
    tp = jax.tree.map(
        lambda a: torch.from_numpy(np.asarray(a, np.float64)), p)
    out = torch_forward_t(tp, np.asarray(images, np.float64),
                          patch_size=patch_size, num_heads=num_heads,
                          num_blocks=num_blocks)
    return out.detach().numpy()


def test_forward_matches_torch_reference_math(devices8):
    cfg = Config(image_size=32, patch_size=8, embed_dim=32, num_heads=2,
                 num_blocks=3, num_classes=10, batch_size=4, dtype="float32",
                 scan_blocks=False, grad_ckpt=False).validate()
    model = build_model(cfg)
    images = np.asarray(jax.random.normal(
        jax.random.key(1), (4, 32, 32, 3), jnp.float32))
    params = jax.jit(model.init, static_argnums=2)(
        jax.random.key(0), jnp.asarray(images)[:1], True)

    got = np.asarray(jax.jit(model.apply, static_argnums=2)(
        params, jnp.asarray(images), True))
    want = torch_forward(params["params"], images,
                         patch_size=cfg.patch_size, num_heads=cfg.num_heads,
                         num_blocks=cfg.num_blocks)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_train_step_matches_torch_reference_math(devices8):
    """FULL train-step parity: the same init, batch, and schedule stepped by
    (a) vitax's compiled step (CE mean -> global-norm clip 1.0 -> AdamW
    (0.9, 0.999, 1e-8, wd on ALL params) -> warmup-cosine lr) and (b) the
    reference's exact torch pipeline (loss.backward, clip_grad_norm_,
    torch.optim.AdamW, per-step lr from the same schedule). Losses and the
    full parameter tree must track across steps — this pins the clip-before-
    update order, AdamW bias correction/eps, decoupled weight-decay
    semantics, and the schedule application point, against torch itself."""
    from vitax.parallel.mesh import batch_pspec, build_mesh
    from vitax.train.schedule import warmup_cosine_schedule
    from vitax.train.state import build_optimizer, make_train_state
    from vitax.train.step import make_train_step
    from jax.sharding import NamedSharding

    cfg = Config(image_size=16, patch_size=8, embed_dim=32, num_heads=2,
                 num_blocks=2, num_classes=8, batch_size=8, dtype="float32",
                 scan_blocks=False, grad_ckpt=False, warmup_steps=2,
                 lr=1e-3, weight_decay=0.1, clip_grad_norm=1.0,
                 fsdp_size=2, dp_size=4).validate()
    n_steps, max_iter = 4, 10
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=max_iter)
    state, sspecs, _ = make_train_state(cfg, model, tx, mesh,
                                        jax.random.key(0))
    step_fn = make_train_step(cfg, model, tx, mesh, sspecs)
    params0 = jax.device_get(state.params)["params"]

    rng = np.random.default_rng(0)
    images = rng.normal(size=(cfg.batch_size, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, cfg.num_classes,
                          size=(cfg.batch_size,)).astype(np.int32)
    sh = NamedSharding(mesh, batch_pspec())
    batch = {"image": jax.device_put(jnp.asarray(images), sh),
             "label": jax.device_put(jnp.asarray(labels), sh)}

    losses_vx = []
    key = jax.random.key(1)
    for _ in range(n_steps):
        state, metrics = step_fn(state, batch, key)
        losses_vx.append(float(jax.device_get(metrics["loss"])))
    final_vx = jax.device_get(state.params)["params"]

    # --- torch side: identical math, float64 ---
    flat0, treedef = jax.tree_util.tree_flatten_with_path(params0)
    tparams = [torch.from_numpy(np.asarray(v, np.float64)).clone()
               .requires_grad_(True) for _, v in flat0]
    sched = warmup_cosine_schedule(cfg.lr, cfg.warmup_steps, max_iter)
    opt = torch.optim.AdamW(tparams, lr=cfg.lr, betas=(0.9, 0.999),
                            eps=1e-8, weight_decay=cfg.weight_decay)
    timages = images.astype(np.float64)
    tlabels = torch.from_numpy(labels.astype(np.int64))

    def torch_tree():
        leaves = [(path, tp) for (path, _), tp in zip(flat0, tparams)]
        out = {}
        for path, tp in leaves:
            node = out
            keys = [str(getattr(k, "key", k)) for k in path]
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = tp
        return out

    losses_t = []
    for step in range(n_steps):
        opt.zero_grad()
        # a torch-tensor tree view over the SAME leaf objects the optimizer
        # owns, so torch_forward_t's graph tracks their grads
        p = torch_tree()
        logits = torch_forward_t(p, timages, patch_size=cfg.patch_size,
                                 num_heads=cfg.num_heads,
                                 num_blocks=cfg.num_blocks)
        loss = torch.nn.functional.cross_entropy(logits, tlabels)
        losses_t.append(float(loss.detach()))
        loss.backward()
        torch.nn.utils.clip_grad_norm_(tparams, cfg.clip_grad_norm)
        # per-step lr from the SAME schedule (reference: LambdaLR over AdamW)
        lr_t = float(sched(step))
        for g in opt.param_groups:
            g["lr"] = lr_t
        opt.step()

    np.testing.assert_allclose(losses_vx, losses_t, rtol=2e-4, atol=2e-5)
    flat_vx = jax.tree_util.tree_leaves_with_path(final_vx)
    for (path, v), tp in zip(flat_vx, tparams):
        np.testing.assert_allclose(
            np.asarray(v, np.float64), tp.detach().numpy(),
            rtol=2e-3, atol=2e-5,
            err_msg=f"param drift at {jax.tree_util.keystr(path)}")


def torch_forward_t(p, images, *, patch_size, num_heads, num_blocks):
    """The reference-math forward on a tree of torch tensors (autograd-
    tracked when they require grad): conv patchify (flax (kh, kw, cin,
    cout) kernel -> torch layout), pos embed, pre-norm timm Blocks (LN eps
    1e-5, fused qkv, exact GELU), final LN eps 1e-6, mean-pool, head."""
    x = torch.from_numpy(images)

    w = p["patch_embed"]["proj"]["kernel"].permute(3, 2, 0, 1)
    b = p["patch_embed"]["proj"]["bias"]
    x = torch.nn.functional.conv2d(
        x.permute(0, 3, 1, 2), w, b, stride=patch_size)
    bsz, d, gh, gw = x.shape
    x = x.flatten(2).transpose(1, 2)
    x = x + p["pos_embed"][0]

    def ln(x, params, eps):
        return torch.nn.functional.layer_norm(
            x, (x.shape[-1],), params["scale"], params["bias"], eps)

    def dense(x, params):
        return x @ params["kernel"] + params["bias"]

    heads, dh = num_heads, d // num_heads
    for i in range(num_blocks):
        blk = p[f"blocks_{i}"]
        y = ln(x, blk["norm1"], 1e-5)
        qkv = dense(y, blk["attn"]["qkv"])
        qkv = qkv.reshape(bsz, -1, 3, heads, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = torch.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
        a = torch.softmax(s, dim=-1)
        y = torch.einsum("bhqk,bkhd->bqhd", a, v).reshape(bsz, -1, d)
        x = x + dense(y, blk["attn"]["proj"])
        y = ln(x, blk["norm2"], 1e-5)
        y = torch.nn.functional.gelu(dense(y, blk["mlp"]["fc1"]))
        x = x + dense(y, blk["mlp"]["fc2"])

    x = ln(x, p["norm"], 1e-6)
    x = x.mean(dim=1)
    return dense(x, p["head"])
