"""Plain reference Laguna decoder: float32 `jax.numpy`, one document at a time.

Written from the published configuration (`poolside/Laguna-XS.2`
`config.json`, `model_type` laguna) and the equations of ISSUE 32, not from
this repository's program. `x` is the RMS-normed input (eps `rms_norm_eps`,
normalised in float32), there are no biases:

  h = embedding[ids]
  each layer i:
    h += W_o[ g * Attn(x) ]      q = W_q x: `num_attention_heads_per_layer[i]`
        heads of `head_dim`; k, v = W_k x, W_v x: `num_key_value_heads` heads,
        each serving heads / kv_heads query heads (query head j reads
        key/value head j // (heads / kv_heads));
        RoPE on q and k in the rotate-half convention on the first
        `partial_rotary_factor` of a head, by `layer_types[i]`
        (`rope_parameters`): plain `rope_theta` in `sliding_attention`
        layers, YaRN in `full_attention` ones (below), `attention_factor` on
        cos and sin;
        softmax(q k^T / sqrt(head_dim)) v over the keys at positions p' with
        p' <= p and, in a sliding layer, p - p' < `sliding_window`;
        g = sigmoid(W_g x), one scalar a head (`gating: true`, ASSUMED
        per-head from the sibling Laguna-S-2.1's `gating: "per-head"` and the
        published parameter count)
    h += F(x)                    `mlp_layer_types[i]` dense:
        W_d(silu(W_g x) * W_u x), `intermediate_size` wide; sparse:
        sum_{k in top-8} w_k E_k(x) + S(x), s = sigmoid(W_r x) over all
        `num_experts` experts, w = `moe_routed_scaling_factor` * s_k /
        sum_top8 s (ASSUMED: sigmoid scores, normalised over the chosen, no
        bias-correction term, weight on the expert's output), E_k and S
        SwiGLU of `moe_intermediate_size` / `shared_expert_intermediate_size`
  logits = RMSNorm(h) @ head (untied)
  loss: next-token cross-entropy, mean over every document's targets

YaRN (arXiv:2309.00071 as `transformers` computes it): with d = the rotated
dimensions and b = `rope_theta`, the dimension at which a frequency turns r
times over `original_max_position_embeddings` L is d ln(L / (2 pi r)) / (2
ln b); between floor(that of `beta_fast`) and ceil(that of `beta_slow`) a
ramp goes from 0 to 1 linearly in the index, and frequency i is
b^(-2i/d) * ((1 - ramp_i) + ramp_i / `factor`).

No kernels, no scan, no packing, no segment ids, no sort, no mixed precision:
a document is an array of ids and is run alone, the mask is a dense matrix
of positions, the layers are a Python loop, and EVERY held expert runs on
EVERY token (one einsum over the stacked experts), times a weight that is 0
where the token did not choose it. Every matmul runs under precision
"highest".

The share: `experts_held = (first, count)` adds only the experts `first ..
first + count - 1` of every sparse layer (the router keeps all its outputs
and its 8 a token, weights are normalised over all 8 chosen) and the shared
expert; what the other experts would add is left out. `None` is the whole
model. The vocabulary is what the parameters hold. It reads the program's
own seeded parameter tree (`run<i>/blocks` stacked on a leading axis, or
`blocks_<j>`) so that the two are compared on the same weights.

Departures that change no value: attention runs in blocks of queries, and
the gradient pass wraps each such block and each layer in `jax.checkpoint`,
or a 4,600-token document would hold 48 x 4,600 x 4,600 scores a layer. The
experts are one einsum and not ISSUE 32's Python loop: with the loop the
chip's compiler took 397 s for the longest document's program alone (my
compile for a described v5e, PR 32), which no run of a cell can pay.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
QUERY_BLOCK = 512


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    source = config.get("source_values", {})
    return dict(
        layer_types=list(config["layer_types"]),
        heads=list(config["num_attention_heads_per_layer"]),
        mlp_types=list(config["mlp_layer_types"]),
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], eps=config["rms_norm_eps"],
        rope=config["rope_parameters"], gating=bool(config["gating"]),
        top_k=config["num_experts_per_tok"],
        routed_scale=config["moe_routed_scaling_factor"],
        experts_routed=source.get("num_experts", config["num_experts"]))


# --- parameters -------------------------------------------------------------

def layer_params(params) -> List[dict]:
    """The program's tree as one dict a layer, in depth order."""
    tree = params["params"] if "params" in params else params
    layers = []
    for name in sorted((k for k in tree if k.startswith("run")),
                       key=lambda k: int(k[3:])):
        run = tree[name]
        if "blocks" in run:
            n = jax.tree.leaves(run["blocks"])[0].shape[0]
            layers += [jax.tree.map(lambda a, i=i: a[i], run["blocks"])
                       for i in range(n)]
        else:
            layers += [run[k] for k in sorted(
                run, key=lambda k: int(k.rsplit("_", 1)[1]))]
    return layers


def _top(params) -> dict:
    return params["params"] if "params" in params else params


# --- pieces -----------------------------------------------------------------

def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def inv_frequencies(rope: dict, head_dim: int) -> Tuple[np.ndarray, float]:
    """(rotated / 2 inverse frequencies, the factor on cos and sin)."""
    d = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    b = float(rope["rope_theta"])
    plain = b ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope.get("rope_type", "default") != "yarn":
        return plain, 1.0
    span = rope["original_max_position_embeddings"]

    def dim_at(turns):
        return d * math.log(span / (2 * math.pi * turns)) / (2 * math.log(b))
    low = max(math.floor(dim_at(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_at(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (plain * ((1.0 - ramp) + ramp / rope["factor"]),
            float(rope["attention_factor"]))


def rotate(x, positions, inv_freq, factor):
    """x (n, heads, head_dim): rotate-half on the first 2 * len(inv_freq)
    dimensions of each head."""
    half = len(inv_freq)
    angle = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def attention(q, k, v, window: Optional[int]):
    """q (n, H, Dh), k and v (n, KV, Dh) of ONE document -> (n, H, Dh), in
    blocks of queries (`jax.lax.map` over equal blocks, the last one filled
    with zeros that are cut off again)."""
    n, h, dh = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)        # query head j reads head j // group
    v = jnp.repeat(v, group, axis=1)
    key_at = jnp.arange(n)
    blocks = -(-n // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - n), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        query_at = start + jnp.arange(QUERY_BLOCK)
        back = query_at[:, None] - key_at[None, :]
        see = back >= 0
        if window is not None:
            see = see & (back < window)
        # (finite: a filled-in query past a sliding layer's window sees no key)
        p = jax.nn.softmax(jnp.where(see[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(blocks, QUERY_BLOCK, h, dh),
                              jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, dh)[:n]


def swiglu(x, p):
    g, u, d = (p[k]["kernel"].astype(jnp.float32)
               for k in ("gate", "up", "down"))
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def routed_and_shared(x, p, *, top_k, routed_scale, experts_routed,
                      experts_held):
    """sum over the chosen experts that are held, plus the shared expert."""
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"].astype(jnp.float32))
    assert scores.shape[-1] == experts_routed, scores.shape
    top, chosen = jax.lax.top_k(scores, top_k)
    weights = routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    first, count = experts_held or (0, experts_routed)
    # (n, count): the weight of held expert e for each token, 0 if not chosen
    w = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(count)[None, None, :],
        weights[:, :, None], 0.0), axis=1)
    gate, up, down = (p[f"experts_{k}"]["kernel"].astype(jnp.float32)
                      for k in ("gate", "up", "down"))
    assert gate.shape[0] == count, (gate.shape, count)
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", x, gate)) \
        * jnp.einsum("nd,edf->nef", x, up)
    y = jnp.einsum("nef,efd->nd", h * w[:, :, None], down)
    if "shared" in p:
        y = y + swiglu(x, p["shared"])
    return y


def hidden(params, ids, *, layer_types, heads, mlp_types, kv_heads, head_dim,
           window, eps, rope, gating, top_k, routed_scale, experts_routed,
           experts_held=None, checkpoint: bool = False):
    """One document's ids (n,) -> the final-normed hidden state (n, D)."""
    top = _top(params)
    n = ids.shape[0]
    positions = jnp.arange(n)
    h = jnp.take(top["embed"]["embedding"].astype(jnp.float32), ids, axis=0)

    def layer(h, p, kind, n_heads, mlp):
        x = rms_norm(h, p["norm1"]["scale"], eps)
        a = p["attn"]
        w = {k: a[k]["kernel"].astype(jnp.float32) for k in a}
        q = (x @ w["wq"]).reshape(n, n_heads, head_dim)
        k = (x @ w["wk"]).reshape(n, kv_heads, head_dim)
        v = (x @ w["wv"]).reshape(n, kv_heads, head_dim)
        freq = inv_frequencies(rope[kind], head_dim)
        q, k = rotate(q, positions, *freq), rotate(k, positions, *freq)
        o = attention(q, k, v, window if kind == "sliding_attention" else None)
        if gating:
            o = o * jax.nn.sigmoid(x @ w["head_gate"])[..., None]
        h = h + o.reshape(n, n_heads * head_dim) @ w["wo"]
        x = rms_norm(h, p["norm2"]["scale"], eps)
        if mlp == "dense":
            return h + swiglu(x, p["mlp"])
        return h + routed_and_shared(
            x, p["moe"], top_k=top_k, routed_scale=routed_scale,
            experts_routed=experts_routed, experts_held=experts_held)

    for p, kind, n_heads, mlp in zip(layer_params(params), layer_types, heads,
                                     mlp_types):
        step = (lambda h, p, kind=kind, n_heads=n_heads, mlp=mlp:
                layer(h, p, kind, n_heads, mlp))
        h = (jax.checkpoint(step) if checkpoint else step)(h, p)
    return rms_norm(h, top["norm"]["scale"], eps)


def _head(params):
    return _top(params)["lm_head"]["kernel"].astype(jnp.float32)


def logits(params, ids, **shape):
    """(n, vocabulary rows held) float32 next-token logits of one document."""
    return hidden(params, ids, **shape) @ _head(params)


def ce_sum_and_logits(params, ids, at, length=None,
                      checkpoint: bool = False, **shape):
    """One document: (the sum over its targets of the next-token
    cross-entropy, its logits at the positions `at`). `length`: the document
    is the first `length` of `ids` and zeros follow, which no position of a
    causal model can see: one program then serves documents of every length
    (`loss_grad_norms_and_logits`), and only the loss has to leave the
    zeros' positions out."""
    z = hidden(params, ids, checkpoint=checkpoint, **shape) @ _head(params)
    logp = z[:-1] - jax.nn.logsumexp(z[:-1], axis=-1, keepdims=True)
    ce = -jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]
    if length is not None:
        ce = jnp.where(jnp.arange(ce.shape[0]) < length - 1, ce, 0.0)
    return jnp.sum(ce), jnp.take(z, at, axis=0)


def loss(params, docs: Sequence[jax.Array], **shape):
    """Mean next-token cross-entropy over every document's targets."""
    targets = sum(int(d.shape[0]) - 1 for d in docs)
    none = jnp.zeros((0,), jnp.int32)
    return sum(ce_sum_and_logits(params, d, none, **shape)[0]
               for d in docs) / targets


def leaf_norms(tree):
    """The norm of each matrix of a parameter-shaped tree: over the last two
    axes of a kernel or table (a stacked run's (L, in, out) gives (L,), its
    experts' (L, E, in, out) gives (L, E)), over the last of a scale."""
    def norm(a):
        axes = (-2, -1) if a.ndim >= 2 else (-1,)
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axis=axes))
    return jax.tree.map(norm, tree)


def global_norm(norms) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(n))
                        for n in jax.tree.leaves(norms)))


def relative_gap(got, want) -> float:
    """||got - want|| / ||want|| over all elements: 0 where they agree, 1
    where `got` is zero (or has nothing of `want` in it)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def loss_grads_and_logits(params, docs: Sequence[jax.Array],
                          ats: Sequence[jax.Array], **shape):
    """(loss, its float32 gradients, each document's logits at its positions
    `ats[i]`, equally many a document). One document at a time, each followed
    by zeros up to the longest one's length so that one compiled program
    serves them all (the chip's compiler takes over two minutes for it), the
    gradients summed into one tree that the program is given and hands
    back: beside the parameters there is one gradient tree."""
    targets = sum(int(d.shape[0]) - 1 for d in docs)
    longest = max(int(d.shape[0]) for d in docs)

    def one(acc, p, ids, at, n):
        (value, z), grads = jax.value_and_grad(
            lambda p: ce_sum_and_logits(p, ids, at, n, True, **shape),
            has_aux=True)(p)
        return value, z, jax.tree.map(jnp.add, acc, grads)

    one = jax.jit(one, donate_argnums=(0,))
    acc = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
    total, rows = 0.0, []
    for ids, at in zip(docs, ats):
        n = int(ids.shape[0])
        value, z, acc = one(acc, params, jnp.pad(ids, (0, longest - n)), at,
                            jnp.asarray(n, jnp.int32))
        total += float(value)
        rows.append(z)
    grads = jax.jit(lambda g: jax.tree.map(lambda a: a / targets, g),
                    donate_argnums=(0,))(acc)
    return total / targets, grads, rows


def unpack(tokens: np.ndarray, segment_ids: np.ndarray) -> List[np.ndarray]:
    """The documents of a packed batch, in order: arrays of ids."""
    docs = []
    for row_tokens, row_seg in zip(np.asarray(tokens),
                                   np.asarray(segment_ids)):
        for s in range(1, int(row_seg.max()) + 1):
            docs.append(row_tokens[row_seg == s])
    return docs
