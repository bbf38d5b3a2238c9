"""Plain reference SmallThinker decoder: float32 `jax.numpy`, one packed row
at a time, the document mask written out.

Written from the published configuration
(`PowerInfer/SmallThinker-21BA3B-Instruct` `config.json`, `model_name`
smallthinker_21b_instruct) and the equations of ISSUE 51, not from this
repository's program. `RMSNorm` normalises in float32 with eps
`rms_norm_eps`; nothing has a bias. With h (n, d) the residual stream of a
row, d = `hidden_size`:

  h = embedding[ids]
  each layer l:
    a   = RMSNorm_1(h)
    z   = a W_r                      `moe_num_primary_experts` logits a token:
                                     the router reads what the ATTENTION
                                     reads, before it (ASSUMED, below)
    e   = the `moe_num_active_primary_experts` largest of z, a token
    w   = softmax(z[e])              over the chosen logits alone
                                     (`moe_primary_router_apply_softmax`;
                                     they sum to 1, so `norm_topk_prob`
                                     changes nothing)
    q, k, v = a W_q, a W_k, a W_v    `num_attention_heads` heads of
                                     `head_dim`, `num_key_value_heads`
                                     key/value heads, query head j reading
                                     key/value head j // (heads / kv heads)
    `rope_layout[l]` 1: q and k rotated over the whole head, rotate-half,
                        `rope_theta`, by the position INSIDE the document;
                     0: nothing is rotated (NoPE)
    o   = softmax(q k^T / sqrt(head_dim)) v over the keys of the query's own
          document that are not after it and, where
          `sliding_window_layout[l]` is 1, fewer than `sliding_window_size`
          positions back (the query's own position included)
    h'  = h + o W_o
    b   = RMSNorm_2(h')
    m   = sum_j w_j (relu(b G_e_j) * (b U_e_j)) D_e_j        (a ReGLU)
    h'' = h' + m
  logits = RMSNorm(h) @ head                  (`tie_word_embeddings` false)
  loss: next-token cross-entropy inside each document, mean over the targets

ASSUMED, where the catalog row is silent (the configuration file's
`assumed`): the router reads RMSNorm_1(h) (the row's `described_as`: "router
placed before attention"; the published modelling code hands the decoder
layer's normed input to its primary router); no secondary experts; no
attention bias, no norm on q and k; no auxiliary balance loss.

No kernels, no scan, no sort, no blocks of sorted rows, no mixed precision: a
row is an array of ids beside its segment ids (0: padding), the mask is a
dense matrix of (same document, not after, inside the window), the position
of a token is counted from its document's first token, the layers are a
Python loop, and EVERY held expert runs on EVERY token (one einsum over the
stacked experts), times a weight that is 0 where the token did not choose
it. Every matmul runs under precision "highest". Padding is a document of
its own that no real token sees and no target lies in.

The share: `experts_held = (first, count)` adds only the experts `first ..
first + count - 1` of every layer (the router keeps all its outputs and its K
a token, the softmax is over all K chosen); what the other experts would add
is left out. `None` is the whole layer. The vocabulary is what the tables
hold. It reads the program's seeded parameter tree by name (`run<i>/blocks`,
`attn`, `moe`, `norm1`, `norm2`) so that the two are compared on the same
weights, and imports nothing of the program's.

Departures that change no value, each for memory: attention runs in blocks of
queries (`jax.lax.map`, each block checkpointed: a 16,384-token row would
hold 28 x 16,384 x 16,384 scores a layer), and each layer is checkpointed in
the gradient pass.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# what is no part of the architecture is shared with the other plain
# references: reading the program's tree, norms and gaps, the rotation
from benchmark.reference.laguna import (  # noqa: F401
    global_norm, inv_frequencies, layer_params, leaf_norms, relative_gap,
    rms_norm, rotate)

PRECISION = "highest"
QUERY_BLOCK = 256


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    assert config["moe_primary_router_apply_softmax"]
    assert not config["tie_word_embeddings"] and not config["rope_scaling"]
    source = config.get("source_values", {})
    return dict(
        rope_layout=list(config["rope_layout"]),
        window_layout=list(config["sliding_window_layout"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window_size"], eps=config["rms_norm_eps"],
        theta=config["rope_theta"],
        top_k=config["moe_num_active_primary_experts"],
        experts_routed=source.get("moe_num_primary_experts",
                                  config["moe_num_primary_experts"]))


def _top(params) -> dict:
    return params["params"] if "params" in params else params


def _f32(leaf):
    return leaf.astype(jnp.float32)


# --- pieces -----------------------------------------------------------------

def positions_in_documents(seg):
    """(n,) the count of each token from its document's first one: a
    document is a run of equal segment ids."""
    at = jnp.arange(seg.shape[0])
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return at - jax.lax.cummax(jnp.where(first, at, 0))


def attention(q, k, v, seg, window: Optional[int]):
    """q (n, H, Dh), k and v (n, KV, Dh) of one ROW with its segment ids (n,)
    -> (n, H, Dh): the mask as a dense matrix, in blocks of queries."""
    n, h, dh = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)        # query head j reads head j // group
    v = jnp.repeat(v, group, axis=1)
    key_at = jnp.arange(n)
    blocks = -(-n // QUERY_BLOCK)
    fill = blocks * QUERY_BLOCK - n
    q = jnp.pad(q, ((0, fill), (0, 0), (0, 0)))
    seg_q = jnp.pad(seg, (0, fill), constant_values=-1)

    @jax.checkpoint
    def block(args):
        qb, sb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        back = (start + jnp.arange(QUERY_BLOCK))[:, None] - key_at[None, :]
        see = (sb[:, None] == seg[None, :]) & (back >= 0)
        if window is not None:
            see = see & (back < window)
        # (finite: a filled-in query sees no key)
        p = jax.nn.softmax(jnp.where(see[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(blocks, QUERY_BLOCK, h, dh),
                              seg_q.reshape(blocks, QUERY_BLOCK),
                              jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, dh)[:n]


def route(a, p, *, top_k, experts_routed):
    """a (n, d), the FIRST norm's output -> (softmax over the chosen logits
    (n, K), the chosen experts (n, K))."""
    z = a @ _f32(p["router"]["kernel"])
    assert z.shape[-1] == experts_routed, z.shape
    top, chosen = jax.lax.top_k(z, top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def _held(chosen, experts_routed, experts_held):
    """(how many experts are held, (n, K, count) bool: choice j of a token is
    held expert e)."""
    first, count = experts_held or (0, experts_routed)
    return count, (chosen[:, :, None]
                   == first + jnp.arange(count)[None, None, :])


def reglu_experts(b, weights, chosen, p, *, experts_routed,
                  experts_held: Optional[Tuple[int, int]] = None):
    """m = the sum over the chosen experts that are held of w_j E_e_j(b):
    b (n, d), the SECOND norm's output -> (n, d)."""
    count, sent = _held(chosen, experts_routed, experts_held)
    # (n, count): the weight of held expert e for each token, 0 if not chosen
    w = jnp.sum(jnp.where(sent, weights[:, :, None], 0.0), axis=1)
    gate, up, down = (_f32(p[f"experts_{k}"]["kernel"])
                      for k in ("gate", "up", "down"))
    assert gate.shape[0] == count, (gate.shape, count)
    h = jax.nn.relu(jnp.einsum("nd,edf->nef", b, gate)) \
        * jnp.einsum("nd,edf->nef", b, up)
    return jnp.einsum("nef,efd->nd", h * w[:, :, None], down)


def hidden_units_live(b, chosen, p, real, *, experts_routed,
                      experts_held=None):
    """What the ReLU gate leaves: the (real token, chosen held expert, hidden
    unit) triples whose gate b G_e is > 0 (`real` (n,) bool: no padding)."""
    _, sent = _held(chosen, experts_routed, experts_held)
    g = jnp.einsum("nd,edf->nef", b, _f32(p["experts_gate"]["kernel"]))
    return jnp.sum((g > 0) & jnp.any(sent, axis=1)[:, :, None]
                   & real[:, None, None], dtype=jnp.int32)


def hidden(params, ids, seg, *, rope_layout, window_layout, heads, kv_heads,
           head_dim, window, eps, theta, top_k, experts_routed,
           experts_held=None, checkpoint: bool = False):
    """One row's ids and segment ids (n,) -> the final-normed hidden state
    (n, d)."""
    top = _top(params)
    n = ids.shape[0]
    positions = positions_in_documents(seg)
    freq = inv_frequencies({"rope_theta": theta}, head_dim)
    h = jnp.take(_f32(top["embed"]["embedding"]), ids, axis=0)

    def layer(h, p, rotates, slides):
        a = rms_norm(h, p["norm1"]["scale"], eps)
        weights, chosen = route(a, p["moe"], top_k=top_k,
                                experts_routed=experts_routed)
        w = {k: _f32(p["attn"][k]["kernel"]) for k in p["attn"]}
        q = (a @ w["wq"]).reshape(n, heads, head_dim)
        k = (a @ w["wk"]).reshape(n, kv_heads, head_dim)
        v = (a @ w["wv"]).reshape(n, kv_heads, head_dim)
        if rotates:
            q, k = rotate(q, positions, *freq), rotate(k, positions, *freq)
        o = attention(q, k, v, seg, window if slides else None)
        h = h + o.reshape(n, heads * head_dim) @ w["wo"]
        b = rms_norm(h, p["norm2"]["scale"], eps)
        return h + reglu_experts(b, weights, chosen, p["moe"],
                                 experts_routed=experts_routed,
                                 experts_held=experts_held)

    for p, rotates, slides in zip(layer_params(params), rope_layout,
                                  window_layout):
        step = (lambda h, p, rotates=rotates, slides=slides:
                layer(h, p, bool(rotates), bool(slides)))
        h = (jax.checkpoint(step) if checkpoint else step)(h, p)
    return rms_norm(h, top["norm"]["scale"], eps)


def logits(params, ids, seg, checkpoint: bool = False, **shape):
    """(n, vocabulary rows held) float32 next-token logits of one row."""
    return hidden(params, ids, seg, checkpoint=checkpoint, **shape) @ _f32(
        _top(params)["lm_head"]["kernel"])


def targets_of(seg):
    """(n - 1,) bool: position t has a target where token t + 1 belongs to
    the same document and that is no padding."""
    return (seg[:-1] > 0) & (seg[1:] == seg[:-1])


def ce_sum_and_logits(params, ids, seg, at, checkpoint: bool = False,
                      **shape):
    """One row: (the sum over its targets of the next-token cross-entropy,
    its logits at the positions `at`)."""
    z = logits(params, ids, seg, checkpoint, **shape)
    logp = z[:-1] - jax.nn.logsumexp(z[:-1], axis=-1, keepdims=True)
    ce = -jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(targets_of(seg), ce, 0.0)), jnp.take(z, at,
                                                                  axis=0)


def loss_grads_and_logits(params, rows, ats: Sequence[jax.Array], **shape):
    """(loss, its float32 gradients, each row's logits at its positions
    `ats[i]`). One row at a time through one compiled program, the gradients
    summed into one tree that the program is given and hands back: beside the
    parameters there is one gradient tree."""
    targets = sum(int(jnp.sum(targets_of(seg))) for _, seg in rows)

    def one(acc, p, ids, seg, at):
        (value, z), grads = jax.value_and_grad(
            lambda p: ce_sum_and_logits(p, ids, seg, at, True, **shape),
            has_aux=True)(p)
        return value, z, jax.tree.map(jnp.add, acc, grads)

    one = jax.jit(one, donate_argnums=(0,))
    acc = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
    total, picked = 0.0, []
    for (ids, seg), at in zip(rows, ats):
        value, z, acc = one(acc, params, ids, seg, at)
        total += float(value)
        picked.append(z)
    grads = jax.jit(lambda g: jax.tree.map(lambda a: a / targets, g),
                    donate_argnums=(0,))(acc)
    return total / targets, grads, picked


def rows_of(tokens, segment_ids):
    """The rows of a packed batch as (ids, segment ids) pairs."""
    return [(jnp.asarray(t), jnp.asarray(s))
            for t, s in zip(tokens, segment_ids)]
