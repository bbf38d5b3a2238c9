"""Plain reference Granite 4.0-H decoder: float32 `jax.numpy`, one document at
a time, the state-space layers as the recurrence itself, token by token.

Written from the published configuration (`ibm-granite/granite-4.0-h-micro`
`config.json`, `model_type` granitemoehybrid), the Mamba-2 paper
(arXiv:2405.21060, section 2: the recurrence; none of its chunked
algorithm) and the equations of ISSUE 35, not from this repository's
program. `RMSNorm` normalises in float32 with eps `rms_norm_eps`; no
projection has a bias (`attention_bias`, `mamba_proj_bias` false):

  h = `embedding_multiplier` * embedding[ids]
  each layer i:
    h += `residual_multiplier` * Mix(RMSNorm(h))
    h += `residual_multiplier` * W_out2[ silu(a) * b ],  (a, b) = W_in2 RMSNorm(h)
        (`shared_intermediate_size` wide; `num_local_experts` 0: no routed part)
  logits = RMSNorm(h) @ embedding^T / `logits_scaling`   (`tie_word_embeddings`)
  loss: next-token cross-entropy, mean over every document's targets

  Mix, `layer_types[i]` attention: q = W_q u of `num_attention_heads` heads of
    head_dim = hidden_size / num_attention_heads (ASSUMED: the row gives no
    head_dim), k, v of `num_key_value_heads` heads, query head j reading
    key/value head j // (heads / kv_heads); NO position encoding
    (`position_embedding_type` nope); softmax(q k^T * `attention_multiplier`)
    v over the keys at positions p' <= p; W_o.
  Mix, `layer_types[i]` mamba (Mamba-2): (z, xBC, dt) = W_in u of widths
    d_inner = `mamba_expand` * hidden_size = `mamba_n_heads` * `mamba_d_head`,
    d_inner + 2 * `mamba_n_groups` * `mamba_d_state`, and `mamba_n_heads`;
    xBC_t <- silu(b + sum_{k < `mamba_d_conv`} w_k xBC_{t - d_conv + 1 + k})
    (depthwise, zeros before the document; `mamba_conv_bias`);
    (x, B, C) = xBC: x of n_heads heads of d_head, B and C of n_groups groups
    of d_state (head j reads group j // (n_heads / n_groups));
    delta_t = softplus(dt_t + dt_bias) (ASSUMED: no clamp, the config gives
    no `time_step_limit`), A = -exp(A_log), a head;
      S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t,   S_{-1} = 0
      y_t = S_t C_t + D x_t
    with S (d_head, d_state) a head, carried by `lax.scan` over positions;
    y <- RMSNorm(y * silu(z)) * weight over each group's d_inner / n_groups
    channels (ASSUMED: the gate before the norm, as transformers'
    MambaRMSNormGated); W_out y.

No chunks, no dual form, no kernels, no packing, no segment ids, no mixed
precision: a document is an array of ids and is run alone, so its first
token starts from a zero state and its convolution from zeros by
construction; the layers are a Python loop; the convolution is `d_conv`
shifted adds. Every matmul runs under precision "highest". The vocabulary
is what the parameters hold (a slice of the table is a smaller vocabulary).
It reads the program's seeded parameter tree by name (`run<i>/blocks`
stacked on a leading axis, or `blocks_<j>`; a mamba layer's leaves under
`mixer`, an attention layer's under `attn`) so that the two are compared on
the same weights, and imports nothing of the program's.

Departures that change no value, each for memory: attention runs in blocks
of queries; the recurrence is scanned in blocks of `TOKEN_BLOCK` tokens under
`jax.checkpoint` (a 2,300-token document's per-token states are 2,300 x 64 x
64 x 128 x 4 B = 4.8 GB a layer otherwise) and each layer is checkpointed in
the gradient pass; documents are followed by zeros up to the longest one's
length, which no position of a causal model can see, so that one compiled
program serves them all.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

# what is no part of the architecture is shared with the other plain
# reference: reading the program's tree, RMSNorm, SwiGLU, norms and gaps
from benchmark.reference.laguna import (  # noqa: F401
    global_norm, layer_params, leaf_norms, relative_gap, rms_norm, swiglu,
    unpack)

PRECISION = "highest"
QUERY_BLOCK = 512
TOKEN_BLOCK = 64


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    assert not config["mamba_proj_bias"] and not config["attention_bias"]
    assert config["position_embedding_type"] == "nope"
    assert config["tie_word_embeddings"] and config["num_local_experts"] == 0
    return dict(
        layer_types=list(config["layer_types"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        eps=config["rms_norm_eps"],
        attention_multiplier=config["attention_multiplier"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        mamba=dict(n_heads=config["mamba_n_heads"],
                   d_head=config["mamba_d_head"],
                   d_state=config["mamba_d_state"],
                   d_conv=config["mamba_d_conv"],
                   n_groups=config["mamba_n_groups"],
                   conv_bias=config["mamba_conv_bias"]))


def _top(params) -> dict:
    return params["params"] if "params" in params else params


def _f32(leaf):
    return leaf.astype(jnp.float32)


# --- pieces -----------------------------------------------------------------

def attention(q, k, v, scale: float):
    """q (n, H, Dh), k and v (n, KV, Dh) of ONE document -> (n, H, Dh): dense
    causal softmax, in blocks of queries (the last one filled with zeros that
    are cut off again)."""
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
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        see = (start + jnp.arange(QUERY_BLOCK))[:, None] >= key_at[None, :]
        p = jax.nn.softmax(jnp.where(see[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(blocks, QUERY_BLOCK, h, dh),
                              jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, dh)[:n]


def convolution(x, kernel, bias):
    """x (n, channels), kernel (d_conv, channels): y_t = b + sum_k w_k
    x_{t - d_conv + 1 + k}, zeros before the document: d_conv shifted adds."""
    n, taps = x.shape[0], kernel.shape[0]
    y = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k
        y = y + kernel[k] * jnp.pad(x, ((back, 0), (0, 0)))[:n]
    return y if bias is None else y + bias


def recurrence(x, delta, a_head, b, c):
    """The state-space recurrence, token by token. x (n, H, P), delta (n, H),
    a_head (H,), b and c (n, H, N) (each head its group's) -> y (n, H, P)
    with y_t = S_t C_t. The state S (H, P, N) starts at zero."""
    n, h, p = x.shape
    blocks = -(-n // TOKEN_BLOCK)
    fill = blocks * TOKEN_BLOCK - n     # zeros after the document: never read

    def token(state, inputs):
        x_t, delta_t, b_t, c_t = inputs
        state = (jnp.exp(delta_t * a_head)[:, None, None] * state
                 + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    def blocked(a):
        a = jnp.pad(a, ((0, fill),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(blocks, TOKEN_BLOCK, *a.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        tuple(map(blocked, (x, delta, b, c))))
    return y.reshape(blocks * TOKEN_BLOCK, h, p)[:n]


def mamba_mixer(u, p, eps, *, n_heads, d_head, d_state, d_conv, n_groups,
                conv_bias):
    """One document's normed input u (n, D) -> the mixer's output (n, D)."""
    n = u.shape[0]
    inner, gn = n_heads * d_head, n_groups * d_state
    z, xbc, dt = jnp.split(u @ _f32(p["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * gn], axis=-1)
    kernel = _f32(p["conv"]["kernel"])
    assert kernel.shape == (d_conv, inner + 2 * gn), kernel.shape
    xbc = jax.nn.silu(convolution(
        xbc, kernel, _f32(p["conv"]["bias"]) if conv_bias else None))
    x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    x = x.reshape(n, n_heads, d_head)
    per_group = n_heads // n_groups         # head j reads group j // per_group
    b = jnp.repeat(b.reshape(n, n_groups, d_state), per_group, axis=1)
    c = jnp.repeat(c.reshape(n, n_groups, d_state), per_group, axis=1)
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]["bias"]))
    a_head = -jnp.exp(_f32(p["A_log"]["scale"]))
    y = recurrence(x, delta, a_head, b, c) \
        + _f32(p["D"]["scale"])[:, None] * x
    y = y.reshape(n, inner) * jax.nn.silu(z)
    y = y.reshape(n, n_groups, inner // n_groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(n, inner) * _f32(p["gate_norm"]["scale"])
    return y @ _f32(p["out_proj"]["kernel"])


def attention_mixer(u, p, *, heads, kv_heads, head_dim, attention_multiplier):
    n = u.shape[0]
    w = {k: _f32(p[k]["kernel"]) for k in ("wq", "wk", "wv", "wo")}
    q = (u @ w["wq"]).reshape(n, heads, head_dim)
    k = (u @ w["wk"]).reshape(n, kv_heads, head_dim)
    v = (u @ w["wv"]).reshape(n, kv_heads, head_dim)
    return attention(q, k, v, attention_multiplier).reshape(
        n, heads * head_dim) @ w["wo"]


def hidden(params, ids, *, layer_types, heads, kv_heads, head_dim, eps,
           attention_multiplier, embedding_multiplier, residual_multiplier,
           logits_scaling, mamba, checkpoint: bool = False):
    """One document's ids (n,) -> the final-normed hidden state (n, D)."""
    del logits_scaling
    top = _top(params)
    h = embedding_multiplier * jnp.take(_f32(top["embed"]["embedding"]), ids,
                                        axis=0)

    def layer(h, p, kind):
        u = rms_norm(h, p["norm1"]["scale"], eps)
        if kind == "mamba":
            mixed = mamba_mixer(u, p["mixer"], eps, **mamba)
        else:
            mixed = attention_mixer(
                u, p["attn"], heads=heads, kv_heads=kv_heads,
                head_dim=head_dim, attention_multiplier=attention_multiplier)
        h = h + residual_multiplier * mixed
        u = rms_norm(h, p["norm2"]["scale"], eps)
        return h + residual_multiplier * swiglu(u, p["mlp"])

    for p, kind in zip(layer_params(params), layer_types):
        step = (lambda h, p, kind=kind: layer(h, p, kind))
        h = (jax.checkpoint(step) if checkpoint else step)(h, p)
    return rms_norm(h, top["norm"]["scale"], eps)


def logits(params, ids, checkpoint: bool = False, **shape):
    """(n, vocabulary rows held) float32 next-token logits of one document:
    the tied table is the head."""
    table = _f32(_top(params)["embed"]["embedding"])
    return hidden(params, ids, checkpoint=checkpoint, **shape) @ table.T \
        / shape["logits_scaling"]


def ce_sum_and_logits(params, ids, at, length=None, checkpoint: bool = False,
                      **shape):
    """One document: (the sum over its targets of the next-token
    cross-entropy, its logits at the positions `at`). `length`: the document
    is the first `length` of `ids` and zeros follow, which no position of a
    causal model can see; only the loss has to leave their positions out."""
    z = logits(params, ids, checkpoint, **shape)
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


def loss_grads_and_logits(params, docs: Sequence[jax.Array],
                          ats: Sequence[jax.Array], **shape):
    """(loss, its float32 gradients, each document's logits at its positions
    `ats[i]`, equally many a document). One document at a time, each followed
    by zeros up to the longest one's length so that one compiled program
    serves them all, the gradients summed into one tree that the program is
    given and hands back: beside the parameters there is one gradient tree."""
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
