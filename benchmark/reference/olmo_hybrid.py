"""Plain reference Olmo-Hybrid decoder: float32 `jax.numpy`, one document at a
time, the Gated-DeltaNet layers as the recurrence itself, token by token.

Written from the published configuration (`allenai/Olmo-Hybrid-7B`
`config.json`, `model_type` olmo_hybrid), the Gated Delta Networks paper
(arXiv:2412.06464: the recurrence, none of its chunked algorithm) as the
`fla` library's `GatedDeltaNet` layer and Qwen3-Next's `linear_*` keys write
it, Olmo 2's block, and the equations of ISSUE 44, not from this
repository's program. `RMSNorm` normalises in float32 with eps
`rms_norm_eps`; no projection has a bias (`attention_bias` false). With h
the residual stream, d = `hidden_size`:

  h = embedding[ids]
  each layer i:   h += RMSNorm_a(Mix(h));   h += RMSNorm_f(SwiGLU(h))
      (Olmo's block: the norm sits on what a half ADDS, a half reads the raw
      stream; SwiGLU = W_down(silu(W_gate h) * W_up h), `intermediate_size`)
  logits = RMSNorm(h) @ head (untied: `tie_word_embeddings` false)
  loss: next-token cross-entropy, mean over every document's targets

  Mix, `layer_types[i]` full_attention: q = RMSNorm_q(W_q h), k =
    RMSNorm_k(W_k h), each norm over the WHOLE projected width with a weight
    of that width, before the heads are split; v = W_v h; heads of
    hidden_size / num_attention_heads; NO rotation (`rope_parameters.
    rope_theta` null); o_h = softmax(q_h . k_h / sqrt(head_dim)) v_h over the
    keys at positions p' <= p; W_o. No gate.
  Mix, `layer_types[i]` linear_attention (Gated DeltaNet), H heads of K =
    `linear_key_head_dim` keys and V = `linear_value_head_dim` values:
    q, k, v = silu(conv([W_q h; W_k h; W_v h])): one depthwise causal
      convolution of `linear_conv_kernel_dim` taps, zeros before the
      document, no bias;
    q = q / sqrt(sum q^2 + 1e-6) / sqrt(K), k = k / sqrt(sum k^2 + 1e-6), a
      head;
    g_t = -exp(A_log) * softplus(W_a h_t + dt_bias) <= 0, ONE number a head,
      no lower bound; b_t = 2 * sigmoid(W_b h_t), in (0, 2)
      (`linear_allow_neg_eigval` true);
      S_t = e^{g_t} (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T,  S_{-1} = 0
      o_t = S_t^T q_t
    with S (K, V) a head, carried by `lax.scan` over positions;
    out = W_o[ o_t / sqrt(mean o_t^2 + eps) * w * silu(W_z h_t) ], the norm
      over each head's V channels (one weight of V shared by the heads), the
      gate a head AND channel.

The shares: the heads are the ones the parameters hold (every projection's
head axis), the vocabulary is what the parameters hold. Two statistics would
cross the chips of a deployment that divides the heads: the QK-norm's mean
square (over all heads' channels) and the norm after W_o (of the summed
output rows). Here, as in the program, both are taken over what is held. For
the share test only, `attention_mixer` takes the whole width's mean squares
as an argument; `gated_delta_mixer` and `attention_mixer` hand back what a
mixer gives BEFORE the norm after W_o, which is where shares add up.

No chunks, no triangular solve, no kernels, no packing, no segment ids, no
mixed precision: a document is an array of ids and is run alone. It reads
the program's seeded parameter tree by name (`run<i>/blocks`; a
linear_attention layer's leaves under `mixer`, an attention layer's under
`attn`) so that the two are compared on the same weights, and imports
nothing of the program's.

Departures that change no value, each for memory: attention runs in blocks
of queries; the recurrence is scanned in blocks of `TOKEN_BLOCK` tokens under
`jax.checkpoint` and each layer is checkpointed in the gradient pass;
documents are followed by zeros up to the longest one's length, which no
position of a causal model can see, so that one compiled program serves
them all.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# what is no part of the architecture is shared with the other plain
# references: reading the program's tree, SwiGLU, norms and gaps, the dense
# causal softmax in blocks of queries, the shifted-add convolution
from benchmark.reference.granite import attention, convolution
from benchmark.reference.laguna import (  # noqa: F401
    global_norm, layer_params, leaf_norms, relative_gap, rms_norm, swiglu,
    unpack)

PRECISION = "highest"
TOKEN_BLOCK = 64
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    assert config["model_type"] == "olmo_hybrid"
    assert not config["attention_bias"] and config["hidden_act"] == "silu"
    assert not config["tie_word_embeddings"]
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["linear_allow_neg_eigval"]
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    assert config["num_attention_heads"] == config["num_key_value_heads"]
    assert set(config["layer_types"]) <= {LINEAR, FULL}
    source = config.get("source_values", {})
    return dict(
        layer_types=list(config["layer_types"]),
        # a head's width follows from the PUBLISHED count of heads
        head_dim=config["hidden_size"] // source.get(
            "num_attention_heads", config["num_attention_heads"]),
        eps=config["rms_norm_eps"],
        linear=dict(key_dim=config["linear_key_head_dim"],
                    value_dim=config["linear_value_head_dim"],
                    taps=config["linear_conv_kernel_dim"]))


def _top(params) -> dict:
    return params["params"] if "params" in params else params


def _f32(leaf):
    return leaf.astype(jnp.float32)


# --- pieces -----------------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. q, k (n, H, K), v (n, H, V), g (n, H)
    <= 0, beta (n, H) -> o (n, H, V) with o_t = S_t^T q_t. The state S
    (H, K, V) starts at zero."""
    n, h, dk = q.shape
    blocks = -(-n // TOKEN_BLOCK)
    fill = blocks * TOKEN_BLOCK - n     # zeros after the document: never read

    def token(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        correction = v_t - jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * correction[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    def blocked(a):
        a = jnp.pad(a, ((0, fill),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(blocks, TOKEN_BLOCK, *a.shape[1:])

    _, o = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                        tuple(map(blocked, (q, k, v, g, beta))))
    return o.reshape(blocks * TOKEN_BLOCK, h, v.shape[-1])[:n]


def gated_delta_mixer(x, p, eps, *, key_dim, value_dim, taps):
    """One document's residual stream x (n, D) -> what the mixer gives
    (n, D), before the block's norm; the heads are those the parameters
    hold."""
    n = x.shape[0]
    w = {name: _f32(p[name]["kernel"])
         for name in ("wq", "wk", "wv", "wa", "wb", "wz", "wo")}
    h = w["wq"].shape[1] // key_dim
    assert w["wv"].shape[1] == h * value_dim, (w["wv"].shape, h, value_dim)
    kernel = _f32(p["conv"]["kernel"])
    assert kernel.shape == (taps, h * (2 * key_dim + value_dim)), kernel.shape
    qkv = jax.nn.silu(convolution(
        jnp.concatenate([x @ w["wq"], x @ w["wk"], x @ w["wv"]], axis=-1),
        kernel, None))

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    q = unit(qkv[:, :h * key_dim].reshape(n, h, key_dim)) / math.sqrt(key_dim)
    k = unit(qkv[:, h * key_dim:2 * h * key_dim].reshape(n, h, key_dim))
    v = qkv[:, 2 * h * key_dim:].reshape(n, h, value_dim)
    g = -jnp.exp(_f32(p["A_log"]["scale"])) * jax.nn.softplus(
        x @ w["wa"] + _f32(p["dt_bias"]["bias"]))
    beta = 2.0 * jax.nn.sigmoid(x @ w["wb"])
    o = delta_rule(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _f32(p["out_norm"]["scale"])
    o = o * jax.nn.silu(x @ w["wz"]).reshape(n, h, value_dim)
    return o.reshape(n, h * value_dim) @ w["wo"]


def width_norm(x, scale, eps, mean_square=None):
    """RMSNorm over the whole width of x (n, W); `mean_square` (n, 1): the
    statistic of a width of which x is a part (the share test)."""
    if mean_square is None:
        mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * _f32(scale)


def attention_mixer(x, p, eps, *, head_dim,
                    mean_squares: Optional[Tuple] = None):
    """One document's residual stream x (n, D) -> what the attention gives
    (n, D), before the block's norm. `mean_squares`: (q's, k's) over the
    whole width, where the parameters hold a part of the heads only."""
    n = x.shape[0]
    w = {name: _f32(p[name]["kernel"]) for name in ("wq", "wk", "wv", "wo")}
    h = w["wq"].shape[1] // head_dim
    of_q, of_k = mean_squares or (None, None)
    q = width_norm(x @ w["wq"], p["q_norm"]["scale"], eps, of_q)
    k = width_norm(x @ w["wk"], p["k_norm"]["scale"], eps, of_k)
    o = attention(q.reshape(n, h, head_dim), k.reshape(n, h, head_dim),
                  (x @ w["wv"]).reshape(n, h, head_dim), head_dim ** -0.5)
    return o.reshape(n, h * head_dim) @ w["wo"]


def mixer(x, p, kind, eps, *, head_dim, linear, mean_squares=None):
    if kind == LINEAR:
        return gated_delta_mixer(x, p["mixer"], eps, **linear)
    return attention_mixer(x, p["attn"], eps, head_dim=head_dim,
                           mean_squares=mean_squares)


def hidden(params, ids, *, layer_types, head_dim, eps, linear,
           checkpoint: bool = False):
    """One document's ids (n,) -> the final-normed hidden state (n, D)."""
    top = _top(params)
    h = jnp.take(_f32(top["embed"]["embedding"]), ids, axis=0)

    def layer(h, p, kind):
        h = h + rms_norm(mixer(h, p, kind, eps, head_dim=head_dim,
                               linear=linear), p["norm1"]["scale"], eps)
        return h + rms_norm(swiglu(h, p["mlp"]), p["norm2"]["scale"], eps)

    for p, kind in zip(layer_params(params), layer_types):
        step = (lambda h, p, kind=kind: layer(h, p, kind))
        h = (jax.checkpoint(step) if checkpoint else step)(h, p)
    return rms_norm(h, top["norm"]["scale"], eps)


def _head(params):
    return _f32(_top(params)["lm_head"]["kernel"])


def logits(params, ids, **shape):
    """(n, vocabulary rows held) float32 next-token logits of one document."""
    return hidden(params, ids, **shape) @ _head(params)


def ce_sum_and_logits(params, ids, at, length=None, checkpoint: bool = False,
                      **shape):
    """One document: (the sum over its targets of the next-token
    cross-entropy, its logits at the positions `at`). `length`: the document
    is the first `length` of `ids` and zeros follow, which no position of a
    causal model can see; only the loss has to leave their positions out."""
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

    # the sum keeps the parameters' own placement, so that the first call
    # (zeros) and the later ones (the call before's sum) are ONE program
    placed = jax.tree.map(lambda a: a.sharding, params)
    one = jax.jit(one, donate_argnums=(0,), out_shardings=(None, None, placed))
    acc = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                  out_shardings=placed)(params)
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
