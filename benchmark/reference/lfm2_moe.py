"""Plain reference LFM2-MoE decoder: float32 `jax.numpy`, one document at a
time.

Written from the published configuration (`LiquidAI/LFM2-24B-A2B`
`config.json`, `model_type` lfm2_moe), the `transformers` `lfm2_moe`
modelling file's order of operations and the equations of ISSUE 48, not from
this repository's program. `RMSNorm` normalises in float32 with eps
`norm_eps`; no projection has a bias (`conv_bias` false). With h the
residual stream, d = `hidden_size`:

  h = table[ids]
  each layer i:   h += Mix(RMSNorm_op(h));   h += F(RMSNorm_ffn(h))
  logits = RMSNorm(h) @ table^T          (the table is tied: ASSUMED, the
                                          `transformers` class's default)
  loss: next-token cross-entropy, mean over every document's targets

  Mix, `layer_types[i]` conv (the gated short convolution, L = `conv_L_cache`
    taps):
      [B; C; x~] = W_in x                 d -> 3 d, split in this order
      u_t = B_t * x~_t
      c_t = sum_{j < L} w_j u_{t-j}       depthwise, causal, zeros before the
                                          document, no bias, NO activation
      out = W_out (C_t * c_t)
  Mix, `layer_types[i]` full_attention: `num_attention_heads` query heads
    over `num_key_value_heads` key/value heads of hidden_size /
    num_attention_heads (query head j reads key/value head j // group):
      q_h = RMSNorm_64(split_h(W_q x)), k_g = RMSNorm_64(split_g(W_k x)):
        the norm over each head's channels, ONE weight of that size for q
        and one for k, after the split and before the rotation;
      q, k rotated: rotate-half RoPE on the whole head, `rope_theta`;
      o_h = softmax(q_h . k_g / sqrt(head_dim)) v_g over the keys at
        positions p' <= p;  out = W_o o.  No gate.
  F, layers before `num_dense_layers`: W_2(silu(W_1 x) * W_3 x),
    `intermediate_size` wide. The others: the routed experts, E =
    `num_experts`, K = `num_experts_per_tok`, `moe_intermediate_size` wide,
    no shared expert:
      s = sigmoid(W_r x) over all E;  chosen = top-K of (s + bias), the bias
        a buffer that takes no gradient (`use_expert_bias`);
      w = s[chosen] / (sum s[chosen] + 1e-6) * `routed_scaling_factor`
        (`norm_topk_prob` true);
      y = sum_k w_k W2_e(silu(W1_e x) * W3_e x)

No kernels, no scan, no packing, no segment ids, no sort, no mixed precision:
a document is an array of ids and is run alone, the convolution is L shifted
adds, the mask a dense matrix of positions, the layers a Python loop, and
EVERY held expert runs on EVERY token, times a weight that is 0 where the
token did not choose it.

The share: `experts_held = (first, count)` adds only the experts `first ..
first + count - 1` of every sparse layer (the router keeps all its outputs
and its K a token, the weights are normalised over all K chosen); what the
other experts would add is left out. `None` is the whole layer. The
vocabulary is what the table holds. It reads the program's seeded parameter
tree by name (`run<i>/blocks`; a conv layer's leaves under `mixer`, an
attention layer's under `attn`, the experts' under `moe`) so that the two
are compared on the same weights, the router's bias among them, and imports
nothing of the program's.

Departures that change no value, each for memory: attention runs in blocks
of queries; each layer is checkpointed in the gradient pass; documents are
followed by zeros up to the longest one's length, which no position of a
causal model can see, so that one compiled program serves them all.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# what is no part of the architecture is shared with the other plain
# references: reading the program's tree, SwiGLU, norms and gaps, the rotation,
# the dense causal softmax in blocks of queries, the shifted-add convolution
from benchmark.reference.granite import attention, convolution
from benchmark.reference.laguna import (  # noqa: F401
    global_norm, inv_frequencies, layer_params, leaf_norms, relative_gap,
    rms_norm, rotate, swiglu, unpack)

PRECISION = "highest"
CONV, ATTENTION = "conv", "full_attention"
WEIGHT_EPS = 1e-6       # in the denominator of the chosen experts' weights


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    assert config["norm_topk_prob"] and config["use_expert_bias"]
    assert not config["conv_bias"]
    source = config.get("source_values", {})
    dense = config["num_dense_layers"]
    layers = config["num_hidden_layers"]
    assert list(config["mlp_layer_types"]) == (
        ["dense"] * dense + ["sparse"] * (layers - dense))
    return dict(
        layer_types=list(config["layer_types"]),
        mlp_types=list(config["mlp_layer_types"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        eps=config["norm_eps"], rope=config["rope_parameters"],
        taps=config["conv_L_cache"], top_k=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        experts_routed=source.get("num_experts", config["num_experts"]))


def _top(params) -> dict:
    return params["params"] if "params" in params else params


def _f32(leaf):
    return leaf.astype(jnp.float32)


# --- pieces -----------------------------------------------------------------

def gated_conv_mixer(x, p, *, taps: int):
    """x (n, d) normed -> (n, d): W_out[C * conv(B * x~)]."""
    b, c, xt = jnp.split(x @ _f32(p["in_proj"]["kernel"]), 3, axis=-1)
    kernel = _f32(p["conv"]["kernel"])
    assert kernel.shape[0] == taps, kernel.shape
    return (c * convolution(b * xt, kernel, None)) @ _f32(
        p["out_proj"]["kernel"])


def attention_mixer(x, p, *, heads, kv_heads, head_dim, eps, rope):
    """x (n, d) normed -> (n, d): the norm a head, then the rotation."""
    n = x.shape[0]
    q = (x @ _f32(p["wq"]["kernel"])).reshape(n, heads, head_dim)
    k = (x @ _f32(p["wk"]["kernel"])).reshape(n, kv_heads, head_dim)
    v = (x @ _f32(p["wv"]["kernel"])).reshape(n, kv_heads, head_dim)
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    freq = inv_frequencies(rope, head_dim)
    positions = jnp.arange(n)
    q, k = rotate(q, positions, *freq), rotate(k, positions, *freq)
    o = attention(q, k, v, head_dim ** -0.5)
    return o.reshape(n, heads * head_dim) @ _f32(p["wo"]["kernel"])


def routed_experts(x, p, *, top_k, routed_scale, experts_routed,
                   experts_held: Optional[Tuple[int, int]] = None):
    """The sum over the chosen experts that are held: x (n, d) -> (n, d)."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]["kernel"]))
    assert scores.shape[-1] == experts_routed, scores.shape
    ranked = scores + jax.lax.stop_gradient(_f32(p["router_bias"]["bias"]))
    _, chosen = jax.lax.top_k(ranked, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = routed_scale * top / (
        jnp.sum(top, axis=-1, keepdims=True) + WEIGHT_EPS)
    first, count = experts_held or (0, experts_routed)
    # (n, count): the weight of held expert e for each token, 0 if not chosen
    w = jnp.sum(jnp.where(
        chosen[:, :, None] == first + jnp.arange(count)[None, None, :],
        weights[:, :, None], 0.0), axis=1)
    gate, up, down = (_f32(p[f"experts_{k}"]["kernel"])
                      for k in ("gate", "up", "down"))
    assert gate.shape[0] == count, (gate.shape, count)
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", x, gate)) \
        * jnp.einsum("nd,edf->nef", x, up)
    return jnp.einsum("nef,efd->nd", h * w[:, :, None], down)


def hidden(params, ids, *, layer_types, mlp_types, heads, kv_heads, head_dim,
           eps, rope, taps, top_k, routed_scale, experts_routed,
           experts_held=None, checkpoint: bool = False):
    """One document's ids (n,) -> the final-normed hidden state (n, d)."""
    top = _top(params)
    h = jnp.take(_f32(top["embed"]["embedding"]), ids, axis=0)

    def layer(h, p, kind, mlp):
        x = rms_norm(h, p["norm1"]["scale"], eps)
        if kind == CONV:
            h = h + gated_conv_mixer(x, p["mixer"], taps=taps)
        else:
            assert kind == ATTENTION, kind
            h = h + attention_mixer(x, p["attn"], heads=heads,
                                    kv_heads=kv_heads, head_dim=head_dim,
                                    eps=eps, rope=rope)
        x = rms_norm(h, p["norm2"]["scale"], eps)
        if mlp == "dense":
            return h + swiglu(x, p["mlp"])
        return h + routed_experts(
            x, p["moe"], top_k=top_k, routed_scale=routed_scale,
            experts_routed=experts_routed, experts_held=experts_held)

    for p, kind, mlp in zip(layer_params(params), layer_types, mlp_types):
        step = (lambda h, p, kind=kind, mlp=mlp: layer(h, p, kind, mlp))
        h = (jax.checkpoint(step) if checkpoint else step)(h, p)
    return rms_norm(h, top["norm"]["scale"], eps)


def logits(params, ids, checkpoint: bool = False, **shape):
    """(n, vocabulary rows held) float32 next-token logits of one document:
    the tied table is the head."""
    table = _f32(_top(params)["embed"]["embedding"])
    return hidden(params, ids, checkpoint=checkpoint, **shape) @ table.T


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
    given and hands back: beside the parameters there is one gradient tree.
    The router's bias takes no gradient: its leaf comes back zero."""
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
