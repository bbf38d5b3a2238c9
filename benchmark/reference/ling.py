"""Plain reference Ling-3.0-flash decoder (the language model of
Ling-3.0-flash-VL): float32 `jax.numpy`, one document at a time, the delta
rule as the recurrence itself, token by token.

Written from the published configuration (`inclusionAI/Ling-3.0-flash-VL`
`config.json`), the Kimi Linear paper (arXiv:2510.26692, the recurrence of
its section 3 and none of its chunked algorithm), DeepSeek-V2's latent
attention and DeepSeek-V3's router, and the equations of ISSUE 41, not from
this repository's program. `RMSNorm` normalises in float32 with eps
`rms_norm_eps`; no projection has a bias. With u the normed input, d =
`hidden_size`, H = `num_attention_heads` heads of `head_dim`:

  h = embedding[ids]
  each layer i:   h += Mix(RMSNorm(h));   h += F(RMSNorm(h))
  logits = RMSNorm(h) @ head (untied);  loss: next-token cross-entropy, mean
  over every document's targets

  Mix, (i + 1) % `layer_group_size` != 0, Kimi Delta Attention:
    q, k, v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u)): a
      depthwise causal convolution of `short_conv_kernel_size` taps, zeros
      before the document, no bias (`linear_silu`);
    q = q / sqrt(sum q^2 + 1e-6) / sqrt(head_dim), k = k / sqrt(sum k^2 +
      1e-6), a head (`use_qk_norm`, ASSUMED to be this L2 normalisation);
    g_t = L * sigmoid(exp(A_log) * (W_f u_t + dt_bias)), L = `kda_lower_bound`
      (`kda_safe_gate`), a head AND channel; W_f is d x (H x head_dim)
      (`no_kda_lora`); b_t = sigmoid(W_b u_t), a head;
      S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T,  S_{-1} = 0
      o_t = S_t^T q_t
    with S (head_dim, head_dim) a head, carried by `lax.scan` over positions;
    out = W_o[ o_t / sqrt(mean o_t^2 + eps) * w * sigmoid(W_g u_t) ], the norm
      over each head's channels (`group_norm_size` 1), the gate one scalar a
      head (`gated_attention_proj_granularity_type` head_wise).
  Mix, (i + 1) % `layer_group_size` == 0, latent attention (`q_lora_rank`
    null): q_h = W_q u = [q_nope (`qk_nope_head_dim`); q_rope
    (`qk_rope_head_dim`)]; [c; k_rope] = W_kva u with c `kv_lora_rank` wide;
    [k_nope_h; v_h (`v_head_dim`)] = W_kvb RMSNorm(c); q_rope and k_rope
    rotated (rotate-half, `rope_theta`, all `rotary_dim` dimensions); k_h =
    [k_nope_h; k_rope], the rotated key the same for every head, written out
    a head here; o_h = softmax(q_h . k_h / sqrt(nope + rope)) v_h over the
    keys at positions p' <= p; out = W_o[ sigmoid(W_g u)_h * o_h ].
  F: the first `first_k_dense_replace` layers a SwiGLU of `intermediate_size`;
    the others s = sigmoid(W_r x) over all `num_experts` (of the DEPLOYMENT),
    s' = s + bias; the experts in `n_group` groups in index order, a group's
    score the sum of its two largest s', the `topk_group` best groups kept,
    the `num_experts_per_tok` largest s' inside them chosen; w_k =
    `routed_scaling_factor` * s_k / sum_chosen s (from s, not s');
    y = sum_k w_k E_k(x) + S(x), E_k and S SwiGLU of `moe_intermediate_size`
    / `moe_shared_expert_intermediate_size`.

The shares: `experts_held = (first, count)` adds only those experts of every
sparse layer (the router keeps all its outputs, groups and 8 a token) and
the shared expert; the heads are the ones the parameters hold (every
projection's head axis); the vocabulary is what the parameters hold. What
the absent experts and heads would add is left out, here as in the program.

No chunks, no triangular solve, no kernels, no packing, no segment ids, no
mixed precision: a document is an array of ids and is run alone. It reads
the program's seeded parameter tree by name (`run<i>/blocks`; a kda layer's
leaves under `mixer`, a latent layer's under `attn`) so that the two are
compared on the same weights, and imports nothing of the program's.

Departures that change no value, each for memory: attention runs in blocks
of queries; the recurrence is scanned in blocks of `TOKEN_BLOCK` tokens under
`jax.checkpoint` and each layer is checkpointed in the gradient pass;
documents are followed by zeros up to the longest one's length, which no
position of a causal model can see, so that one compiled program serves
them all; every held expert runs on every token (one einsum over the
stacked experts) times a weight that is 0 where the token did not choose it.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# what is no part of the architecture is shared with the other plain
# references: reading the program's tree, RMSNorm, SwiGLU, norms and gaps,
# the dense causal softmax in blocks of queries, the shifted-add convolution
from benchmark.reference.granite import convolution
from benchmark.reference.laguna import (  # noqa: F401
    global_norm, layer_params, leaf_norms, relative_gap, rms_norm, rotate,
    swiglu, unpack)

PRECISION = "highest"
QUERY_BLOCK = 512
TOKEN_BLOCK = 64
L2_EPS = 1e-6


def layer_kinds(config: dict) -> list:
    period = config["layer_group_size"]
    return ["latent" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def shape_of(config: dict) -> dict:
    """What the functions below take, from a configuration file's dict under
    the SOURCE's names (not the nested block the program reads)."""
    assert config["q_lora_rank"] is None and config["score_function"] == \
        "sigmoid" and config["norm_topk_prob"] and not config["use_mla_nope"]
    assert config["kda_safe_gate"] and config["no_kda_lora"]
    assert config["group_norm_size"] == 1 and config["linear_silu"]
    assert config["gated_attention_proj_granularity_type"] == "head_wise"
    assert config["rotary_dim"] == config["qk_rope_head_dim"]
    n = config["num_hidden_layers"]
    assert not any(config["expert_swiglu_limit_list"][:n]) and not any(
        config["share_expert_swiglu_limit_list"][:n])
    source = config.get("source_values", {})
    return dict(
        kinds=layer_kinds(config),
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"], head_dim=config["head_dim"],
        eps=config["rms_norm_eps"], taps=config["short_conv_kernel_size"],
        gate_bound=float(config["kda_lower_bound"]),
        latent=dict(rank=config["kv_lora_rank"],
                    nope=config["qk_nope_head_dim"],
                    rope=config["qk_rope_head_dim"],
                    value=config["v_head_dim"],
                    theta=float(config["rope_theta"])),
        router=dict(top_k=config["num_experts_per_tok"],
                    groups=config["n_group"], groups_kept=config["topk_group"],
                    scale=config["routed_scaling_factor"],
                    bias=bool(config["moe_router_enable_expert_bias"]),
                    experts_routed=source.get("num_experts",
                                              config["num_experts"])))


def _top(params) -> dict:
    return params["params"] if "params" in params else params


def _f32(leaf):
    return leaf.astype(jnp.float32)


# --- pieces -----------------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. q, k (n, H, K), v (n, H, V), g (n, H,
    K) <= 0, beta (n, H) -> o (n, H, V) with o_t = S_t^T q_t. The state S
    (H, K, V) starts at zero."""
    n, h, dk = q.shape
    blocks = -(-n // TOKEN_BLOCK)
    fill = blocks * TOKEN_BLOCK - n     # zeros after the document: never read

    def token(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        state = jnp.exp(g_t)[:, :, None] * state
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


def kda_mixer(u, p, eps, *, head_dim, taps, gate_bound):
    """One document's normed input u (n, D) -> the mixer's output (n, D);
    the heads are those the parameters hold."""
    n = u.shape[0]
    w = {name: _f32(p[name]["kernel"])
         for name in ("wq", "wk", "wv", "wf", "wb", "head_gate", "wo")}
    inner = w["wq"].shape[1]
    h = inner // head_dim
    kernel = _f32(p["conv"]["kernel"])
    assert kernel.shape == (taps, 3 * inner), kernel.shape

    def convolved(x, part):
        return jax.nn.silu(convolution(
            x, kernel[:, part * inner:(part + 1) * inner], None)).reshape(
                n, h, head_dim)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q = unit(convolved(u @ w["wq"], 0)) / math.sqrt(head_dim)
    k = unit(convolved(u @ w["wk"], 1))
    v = convolved(u @ w["wv"], 2)
    rate = jnp.exp(_f32(p["A_log"]["scale"]))[:, None]              # (H, 1)
    g = gate_bound * jax.nn.sigmoid(rate * (
        u @ w["wf"] + _f32(p["dt_bias"]["bias"])).reshape(n, h, head_dim))
    beta = jax.nn.sigmoid(u @ w["wb"])
    o = delta_rule(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _f32(p["out_norm"]["scale"])
    o = o * jax.nn.sigmoid(u @ w["head_gate"])[:, :, None]
    return o.reshape(n, inner) @ w["wo"]


def attention(q, k, v):
    """q, k (n, H, Dqk), v (n, H, Dv) of ONE document -> (n, H, Dv): dense
    causal softmax with every head's key written out, in blocks of queries
    (the last one filled with zeros that are cut off again)."""
    n, h, dqk = q.shape
    key_at = jnp.arange(n)
    blocks = -(-n // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - n), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dqk)
        see = (start + jnp.arange(QUERY_BLOCK))[:, None] >= key_at[None, :]
        p = jax.nn.softmax(jnp.where(see[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(blocks, QUERY_BLOCK, h, dqk),
                              jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, h, v.shape[-1])[:n]


def latent_mixer(u, p, eps, *, rank, nope, rope, value, theta):
    n = u.shape[0]
    w = {name: _f32(p[name]["kernel"])
         for name in ("wq", "wkva", "wkvb", "head_gate", "wo")}
    h = w["wq"].shape[1] // (nope + rope)
    positions = jnp.arange(n)
    inv_freq = theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    q = (u @ w["wq"]).reshape(n, h, nope + rope)
    q_rope = rotate(q[..., nope:], positions, inv_freq, 1.0)
    down = u @ w["wkva"]
    latent = rms_norm(down[:, :rank], p["latent_norm"]["scale"], eps)
    k_rope = rotate(down[:, None, rank:], positions, inv_freq, 1.0)  # (n, 1, rope)
    up = (latent @ w["wkvb"]).reshape(n, h, nope + value)
    # every head's key written out: its own part beside the shared rotated one
    k = jnp.concatenate([up[..., :nope], jnp.repeat(k_rope, h, axis=1)],
                        axis=-1)
    o = attention(jnp.concatenate([q[..., :nope], q_rope], axis=-1), k,
                  up[..., nope:])
    o = o * jax.nn.sigmoid(u @ w["head_gate"])[:, :, None]
    return o.reshape(n, h * value) @ w["wo"]


def chosen_experts(scores, bias, *, top_k, groups, groups_kept):
    """(n, top_k) indices: the `top_k` largest s' = scores + bias inside the
    `groups_kept` groups whose two largest s' sum highest."""
    n, e = scores.shape
    biased = scores if bias is None else scores + bias
    per = e // groups
    two_best = jnp.sort(biased.reshape(n, groups, per), axis=-1)[..., -2:]
    group_score = jnp.sum(two_best, axis=-1)                        # (n, G)
    order = jnp.argsort(-group_score, axis=-1)[:, :groups_kept]
    kept = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], order].set(True)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), biased, -jnp.inf)
    return jnp.argsort(-masked, axis=-1)[:, :top_k]


def routed_and_shared(x, p, *, top_k, groups, groups_kept, scale, bias,
                      experts_routed, experts_held):
    """sum over the chosen experts that are held, plus the shared expert."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]["kernel"]))
    assert scores.shape[-1] == experts_routed, scores.shape
    chosen = chosen_experts(
        scores, _f32(p["router_bias"]["bias"]) if bias else None,
        top_k=top_k, groups=groups, groups_kept=groups_kept)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * top / jnp.sum(top, axis=-1, keepdims=True)
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
    return jnp.einsum("nef,efd->nd", h * w[:, :, None], down) \
        + swiglu(x, p["shared"])


def hidden(params, ids, *, kinds, dense_layers, heads, head_dim, eps, taps,
           gate_bound, latent, router, experts_held=None,
           checkpoint: bool = False):
    """One document's ids (n,) -> the final-normed hidden state (n, D)."""
    del heads                    # the parameters' own head axis is the share
    top = _top(params)
    h = jnp.take(_f32(top["embed"]["embedding"]), ids, axis=0)

    def layer(h, p, kind, dense):
        u = rms_norm(h, p["norm1"]["scale"], eps)
        if kind == "kda":
            h = h + kda_mixer(u, p["mixer"], eps, head_dim=head_dim,
                              taps=taps, gate_bound=gate_bound)
        else:
            h = h + latent_mixer(u, p["attn"], eps, **latent)
        u = rms_norm(h, p["norm2"]["scale"], eps)
        if dense:
            return h + swiglu(u, p["mlp"])
        return h + routed_and_shared(u, p["moe"], experts_held=experts_held,
                                     **router)

    for i, (p, kind) in enumerate(zip(layer_params(params), kinds)):
        step = (lambda h, p, kind=kind, dense=i < dense_layers:
                layer(h, p, kind, dense))
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
    serves them all (compiled once: PR 32's form of this loop compiled it a
    second time for the second document, whose sum came back placed
    otherwise than the zeros), the gradients summed into one tree that the
    program is given and hands back: beside the parameters there is one
    gradient tree."""
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
