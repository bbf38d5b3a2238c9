"""Routed experts of which this chip holds a share, beside a shared expert.

The feed-forward of a sparse decoder layer (vitax/models/decoder.py):

    y = sum_{k in top-K} w_k E_k(x)  +  S(x)

`s = sigmoid(W_r x)` scores ALL `experts_routed` experts in float32, the K
best are chosen over all of them (the plain path: `route_groups` 0, no
bias), `w = routed_scale * s_k / (sum_topK s + weight_eps)` (`weight_eps` is
the model's: 0, or LFM2's 1e-6); every `E_k` and the shared `S` (`shared_dim`
0: none) is a SwiGLU. The layer is told which experts it holds (`experts_held` from
`expert_first` on: one chip's share of a deployment in which several chips
share each layer) and adds the terms whose expert it holds, and the shared
expert; what the absent experts would add is left out. With `experts_held
== experts_routed` it is the whole layer. There is no exchange and nothing
stands in for the absent chips: expert parallelism over a mesh axis would
put the all-to-all pair around `expert_ffn` and is not built.

What groups and a bias change is the CHOICE alone (`choose`, the DeepSeek-V3
router): with `route_bias` the experts are ranked by s' = s + bias (a
float32 leaf an expert, which receives no gradient; the trainer moves it
once a step from `route_load`, the real tokens that chose each of ALL the
routed experts, which a layer with a bias sows: vitax/train/step.py
`balance_router_bias`); with
`route_groups` G the experts form G equal groups in index order, a group
scores the sum of its two best s', the `groups_per_token` best groups are
kept and the K best s' are taken inside them. The weights come from the
unbiased s of the chosen, as on the plain path. The layer still scores all
experts, keeps all groups and all K a token; `tokens_choosing_held_group`
(sown beside `expert_load`) counts the tokens that kept the group of the
first held expert.

No token is dropped: the (token, choice) slots whose expert is held are
sorted by expert and the held experts' three products run as grouped matrix
products over the sorted rows (`jax.lax.ragged_dot`; on the TPU the compiler
makes a grouped-matmul kernel of it that skips the rows past the last
group). The buffer of sorted rows has the static size tokens x K, the most
that could be routed here; rows past the held slots compute nothing and are
masked out of values and gradients alike. Dispatch and combine are gathers
in both directions (each is the other's transpose), so no scatter runs.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from vitax.models.ssm import Leaf
from vitax.models.vit import Array, Dtype, default_init


class SwiGLU(nn.Module):
    """down(silu(gate(x)) * up(x)), no biases."""

    hidden_dim: int
    out_dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: Array) -> Array:
        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, kernel_init=default_init,
                            name=name)
        h = nn.silu(dense(self.hidden_dim, "gate")(x)) \
            * dense(self.hidden_dim, "up")(x)
        return dense(self.out_dim, "down")(h)


class Table(nn.Module):
    """One float32 array under a leaf name the sharding rules know
    (vitax/parallel/rules.py): an embedding, a head, or one matrix of every
    held expert stacked (experts, in, out)."""

    shape: Tuple[int, ...]
    leaf: str = "kernel"

    @nn.compact
    def __call__(self) -> Array:
        return self.param(self.leaf, default_init, self.shape, jnp.float32)


@jax.custom_vjp
def dispatch(x, slot_of_row, row_of_slot):
    """Sorted rows from tokens: xs[r] = x[slot_of_row[r] // K]. (N, D) ->
    (M, D). `row_of_slot` (N, K) is the inverse permutation."""
    return jnp.take(x, slot_of_row // row_of_slot.shape[1], axis=0)


def _dispatch_fwd(x, slot_of_row, row_of_slot):
    return dispatch(x, slot_of_row, row_of_slot), row_of_slot


def _dispatch_bwd(row_of_slot, dxs):
    # dx[n] = sum over the token's K slots of dxs[row of that slot]: a gather
    dx = jnp.sum(jnp.take(dxs, row_of_slot, axis=0).astype(jnp.float32),
                 axis=1).astype(dxs.dtype)
    return dx, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys, weights, slot_of_row, row_of_slot):
    """Tokens from sorted rows: y[n] = sum_k weights[n, k] * ys[row_of_slot[n, k]].
    (M, D), (N, K) float32 -> (N, D) float32. `weights` is 0 on a slot whose
    expert is not held here."""
    del slot_of_row
    picked = jnp.take(ys, row_of_slot, axis=0).astype(jnp.float32)  # (N, K, D)
    return jnp.sum(picked * weights[..., None], axis=1)


def _combine_fwd(ys, weights, slot_of_row, row_of_slot):
    return (combine(ys, weights, slot_of_row, row_of_slot),
            (ys, weights, slot_of_row, row_of_slot))


def _combine_bwd(res, dy):
    ys, weights, slot_of_row, row_of_slot = res
    k = weights.shape[1]
    # each sorted row's token and weight, by the gather that sorted the rows
    w_of_row = jnp.take(weights.reshape(-1), slot_of_row)
    dys = (jnp.take(dy, slot_of_row // k, axis=0)
           * w_of_row[:, None]).astype(ys.dtype)
    picked = jnp.take(ys, row_of_slot, axis=0).astype(jnp.float32)
    dw = jnp.sum(picked * dy[:, None, :], axis=-1)
    return dys, dw, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def choose(scores: Array, bias, k: int, groups: int, groups_kept: int):
    """The `k` experts a token is sent to and their scores: (top (N, K) taken
    from `scores` (N, E), chosen (N, K), kept (N, groups) bool or None). The
    ranking is by `scores + bias` (`bias` (E,) or None, no gradient), inside
    the `groups_kept` best of `groups` equal groups, a group scored by the
    sum of its two best ranked experts (0 groups: over all experts)."""
    ranked = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    kept = None
    if groups:
        n, e = scores.shape
        best_two, _ = jax.lax.top_k(ranked.reshape(n, groups, e // groups), 2)
        _, best = jax.lax.top_k(jnp.sum(best_two, axis=-1), groups_kept)
        kept = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        ranked = jnp.where(jnp.repeat(kept, e // groups, axis=1), ranked,
                           -jnp.inf)
    _, chosen = jax.lax.top_k(ranked, k)
    return jnp.take_along_axis(scores, chosen, axis=-1), chosen, kept


class SharedRoutedExperts(nn.Module):
    """(R, T, D) -> (R, T, D); `valid` (R, T) bool marks real tokens (padding
    is routed nowhere and counted in no expert's load)."""

    experts_routed: int
    experts_held: int
    expert_first: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 1.0
    dtype: Dtype = jnp.bfloat16
    route_groups: int = 0           # 0: the K best over all experts
    groups_per_token: int = 0
    route_bias: bool = False
    weight_eps: float = 0.0         # on the sum that normalises the weights

    @nn.compact
    def __call__(self, x: Array, valid: Array) -> Array:
        r, t, d = x.shape
        n, k, held = r * t, self.experts_per_token, self.experts_held
        xf = x.reshape(n, d)

        with jax.named_scope("moe_route"):
            scores = jax.nn.sigmoid(nn.Dense(
                self.experts_routed, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=default_init,
                name="router")(xf.astype(jnp.float32)))           # (N, E)
            if self.route_groups or self.route_bias:
                bias = Leaf((self.experts_routed,), nn.initializers.zeros,
                            "bias", name="router_bias")() \
                    if self.route_bias else None
                top, chosen, kept = choose(scores, bias, k, self.route_groups,
                                           self.groups_per_token)
                if kept is not None:
                    mine = self.expert_first // (
                        self.experts_routed // self.route_groups)
                    self.sow("intermediates", "tokens_choosing_held_group",
                             jnp.sum(kept[:, mine] & valid.reshape(n),
                                     dtype=jnp.int32))
            else:
                top, chosen = jax.lax.top_k(scores, k)            # (N, K)
            scaled = self.routed_scale * top
            total = jnp.sum(top, axis=-1, keepdims=True)
            if self.weight_eps:
                total = total + self.weight_eps
            weights = scaled / total
            if self.route_bias:     # what the trainer's balance rule reads
                self.sow("intermediates", "route_load", jnp.sum(
                    jax.nn.one_hot(chosen, self.experts_routed,
                                   dtype=jnp.int32)
                    * valid.reshape(n, 1, 1), axis=(0, 1)))       # (E,)
            local = chosen - self.expert_first
            here = ((local >= 0) & (local < held)
                    & valid.reshape(n)[:, None])                  # (N, K)
            weights = jnp.where(here, weights, 0.0)
            # a slot whose expert is elsewhere sorts behind every held one
            group = jnp.where(here, local, held).reshape(-1)      # (N*K,)
            load = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32),
                           axis=0)                                # (held,)
            self.sow("intermediates", "expert_load", load)

        with jax.named_scope("moe_dispatch"):
            slot_of_row = jnp.argsort(group, stable=True)         # (M,)
            row_of_slot = jnp.argsort(slot_of_row).reshape(n, k)
            in_a_group = jnp.arange(n * k) < jnp.sum(load)
            xs = dispatch(xf, slot_of_row, row_of_slot)
            xs = jnp.where(in_a_group[:, None], xs, jnp.zeros((), xs.dtype))

        with jax.named_scope("expert_ffn"):
            def kernels(name, a, b):
                return Table((held, a, b), name=name)().astype(self.dtype)

            def grouped(rows, w):
                return jax.lax.ragged_dot(
                    rows, w, load, preferred_element_type=jnp.float32)

            h = (nn.silu(grouped(xs, kernels("experts_gate", d, self.expert_dim)))
                 * grouped(xs, kernels("experts_up", d, self.expert_dim)))
            ys = grouped(h.astype(self.dtype),
                         kernels("experts_down", self.expert_dim, d))
            ys = jnp.where(in_a_group[:, None], ys, 0.0).astype(self.dtype)

        with jax.named_scope("moe_combine"):
            y = combine(ys, weights, slot_of_row, row_of_slot)

        if self.shared_dim:
            with jax.named_scope("shared_expert"):
                y = y + SwiGLU(self.shared_dim, d, dtype=self.dtype,
                               name="shared")(xf)
        return y.astype(self.dtype).reshape(r, t, d)
