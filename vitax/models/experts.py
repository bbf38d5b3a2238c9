"""Routed experts of which this chip holds a share, beside a shared expert.

The feed-forward of a sparse decoder layer (vitax/models/decoder.py):

    y = sum_{k in top-K} w_k E_k(x)  +  S(x)

Two routers, both over ALL `experts_routed` experts in float32 (`route_form`).
`sigmoid`: `s = sigmoid(W_r x)`, the K best scores are chosen (the plain path:
`route_groups` 0, no bias), `w = routed_scale * s_k / (sum_topK s +
weight_eps)` (`weight_eps` is the model's: 0, or LFM2's 1e-6).
`softmax_chosen` (SmallThinker): the K largest of the LOGITS `W_r x` are
chosen and `w = routed_scale * softmax` over those K logits alone; no bias,
no groups. What the router reads may be another tensor than the experts do
(`route_from`: SmallThinker's router reads the layer's FIRST norm's output,
what its attention reads, and the experts the second's). Two expert
activations (`activation`): every `E_k` is `down(act(gate x) * up x)` with
act `silu` (a SwiGLU) or `relu` (SmallThinker's ReGLU, whose gate leaves a
hidden unit at exactly 0: the loops count the pairs of a live sorted row and
a hidden unit whose gate is > 0 and the layer sows them as
`expert_hidden_live`); the shared `S` (`shared_dim` 0: none) is a SwiGLU.

The layer is told which experts it holds (`experts_held` from `expert_first`
on: one chip's share of a deployment in which several chips share each
layer) and adds the terms whose expert it holds, and the shared expert; what
the absent experts would add is left out. With `experts_held ==
experts_routed` it is the whole layer. There is no exchange and nothing
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
products over the sorted rows (`jax.lax.ragged_dot`, the TPU compiler's
`ragged-dot*` kernels). The sort's buffer has the static size tokens x K,
the most that could be routed here, and a chip of a deployment holds an
eighth or a sixty-fourth of it. On the chip the compiler's kernel does NOT
skip the rows past the last group: it spares them the MXU and pays for them
in HBM (PERF.md section 7 (mm): Ling's 72 products a step took 34 ms for a
need of 2.24), and every gather, mask and `silu * up` around it is as long
as the buffer too. So the layer bounds its own work (`routed_experts`, one
`jax.custom_vjp` from tokens to tokens): forward and backward are loops over
blocks of B consecutive sorted rows, `ceil(slots held / B)` trips read from
`load` at run time (reverse mode through a loop with a traced trip count
does not exist, hence the hand-written rules), each trip a gather of B
tokens, the products on B rows with the block's own group sizes, and B rows
written. B is a rule of static shapes (`block_rows`). With every expert held
all tokens x K / B blocks run: the same work as one buffer-long pass. The
kernels' gradients are the one part a row does not bound: the product that
contracts the rows writes all held experts' float32 (in, out) whatever the
rows, and the sum over trips reads and writes that again; the backward's
blocks therefore only lay out what those gradients are made of, in buffers
of `BLOCKS_A_CHUNK` blocks, and the three products run once a chunk. A row
past the last live one is never read as a number: where a product leaves
something in a dead row of a live block it is selected away, not multiplied
by its weight of 0. The layer sows `expert_rows_computed` (blocks x B)
beside `expert_load`: rows worked on over slots held says how far the
loops' work follows the load.

Still tokens x K rows, by their OUTPUT shape whatever the load: combine's
forward gather of the sorted rows' results (N, K, D) and dispatch's backward
gather of their gradients, each the transpose of a B-row gather in the loop
(no scatter runs: a scatter-add of B rows into the tokens is serial on this
chip), the zero-fill of the two (tokens x K, D) buffers they read, and the
two sorts. Later work (ROADMAP A18).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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


# blocks of sorted rows: see `block_rows`
BLOCK_FLOOR, BLOCK_CEILING = 512, 4096
# ... and the blocks whose rows meet the kernels' gradients in ONE product
BLOCKS_A_CHUNK = 8

_ROWS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(       # (B, D) x (B, F)
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def block_rows(slots: int, held: int, routed: int) -> int:
    """B, the sorted rows one trip of the layer's loops works on, from static
    shapes alone: a quarter of the `slots * held / routed` rows a balanced
    router sends here, as a power of two between 512 and 4,096 (the whole
    buffer where that is smaller). A quarter, so that the expected load ends
    INSIDE the fifth block and a seed's few rows more or less do not cross a
    block's edge."""
    want = max(slots * held // (4 * routed), 1)
    block = min(max(1 << want.bit_length() - 1, BLOCK_FLOOR), BLOCK_CEILING)
    return min(block, slots)


def blocks_of(rows, block: int) -> Array:
    """The blocks of `block` sorted rows that hold one of `rows` live rows:
    the trip count of the layer's loops, read from the load."""
    return (rows + block - 1) // block


def _sizes_within(load: Array, first, rows: int) -> Array:
    """Each held expert's rows inside the sorted rows [first, first + rows)."""
    ends = jnp.cumsum(load)
    return (jnp.clip(ends - first, 0, rows)
            - jnp.clip(ends - load - first, 0, rows))


def _grouped(rows, kernels, sizes, dims=None):
    if dims is None:
        return jax.lax.ragged_dot(rows, kernels, sizes,
                                  preferred_element_type=jnp.float32)
    return jax.lax.ragged_dot_general(rows, kernels, sizes, dims,
                                      preferred_element_type=jnp.float32)


def _padded(slot_of_row, block: int):
    """`slot_of_row` to whole blocks: a row past the buffer is a dead row."""
    short = -slot_of_row.shape[0] % block
    return jnp.pad(slot_of_row, (0, short)) if short else slot_of_row


def _rows_of_block(xf, slot_of_row, first, total, block: int, k: int):
    """(the block's slots, which of its rows are live (B, 1), its tokens)."""
    with jax.named_scope("moe_dispatch"):
        slots = jax.lax.dynamic_slice_in_dim(slot_of_row, first, block)
        live = (first + jnp.arange(block) < total)[:, None]
        xs = jnp.where(live, jnp.take(xf, slots // k, axis=0),
                       jnp.zeros((), xf.dtype))
    return slots, live, xs


ACTIVATIONS = ("silu", "relu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def routed_experts(xf, weights, slot_of_row, row_of_slot, load,
                   gate, up, down, block: int, activation: str = "silu"):
    """y[n] = sum_k weights[n, k] * E(x[n]) over the slots whose expert is
    held: (N, D), (N, K) float32 (0 on a slot whose expert is elsewhere), the
    sort's two permutations, the held experts' load and their three stacked
    kernels -> ((N, D) float32, hidden units live). Works on
    `blocks_of(sum(load), block)` blocks of `block` sorted rows, forward and
    backward. `activation` is E's, `silu` or `relu`; under `relu` the second
    result is the int32 count of the (live sorted row, hidden unit) pairs
    whose gate is > 0, under `silu` (no gate is 0) it is None."""
    return _routed_fwd(xf, weights, slot_of_row, row_of_slot, load,
                       gate, up, down, block, activation)[0]


def _routed_fwd(xf, weights, slot_of_row, row_of_slot, load, gate, up, down,
                block, activation):
    k, dtype = row_of_slot.shape[1], gate.dtype
    total = jnp.sum(load)
    padded = _padded(slot_of_row, block)
    relu = activation == "relu"

    def one_block(b, carry):
        ys, hidden_live = carry
        first = b * block
        _, live, xs = _rows_of_block(xf, padded, first, total, block, k)
        with jax.named_scope("expert_ffn"):
            sizes = _sizes_within(load, first, block)
            g = _grouped(xs, gate, sizes)
            if relu:
                hidden_live += jnp.sum(live & (g > 0), dtype=jnp.int32)
                h = jnp.maximum(g, 0.0) * _grouped(xs, up, sizes)
            else:
                h = nn.silu(g) * _grouped(xs, up, sizes)
            y = _grouped(h.astype(dtype), down, sizes)
            # a dead row of a live block is whatever the product left there
            y = jnp.where(live, y, 0.0).astype(dtype)
            return (jax.lax.dynamic_update_slice_in_dim(ys, y, first, 0),
                    hidden_live)

    ys, hidden_live = jax.lax.fori_loop(
        0, blocks_of(total, block), one_block,
        (jnp.zeros((padded.shape[0], xf.shape[1]), dtype),
         jnp.zeros((), jnp.int32) if relu else None))
    with jax.named_scope("moe_combine"):
        # gathered (K, N, D): a token's K rows lie N apart, and no tile is
        # padded from K rows to its 16 (as (N, K, D) a relayout a gather)
        picked = jnp.take(ys, row_of_slot.T, axis=0).astype(jnp.float32)
        y = jnp.sum(picked * weights.T[..., None], axis=0)        # (N, D)
    return (y, hidden_live), (xf, weights, slot_of_row, row_of_slot, load,
                              gate, up, down)


def _routed_bwd(block, activation, res, cotangents):
    dy, _ = cotangents              # a count takes no cotangent
    xf, weights, slot_of_row, row_of_slot, load, gate, up, down = res
    k, dtype = row_of_slot.shape[1], gate.dtype
    (held, d, f) = gate.shape
    total = jnp.sum(load)
    padded = _padded(slot_of_row, block)
    m = padded.shape[0]
    chunk = min(block * BLOCKS_A_CHUNK, m)
    with jax.named_scope("expert_ffn"):
        # once, out here: the compiler's grouped product contracts a
        # kernel's middle dimension, and inside a loop it would lay a
        # kernel out anew every trip
        gate_t, up_t, down_t = (jnp.swapaxes(w, 1, 2)
                                for w in (gate, up, down))

    def one_block(first, at, carry):
        """Rows [first, first + block): `dxs` and the weights' gradients of
        those rows, and at row `at` of the chunk's five buffers what the
        kernels' gradients are made of."""
        dxs, dws, xs_c, dys_c, dg_c, du_c, h_c = carry
        slots, live, xs = _rows_of_block(xf, padded, first, total, block, k)
        put = jax.lax.dynamic_update_slice_in_dim
        with jax.named_scope("moe_combine"):
            # dy[token of row], and times the weight of the row (0 on a dead
            # row: its slot's expert is elsewhere)
            dy_rows = jnp.take(dy, slots // k, axis=0)
            w_rows = jnp.take(weights.reshape(-1), slots)[:, None]
            dys = jnp.where(live, dy_rows * w_rows, 0.0).astype(dtype)
        with jax.named_scope("expert_ffn"):
            sizes = _sizes_within(load, first, block)
            # a dead row of a live block is whatever a product left there:
            # selected away here, so that what follows is 0 on it
            g, u, dh = (jnp.where(live, _grouped(*of, sizes), 0.0) for of in (
                (xs, gate), (xs, up), (dy_rows.astype(dtype), down_t)))
            relu = activation == "relu"
            s = None if relu else jax.nn.sigmoid(g)
            a = jnp.maximum(g, 0.0) if relu else g * s          # act(g)
            h = a * u
            # through `down` once for both: the weight's gradient is the
            # row's <dy, y> = <dy down^T, h>, the rows' is w * dy down^T
            dw = jnp.sum(dh * h, axis=-1)
            dh = dh * w_rows
            if relu:                # act'(g) = [g > 0]
                dg = jnp.where(g > 0, dh * u, 0.0).astype(dtype)
                du = (dh * a).astype(dtype)
            else:
                dg = (dh * u * s * (1.0 + g * (1.0 - s))).astype(dtype)
                du = (dh * g * s).astype(dtype)
            dx = _grouped(dg, gate_t, sizes) + _grouped(du, up_t, sizes)
            dx = jnp.where(live, dx, 0.0).astype(dtype)
        with jax.named_scope("moe_combine"):
            dws = put(dws, dw, first, 0)
        return (put(dxs, dx, first, 0), dws, put(xs_c, xs, at, 0),
                put(dys_c, dys, at, 0), put(dg_c, dg, at, 0),
                put(du_c, du, at, 0), put(h_c, h.astype(dtype), at, 0))

    def one_chunk(c, carry):
        first = c * chunk
        rows = jax.lax.fori_loop(
            0, blocks_of(jnp.minimum(total - first, chunk), block),
            lambda b, rows: one_block(first + b * block, b * block, rows),
            carry[:-1])
        with jax.named_scope("expert_ffn"):
            # stale rows of the last chunk lie past every group
            sizes = _sizes_within(load, first, chunk)
            xs_c, dys_c, dg_c, du_c, h_c = rows[2:]
            # the first chunk's IS the sum so far: no pass over zeros
            kernels = tuple(
                jax.lax.cond(c == 0, lambda so_far, new: new, jnp.add, so_far,
                             _grouped(left, right, sizes, _ROWS_BY_ROWS))
                for so_far, left, right in zip(
                    carry[-1], (xs_c, xs_c, h_c), (dg_c, du_c, dys_c)))
        return (*rows, kernels)

    dxs, dws, *_, (dgate, dup, ddown) = jax.lax.fori_loop(
        0, blocks_of(total, chunk), one_chunk,
        (jnp.zeros((m, d), dtype), jnp.zeros((m,), jnp.float32),
         jnp.zeros((chunk, d), dtype), jnp.zeros((chunk, d), dtype),
         jnp.zeros((chunk, f), dtype), jnp.zeros((chunk, f), dtype),
         jnp.zeros((chunk, f), dtype),
         (jnp.zeros((held, d, f), jnp.float32),
          jnp.zeros((held, d, f), jnp.float32),
          jnp.zeros((held, f, d), jnp.float32))))
    with jax.named_scope("moe_dispatch"):
        # dx[n] = sum over the token's K slots of dxs[row of that slot]
        dx = jnp.sum(jnp.take(dxs, row_of_slot.T, axis=0).astype(jnp.float32),
                     axis=0).astype(xf.dtype)
    with jax.named_scope("moe_combine"):
        dweights = jnp.take(dws, row_of_slot, axis=0)             # scalars
    return (dx, dweights, None, None, None, dgate.astype(dtype),
            dup.astype(dtype), ddown.astype(dtype))


routed_experts.defvjp(_routed_fwd, _routed_bwd)


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
    route_form: str = "sigmoid"     # | "softmax_chosen"
    activation: str = "silu"        # the routed experts'; | "relu"

    @nn.compact
    def __call__(self, x: Array, valid: Array,
                 route_from: Optional[Array] = None) -> Array:
        """`route_from` (R, T, D): what the router reads where that is not
        `x`, which the experts transform."""
        r, t, d = x.shape
        n, k, held = r * t, self.experts_per_token, self.experts_held
        xf = x.reshape(n, d)
        assert self.activation in ACTIVATIONS, self.activation

        with jax.named_scope("moe_route"):
            logits = nn.Dense(
                self.experts_routed, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=default_init,
                name="router")((xf if route_from is None else
                                route_from.reshape(n, d)).astype(jnp.float32))
            if self.route_form == "softmax_chosen":
                top, chosen = jax.lax.top_k(logits, k)            # (N, K)
                weights = self.routed_scale * jax.nn.softmax(top, axis=-1)
            else:
                scores = jax.nn.sigmoid(logits)                   # (N, E)
                if self.route_groups or self.route_bias:
                    bias = Leaf((self.experts_routed,),
                                nn.initializers.zeros, "bias",
                                name="router_bias")() \
                        if self.route_bias else None
                    top, chosen, kept = choose(
                        scores, bias, k, self.route_groups,
                        self.groups_per_token)
                    if kept is not None:
                        mine = self.expert_first // (
                            self.experts_routed // self.route_groups)
                        self.sow("intermediates",
                                 "tokens_choosing_held_group",
                                 jnp.sum(kept[:, mine] & valid.reshape(n),
                                         dtype=jnp.int32))
                else:
                    top, chosen = jax.lax.top_k(scores, k)        # (N, K)
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
            block = block_rows(n * k, held, self.experts_routed)
            self.sow("intermediates", "expert_rows_computed",
                     blocks_of(jnp.sum(load), block) * block)

        with jax.named_scope("moe_dispatch"):
            slot_of_row = jnp.argsort(group, stable=True)         # (M,)
            row_of_slot = jnp.argsort(slot_of_row).reshape(n, k)

        with jax.named_scope("expert_ffn"):
            gate, up, down = (
                Table((held, a, b), name=name)().astype(self.dtype)
                for name, a, b in (("experts_gate", d, self.expert_dim),
                                   ("experts_up", d, self.expert_dim),
                                   ("experts_down", self.expert_dim, d)))
        y, hidden_live = routed_experts(
            xf, weights, slot_of_row, row_of_slot, load, gate, up, down,
            block, self.activation)
        if hidden_live is not None:
            self.sow("intermediates", "expert_hidden_live", hidden_live)

        if self.shared_dim:
            with jax.named_scope("shared_expert"):
                y = y + SwiGLU(self.shared_dim, d, dtype=self.dtype,
                               name="shared")(xf)
        return y.astype(self.dtype).reshape(r, t, d)
