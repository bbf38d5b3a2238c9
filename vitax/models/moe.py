"""Mixture-of-Experts MLP with expert parallelism over the "ep" mesh axis.

Capability beyond the reference (SURVEY.md section 2.3 lists EP as absent —
the reference's ViT is dense). TPU-first formulation is the GShard/Switch
einsum form: routing produces a (tokens, experts, capacity) combine tensor,
dispatch and combine are einsums, and the expert weights carry a leading
(E, ...) dim sharded over "ep" (vitax/parallel/sharding.py). GSPMD then
inserts the batch<->expert all-to-alls from the shardings alone — no manual
collectives, same stance as the FSDP core. The "ep" mesh axis also carries
batch (vitax/parallel/mesh.py): dense params are replicated over it like dp,
expert weights stay local to their shard.

Design choices (Switch Transformer, arXiv:2101.03961):
- top-1 routing with probabilities in float32;
- static per-group capacity C = ceil(capacity_factor * N / E) (group = one
  sample's N tokens) — XLA-friendly static shapes; tokens over capacity are
  dropped (their MoE contribution is zero; the block residual passes them
  through);
- auxiliary load-balance loss E * sum_e(frac_tokens_e * mean_prob_e), sown
  into the "intermediates" collection and added to the CE loss with weight
  --moe_aux_weight (vitax/train/step.py).

Dispatch and combine are the GShard (B, N, E, C) one-hot einsums, the combine
tensor built directly in the activation dtype (identical numerics: disjoint
top-2 slots never accumulate; half the HBM bytes). A second arm, an integer
scatter of slot indices with `take_along_axis` gathers and no (B, N, E, C)
tensor, measured 477-527 img/s on b16_moe against the einsums' 617-650 on v5e
(round 5: the one-hot matmuls map onto the MXU, batched row gathers do not)
and is gone with its `--moe_impl gather` switch (PR 47).
"""

from __future__ import annotations

import math

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from vitax.models.vit import Array, Dtype, default_init


class MoeMlp(nn.Module):
    """Drop-in replacement for the block Mlp: Dense->GELU->Dense per expert,
    top-1 routed. (B, N, D) -> (B, N, D)."""

    num_experts: int
    hidden_dim: int
    out_dim: int
    capacity_factor: float = 1.25
    top_k: int = 1                  # 1 = Switch; 2 = GShard-style top-2
    # manual expert parallelism (the pipeline body, where every batch axis is
    # already manual inside jax.shard_map and GSPMD cannot see the einsums):
    # ep_axis names the mesh axis; expert params are declared at their LOCAL
    # (E/ep_size, ...) shard shape and two tiled all_to_alls exchange
    # batch<->experts around the expert einsums — the hand-written form of
    # the a2a pair GSPMD derives from dispatch_sharding on the scan path.
    # The GLOBAL param tree keeps its (E, ...) shape (the shard_map in_specs
    # carry the "ep" placement), so checkpoints stay topology-independent.
    ep_axis: Optional[str] = None
    ep_size: int = 1
    dtype: Dtype = jnp.bfloat16
    # NamedSharding for the (E, B, C, D) dispatched tensor: P("ep", batch...)
    # anchors GSPMD so the dispatch/combine einsums lower to all-to-alls
    # instead of the partitioner's "involuntary full rematerialization"
    dispatch_sharding: Optional[Any] = None
    # NamedSharding for (B, N, D) activations: the combine einsum's output is
    # anchored back to the block's token layout so the residual add and the
    # next block see the batch-sharded form, not an expert-flavored remnant
    token_sharding: Optional[Any] = None

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True) -> Array:
        del deterministic  # no dropout inside the MoE MLP (v1)
        b, n, d = x.shape
        e = self.num_experts
        c = max(1, math.ceil(self.capacity_factor * n / e))  # static

        # --- router (float32 end to end: small and stability-critical) ---
        logits = nn.Dense(
            e, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=default_init, bias_init=nn.initializers.zeros,
            name="router",
        )(x.astype(jnp.float32))                      # (B, N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate1 = jnp.max(probs, axis=-1)               # (B, N)
        expert1 = jnp.argmax(probs, axis=-1)          # (B, N) int
        onehot1 = jax.nn.one_hot(expert1, e, dtype=jnp.float32)  # (B, N, E)

        # --- load-balance aux loss ingredients (Switch eq. 4-6; GShard uses
        # the same first-choice fractions under top-2). frac and prob are
        # sown SEPARATELY (not pre-multiplied into the aux scalar): they are
        # linear in the tokens, so per-microbatch means average exactly to
        # the full-batch means — the GPipe pipeline combines them across
        # microbatches before the nonlinear product and its aux matches the
        # scan path's bit-for-bit (vitax/parallel/pipeline.py,
        # vitax/train/step.py:aux_from_frac_prob) ---
        frac_tokens = jnp.mean(onehot1, axis=(0, 1))            # (E,)
        mean_prob = jnp.mean(probs, axis=(0, 1))                # (E,)
        self.sow("intermediates", "moe_frac_tokens", frac_tokens)
        self.sow("intermediates", "moe_mean_prob", mean_prob)

        # --- capacity assignment: slot = rank of the token among those
        # routed to the same expert within its (sample) group; under top-2,
        # ALL first choices rank before ALL second choices (GShard order) ---
        def slots_of(onehot, offset):
            position = jnp.cumsum(onehot, axis=1) * onehot      # (B, N, E)
            per_expert = position + offset * onehot             # rank incl. offset
            slot = (jnp.sum(per_expert, axis=-1) - 1.0).astype(jnp.int32)
            return slot, slot < c                               # (B, N) each

        def combine_of(gate, keep, onehot, slot):
            # combine[b, n, e, c] = gate at the token's (expert, slot).
            # Built directly in the ACTIVATION dtype: the old path built it
            # f32 and cast at the einsum — identical numerics (the gate
            # rounds to bf16 either way, and top-1/top-2 combines have
            # disjoint nonzero slots, so their sum never accumulates in
            # bf16) at HALF the HBM traffic on the largest MoE tensors
            # (the round-4 profile's 20.3% HBM-bound band).
            return ((gate * keep).astype(self.dtype)[:, :, None, None]
                    * onehot.astype(self.dtype)[:, :, :, None]
                    * jax.nn.one_hot(slot, c,
                                     dtype=self.dtype)[:, :, None, :])

        if self.top_k == 1:
            slot1, keep1 = slots_of(onehot1, 0.0)
            choices = [(gate1, keep1, onehot1, slot1)]
        else:
            assert self.top_k == 2, self.top_k
            probs2 = probs * (1.0 - onehot1)          # mask the first choice
            gate2 = jnp.max(probs2, axis=-1)
            expert2 = jnp.argmax(probs2, axis=-1)
            onehot2 = jax.nn.one_hot(expert2, e, dtype=jnp.float32)
            # renormalize the two gates (GShard: g_i = p_i / (p1 + p2))
            denom = gate1 + gate2 + 1e-9
            g1, g2 = gate1 / denom, gate2 / denom
            slot1, keep1 = slots_of(onehot1, 0.0)
            # second choices queue behind every first choice of that expert
            count1 = jnp.sum(onehot1, axis=1, keepdims=True)    # (B, 1, E)
            slot2, keep2 = slots_of(onehot2, count1)
            choices = [(g1, keep1, onehot1, slot1),
                       (g2, keep2, onehot2, slot2)]

        manual_ep = self.ep_axis is not None and self.ep_size > 1
        e_p = e // self.ep_size if manual_ep else e  # local expert shard
        w1 = self.param("w1", default_init, (e_p, d, self.hidden_dim), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e_p, self.hidden_dim), jnp.float32)
        w2 = self.param("w2", default_init, (e_p, self.hidden_dim, self.out_dim), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e_p, self.out_dim), jnp.float32)

        combine = sum(combine_of(g, k, oh, s)
                      for g, k, oh, s in choices)               # (B, N, E, C)
        dispatch = (combine > 0).astype(self.dtype)
        # dispatch -> per-expert batches (GShard einsum form)
        xe = jnp.einsum("bnec,bnd->ebcd", dispatch,
                        x.astype(self.dtype))                   # (E, B, C, D)
        if self.dispatch_sharding is not None:
            xe = jax.lax.with_sharding_constraint(xe, self.dispatch_sharding)
        if manual_ep:
            # each shard dispatched its LOCAL batch to all E experts; keep
            # this shard's E/ep experts for the whole group's batches:
            # (E, B, C, D) -> (E/ep, B*ep, C, D)
            xe = jax.lax.all_to_all(xe, self.ep_axis, 0, 1, tiled=True)
        h = jnp.einsum("ebcd,edh->ebch", xe, w1.astype(self.dtype))
        h = h + b1.astype(self.dtype)[:, None, None, :]
        h = nn.gelu(h, approximate=False)
        ye = jnp.einsum("ebch,eho->ebco", h, w2.astype(self.dtype))
        ye = ye + b2.astype(self.dtype)[:, None, None, :]
        if manual_ep:
            # inverse exchange: back to (E, B, C, D) in original batch order
            # (autodiff transposes each a2a into its inverse)
            ye = jax.lax.all_to_all(ye, self.ep_axis, 1, 0, tiled=True)
        if self.dispatch_sharding is not None:
            ye = jax.lax.with_sharding_constraint(ye, self.dispatch_sharding)
        out = jnp.einsum("bnec,ebcd->bnd", combine, ye)
        if self.token_sharding is not None:
            out = jax.lax.with_sharding_constraint(out, self.token_sharding)
        return out
