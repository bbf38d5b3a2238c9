"""Why the Ling cell's gradients sit a tenth or more off the float32 reference.

The cell `ling3_flash_vl_ep64tp2_train_packed4k` (benchmark/generators/
train_latent_packed.py) holds the timed step's gradients to the plain
reference element by element. On the chip the router and the held experts'
gate matrices of the first sparse layer read 0.2-0.4, the delta-rule leaves
about 0.1. This tool reads, on the cell's own weights and batch of `--seed`:

1. **The choices.** The experts each token chose, layer by layer, in the
   program (bf16, as the step runs it) and in the reference (float32): the
   tokens whose sets differ, and the (token, held expert) slots that only
   one side routes here. The gradients of a sparse layer's router and held
   experts are sums over those slots, so a slot that one side lacks is a
   whole term of the sum. The program's choices are those of the very
   forward pass whose gradients are compared: another compiled program of
   the same model rounds otherwise and other tokens sit on the edge.
2. **The gaps with those tokens routed nowhere** on BOTH sides (`masked`:
   their chosen experts replaced by ones no chip holds here, in every
   sparse layer): what is left is rounding alone.
3. **The gaps with float32 where the program rounds**: `hi`, the bf16
   program under `jax.default_matmul_precision("highest")` outside the
   attention kernels and the grouped expert products (a float32 cotangent
   times a bf16 operand and the float32 router are float32 products, which
   the TPU multiplies in one bf16 pass by default and the CPU in float32);
   `kda32`, the delta rule's chunked core on float32 operands; `f32`, the
   whole program in float32 (on the CPU only, where the latent layer's
   attention is the dense form: the `flash_latent_*` kernels' tiles are
   sized for bf16 operands and ask 132 MB of the v5e's 128 MB of VMEM on
   float32 ones).

Each arm also splits the kda leaves' gaps by layer (the cell compares them
over all six kda layers at once).

`--rehearse` runs the same control flow at the tiny shapes on the CPU;
`--describe` compiles each variant for a described v5e chip and runs
nothing; with neither, the cell's own size runs on whatever JAX finds (on
this sandbox's CPU the `f32` arm alone takes about half an hour). On the
chip (about 12 chip-minutes with nothing cached; `bf16,masked` alone about 8):

    chiprun --timeout 1500 -- python3 tools/ling_gap_witness.py --seed 4300000331

The result is one JSON object on stdout and in
`chiprun_out/ling_gap_witness.<seed>[.cpu].json`, written arm by arm.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "ling3_flash_vl_ep64tp2_train_packed4k"
VARIANTS = ("bf16", "masked", "hi", "kda32", "f32")


class Hook:
    """What the patched choices read while a program is traced."""
    mask = None         # (tokens,) bool: False routes the token nowhere here
    sow = False         # the program: sow every layer's choice
    record = None       # the reference: a list that takes every layer's


def not_held(scores, k):
    """`k` experts at the far end of the router's outputs: held by no chip
    of this cell (it holds the first ones)."""
    import jax.numpy as jnp
    return scores.shape[-1] - 1 - jnp.arange(k)


def patch_choices(held):
    import jax.numpy as jnp
    from flax.linen import module as flax_module
    from benchmark.reference import ling as reference
    from vitax.models import experts
    first, count = held
    program_choose, reference_choose = experts.choose, reference.chosen_experts

    def reroute(scores, chosen, k):
        far = not_held(scores, k)
        assert scores.shape[-1] - k >= first + count, "no expert is far"
        return jnp.where(Hook.mask[:, None], chosen, far)

    def choose(scores, bias, k, groups, groups_kept):
        top, chosen, kept = program_choose(scores, bias, k, groups,
                                           groups_kept)
        if Hook.sow:
            flax_module._context.module_stack[-1].sow(
                "intermediates", "chosen", chosen)
        if Hook.mask is not None:
            chosen = reroute(scores, chosen, k)
            top = jnp.take_along_axis(scores, chosen, axis=-1)
        return top, chosen, kept

    def chosen_experts(scores, bias, *, top_k, groups, groups_kept):
        chosen = reference_choose(scores, bias, top_k=top_k, groups=groups,
                                  groups_kept=groups_kept)
        if Hook.record is not None:
            Hook.record.append(chosen)
        if Hook.mask is not None:
            chosen = reroute(scores, chosen, top_k)
        return chosen

    experts.choose, reference.chosen_experts = choose, chosen_experts


@contextlib.contextmanager
def float32_delta_rule():
    """The chunked core of vitax/models/kda.py on float32 operands."""
    import jax
    import jax.numpy as jnp
    from vitax.models import kda as module
    plain = module.kda

    def core(q, k, v, g, beta, segment_ids, chunk, sub, dtype):
        with jax.default_matmul_precision("highest"):
            return plain(q, k, v, g, beta, segment_ids, chunk, sub,
                         jnp.float32)

    module.kda = core
    try:
        yield
    finally:
        module.kda = plain


@contextlib.contextmanager
def highest_outside_kernels():
    """`jax.default_matmul_precision("highest")` for every product but the
    packed attention kernels' and the experts' grouped ones, which keep the
    default: Mosaic, which the compiler's `ragged-dot` kernels go through
    too, takes no float32-precision product of a float32 and a bf16
    operand."""
    import jax
    from vitax.ops import flash_blocked as module
    plain = module._packed_fwd, module._packed_bwd, jax.lax.ragged_dot

    def at_default(fn):
        def kernel(*args, **kwargs):
            with jax.default_matmul_precision("default"):
                return fn(*args, **kwargs)
        return kernel

    module._packed_fwd, module._packed_bwd, jax.lax.ragged_dot = map(
        at_default, plain)
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        module._packed_fwd, module._packed_bwd, jax.lax.ragged_dot = plain


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4300000331)
    ap.add_argument("--variants", default="bf16,masked,hi,kda32")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe", action="store_true")
    args = ap.parse_args()
    variants = [v for v in args.variants.split(",") if v]
    assert set(variants) <= set(VARIANTS), variants

    from benchmark import manifest as mf
    man = mf.Manifest()
    cell = man.cell(CELL)
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    if args.rehearse or args.describe:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.rehearse:
        mf.apply_rehearsal(config, traffic, man.family(config["family"]))
    out, sys.stdout = sys.stdout, sys.stderr

    from vitax.platform import setup_compile_cache
    setup_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.generators import train_latent_packed as gen
    from benchmark.reference import ling as reference
    from vitax.parallel.sharding import make_comm_precision
    from vitax.programs.builder import Geometry, abstract_batch
    from vitax.train.step import decoder_inputs, decoder_loss

    cfg = gen.build_config(man.config_kwargs(config), traffic, 1, args.seed)
    held = (cfg.expert_first, cfg.experts_held)
    patch_choices(held)
    result = {"seed": args.seed, "rehearse": args.rehearse,
              "device": jax.devices()[0].device_kind}
    path = os.path.join("chiprun_out", "ling_gap_witness.{}{}.json".format(
        args.seed, "" if jax.devices()[0].platform == "tpu" else ".cpu"))

    def report():
        os.makedirs("chiprun_out", exist_ok=True)
        with open(path, "w") as f:
            json.dump(result, f, indent=1)

    def assembled(cfg, **kwargs):
        geom = Geometry.assemble(cfg, gen.MAX_ITERATION, **kwargs)
        return geom, make_comm_precision(cfg, geom.mesh,
                                         geom.state_specs.params)

    def program(geom, comm, cfg):
        """jit((params, batch, mask) -> (the watched gradients, the (sparse
        layers, tokens, K) experts this very program's forward chose))."""
        def loss(params, batch, mask):
            Hook.mask, Hook.sow = mask, True
            try:
                cast = comm.cast(params) if comm is not None else params
                logits, cols = geom.model.apply(
                    cast, decoder_inputs(batch), True,
                    mutable=["intermediates"])
            finally:
                Hook.mask, Hook.sow = None, False
            leaves = [leaf for at, leaf in
                      jax.tree_util.tree_leaves_with_path(cols)
                      if any(getattr(k, "key", None) == "chosen" for k in at)]
            return decoder_loss(logits, batch), jnp.concatenate(
                [x.reshape(-1, *x.shape[-2:]) for x in leaves])

        def watched(params, batch, mask):
            grads, chosen = jax.grad(loss, has_aux=True)(params, batch, mask)
            return gen.watched_leaves(grads, cfg), chosen

        return jax.jit(watched)

    cfg32 = dataclasses.replace(cfg, dtype="float32").validate()

    highest = highest_outside_kernels

    # variant -> (Config, the contexts its program is traced and run under)
    arms = {"bf16": (cfg, ()), "masked": (cfg, ()), "hi": (cfg, (highest,)),
            "kda32": (cfg, (float32_delta_rule,)), "f32": (cfg32, (highest,))}

    if args.describe:
        from jax.experimental import topologies
        devices = list(topologies.get_topology_desc("v5e:2x2", "tpu").devices)
        for name in variants:
            if name == "masked":
                continue
            arm_cfg, contexts = arms[name]
            geom, comm = assembled(arm_cfg, devices=devices[:1],
                                   force_tpu_kernels=True)
            batch = abstract_batch(arm_cfg, geom.mesh)
            mask = jax.ShapeDtypeStruct((cfg.pack_tokens,), jnp.bool_)
            t0 = time.time()
            try:
                with contextlib.ExitStack() as stack:
                    for c in contexts:
                        stack.enter_context(c())
                    mem = program(geom, comm, arm_cfg).lower(
                        geom.abstract_state.params, batch, mask
                    ).compile().memory_analysis()
                result[name] = {
                    "compile_s": time.time() - t0,
                    "gb": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes) / 1e9}
            except Exception as e:
                result[name] = {"error": f"{type(e).__name__}: {e}"[:600]}
            print(name, result[name], flush=True)
        print(json.dumps(result), file=out)
        return 0

    t0 = time.time()
    geom, comm = assembled(cfg, materialize=True)
    params = geom.state.params
    for leaf in jax.tree.leaves(geom.state.opt_state):
        leaf.delete()
    batch = gen.make_inputs(cfg, geom.mesh, args.seed,
                            gen.layout(cfg, traffic["rows"], 1))
    host = jax.device_get(batch)
    seg = np.asarray(host["segment_ids"])
    assert seg.shape[0] == 1, "one row a chip"
    docs = reference.unpack(host["tokens"], seg)
    starts = [int(np.argmax(seg[0] == s)) for s in range(1, len(docs) + 1)]
    longest = max(len(d) for d in docs)
    valid = seg[0] > 0
    result["state_s"] = time.time() - t0

    # --- 1. the choices ----------------------------------------------------
    shape = reference.shape_of(config)

    def reference_choices(params, ids):
        Hook.record = []
        try:
            reference.hidden(params, ids, experts_held=held, **shape)
            return jnp.stack(Hook.record)
        finally:
            Hook.record = None

    def rows_of(per_doc):
        """(layers, tokens, K) in the row's positions from one array a
        document, each followed by the zeros it was run with."""
        row = np.zeros((per_doc[0].shape[0], seg.shape[1],
                        per_doc[0].shape[-1]), np.int64)
        for at, doc, got in zip(starts, docs, per_doc):
            row[:, at:at + len(doc)] = np.asarray(got)[:, :len(doc)]
        return row

    def compare(got, want):
        """Per sparse layer: tokens whose chosen sets differ, and the
        (token, held expert) slots of either side. -> (rows, the tokens
        whose held slots differ in any layer)."""
        first, count = held
        rows, differ = [], np.zeros(seg.shape[1], bool)
        for mine, theirs in zip(np.sort(got, -1), np.sort(want, -1)):
            def here(chosen):
                return (chosen[:, :, None] == first + np.arange(count)
                        ).any(1) & valid[:, None]           # (tokens, held)
            a, b = here(mine), here(theirs)
            moved = (a != b).any(1)
            differ |= moved
            rows.append({
                "tokens_whose_set_differs": int(
                    ((mine != theirs).any(1) & valid).sum()),
                "held_slots_both": int((a & b).sum()),
                "held_slots_program_only": int((a & ~b).sum()),
                "held_slots_reference_only": int((~a & b).sum()),
                "tokens_whose_held_slots_differ": int(moved.sum())})
        return rows, differ

    t0 = time.time()
    with jax.default_matmul_precision(reference.PRECISION):
        one = jax.jit(reference_choices)
        want_choice = rows_of([one(params, jnp.pad(
            jnp.asarray(d), (0, longest - len(d)))) for d in docs])
    result["valid_tokens"] = int(valid.sum())
    result["reference_choices_s"] = time.time() - t0

    # --- 2. and 3. the gradients ---------------------------------------------
    def reference_watched(keep):
        """The reference's watched gradients, one document at a time (as
        `reference.loss_grads_and_logits` runs them), `keep` (tokens,) bool
        or None."""
        targets = sum(len(d) - 1 for d in docs)
        none = jnp.zeros((0,), jnp.int32)

        def one(acc, params, ids, n, mask):
            Hook.mask = mask
            try:
                grads = jax.grad(lambda p: reference.ce_sum_and_logits(
                    p, ids, none, n, True, experts_held=held, **shape)[0])(
                        params)
            finally:
                Hook.mask = None
            return jax.tree.map(jnp.add, acc, gen.watched_leaves(grads, cfg))

        one = jax.jit(one, donate_argnums=(0,))
        acc = jax.tree.map(jnp.zeros_like, jax.eval_shape(
            lambda p: gen.watched_leaves(p, cfg), params))
        keep = np.ones(seg.shape[1], bool) if keep is None else keep
        with jax.default_matmul_precision(reference.PRECISION):
            for at, doc in zip(starts, docs):
                mask = np.zeros(longest, bool)
                mask[:len(doc)] = keep[at:at + len(doc)]
                acc = one(acc, params, jnp.pad(
                    jnp.asarray(doc), (0, longest - len(doc))),
                    jnp.asarray(len(doc), jnp.int32), jnp.asarray(mask))
        return {k: np.asarray(v) / targets
                for k, v in jax.device_get(acc).items()}

    t0 = time.time()
    want = reference_watched(None)
    result["reference_s"] = time.time() - t0
    everyone, differ, bf16 = np.ones(seg.shape[1], bool), None, None
    layers = cfg.layer_kinds.count("kda")
    for name in variants:
        arm_cfg, contexts = arms[name]
        t0 = time.time()
        try:
            ref, keep = want, everyone
            if name == "masked":
                assert differ is not None, "the masked arm follows bf16"
                ref, keep = reference_watched(~differ), ~differ
            with contextlib.ExitStack() as stack:
                for c in contexts:
                    stack.enter_context(c())
                if name in ("bf16", "masked"):
                    bf16 = bf16 or program(geom, comm, cfg)
                    run = bf16
                else:
                    run = program(*assembled(arm_cfg), arm_cfg)
                got, chosen = jax.device_get(run(params, batch,
                                                 jnp.asarray(keep)))
            # the choices of THIS program's own forward pass against the
            # reference's (under the mask: of the tokens still routed)
            rows, moved = compare(np.where(keep[None, :, None], chosen, -1),
                                  np.where(keep[None, :, None], want_choice,
                                           -1))
            if name == "bf16":
                differ = moved

            def by_layer(k):    # a kda leaf holds its layers in turn
                return [reference.relative_gap(a, b) for a, b in zip(
                    np.split(got[k], layers), np.split(ref[k], layers))]

            result[name] = {
                "seconds": time.time() - t0,
                "tokens_routed_nowhere": int((~keep & valid).sum()),
                "leaf_gaps": {k: reference.relative_gap(got[k], ref[k])
                              for k in sorted(ref)},
                "kda_gaps_by_layer": {k: by_layer(k) for k in sorted(ref)
                                      if k.startswith("kda.")},
                "choices": rows}
        except Exception as e:      # an arm the compiler refuses: go on
            result[name] = {"error": f"{type(e).__name__}: {e}"[:600]}
        report()
        print(name, json.dumps(result[name]), flush=True)
    print(json.dumps(result), file=out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
