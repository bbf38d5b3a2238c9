"""What the four decoder-family files share (tests/test_decoder.py,
test_latent_decoder.py, test_hybrid_decoder.py, test_olmo_decoder.py): the
packed batch, the seeded weights, one tiny model against its plain float32
reference, built once a module, and the equations of a traced program (what
the remat policy is asked, and where a kernel stands).

The rule of the test tree: a test calls compiled programs. Every `init`,
`apply`, train step, reference call and gradient here goes through one
`jax.jit`; outside one, each `jnp` operation is a program of its own to
compile, a few hundred a model. What several cases of a file need is computed
on first use and kept (`functools.cached_property`), so a module-scoped
fixture that builds a `DecoderCase` hands every case the same compiled
programs and the same results."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.laguna import relative_gap, unpack
from vitax.config import parse_config
from vitax.data.packing import document_layout
from vitax.models import decoder
from vitax.programs.kernels import Kernels
from vitax.train.step import decoder_inputs, decoder_loss


def make_batch(cfg, lengths, seed=0, rows_held=None):
    """Documents of `lengths` (a list a row) packed as the loader packs them,
    ids drawn below `rows_held` (default: every vocabulary row)."""
    lay = document_layout(lengths, cfg.pack_tokens, cfg.pack_images)
    ids = np.random.default_rng(seed).integers(
        0, rows_held or cfg.vocab_rows,
        lay["segment_ids"].shape).astype(np.int32)
    return {"tokens": jnp.asarray(ids * (lay["segment_ids"] > 0)),
            **{k: jnp.asarray(v) for k, v in lay.items()}}


@functools.partial(jax.jit, static_argnames=("key", "by"))
def moved(tree, key=2, by=0.05):
    """Every leaf moved off its initial value (a router's bias off zero too),
    so that a reference that dropped a scale, a gate, a bias or D would not
    agree."""
    leaves, struct = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    return jax.tree.unflatten(struct, [
        a + by * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])


def seeded(model, cfg):
    """The model's seeded weights, moved: one program."""
    return jax.jit(lambda: moved(model.init(
        jax.random.key(0), decoder.sample_documents(cfg, 1), True)))()


def loss_grads_and_logits(model, batch):
    """The program whose one call gives ((loss, logits), gradients) of
    `model` on `batch` from its variables."""
    def loss(variables):
        logits = model.apply(variables, batch, True)
        return decoder_loss(logits, batch), logits
    return jax.jit(jax.value_and_grad(loss, has_aux=True))


class DecoderCase:
    """One tiny configuration: the model, its seeded weights and its batch,
    the program's logits, loss and gradients, and the reference's.
    `reference`: the module under benchmark/reference; `shape`: the keywords
    its functions take after the parameters and the ids."""

    def __init__(self, cfg, reference, shape, lengths):
        self.cfg, self.reference, self.shape = cfg, reference, shape
        self.lengths = lengths
        self.model = decoder.build_decoder(cfg)
        self.batch = make_batch(cfg, lengths)

    @functools.cached_property
    def variables(self):
        return seeded(self.model, self.cfg)

    @functools.cached_property
    def program(self):
        """`variables -> ((loss, logits), gradients)`, compiled once."""
        return loss_grads_and_logits(self.model, self.batch)

    @functools.cached_property
    def _seeded_run(self):
        return self.program(self.variables)

    @property
    def logits(self):
        """(rows, tokens, vocabulary rows) as numpy."""
        return np.asarray(self._seeded_run[0][1])

    @property
    def loss_and_grads(self):
        (loss, _), grads = self._seeded_run
        return loss, grads

    @functools.cached_property
    def documents(self):
        return [jnp.asarray(d) for d in unpack(
            np.asarray(self.batch["tokens"]),
            np.asarray(self.batch["segment_ids"]))]

    @functools.cached_property
    def reference_logits(self):
        """`(variables, ids) -> logits` of one document alone, compiled once
        a length."""
        def alone(variables, ids):
            with jax.default_matmul_precision("highest"):
                return self.reference.logits(variables, ids, **self.shape)
        return jax.jit(alone)

    @functools.cached_property
    def plain(self):
        """The reference's (loss, gradients, logits at each document's first
        and last position)."""
        ats = [jnp.asarray([0, len(d) - 1]) for d in self.documents]
        with jax.default_matmul_precision("highest"):
            return self.reference.loss_grads_and_logits(
                self.variables, self.documents, ats, **self.shape)

    @functools.cached_property
    def plain_loss(self):
        """The reference's loss summed document by document, no padding."""
        def loss(variables):
            with jax.default_matmul_precision("highest"):
                return self.reference.loss(variables, self.documents,
                                           **self.shape)
        return jax.jit(loss)(self.variables)

    def check_logits(self, padded):
        """Each document's logits against the reference on that document
        alone; `padded`: followed by zeros it cannot see up to the row's
        length, so that one program serves every document."""
        got, seg = self.logits, np.asarray(self.batch["segment_ids"])
        for r in range(seg.shape[0]):
            for s in range(1, seg[r].max() + 1):
                at = np.where(seg[r] == s)[0]
                ids = self.batch["tokens"][r, at]
                if padded:
                    ids = jnp.pad(ids, (0, seg.shape[1] - len(at)))
                want = self.reference_logits(self.variables, ids)[:len(at)]
                np.testing.assert_allclose(got[r, at], want, rtol=2e-4,
                                           atol=2e-5)

    def check_first_rows(self):
        """The reference's logits at the first document's ends, from the
        program that gave its gradients."""
        first = self.logits[0, [0, self.lengths[0][0] - 1]]
        np.testing.assert_allclose(self.plain[2][0], first, rtol=2e-4,
                                   atol=2e-5)

    def check_float8_control(self, generator, names):
        """The benchmark's control (weights rounded to float8_e4m3 for the
        program, the reference on the seeded ones) is off the reference by
        tens of times what the program is, gradient by gradient. Hands back
        the reference's watched leaves."""
        watched = jax.jit(lambda g: generator.watched_leaves(g, self.cfg))
        want = watched(self.plain[1])
        assert sorted(want) == names
        sound = watched(self.loss_and_grads[1])
        control = watched(self.program(jax.jit(generator.round_to_float8)(
            self.variables))[1])
        for name in want:
            assert relative_gap(sound[name], want[name]) < 2e-3, name
            assert relative_gap(control[name], want[name]) > 2e-2, name
        return want


def check_conv_kernels_match_the_plain_path(cfg, conv, batch, gap):
    """The model with its mixers' convolution forced to the kernel pair
    `conv` (interpret mode) against the plain path: logits, loss and every
    leaf's gradient."""
    models = [decoder.build_decoder(cfg),
              decoder.build_decoder(cfg, kernels=Kernels(conv=conv))]
    variables = seeded(models[0], cfg)
    want, got = (loss_grads_and_logits(m, batch)(variables) for m in models)
    np.testing.assert_allclose(got[0][0], want[0][0], rtol=1e-6)
    np.testing.assert_allclose(got[0][1], want[0][1], rtol=2e-4, atol=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want[1]),
                            jax.tree.leaves(got[1])):
        assert gap(b, a) < 2e-4, jax.tree_util.keystr(path)


def assembled(cfg):
    """(geometry, materialized state, the compiled train step) on one
    device."""
    from vitax.programs.builder import Geometry, build_program
    geom = Geometry.assemble(cfg, 100, materialize=True,
                             devices=jax.devices()[:1])
    state, geom.state = geom.state, None
    return geom, state, build_program("train", geom)


def take_steps(step, state, batch, n):
    """`n` steps on one batch: (state, the last step's metrics, losses)."""
    losses = []
    for _ in range(n):
        state, m = step(state, batch, jax.random.key(1))
        losses.append(float(m["loss"]))
    return state, m, losses


def check_first_steps_moments(generator, cfg, batch, clipped):
    """What the benchmark holds the TIMED step to: the gradients read from
    the optimizer state its first call left (`step_gradients`) are the
    model's own, with the clip at work or without. Hands back (the geometry,
    the step, the state after its first call, that call's metrics)."""
    geom, state, step = assembled(cfg)
    want = generator.watched_leaves(jax.jit(jax.grad(
        lambda v: decoder_loss(geom.model.apply(
            v, decoder_inputs(batch), True), batch)))(state.params), cfg)
    state, m = step(state, batch, jax.random.key(1))
    norm = float(m["grad_norm"])
    assert (norm > cfg.clip_grad_norm) == clipped
    got = generator.step_gradients(state.opt_state, norm, cfg)
    assert sorted(got) == sorted(want)
    for name in want:
        assert relative_gap(got[name], want[name]) < 1e-5, name
    return geom, step, state, m


def equations(jaxpr, path=()):
    """(the primitives it sits under, the equation) for every equation of a
    jaxpr and of the jaxprs inside it, in order."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner,
                                         path + (eqn.primitive.name,))


def kernel_name(eqn):
    """A `pallas_call` equation's name, where this JAX puts it."""
    return eqn.params.get("name") or ""


def kernels_traced(fn, *args):
    """(path, equation) of every `pallas_call` in `fn`'s trace on `args`
    (shapes do: nothing runs)."""
    return [(path, eqn)
            for path, eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def kept(policy, eqn):
    """What `jax.checkpoint` asks a policy of an equation: whether its
    outputs may be kept for the backward."""
    return policy(eqn.primitive, *(v.aval for v in eqn.invars), **eqn.params)


def check_the_policy_keeps_by_the_traced_name(model, cfg, policy, name):
    """The policy on the equations of a TRACED forward of `model` (built
    through the kernels), not on a hand-made one: it keeps the attention
    forward kernel `name` where this JAX writes that name, and refuses a
    `pallas_call` of another name and a `dot_general`."""
    from jax.experimental import pallas as pl
    batch = decoder.sample_documents(cfg, 1)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), batch,
                                               True))
    traced = [eqn for _, eqn in equations(jax.make_jaxpr(
        lambda v: model.apply(v, batch, True))(shapes).jaxpr)]
    forwards = [eqn for eqn in traced if eqn.primitive.name == "pallas_call"
                and kernel_name(eqn) == name]
    assert forwards, name
    assert all(kept(policy, eqn) for eqn in forwards)
    dots = [eqn for eqn in traced if eqn.primitive.name == "dot_general"]
    assert dots and not any(kept(policy, eqn) for eqn in dots)

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    [(_, other)] = kernels_traced(pl.pallas_call(
        copy, out_shape=x, name="grouped_matmul"), x)
    assert kernel_name(other) == "grouped_matmul"
    assert not kept(policy, other)


def family_declares(name):
    """The shape fields benchmark/shapes/<name>.json declares; none is a
    knob."""
    from benchmark import forms
    from benchmark import manifest as mf
    keys = forms.declared_keys(mf.read_json(
        os.path.join(mf.BENCH_DIR, "shapes", name + ".json")))
    assert not keys & forms.knob_keys(forms.rules())
    return keys


def train_through_the_cli(tmp_path, *shape_flags):
    """`python -m vitax.train --fake_data --model_family decoder` with
    `shape_flags` (the flags through `parse_config`, then the loop the entry
    point calls): three steps of 8 rows, a falling finite loss, a checkpoint.
    Hands back (the parsed configuration, the step records)."""
    from vitax.train.loop import train
    cfg = parse_config((
        "--fake_data", "--model_family", "decoder", *shape_flags,
        "--batch_size", "8", "--num_epochs", "1", "--steps_per_epoch", "3",
        "--lr", "3e-3", "--log_step_interval", "1", "--warmup_steps", "1",
        "--ckpt_dir", str(tmp_path / "ckpt"),
        "--metrics_dir", str(tmp_path / "metrics")))
    train(cfg)
    with open(tmp_path / "metrics" / "metrics.jsonl") as f:
        steps = [r for r in map(json.loads, f) if "kind" not in r]
    losses = [r["loss"] for r in steps]
    assert len(losses) == 3 and np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert all(0.0 <= r["padding_frac"] < 1.0 for r in steps)
    assert (tmp_path / "ckpt" / "epoch_1").exists()
    return cfg, steps
