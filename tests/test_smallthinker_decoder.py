"""The sixth decoder shape (SmallThinker: one full layer that rotates nothing
to three sliding layers that rotate the whole head, 7 query heads a key/value
head, a router that reads the layer's FIRST norm's output and takes a softmax
over the chosen logits, ReLU-gated experts, an untied head;
vitax/models/decoder.py, experts.py) at small sizes on the CPU, seeded
weights: the program against the plain reference
(benchmark/reference/smallthinker.py) for the whole 4-layer model in float32
and in bf16 beside a float8 control, the eight shares of the experts tied to
the uncut layer, the ReLU rule of `routed_experts` against autodiff and the
silu rule against what it was, where the router's cotangent lands, rotation
by layer kind, the count of live hidden units, the closed-form parameter
count, the step's counters, the flags and the loop."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker as reference
from tests import decoder_cases as cases
from vitax.config import Config
from vitax.models import decoder, experts
from vitax.models.experts import SharedRoutedExperts

KINDS = ["full_attention"] + ["sliding_attention"] * 3
# row 0: two documents, the first longer than the window of 8
LENGTHS = [[30, 14], [20, 17, 9]]
TINY = dict(
    model_family="decoder", embed_dim=32, num_blocks=4, vocab_rows=48,
    kv_heads=2, head_size=8, layer_kinds=KINDS, layer_heads=[14] * 4,
    layer_mlps=["sparse"] * 4, window_tokens=8, expert_dim=24,
    experts_routed=16, experts_held=8, expert_first=0, experts_per_token=3,
    norm_eps=1e-6, rope_fraction_full=0.0, rope_theta_window=1.5e6,
    route_form="softmax_chosen", route_early=True, expert_activation="relu",
    pack_tokens=48, pack_images=3, batch_size=2, dtype="float32")
# the configuration of the benchmark's cell under the program's names
SMALLTHINKER = dict(
    model_family="decoder", embed_dim=2560, num_blocks=4, vocab_rows=18992,
    kv_heads=4, head_size=128, layer_kinds=KINDS, layer_heads=[28] * 4,
    layer_mlps=["sparse"] * 4, window_tokens=4096, expert_dim=768,
    experts_routed=64, experts_held=8, expert_first=0, experts_per_token=6,
    norm_eps=1e-6, rope_fraction_full=0.0, rope_theta_window=1500000,
    rope_fraction_window=1.0, route_form="softmax_chosen", route_early=True,
    expert_activation="relu", pack_tokens=16384, pack_images=4, batch_size=1)


def reference_shape(cfg):
    slides = [int(k == "sliding_attention") for k in cfg.layer_kinds]
    return dict(
        rope_layout=slides, window_layout=slides, heads=cfg.layer_heads[0],
        kv_heads=cfg.kv_heads, head_dim=cfg.head_size,
        window=cfg.window_tokens, eps=cfg.norm_eps,
        theta=cfg.rope_theta_window, top_k=cfg.experts_per_token,
        experts_routed=cfg.experts_routed,
        experts_held=(cfg.expert_first, cfg.experts_held))


class Case:
    """The tiny model, its seeded weights and its batch; the program's loss,
    logits and gradients and the reference's, each computed once."""

    def __init__(self):
        self.cfg = Config(**TINY).validate()
        self.model = decoder.build_decoder(self.cfg)
        self.batch = cases.make_batch(self.cfg, LENGTHS)
        self.variables = cases.seeded(self.model, self.cfg)
        self.program = cases.loss_grads_and_logits(self.model, self.batch)
        (self.loss, self.logits), self.grads = self.program(self.variables)
        self.rows = reference.rows_of(np.asarray(self.batch["tokens"]),
                                      np.asarray(self.batch["segment_ids"]))
        every = jnp.arange(self.cfg.pack_tokens)
        with jax.default_matmul_precision("highest"):
            self.plain = reference.loss_grads_and_logits(
                self.variables, self.rows, [every] * len(self.rows),
                **reference_shape(self.cfg))


@pytest.fixture(scope="module")
def case():
    return Case()


# --- (a) the whole model --------------------------------------------------------

def test_loss_and_logits_match_the_reference(case):
    loss, _, rows = case.plain
    np.testing.assert_allclose(loss, case.loss, rtol=1e-5)
    seg = np.asarray(case.batch["segment_ids"])
    assert seg[0].max() == 2 and (seg[0] == 1).sum() > case.cfg.window_tokens
    got = np.asarray(case.logits)
    assert np.abs(got).max() > 0.2
    for r, want in enumerate(rows):
        real = seg[r] > 0
        np.testing.assert_allclose(got[r][real], np.asarray(want)[real],
                                   rtol=2e-4, atol=2e-5)
    assert float(np.abs(got[seg == 0]).max()) < 10.0      # finite at padding
    # a document alone gives the logits it has inside its row: the document
    # mask and the positions counted from the document's first token
    ids, row_seg = case.rows[0]
    second = np.flatnonzero(seg[0] == 2)
    with jax.default_matmul_precision("highest"):
        alone = jax.jit(lambda v, ids: reference.logits(
            v, ids, jnp.ones_like(ids), **reference_shape(case.cfg)))(
            case.variables, ids[second])
    np.testing.assert_allclose(alone, np.asarray(rows[0])[second], rtol=2e-4,
                               atol=2e-5)


def test_every_gradient_leaf_matches_the_reference(case):
    _, grads, _ = case.plain
    flat = jax.tree_util.tree_leaves_with_path(case.grads)
    # embedding, head and final norm; two runs of 4 attention leaves, 4 of
    # the experts (no bias, no shared expert) and 2 norms
    assert len(flat) == len(jax.tree.leaves(grads)) == 3 + 2 * 10
    for (path, a), b in zip(flat, jax.tree.leaves(grads)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(a))) > 0.0, name
        assert reference.relative_gap(b, a) < 2e-3, name
    np.testing.assert_allclose(*(
        jax.jit(lambda g: reference.global_norm(reference.leaf_norms(g)))(g)
        for g in (grads, case.grads)), rtol=1e-4)


def test_bfloat16_stays_inside_limits_that_a_float8_control_breaks(case):
    """The benchmark's control (weights rounded to float8_e4m3 for the
    program, the reference on the seeded ones) against the program in the
    precision the configuration states, gradient by gradient and on the
    logits: one limit between the two, as the cell's `correct` has."""
    from benchmark.generators.train_early_router_packed import (
        round_to_float8, watched_leaves)
    from vitax.train.step import decoder_loss
    cfg, batch = case.cfg, case.batch
    model = decoder.build_decoder(Config(**{**TINY, "dtype": "bfloat16"}))

    @jax.jit
    def grads_and_logits(v):
        grads = jax.grad(lambda v: decoder_loss(
            model.apply(v, batch, True), batch))(v)
        return watched_leaves(grads, cfg), model.apply(v, batch, True)

    want = jax.jit(lambda g: watched_leaves(g, cfg))(case.plain[1])
    assert sorted(want) == [
        "full.norm1", "full.wk", "full.wq", "layer0.experts_gate",
        "layer0.router", "sliding.norm1", "sliding.wk", "sliding.wq"]
    assert want["full.norm1"].shape == (32,)
    assert want["sliding.wq"].shape == (32, 14 * 8)
    assert want["layer0.experts_gate"].shape == (8, 32, 24)
    (sound, logits), (control, off) = (
        grads_and_logits(case.variables),
        grads_and_logits(jax.jit(round_to_float8)(case.variables)))
    real = np.asarray(batch["segment_ids"][0]) > 0
    rows = np.asarray(case.plain[2][0])[real]
    assert reference.relative_gap(np.asarray(logits[0])[real], rows) < 0.02
    assert reference.relative_gap(np.asarray(off[0])[real], rows) > 0.02
    for name in want:
        # a router's gradient hangs on which tokens chose which expert: a
        # token whose fourth logit lies within the rounding of its third goes
        # elsewhere than in the float32 reference, and so do the gradients of
        # that expert's rows
        limit = 0.128 if name.endswith(("router", "experts_gate")) else 0.07
        assert reference.relative_gap(sound[name], want[name]) < limit, name
        assert reference.relative_gap(control[name], want[name]) > limit, name


def test_the_layer_pattern_and_its_runs(case):
    cfg, p = case.cfg, case.variables["params"]
    assert decoder.layer_runs(cfg.layer_kinds, cfg.layer_heads,
                              cfg.layer_mlps) == [
        (("full_attention", 14, "sparse"), 1),
        (("sliding_attention", 14, "sparse"), 3)]
    assert sorted(p) == ["embed", "lm_head", "norm", "run0", "run1"]
    assert sorted(p["run1"]["blocks"]["attn"]) == ["wk", "wo", "wq", "wv"]
    assert p["run1"]["blocks"]["attn"]["wq"]["kernel"].shape == (3, 32, 112)
    moe = p["run1"]["blocks"]["moe"]
    assert sorted(moe) == ["experts_down", "experts_gate", "experts_up",
                           "router"]            # no bias, no shared expert
    assert moe["router"]["kernel"].shape == (3, 32, 16)


def test_the_scopes_a_metric_reads_are_in_the_lowered_program(case):
    model, variables, batch = case.model, case.variables, case.batch
    text = jax.jit(lambda v: model.apply(v, batch, True)).lower(
        variables).as_text(debug_info=True)
    for scope in ("rope1d", "moe_route", "moe_dispatch", "expert_ffn",
                  "moe_combine", "lm_head_loss"):
        assert f"/{scope}/" in text, scope
    assert "shared_expert" not in text and "head_gate" not in text


def test_remat_keeps_o_and_lse_in_sliding_runs_at_the_cells_window():
    """A window of 4,096 is a span at which PR 30's rule SELECTS the policy
    that keeps o and lse: the first cell whose sliding runs it selects it for
    (what that policy then keeps: the witness further down), and the first
    with a kept run of several layers, the only length the policy is given
    at (`run_remat_policy`)."""
    from vitax.models.vit import ATTN_KEEP_MIN_SPAN
    from vitax.programs.kernels import Kernels
    from vitax.train.loop import _attention_remat_note
    real_cfg = Config(**SMALLTHINKER).validate()
    real = decoder.build_decoder(real_cfg,
                                 kernels=Kernels(attention=lambda *a: a[0]))
    assert real.span("sliding_attention") == 4096 >= ATTN_KEEP_MIN_SPAN
    assert decoder.keeps_attention_residuals(real, "sliding_attention")
    assert decoder.keeps_attention_residuals(real, "full_attention")
    # by the run's length too: the full run of one layer is left to the
    # compiler's merge, and the loop's first line says which is which
    assert [decoder.run_remat_policy(real, shape[0], n)
            for shape, n in real.runs()] == [
        decoder._decoder_nothing_saveable,
        decoder._decoder_attention_saveable]
    assert _attention_remat_note(real_cfg, real, None) == (
        "; remat runs the forward again in full_attention runs of one layer "
        "(merged with the first when compiled) (span 16384), keeps o and lse "
        "in sliding_attention runs of several layers (span 4096)")
    short = decoder.build_decoder(
        Config(**{**SMALLTHINKER, "window_tokens": 512}).validate(),
        kernels=Kernels(attention=lambda *a: a[0]))
    assert not decoder.keeps_attention_residuals(short, "sliding_attention")


def test_a_kept_runs_backward_runs_no_second_forward_kernel():
    """Trace only, nothing runs: the gradient of a model whose sliding run of
    three layers spans 1,024 keys holds `flash_window_fwd` in the forward scan
    and NOT in the rematted backward, because the policy PR 30's rule selects
    keeps the kernel's o and lse (`_decoder_attention_saveable` reads the
    name this JAX's `pallas_call` carries). In a run of one layer the chip's
    compiler merges a second forward with the first; in a run of several it
    cannot, so this holds what no one-layer cell shows (PR 52: 21 ms of a
    387 ms step in `smallthinker_21b_a3b_ep8_train_longrow`)."""
    from vitax.ops.attention import make_attention_impl
    from vitax.programs.kernels import Kernels
    from vitax.train.step import decoder_loss
    cfg = Config(**{**TINY, "num_blocks": 3, "layer_kinds": KINDS[:3],
                    "layer_heads": [14] * 3, "layer_mlps": ["sparse"] * 3,
                    "window_tokens": 1024, "pack_tokens": 2048,
                    "batch_size": 1, "dtype": "bfloat16"}).validate()
    model = decoder.build_decoder(cfg, kernels=Kernels(
        attention=make_attention_impl(cfg, None, force_tpu_kernels=True)))
    assert decoder.keeps_attention_residuals(model, "sliding_attention")
    batch = cases.make_batch(cfg, [[1500, 500]])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), decoder.sample_documents(cfg, 1), True))
    forwards = [path for path, eqn in cases.kernels_traced(
        jax.grad(lambda v: decoder_loss(model.apply(v, batch, True), batch)),
        shapes) if cases.kernel_name(eqn) == "flash_window_fwd"]
    assert len(forwards) >= 1
    assert not [path for path in forwards if "remat2" in path], forwards


# --- (b) the share tied to the model --------------------------------------------

def _layer(held, first, dtype=jnp.float32):
    return SharedRoutedExperts(
        experts_routed=16, experts_held=held, expert_first=first,
        experts_per_token=3, expert_dim=24, shared_dim=0, dtype=dtype,
        route_form="softmax_chosen", activation="relu")


def _plain_layer(p, a, b, held=None):
    """The reference's feed-forward: routed from `a`, the experts on `b`."""
    weights, chosen = reference.route(a, p, top_k=3, experts_routed=16)
    return reference.reglu_experts(b, weights, chosen, p, experts_routed=16,
                                   experts_held=held)


def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """What the eight chips that divide the 16 experts hold, each run alone
    by the PROGRAM's layer (router whole, two experts held, routed from one
    tensor and transforming another), adds up to what the uncut REFERENCE
    gives for the whole layer's m: there is no shared expert to count once.
    And each share is the reference's on that share."""
    n, d = 40, 32
    a, b = jax.random.normal(jax.random.key(3), (2, 1, n, d))
    valid = jnp.ones((1, n), bool)
    whole = _layer(16, 0)
    p = cases.moved(jax.jit(whole.init)(jax.random.key(0), b, valid, a))[
        "params"]
    plain = jax.jit(_plain_layer, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        uncut = plain(p, a[0], b[0], None)
        total = 0.0
        for share in range(8):
            first = 2 * share
            part = {**p, **{f"experts_{m}": {"kernel": p[f"experts_{m}"][
                "kernel"][first:first + 2]} for m in ("gate", "up", "down")}}
            got = jax.jit(_layer(2, first).apply)({"params": part}, b, valid,
                                                  a)[0]
            np.testing.assert_allclose(got, plain(part, a[0], b[0],
                                                  (first, 2)),
                                       rtol=2e-4, atol=2e-6)
            total = total + got
        np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-6)
        assert float(jnp.max(jnp.abs(uncut))) > 0.02
        # the program's whole layer is the reference's too, and routing from
        # `b` itself (a late router) is another layer
        np.testing.assert_allclose(
            jax.jit(whole.apply)({"params": p}, b, valid, a)[0], uncut,
            rtol=2e-4, atol=2e-6)
        late = jax.jit(whole.apply)({"params": p}, b, valid)[0]
    assert float(jnp.max(jnp.abs(late - uncut))) > 1e-2


# --- (c) the two rules of `routed_experts` ----------------------------------------

def test_the_relu_rule_is_autodiff_of_the_plain_layer():
    """Value and the five gradients (tokens routed from, tokens transformed,
    router and the three stacked kernels) of the layer's hand-written loops
    under `relu` against `jax.grad` of the reference's plain layer, with
    padding tokens, a share that starts at expert 4 and two blocks of rows."""
    n, d = 600, 32
    a, b = jax.random.normal(jax.random.key(5), (2, 1, n, d))
    valid = jnp.ones((1, n), bool).at[0, :37].set(False)
    layer = _layer(8, 4)
    p = cases.moved(jax.jit(layer.init)(jax.random.key(0), b, valid, a),
                    by=0.2)["params"]
    push = jax.random.normal(jax.random.key(9), b.shape)
    assert experts.block_rows(n * 3, 8, 16) == 512 < n * 3 * 8 // 16

    def blocked(p, a, b):
        return jnp.sum(layer.apply({"params": p}, b, valid, a) * push)

    def plain(p, a, b):
        with jax.default_matmul_precision("highest"):
            m = _plain_layer(p, a[0], b[0], (4, 8))
        return jnp.sum(jnp.where(valid[0][:, None], m, 0.0) * push[0])

    got = jax.jit(jax.value_and_grad(blocked, argnums=(0, 1, 2)))(p, a, b)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2)))(p, a, b)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    flat_want = jax.tree.leaves(want[1])
    assert len(flat_want) == 4 + 2
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            flat_want):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(w)) > 0, name
        gap = float(jnp.linalg.norm(g - w))
        assert gap <= 1e-5 * float(jnp.linalg.norm(w)), (name, gap)


def test_the_silu_rule_is_bit_for_bit_what_it_was():
    """Forward and backward of a sigmoid-routed, silu-gated layer are the
    same primitives on the same shapes in the same order as at the parent of
    PR 51 (the digest was read off that tree's `routed_experts`: 454
    equations), so the three accepted expert cells compute what they did to
    the bit; the activation argument left out is `silu`; and the layer sows
    no `expert_hidden_live`."""
    layer = SharedRoutedExperts(8, 4, 2, 3, 16, 0, 2.5, jnp.float32)
    x = jnp.zeros((1, 640, 32), jnp.float32)
    valid = jnp.ones((1, 640), bool)
    p = jax.eval_shape(layer.init, jax.random.key(0), x, valid)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, x, valid)),
        argnums=(0, 1)))(p, x).jaxpr
    # every primitive with the shapes and dtypes it writes
    lines = [f"{eqn.primitive.name}:" + ",".join(
        str(v.aval) for v in eqn.outvars) for _, eqn in cases.equations(jaxpr)]
    assert len(lines) == 454
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest() == (
        "ba58886e837c196263365f0a6edd4b721f923d194f168f1c68f9f5be114ad1d8")
    assert layer.activation == "silu" and layer.route_form == "sigmoid"
    _, cols = jax.eval_shape(
        lambda p, x: layer.apply(p, x, valid, mutable=["intermediates"]),
        p, x)
    assert sorted(cols["intermediates"]) == ["expert_load",
                                             "expert_rows_computed"]


# --- (d) where the router's cotangent lands ---------------------------------------

def test_the_routers_cotangent_reaches_norm1(case):
    """With the attention's output projection at zero the attention adds
    nothing to the stream, and what is left of the first norm's gradient came
    through the router: something in a model that routes early, exactly
    nothing in one that routes late (whose router reads `norm2`). With the
    seeded weights the two models' `norm1` gradients differ."""
    late = decoder.build_decoder(
        Config(**{**TINY, "route_early": False}).validate())
    late_grads = cases.loss_grads_and_logits(late, case.batch)(
        case.variables)[1]
    blocks = case.grads["params"]["run0"]["blocks"]
    assert reference.relative_gap(
        late_grads["params"]["run0"]["blocks"]["norm1"]["scale"],
        blocks["norm1"]["scale"]) > 1e-2

    @jax.jit
    def without_attention(v):
        v = jax.tree.map(lambda a: a, v)
        for run in ("run0", "run1"):
            wo = v["params"][run]["blocks"]["attn"]["wo"]
            wo["kernel"] = jnp.zeros_like(wo["kernel"])
        return v

    silent = without_attention(case.variables)
    early = case.program(silent)[1]["params"]
    routed_late = cases.loss_grads_and_logits(late, case.batch)(
        silent)[1]["params"]
    for run in ("run0", "run1"):
        assert float(jnp.max(jnp.abs(
            early[run]["blocks"]["norm1"]["scale"]))) > 1e-6
        assert not np.asarray(
            routed_late[run]["blocks"]["norm1"]["scale"]).any()
        assert float(jnp.max(jnp.abs(
            routed_late[run]["blocks"]["norm2"]["scale"]))) > 1e-6


# --- (e) rotation by layer kind ------------------------------------------------------

def test_a_full_layer_ignores_a_shift_of_positions_and_a_sliding_one_does_not(
        case):
    """Positions reach the model only through the rotation: a model of full
    layers alone (rotated share 0) gives the same logits whatever they are; a
    sliding layer's logits move. Shifted by 5 tokens, the DIFFERENCES of
    positions are what they were, so only the tables' rounding moves them;
    scaled by 3, the rotation itself changes."""
    shifted = dict(case.batch, positions=case.batch["positions"] + 5)
    scaled = dict(case.batch, positions=case.batch["positions"] * 3)
    full_only = Config(**{**TINY, "num_blocks": 1, "layer_kinds": KINDS[:1],
                          "layer_heads": [14], "layer_mlps": ["sparse"]}
                       ).validate()
    model = decoder.build_decoder(full_only)
    variables = cases.seeded(model, full_only)
    apply = jax.jit(lambda m, v, b: m.apply(v, b, True), static_argnums=0)
    want = apply(model, variables, case.batch)
    for other in (shifted, scaled):
        np.testing.assert_array_equal(apply(model, variables, other), want)
    real = np.asarray(case.batch["segment_ids"]) > 0
    got = np.asarray(case.logits)[real]
    moved = np.asarray(apply(case.model, case.variables, scaled))[real]
    assert np.abs(moved - got).max() > 1e-3
    same = np.asarray(apply(case.model, case.variables, shifted))[real]
    np.testing.assert_allclose(same, got, rtol=1e-3, atol=1e-4)


# --- (f) the count of live hidden units --------------------------------------------

def test_expert_hidden_live_equals_a_count_taken_from_the_plain_layer():
    n, d = 600, 32
    a, b = jax.random.normal(jax.random.key(6), (2, 1, n, d))
    valid = jnp.ones((1, n), bool).at[0, 100:160].set(False)
    layer = _layer(8, 2)
    p = cases.moved(jax.jit(layer.init)(jax.random.key(0), b, valid, a),
                    by=0.2)["params"]
    _, cols = jax.jit(lambda p: layer.apply(
        {"params": p}, b, valid, a, mutable=["intermediates"]))(p)
    sown = cols["intermediates"]

    @jax.jit
    def plain(p):
        with jax.default_matmul_precision("highest"):
            _, chosen = reference.route(a[0], p, top_k=3, experts_routed=16)
            return reference.hidden_units_live(
                b[0], chosen, p, valid[0], experts_routed=16,
                experts_held=(2, 8))

    live = int(sown["expert_hidden_live"][0])
    slots = int(jnp.sum(sown["expert_load"][0]))
    # a gate within float32's rounding of 0 may fall on either side
    assert abs(live - int(plain(p))) <= 2
    assert 0.3 * slots * 24 < live < 0.7 * slots * 24
    # two blocks of 512 sorted rows
    assert int(sown["expert_rows_computed"][0]) == 1024 > slots > 512


# --- (g) counts, counters, configuration ----------------------------------------------

def _count(cfg):
    shapes = jax.eval_shape(
        lambda: decoder.build_decoder(cfg).init(
            jax.random.key(0), decoder.sample_documents(cfg, 1), True))
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


def test_closed_form_parameter_count_and_the_configurations(case):
    assert sum(a.size for a in jax.tree.leaves(case.variables)) \
        == decoder.expected_param_count(case.cfg)
    # the configuration of the benchmark's cell, by shapes alone
    real = Config(**SMALLTHINKER).validate()
    assert _count(real) == decoder.expected_param_count(real) == 370_547_200
    from benchmark import flops_smallthinker
    from benchmark import manifest as mf
    config = mf.Manifest().config("smallthinker_21b_a3b_ep8")
    assert flops_smallthinker.param_count(config) == config["parameters"] \
        == 370_547_200
    built = Config(**mf.Manifest().config_kwargs(config), pack_tokens=16384,
                   pack_images=4, batch_size=1).validate()
    for key in SMALLTHINKER:    # the nested block is the shape above
        assert getattr(built, key) == getattr(real, key), key
    parts = flops_smallthinker.param_counts_by_part(config)
    assert parts["attention"] == 20_971_520 and parts["router"] == 163_840
    assert parts["experts_held"] == 47_185_920
    # the whole published model: 52 layers, 64 experts, 151,936 rows: the
    # model's own name, 21B-A3B
    whole = Config(**{
        **SMALLTHINKER, "num_blocks": 52, "layer_kinds": KINDS * 13,
        "layer_heads": [28] * 52, "layer_mlps": ["sparse"] * 52,
        "experts_held": 64, "vocab_rows": 151936}).validate()
    assert decoder.expected_param_count(whole) == 21_506_562_560
    active = (decoder.expected_param_count(whole)
              - 52 * 58 * 3 * 2560 * 768)       # 6 of 64 experts a token
    assert round(active / 1e9, 2) == 3.72       # 2.94 without the two tables
    assert round((active - 2 * 151936 * 2560) / 1e9, 2) == 2.94


def test_train_step_counters_and_the_first_steps_moments():
    """Documents of 30, 14 and 20, 17, 9 tokens in two rows of 48: 90 tokens,
    6 of padding, 85 targets; causal pairs 465 + 105 + 210 + 153 + 45, and
    inside a window of 8: 212 + 84 + 132 + 108 + 44; the slots routed here
    and the hidden units the ReLU gates left live are counted over the four
    layers. And what the benchmark holds the TIMED step to: the gradients
    read from the optimizer state its first call left (`step_gradients`) are
    the model's own, with the clip at work."""
    from benchmark.generators import train_early_router_packed
    cfg = Config(**{**TINY, "warmup_steps": 1, "lr": 2e-3,
                    "clip_grad_norm": 0.05}).validate()
    batch = cases.make_batch(cfg, LENGTHS)
    _, step, state, first = cases.check_first_steps_moments(
        train_early_router_packed, cfg, batch, clipped=True)
    _, m, losses = cases.take_steps(step, state, batch, 3)
    losses.insert(0, float(first["loss"]))
    got = {k: float(m[k]) for k in (
        "tokens", "padding_tokens", "images", "targets", "causal_pairs",
        "window_pairs")}
    assert got == dict(tokens=90, padding_tokens=6, images=5, targets=85,
                       causal_pairs=978, window_pairs=580)
    load = np.asarray(m["expert_load"])
    assert load.shape == (4, 8) and load.sum() == m["expert_slots_here"]
    assert 0 < load.sum() <= 4 * 90 * 3
    assert 0 < m["expert_hidden_live"] < load.sum() * 24
    assert m["expert_rows_computed"] >= load.sum()
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert "route_load_max_over_mean" not in m and "kda_pairs" not in m
    from benchmark import flops_smallthinker
    # the cell's layout (ISSUE 51)
    assert flops_smallthinker.layout_counts(
        [[12000, 2600, 1100, 420]], 16384, 4096) == dict(
            tokens=16_120, documents=4, targets=16_116,
            causal_pairs=76_081_260, window_pairs=44_840_700,
            padding_tokens=264)


def test_a_step_record_carries_the_live_share():
    from vitax.telemetry.record import Recorder
    cfg = Config(**TINY).validate()
    records = []

    class Sink:
        def write(self, record):
            records.append(record)

        def close(self):
            pass

    recorder = Recorder(cfg, [Sink()], 1, "cpu")
    counts = dict(tokens=90.0, padding_tokens=6.0, images=5.0, targets=85.0,
                  causal_pairs=978.0, window_pairs=580.0,
                  expert_slots_here=500.0, expert_rows_computed=1024.0,
                  expert_hidden_live=6000.0)
    recorder.record_step(step=1, epoch=1, step_in_epoch=1, loss=1.0, lr=1e-3,
                         sec_per_iter=0.1, data_wait_s=0.0,
                         packed_counts=counts, expert_load=[[1]])
    recorder.close()
    assert records[-1]["expert_hidden_live"] == 6000.0
    assert records[-1]["expert_hidden_live_share"] == 6000.0 / (500.0 * 24)
    assert records[-1]["expert_rows_over_slots"] == 1024.0 / 500.0


def test_flops_count_the_new_shape():
    from vitax.telemetry.flops import decoder_flops_per_step
    cfg = Config(**SMALLTHINKER).validate()
    slots = 4 * 16_120 * 6 / 8
    at = (16_120, 16_116, 76_081_260, 44_840_700, slots)
    flops = decoder_flops_per_step(cfg, *at)
    # ISSUE 51: about 9.0 TFLOP of attention beside 14.6 of projections,
    # experts and head
    attention = 3 * 4 * (76_081_260 + 3 * 44_840_700) * 28 * 128
    assert 9.0e12 < attention < 9.1e12
    assert 14.0e12 < flops - attention < 15.0e12
    fewer = decoder_flops_per_step(cfg, 16_119, *at[1:])
    d = 2560
    per_token = 4 * (2 * (2 * d * 3584 + 2 * d * 512) + 2 * d * 64)
    assert flops - fewer == 3 * per_token
    no_window = decoder_flops_per_step(cfg, *at[:3], 0, slots)
    assert flops - no_window == 3 * 4 * 3 * 44_840_700 * 28 * 128


@pytest.mark.parametrize("change,message", [
    (dict(rope_fraction_full=0.1), "rotate an even number"),
    (dict(rope_fraction_window=1.5), "rotate nothing, beside a kind"),
    (dict(route_bias=True), "softmax_chosen takes the largest logits"),
    (dict(route_groups=4, groups_per_token=2), "it has no --route_groups"),
    (dict(route_form="softmax"), "unknown --route_form"),
    (dict(expert_activation="gelu"), "--expert_activation 'gelu'"),
    (dict(layer_heads=[14, 14, 14, 13]), "multiple of --kv_heads"),
])
def test_config_refuses_what_is_not_built(change, message):
    with pytest.raises(AssertionError, match=message):
        Config(**{**TINY, **change}).validate()


def test_a_rotated_share_of_zero_is_a_kind_that_rotates_nothing():
    """`rope_fraction_*` 0 is admitted for either kind, beside
    `position_embedding` nope, which stays the model no layer of which
    rotates."""
    for name in ("rope_fraction_full", "rope_fraction_window"):
        Config(**{**TINY, "rope_fraction_full": 1.0, name: 0.0}).validate()
    cfg = Config(**TINY).validate()
    model = decoder.build_decoder(cfg)
    full, sliding = jax.eval_shape(
        model._rope, jnp.zeros((2, 48), jnp.int32))
    assert full is None and sliding[0].shape == (2, 48, 1, 4)
    nope = decoder.build_decoder(
        Config(**{**TINY, "position_embedding": "nope"}).validate())
    assert not nope.rope and model.rope


def test_the_family_declares_the_new_shape_fields():
    assert {"route_form", "route_early", "expert_activation",
            "rope_fraction_full", "window_tokens"} \
        <= cases.family_declares("smallthinker")


def test_training_through_the_cli_path(tmp_path, capsys):
    """`python -m vitax.train --fake_data --model_family decoder` with the
    three new flags and a rotated share of 0 for the full layers (the flags
    through `parse_config`, then the loop the entry point calls): a falling
    loss and the live hidden units on the step records. (`--logits_scaling`
    is no part of the shape: three steps on random ids have a loss to bring
    down only where the logits start large, as in the hybrid shape's case.)"""
    cfg, steps = cases.train_through_the_cli(
        tmp_path, "--pack_tokens", "64",
        "--pack_images", "6", "--embed_dim", "32", "--num_blocks", "4",
        "--vocab_rows", "48", "--kv_heads", "2", "--head_size", "8",
        "--layer_kinds", ",".join(KINDS), "--layer_heads", "14,14,14,14",
        "--layer_mlps", "sparse,sparse,sparse,sparse",
        "--window_tokens", "8", "--expert_dim", "24",
        "--experts_routed", "16", "--experts_held", "8",
        "--experts_per_token", "3", "--rope_fraction_full", "0",
        "--rope_theta_window", "1.5e6", "--route_form", "softmax_chosen",
        "--route_early", "--expert_activation", "relu",
        "--logits_scaling", "0.05")
    assert cfg.route_early and cfg.route_form == "softmax_chosen"
    assert cfg.expert_activation == "relu" and cfg.rope_fraction_full == 0.0
    out = capsys.readouterr().out
    assert "attention core: dense jnp" in out
    for r in steps:
        assert r["causal_pairs"] >= r["window_pairs"] > 0
        assert 0 < r["expert_hidden_live"] \
            < r["expert_slots_here"] * cfg.expert_dim
        assert r["expert_hidden_live_share"] == (
            r["expert_hidden_live"] / (r["expert_slots_here"] * 24))
        assert "route_load_max_over_mean" not in r
