"""vitax.serve end-to-end on the 8-virtual-device CPU mesh: 2-step fake-data
train -> checkpoint -> engine load (Orbax + consolidated npz) -> dynamic
batcher (flush-by-size / flush-by-timeout) -> HTTP predict round-trip on an
ephemeral port -> zero recompiles after warmup -> serve.jsonl contract ->
serve_bench summary, plus the consolidate round-trip and serve-flag
validation satellites.
"""

import base64
import io
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from vitax.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=16, dtype="float32", lr=1e-3, warmup_steps=2,
        serve_max_batch=4, serve_topk=3, max_batch_wait_ms=10.0, seed=0,
    )
    base.update(kw)
    return Config(**base).validate()


def post_json(url: str, payload: dict, timeout: float = 60.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def post_bytes(url: str, body: bytes, content_type: str = "image/png",
               timeout: float = 60.0) -> dict:
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": content_type})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def get_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


def png_bytes(size: int = 20, seed: int = 0) -> bytes:
    from PIL import Image
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, size=(size, size, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr, "RGB").save(buf, "PNG")
    return buf.getvalue()


# --- the served stack: train -> checkpoint -> engine -> HTTP (module-scoped:
# warmup compiles every bucket once for all tests below) ---

@pytest.fixture(scope="module")
def served(devices8, tmp_path_factory):
    from vitax.serve import InferenceEngine, start_server, stop_server
    from vitax.train.loop import train

    root = tmp_path_factory.mktemp("serve")
    ckpt_dir = str(root / "ckpt")
    metrics_dir = str(root / "metrics")
    cfg = tiny_cfg(
        fake_data=True, num_epochs=1, steps_per_epoch=2, log_step_interval=1,
        ckpt_dir=ckpt_dir, ckpt_epoch_interval=1, test_epoch_interval=1,
        num_workers=2, eval_max_batches=1, metrics_dir=metrics_dir,
        serve_port=0,
    )
    train(cfg)  # 2 real optimizer steps; writes epoch_1
    assert os.path.isdir(os.path.join(ckpt_dir, "epoch_1"))

    engine = InferenceEngine.from_checkpoint(cfg, ckpt_dir, 1)
    engine.warmup()
    httpd, ctx = start_server(cfg, engine, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield cfg, engine, url, metrics_dir
    stop_server(httpd, ctx)


# --- engine -----------------------------------------------------------------


def test_engine_buckets_and_warmup(served):
    _, engine, _, _ = served
    assert engine.buckets == (1, 2, 4)
    # AOT warmup compiled each bucket exactly once
    assert engine.compile_count == 3


def test_engine_predict_shapes_and_padding(served):
    cfg, engine, _, _ = served
    for n in (1, 2, 3, 4):
        ids, probs = engine.predict(
            np.zeros((n, cfg.image_size, cfg.image_size, 3), np.uint8))
        assert ids.shape == (n, engine.topk)
        assert probs.shape == (n, engine.topk)
        # top-k probs are descending and valid
        assert np.all(np.diff(probs, axis=1) <= 1e-6)
        assert np.all((probs >= 0) & (probs <= 1))
    # identical rows -> identical outputs regardless of bucket padding
    img = np.full((1, cfg.image_size, cfg.image_size, 3), 7, np.uint8)
    one = engine.predict(img)
    three = engine.predict(np.repeat(img, 3, axis=0))
    np.testing.assert_array_equal(one[0][0], three[0][2])
    np.testing.assert_allclose(one[1][0], three[1][2], rtol=1e-5)


def test_engine_zero_recompiles_after_warmup(served):
    """Mixed-size bursts execute precompiled buckets only: the compile count
    is pinned at len(buckets) and an unseen batch size raises instead of
    silently recompiling."""
    cfg, engine, _, _ = served
    before = engine.compile_count
    for n in (3, 1, 4, 2, 1, 3):
        engine.predict(
            np.zeros((n, cfg.image_size, cfg.image_size, 3), np.uint8))
    assert engine.compile_count == before == len(engine.buckets)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        engine.predict(
            np.zeros((5, cfg.image_size, cfg.image_size, 3), np.uint8))


def test_engine_npz_round_trip_matches_checkpoint(served, tmp_path):
    """consolidate -> from_npz restores the exact param tree: same compiled
    program, same input => identical predictions (the regression test of the
    shared flatten/unflatten key convention)."""
    from vitax.checkpoint.consolidate import consolidate
    from vitax.serve import InferenceEngine

    cfg, engine, _, _ = served
    out = str(tmp_path / "full.npz")
    consolidate(cfg.ckpt_dir, 1, out)
    engine2 = InferenceEngine.from_npz(cfg, out)
    engine2.warmup()
    # exact round trip: every leaf bitwise-equal to the served params
    flat_a = jax.tree.leaves(engine.params)
    flat_b = jax.tree.leaves(engine2.params)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.default_rng(3)
    img = rng.integers(0, 256,
                       size=(3, cfg.image_size, cfg.image_size, 3),
                       ).astype(np.uint8)
    ids_a, probs_a = engine.predict(img)
    ids_b, probs_b = engine2.predict(img)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_allclose(probs_a, probs_b, rtol=1e-6)


# --- the compute tree: weights cast once at warm-up (engine.py) -------------

# every leaf of the tiny bf16 trees below, by what the forward does with it
# first (vitax/models/vit.py cast_before_use): CAST leaves reach the bucket
# programs already in bf16, KEPT leaves are read in float32 and must stay so
CAST = {"patch_embed/proj/kernel", "patch_embed/proj/bias", "pos_embed",
        "attn/qkv/kernel", "attn/qkv/bias", "attn/proj/kernel",
        "attn/proj/bias", "mlp/fc1/kernel", "mlp/fc1/bias", "mlp/fc2/kernel",
        "mlp/fc2/bias", "moe/w1", "moe/b1", "moe/w2", "moe/b2"}
KEPT = {"norm1/scale", "norm1/bias", "norm2/scale", "norm2/bias",
        "norm/scale", "norm/bias", "head/kernel", "head/bias",
        "moe/router/kernel", "moe/router/bias"}
VARIANTS = {"scan": {}, "unrolled": {"scan_blocks": False},
            "moe": {"moe_experts": 4}}


def perturbed_engine(**kw):
    """A tiny engine over the trainer's initialisation with EVERY leaf moved
    off it (LayerNorm scales != 1, biases != 0), sharded as the engine
    shards: a leaf cast that the forward reads in float32 changes outputs."""
    import jax.numpy as jnp
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import param_specs, shardings_of
    from vitax.serve import engine as serve_engine
    cfg = tiny_cfg(**kw)
    mesh = build_mesh(cfg)
    model = serve_engine._build_model(cfg, mesh, quantized=False)
    sample = jnp.zeros((mesh.shape["dp"] * mesh.shape["fsdp"],
                        cfg.image_size, cfg.image_size, 3), jnp.float32)

    def init(rng):
        params = model.init(rng, sample, True)
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.key(7), len(leaves))
        return treedef.unflatten(
            [v + 0.3 * jax.random.normal(k, v.shape, v.dtype)
             for v, k in zip(leaves, keys)])

    abstract = jax.eval_shape(init, jax.random.key(cfg.seed))
    shardings = shardings_of(mesh, param_specs(abstract, cfg, mesh))
    params = jax.jit(init, out_shardings=shardings)(jax.random.key(cfg.seed))
    return serve_engine.InferenceEngine(cfg, mesh, model, params), abstract


def leaf_roles(engine):
    """({cast leaf names}, {kept leaf names}) read off the compute tree
    itself, block prefix dropped: a kept leaf is the very array `params`
    holds, a cast one the engine's own bf16 copy."""
    own = jax.tree_util.tree_flatten_with_path(engine.compute_params)[0]
    cast, kept = set(), set()
    for (path, leaf), shared in zip(own, jax.tree.leaves(engine.params)):
        parts = [k.key for k in path
                 if k.key != "params" and not k.key.startswith("blocks")]
        (kept if leaf is shared else cast).add("/".join(parts))
    return cast, kept


def parent_formulation(engine, images):
    """What the engine served before it kept a compute tree: the same
    forward jitted over the float32 tree, every cast inside the program."""
    top_i, top_p = jax.jit(engine._predict_fn())(engine.params, images)
    return np.asarray(top_i), np.asarray(top_p)


@pytest.fixture(scope="module")
def bf16_engines(devices8):
    engines = {}
    for name, kw in VARIANTS.items():
        engines[name], _ = perturbed_engine(dtype="bfloat16", **kw)
        engines[name].warmup()
    return engines


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_compute_tree_bit_identical_to_f32_forward(bf16_engines, variant):
    engine = bf16_engines[variant]
    cast, kept = leaf_roles(engine)
    assert cast == CAST - ({"mlp/fc1/kernel", "mlp/fc1/bias",
                            "mlp/fc2/kernel", "mlp/fc2/bias"}
                           if variant == "moe" else
                           {"moe/w1", "moe/b1", "moe/w2", "moe/b2"})
    assert kept == KEPT - (set() if variant == "moe" else
                           {"moe/router/kernel", "moe/router/bias"})
    assert engine.precast_leaves == sum(
        a is not b for a, b in zip(jax.tree.leaves(engine.compute_params),
                                   jax.tree.leaves(engine.params)))
    s = engine.cfg.image_size
    images = np.random.default_rng(3).integers(
        0, 256, (4, s, s, 3), dtype=np.uint8)
    ids, probs = engine.predict(images)
    want_ids, want_probs = parent_formulation(engine, images)
    assert np.array_equal(ids, want_ids)
    assert np.array_equal(probs, want_probs)     # ==, not allclose
    # params is what the caller handed in: still float32, every leaf
    assert {str(v.dtype) for v in jax.tree.leaves(engine.params)} == {
        "float32"}
    assert engine.weights_dtype == "float32"


@pytest.mark.parametrize("wrong", ["norm1/scale", "norm/bias", "head/kernel",
                                   "moe/router/kernel"])
def test_leaf_wrongly_cast_breaks_identity(devices8, monkeypatch, wrong):
    """The identity test can fail: cast one leaf the forward reads in
    float32 and the served probabilities move."""
    from vitax.serve import engine as serve_engine
    right = serve_engine.cast_before_use

    def too_eager(path):
        names = [getattr(k, "key", k) for k in path]
        return right(path) or "/".join(names).endswith(wrong)

    monkeypatch.setattr(serve_engine, "cast_before_use", too_eager)
    engine, _ = perturbed_engine(
        dtype="bfloat16", **(VARIANTS["moe"] if "moe" in wrong else {}))
    engine.warmup()
    assert wrong in leaf_roles(engine)[0]
    s = engine.cfg.image_size
    images = np.random.default_rng(3).integers(
        0, 256, (4, s, s, 3), dtype=np.uint8)
    _, probs = engine.predict(images)
    assert not np.array_equal(probs, parent_formulation(engine, images)[1])


def weight_converts(mlir: str, engine, threshold: int):
    """`stablehlo.convert` ops from float32 whose operand has the shape of a
    parameter leaf of at least `threshold` bytes (or of its per-layer
    slice): a weight cast inside the program."""
    import re
    shapes = set()
    for v in jax.tree.leaves(engine.params):
        if v.size * 4 >= threshold:
            shapes.add("x".join(map(str, v.shape)))
            shapes.add("x".join(map(str, v.shape[1:])))
    found = re.findall(
        r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\) -> "
        r"tensor<[0-9x]+xbf16>", mlir)
    return [shape for shape in found if shape in shapes]


def test_bucket_program_has_no_weight_cast(bf16_engines):
    from vitax.analysis import hlo
    from vitax.analysis.rules import large_param_threshold_bytes
    engine = bf16_engines["scan"]
    threshold = large_param_threshold_bytes(engine.cfg)
    bucket = engine.buckets[-1]
    mlir = engine.lower_bucket_mlir(bucket)
    big_f32 = [a for a in hlo.mlir_main_args(mlir)
               if a["dtype"] == "f32" and a["bytes"] >= threshold]
    assert big_f32 == []
    assert weight_converts(mlir, engine, threshold) == []
    # the detector sees the parent's program: four stacked block kernels
    # and the patchify kernel, each cast inside it
    s = engine.cfg.image_size
    parent = jax.jit(engine._predict_fn()).lower(
        engine.params, jax.ShapeDtypeStruct((bucket, s, s, 3), np.uint8))
    assert len(weight_converts(parent.as_text(), engine, threshold)) == 5
    assert engine.compile_count == len(engine.buckets)


def test_bucket_lowers_over_abstract_params(bf16_engines):
    """The analysis and AOT arms build the engine over jax.eval_shape
    params: the compute tree is then eval_shape of the same cast, and the
    bucket lowers to the very program the live engine compiled."""
    from vitax.serve.engine import InferenceEngine
    live = bf16_engines["scan"]
    _, abstract = perturbed_engine(dtype="bfloat16")
    engine = InferenceEngine(live.cfg, live.mesh, live.model, abstract)
    assert all(isinstance(v, jax.ShapeDtypeStruct)
               for v in jax.tree.leaves(engine.compute_params))
    bucket = engine.buckets[-1]
    assert engine.lower_bucket_mlir(bucket) == live.lower_bucket_mlir(bucket)
    assert engine.compile_count == 0
    assert engine.precast_leaves == live.precast_leaves


def test_precast_stops_at_the_memory_share(devices8, monkeypatch):
    """Copies are taken largest leaf first, only while the weights on one
    device stay within WEIGHTS_MEMORY_SHARE of its memory; a leaf left out
    is cast inside the program as before, and outputs stay bit-identical."""
    from vitax.analysis import hlo
    from vitax.analysis.rules import large_param_threshold_bytes
    from vitax.serve import engine as serve_engine
    # replicated weights (dp only), so a leaf's shard is the leaf
    kw = dict(dtype="bfloat16", run_without_fsdp=True)
    free, _ = perturbed_engine(**kw)               # CPU: no limit reported
    assert serve_engine.device_memory_limit(free.mesh) is None
    assert leaf_roles(free)[0] == CAST - {"moe/w1", "moe/b1", "moe/w2",
                                          "moe/b2"}
    # room for the two largest copies (the fc1 and fc2 kernels) and a byte
    # short of the proj kernel's: qkv, proj and the patchify kernel stay out
    resident = tree_bytes(free.params)
    copies = sorted((v.size * 2 for v in jax.tree.leaves(free.params)),
                    reverse=True)
    proj = 2 * 32 * 32 * 2
    assert copies[0] == copies[1] > copies[2] > proj
    room = copies[0] + copies[1] + proj - 1
    limit = (resident + room) / serve_engine.WEIGHTS_MEMORY_SHARE
    monkeypatch.setattr(serve_engine, "device_memory_limit",
                        lambda mesh: limit)
    engine, _ = perturbed_engine(**kw)
    cast, kept = leaf_roles(engine)
    assert {"mlp/fc1/kernel", "mlp/fc2/kernel", "pos_embed"} <= cast
    assert {"attn/qkv/kernel", "attn/proj/kernel",
            "patch_embed/proj/kernel"} <= kept
    assert engine.precast_leaves == free.precast_leaves - 3
    assert copies[0] + copies[1] < engine.precast_bytes <= room
    assert engine.param_bytes() == resident + engine.precast_bytes
    engine.warmup()
    threshold = large_param_threshold_bytes(engine.cfg)
    mlir = engine.lower_bucket_mlir(engine.buckets[-1])
    assert sorted(weight_converts(mlir, engine, threshold)) == [
        "32x32", "32x96", "8x8x3x32"]   # proj, qkv (a block's slice), patchify
    assert {a["dtype"] for a in hlo.mlir_main_args(mlir)} == {
        "bf16", "f32", "ui8"}
    s = engine.cfg.image_size
    images = np.random.default_rng(5).integers(
        0, 256, (3, s, s, 3), dtype=np.uint8)
    ids, probs = engine.predict(images)
    want_ids, want_probs = parent_formulation(engine, images)
    assert np.array_equal(ids, want_ids)
    assert np.array_equal(probs, want_probs)
    # no room at all: nothing is pre-cast and the compute tree is params
    monkeypatch.setattr(serve_engine, "device_memory_limit",
                        lambda mesh: resident)
    full, _ = perturbed_engine(**kw)
    assert full.precast_leaves == 0 and full.compute_params is full.params


def tree_bytes(tree) -> int:
    return sum(int(v.nbytes) for v in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def engines_by_weights(devices8, bf16_engines):
    from vitax.analysis.rules import arm_config, build_serve_program
    f32, _ = perturbed_engine(dtype="float32")
    int8 = build_serve_program(arm_config("serve_quant"),
                               arm="serve_quant").engine
    return {"bfloat16": bf16_engines["scan"], "float32": f32, "int8": int8}


@pytest.mark.parametrize("weights", ["bfloat16", "float32", "int8"])
def test_precast_counters_on_metrics_and_serve_start(
        engines_by_weights, tmp_path, weights):
    import dataclasses
    from vitax.serve import start_server, stop_server
    engine = engines_by_weights[weights]
    cfg = dataclasses.replace(engine.cfg, metrics_dir=str(tmp_path))
    httpd, ctx = start_server(cfg, engine, port=0)
    try:
        snap = get_json(
            f"http://127.0.0.1:{httpd.server_address[1]}/metrics")
    finally:
        stop_server(httpd, ctx)
    with open(tmp_path / "serve.jsonl", encoding="utf-8") as f:
        start = [json.loads(line) for line in f if line.strip()][0]
    assert start["kind"] == "serve_start"
    own = [a for a, b in zip(jax.tree.leaves(engine.compute_params),
                             jax.tree.leaves(engine.params)) if a is not b]
    for rec in (snap, start):
        assert rec["precast_leaves"] == engine.precast_leaves == len(own)
        assert rec["precast_bytes"] == engine.precast_bytes == tree_bytes(own)
    if weights == "bfloat16":
        assert engine.precast_leaves == 11 and engine.precast_bytes > 0
        assert {str(a.dtype) for a in own} == {"bfloat16"}
    else:   # nothing to cast: the compute tree IS params, no copy
        assert engine.compute_params is engine.params
        assert engine.precast_leaves == engine.precast_bytes == 0
    # both trees, a shared leaf once, plus the quant scales
    assert snap["param_bytes"] == engine.param_bytes() == (
        tree_bytes(engine.params) + tree_bytes(own)
        + tree_bytes(engine.scales))
    assert snap["weights_dtype"] == engine.weights_dtype == (
        "float32" if weights == "bfloat16" else weights)


def test_quantized_engine_program_unchanged(engines_by_weights):
    """A quantized engine takes its int8 leaves as it always did: VTX-R007
    and VTX-R006 hold on it, and no argument of its program is bf16."""
    from vitax.analysis import hlo
    from vitax.analysis.rules import (QUANT_WEIGHTS_RESIDENT,
                                      SERVE_NO_RECOMPILE, Program)
    engine = engines_by_weights["int8"]
    prog = Program(kind="serve", arm="serve_quant", config=engine.cfg,
                   engine=engine)
    assert QUANT_WEIGHTS_RESIDENT.check(prog, engine.cfg) == []
    assert SERVE_NO_RECOMPILE.check(prog, engine.cfg) == []
    args = hlo.mlir_main_args(engine.lower_bucket_mlir(engine.buckets[-1]))
    assert {a["dtype"] for a in args} == {"i8", "f32", "ui8"}


# --- consolidation round-trip (satellite) -----------------------------------


def test_flatten_unflatten_round_trip():
    from vitax.checkpoint.consolidate import flatten_tree, unflatten_tree
    tree = {"params": {"blocks": {"attn": {"kernel": np.arange(6.0).reshape(2, 3)},
                                  "bias": np.zeros(3)},
                       "head": {"kernel": np.ones((3, 4))}}}
    flat = flatten_tree(tree)
    assert set(flat) == {"params/blocks/attn/kernel", "params/blocks/bias",
                         "params/head/kernel"}
    rebuilt = unflatten_tree(flat)
    assert jax.tree_util.tree_structure(tree) == \
        jax.tree_util.tree_structure(rebuilt)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [None, "float32", "bfloat16", "int8",
                                   "float8_e4m3"])
def test_save_npz_dtype_round_trip(tmp_path, dtype):
    import ml_dtypes
    from vitax.checkpoint.consolidate import load_npz, save_npz
    flat = {"a/kernel": np.arange(6, dtype=np.float32).reshape(2, 3),
            "a/b": np.ones(3, np.float32),
            "step": np.asarray(7, np.int32)}
    out = str(tmp_path / f"x_{dtype}.npz")
    save_npz(out, flat, dtype=dtype)
    back = load_npz(out)
    assert set(back) == set(flat)
    if dtype == "bfloat16":
        assert back["a/kernel"].dtype == ml_dtypes.bfloat16
        np.testing.assert_allclose(
            back["a/kernel"].astype(np.float32), flat["a/kernel"], rtol=1e-2)
    elif dtype in ("int8", "float8_e4m3"):
        # generic load dequantizes back to f32 within half a quant step
        # (fp8 has ~2 mantissa bits -> coarser bound than the int8 grid)
        assert back["a/kernel"].dtype == np.float32
        qmax = 127.0 if dtype == "int8" else 240.0
        atol = float(np.abs(flat["a/kernel"]).max()) / qmax
        rtol = 0.0 if dtype == "int8" else 0.08
        np.testing.assert_allclose(back["a/kernel"], flat["a/kernel"],
                                   atol=atol, rtol=rtol)
        # the bias is not a matmul weight: untouched
        np.testing.assert_array_equal(back["a/b"], flat["a/b"])
    else:
        assert back["a/kernel"].dtype == np.float32
        np.testing.assert_array_equal(back["a/kernel"], flat["a/kernel"])
    # non-float leaves are never cast
    assert back["step"].dtype == np.int32 and int(back["step"]) == 7


def test_save_npz_fp8_raw_view_pin(tmp_path):
    """fp8 leaves store as a uint8 bit-view + manifest entry and load back
    EXACTLY (bit-for-bit) through load_npz_raw — the serve load path.

    The npz container has no fp8 dtype, so the export convention is the
    same bit-view trick the bf16 path uses with uint16: a wrong view dtype
    or a dropped manifest entry would silently reinterpret the bytes."""
    import ml_dtypes
    from vitax.checkpoint.consolidate import load_npz_raw, save_npz
    rng = np.random.default_rng(0)
    flat = {"blocks/fc1/kernel": rng.standard_normal((8, 16)).astype(
                np.float32),
            "blocks/fc1/bias": np.ones(16, np.float32)}
    out = str(tmp_path / "fp8.npz")
    save_npz(out, flat, dtype="float8_e4m3")
    raw, scales, manifest = load_npz_raw(out)
    assert manifest == {"blocks/fc1/kernel": "float8_e4m3"}
    assert raw["blocks/fc1/kernel"].dtype == ml_dtypes.float8_e4m3
    assert set(scales) == {"blocks/fc1/kernel"}
    assert scales["blocks/fc1/kernel"].dtype == np.float32
    # the stored payload IS the uint8 view of the fp8 leaf: re-deriving the
    # quantization host-side reproduces it bit-for-bit
    s = scales["blocks/fc1/kernel"]
    want = (flat["blocks/fc1/kernel"] / s).astype(ml_dtypes.float8_e4m3)
    np.testing.assert_array_equal(
        raw["blocks/fc1/kernel"].view(np.uint8), want.view(np.uint8))
    # bias rides along untouched
    np.testing.assert_array_equal(raw["blocks/fc1/bias"],
                                  flat["blocks/fc1/bias"])
    # determinism: a second export of the same tree is byte-identical
    out2 = str(tmp_path / "fp8_b.npz")
    save_npz(out2, flat, dtype="float8_e4m3")
    raw2, _, _ = load_npz_raw(out2)
    np.testing.assert_array_equal(
        raw["blocks/fc1/kernel"].view(np.uint8),
        raw2["blocks/fc1/kernel"].view(np.uint8))


# --- batcher (engine-free: a fake predict_fn pins flush semantics) ----------


def _fake_predict(calls, delay_s=0.0):
    def predict(images):
        if delay_s:
            time.sleep(delay_s)
        calls.append(images.shape[0])
        n = images.shape[0]
        return (np.tile(np.arange(3, dtype=np.int32), (n, 1)),
                np.tile(np.array([0.5, 0.3, 0.2], np.float32), (n, 1)))
    return predict


def test_batcher_flush_by_size():
    """max_batch simultaneous submissions flush as ONE batch well before the
    (deliberately huge) deadline."""
    from vitax.serve import DynamicBatcher
    calls = []
    b = DynamicBatcher(_fake_predict(calls), max_batch=4,
                       max_wait_ms=60_000.0)
    try:
        t0 = time.time()
        futs = [b.submit(np.zeros((4, 4, 3), np.uint8)) for _ in range(4)]
        results = [f.result(timeout=30) for f in futs]
        assert time.time() - t0 < 30  # did not wait out the minute deadline
        assert calls == [4]
        assert all(r.batch_size == 4 for r in results)
        assert all(r.classes.shape == (3,) for r in results)
    finally:
        b.close()


def test_batcher_flush_by_timeout():
    """A lone request flushes at the deadline, not at bucket-full."""
    from vitax.serve import DynamicBatcher
    calls = []
    b = DynamicBatcher(_fake_predict(calls), max_batch=4, max_wait_ms=50.0)
    try:
        t0 = time.time()
        r = b.submit(np.zeros((4, 4, 3), np.uint8)).result(timeout=30)
        elapsed = time.time() - t0
        assert calls == [1]
        assert r.batch_size == 1
        assert elapsed >= 0.04  # waited (most of) the deadline for company
    finally:
        b.close()


def test_batcher_error_propagates_to_futures():
    from vitax.serve import DynamicBatcher

    def boom(images):
        raise RuntimeError("engine fell over")

    b = DynamicBatcher(boom, max_batch=2, max_wait_ms=5.0)
    try:
        fut = b.submit(np.zeros((4, 4, 3), np.uint8))
        with pytest.raises(RuntimeError, match="fell over"):
            fut.result(timeout=30)
        # the worker survived the exception and still serves
        assert b.submit is not None and b.queue_depth() == 0
    finally:
        b.close()


# --- batcher over a two-phase engine (one batch queued behind the running one)


class TwoPhaseEngine:
    """`dispatch(images) -> handle` stand-in whose batches finish when the
    test says so. Batch k (in order of dispatch) answers row r with class ids
    `[k, pixel of its image, r]`, so a reply names its batch, its request and
    its row. `log` is the order of what the worker did."""

    def __init__(self, hold=True, fail_dispatch=(), fail_result=()):
        self.hold = hold
        self.fail_dispatch, self.fail_result = set(fail_dispatch), set(fail_result)
        self.handles, self.log = [], []
        self.gate = None            # set: dispatch blocks until it is released
        self.in_dispatch = threading.Event()

    def dispatch(self, images):
        k = len(self.handles)
        self.in_dispatch.set()
        if self.gate is not None:
            assert self.gate.wait(timeout=30)
        handle = TwoPhaseHandle(self, k, images)
        self.handles.append(handle)
        if k in self.fail_dispatch:
            self.log.append(("dispatch_failed", k))
            raise RuntimeError(f"dispatch {k} fell over")
        self.log.append(("dispatch", k))
        return handle

    def release(self, k):
        wait_until(lambda: len(self.handles) > k)
        self.handles[k].finished.set()

    def order(self, *events):
        """Positions of `events` in the log, which must hold each once."""
        assert all(self.log.count(e) == 1 for e in events), self.log
        return [self.log.index(e) for e in events]


class TwoPhaseHandle:
    def __init__(self, engine, k, images):
        self.engine, self.k, self.images = engine, k, images
        self.finished = threading.Event()
        if not engine.hold:
            self.finished.set()
        self.t_dispatch = time.time()
        self.t_wait = None

    def done(self):
        return self.finished.is_set()

    def result(self):
        self.t_wait = time.time()
        self.engine.log.append(("result_begin", self.k))
        assert self.finished.wait(timeout=30)
        self.engine.log.append(("result_end", self.k))
        if self.k in self.engine.fail_result:
            raise RuntimeError(f"result {self.k} fell over")
        n = self.images.shape[0]
        ids = np.stack([np.array([self.k, self.images[r, 0, 0, 0], r],
                                 np.int32) for r in range(n)])
        return ids, np.tile(np.array([0.5, 0.3, 0.2], np.float32), (n, 1))


def wait_until(cond, timeout=30.0):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, "timed out"
        time.sleep(0.001)


def image(pixel):
    return np.full((4, 4, 3), pixel, np.uint8)


def two_phase_batcher(engine, events=None, **kw):
    from vitax.serve import DynamicBatcher
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_ms", 60_000.0)
    return DynamicBatcher(None, dispatch_fn=engine.dispatch,
                          on_batch=None if events is None else events.append,
                          **kw)


BATCH_MARKS = ("t_collect", "t_stack", "t_put", "t_dispatch", "t_wait",
               "t_deliver", "t_end")


def test_full_bucket_is_dispatched_before_the_running_batch_is_fetched():
    """(a) Three full buckets queued: batch 1 goes to the device before
    batch 0's `result()` returns, batch 2 only after batch 0 is delivered
    (one queued batch, never two); every reply reaches its own future, in
    the order of dispatch."""
    engine, events = TwoPhaseEngine(), []
    engine.gate = threading.Event()     # hold batch 0 inside its dispatch ...
    b = two_phase_batcher(engine, events)
    try:
        futs = [b.submit(image(10 + i)) for i in range(6)]
        assert engine.in_dispatch.wait(timeout=30)
        engine.gate.set()               # ... until all three buckets wait
        wait_until(lambda: ("result_begin", 0) in engine.log)
        assert engine.log == [("dispatch", 0), ("dispatch", 1),
                              ("result_begin", 0)]
        time.sleep(0.05)                # the third bucket stays in the queue
        assert b.queue_depth() == 2 and len(engine.handles) == 2
        done_order = []
        for f in futs:
            f.add_done_callback(lambda f: done_order.append(
                int(f.result().classes[1])))
        engine.release(0)       # batch 2 goes out while batch 1 still runs
        wait_until(lambda: ("result_begin", 1) in engine.log)
        engine.release(1)
        engine.release(2)
        results = [f.result(timeout=30) for f in futs]
        d1, r0, d2, b1 = engine.order(("dispatch", 1), ("result_end", 0),
                                      ("dispatch", 2), ("result_begin", 1))
        assert d1 < r0 < d2 < b1
        for i, r in enumerate(results):
            assert list(r.classes) == [i // 2, 10 + i, i % 2]
            assert (r.batch_id, r.batch_size) == (i // 2, 2)
        assert done_order == [10, 11, 12, 13, 14, 15]
        wait_until(lambda: len(events) == 3)
        assert [e["overlapped"] for e in events] == [0, 1, 1]
        assert (b.batches_total, b.batches_overlapped) == (3, 2)
    finally:
        b.close()


@pytest.mark.parametrize("ending", ["bucket_fills", "batch_in_flight_done"])
def test_partial_bucket_is_not_queued_behind_a_running_batch(ending):
    """(b) A batch in flight and less than a full bucket pending: the
    deadline (5 ms) alone flushes nothing. The bucket filling does, at once
    and ahead of the fetch; failing that, the running batch's delivery."""
    engine, events = TwoPhaseEngine(), []
    b = two_phase_batcher(engine, events, max_batch=4, max_wait_ms=5.0)
    try:
        first = [b.submit(image(i)) for i in range(4)]
        wait_until(lambda: ("dispatch", 0) in engine.log)
        late = [b.submit(image(20 + i)) for i in range(2)]
        time.sleep(0.1)                 # twenty deadlines
        assert engine.log == [("dispatch", 0)] and b.queue_depth() == 2
        if ending == "bucket_fills":
            late += [b.submit(image(22 + i)) for i in range(2)]
            wait_until(lambda: ("result_begin", 0) in engine.log)
            assert engine.log == [("dispatch", 0), ("dispatch", 1),
                                  ("result_begin", 0)]
        engine.release(0)
        assert [int(f.result(timeout=30).classes[1]) for f in first] == [
            0, 1, 2, 3]
        engine.release(1)
        results = [f.result(timeout=30) for f in late]
        assert [int(r.classes[1]) for r in results] == [
            20 + i for i in range(len(late))]
        assert {r.batch_size for r in results} == {len(late)}
        wait_until(lambda: len(events) == 2)
        assert [e["overlapped"] for e in events] == [
            0, int(ending == "bucket_fills")]
        if ending == "batch_in_flight_done":
            d1, r0 = engine.order(("dispatch", 1), ("result_end", 0))
            assert r0 < d1
            # a batch that did not overlap begins where the last one ended
            assert events[1]["t_collect"] == events[0]["t_end"]
    finally:
        b.close()


def test_nothing_in_flight_keeps_the_deadline_rule():
    """(c) A lone request waits for company until its deadline, then goes
    alone; a full bucket goes at once."""
    engine = TwoPhaseEngine(hold=False)
    b = two_phase_batcher(engine, max_batch=4, max_wait_ms=50.0)
    try:
        t0 = time.time()
        r = b.submit(image(7)).result(timeout=30)
        assert time.time() - t0 >= 0.04 and r.batch_size == 1
        t0 = time.time()
        full = [b.submit(image(i)) for i in range(4)]
        assert {f.result(timeout=30).batch_size for f in full} == {4}
        assert time.time() - t0 < 0.04
    finally:
        b.close()


@pytest.mark.parametrize("failing", ["dispatch", "result"])
@pytest.mark.parametrize("overlap", [False, True])
def test_a_failure_reaches_its_own_batch_only(failing, overlap):
    """(d) Batch 1 fails, in its dispatch or in its `result()`, alone or
    queued behind batch 0: its futures get the exception, batches 0 and 2
    their answers, and the worker lives."""
    engine = TwoPhaseEngine(hold=overlap, **{f"fail_{failing}": [1]})
    b = two_phase_batcher(engine)
    try:
        if overlap:
            engine.gate = threading.Event()
        futs = [b.submit(image(i)) for i in range(4)]
        if overlap:
            assert engine.in_dispatch.wait(timeout=30)
            engine.gate.set()
            wait_until(lambda: ("result_begin", 0) in engine.log)
            for k in range(2):
                engine.release(k)
        for f in futs[:2]:
            assert int(f.result(timeout=30).classes[0]) == 0
        for f in futs[2:]:
            with pytest.raises(RuntimeError, match=f"{failing} 1 fell over"):
                f.result(timeout=30)
        engine.hold = False
        again = [b.submit(image(30 + i)) for i in range(2)]
        assert [int(f.result(timeout=30).classes[1]) for f in again] == [
            30, 31]
    finally:
        b.close()


def test_close_delivers_the_batch_in_flight_and_the_queue():
    """(e) `close()` with one batch running and a partial bucket queued:
    both are answered before the worker is joined."""
    engine = TwoPhaseEngine()
    b = two_phase_batcher(engine)
    futs = [b.submit(image(i)) for i in range(2)]
    wait_until(lambda: ("dispatch", 0) in engine.log)
    futs.append(b.submit(image(2)))
    closer = threading.Thread(target=b.close)
    closer.start()
    try:
        time.sleep(0.02)
        assert closer.is_alive() and not futs[0].done()
        engine.hold = False
        engine.release(0)
        assert [int(f.result(timeout=30).classes[1]) for f in futs] == [
            0, 1, 2]
    finally:
        closer.join(timeout=30)
    assert not closer.is_alive() and not b._worker.is_alive()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(image(3))


def test_many_submitters_against_an_overlapping_worker():
    """Stress: 16 threads submit 40 requests each while the worker runs one
    batch behind another, on a 10 us switch interval. Every reply is its own
    request's, batches come back in the order of dispatch, and the counters
    add up."""
    engine, events = TwoPhaseEngine(hold=False), []
    slow = engine.dispatch

    def dispatch(images):       # a batch is done 2 ms after its dispatch
        handle = slow(images)
        handle.finished.clear()
        threading.Timer(0.002, handle.finished.set).start()
        return handle

    engine.dispatch = dispatch
    b = two_phase_batcher(engine, events, max_batch=4, max_wait_ms=1.0)
    wrong, delivered = [], []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def submitter(i):
            for k in range(40):
                pixel = (7 * i + k) % 256
                r = b.submit(image(pixel)).result(timeout=60)
                if int(r.classes[1]) != pixel:
                    wrong.append((i, k, r.classes))
                delivered.append(r.batch_id)

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        b.close()
    assert not wrong and len(delivered) == 640
    assert [e["batch_id"] for e in events] == list(range(len(events)))
    assert sum(e["batch_size"] for e in events) == 640
    assert b.batches_total == len(events) == len(engine.handles)
    assert b.batches_overlapped == sum(e["overlapped"] for e in events) > 0
    ends = [e["t_end"] for e in events]
    assert ends == sorted(ends)         # delivered in the order of dispatch


def overlapped_records():
    """Records of four full buckets through a two-phase engine, queued
    before the first is fetched; each batch runs 20 ms."""
    engine, events = TwoPhaseEngine(), []
    engine.gate = threading.Event()
    b = two_phase_batcher(engine, events)
    try:
        futs = [b.submit(image(i)) for i in range(8)]
        assert engine.in_dispatch.wait(timeout=30)
        engine.gate.set()
        for k in range(4):
            time.sleep(0.02)
            engine.release(k)
        for f in futs:
            f.result(timeout=30)
    finally:
        b.close()
    return engine, events


def plain_records():
    """The same four buckets through a `predict_fn` that blocks 20 ms."""
    from vitax.serve import DynamicBatcher
    events, started = [], threading.Event()
    gate = threading.Event()

    def predict(images):
        started.set()
        assert gate.wait(timeout=30)
        return _fake_predict([], delay_s=0.02)(images)

    b = DynamicBatcher(predict, max_batch=2, max_wait_ms=60_000.0,
                       on_batch=events.append)
    try:
        futs = [b.submit(image(i)) for i in range(8)]
        assert started.wait(timeout=30)
        gate.set()
        for f in futs:
            f.result(timeout=30)
    finally:
        b.close()
    return None, events


def check_marks_in_order(engine, events):
    assert [e["batch_id"] for e in events] == [0, 1, 2, 3]
    for e in events:
        marks = [e[m] for m in BATCH_MARKS]
        assert marks == sorted(marks), e
        assert e["infer_s"] == e["t_deliver"] - e["t_put"]
        assert set(e) == {"batch_id", "batch_size", "bucket", "infer_s",
                          "overlapped", *BATCH_MARKS}


def check_marks_are_the_batchs_own(engine, events):
    """`t_dispatch` and `t_wait` come off the batch's handle: with two in
    flight, the engine's last marks would be the neighbour's."""
    assert [e["overlapped"] for e in events] == [0, 1, 1, 1]
    for e, handle in zip(events, engine.handles):
        assert (e["t_dispatch"], e["t_wait"]) == (handle.t_dispatch,
                                                  handle.t_wait)
    for prev, nxt in zip(events, events[1:]):
        # batch n+1 was on the device before batch n's fetch began, and its
        # own fetch began after batch n's futures were resolved
        assert nxt["t_dispatch"] <= prev["t_wait"]
        assert nxt["t_wait"] >= prev["t_end"]


def check_overlapped_timelines(engine, events):
    """A batch dispatched behind a running one has its collect, stack, put
    and dispatch marks inside that batch's device time."""
    for prev, nxt in zip(events, events[1:]):
        assert nxt["t_collect"] < prev["t_deliver"]
        assert prev["t_dispatch"] <= nxt["t_dispatch"] <= prev["t_deliver"]
    # after the first pair: collect of n+1 begins at the end of n-1
    for before, nxt in zip(events[1:], events[3:]):
        assert nxt["t_collect"] == before["t_end"]


def check_plain_predict_never_overlaps(engine, events):
    """(g) A `predict_fn` that blocks gives the parent's records: no hole
    between two batches, and a handle that marks nothing leaves `put` and
    `dispatch` empty."""
    assert [e["overlapped"] for e in events] == [0, 0, 0, 0]
    for prev, nxt in zip(events, events[1:]):
        assert nxt["t_collect"] == prev["t_end"]
    for e in events:
        assert e["t_put"] == e["t_dispatch"] == e["t_wait"]
        assert e["t_deliver"] - e["t_wait"] >= 0.02


RECORD_STATEMENTS = {
    "two_phase-marks_in_order": (overlapped_records, check_marks_in_order),
    "two_phase-own_marks": (overlapped_records, check_marks_are_the_batchs_own),
    "two_phase-timelines_overlap": (overlapped_records,
                                    check_overlapped_timelines),
    "plain-marks_in_order": (plain_records, check_marks_in_order),
    "plain-never_overlaps": (plain_records,
                             check_plain_predict_never_overlaps),
}


@pytest.mark.parametrize("statement", sorted(RECORD_STATEMENTS))
def test_serve_batch_records_of_overlapped_batches(statement):
    """(f), (g)"""
    records, check = RECORD_STATEMENTS[statement]
    check(*records())


@pytest.mark.parametrize("weights", ["bfloat16", "float32", "int8"])
def test_predict_is_dispatch_then_result(engines_by_weights, weights):
    """`predict` against `dispatch().result()`, bitwise, with a second batch
    queued behind the first and the answers fetched in order; the marks are
    each handle's own."""
    engine = engines_by_weights[weights]
    if not engine.ready:
        engine.warmup()
    s = engine.cfg.image_size
    rng = np.random.default_rng(11)
    batches = [rng.integers(0, 256, (n, s, s, 3), dtype=np.uint8)
               for n in (3, 4)]
    want = [engine.predict(images) for images in batches]
    compiles = engine.compile_count
    handles = [engine.dispatch(images) for images in batches]
    assert all(h.t_wait is None for h in handles)
    got = [h.result() for h in handles]
    for (ids, probs), (want_ids, want_probs) in zip(got, want):
        assert ids.dtype == np.int32 and ids.shape == want_ids.shape
        assert np.array_equal(ids, want_ids)
        assert np.array_equal(probs, want_probs)    # ==, not allclose
    wait_until(lambda: all(h.done() for h in handles))
    first, second = handles
    assert first.t_dispatch <= second.t_dispatch <= first.t_wait <= second.t_wait
    assert engine.compile_count == compiles
    assert not hasattr(engine, "phase_marks")   # the marks live on the handle


# --- HTTP -------------------------------------------------------------------


def test_http_predict_round_trip(served):
    cfg, engine, url, _ = served
    # raw image bytes
    resp = post_bytes(url + "/predict", png_bytes(seed=1))
    assert len(resp["classes"]) == engine.topk
    assert len(resp["probs"]) == engine.topk
    assert all(0 <= c < cfg.num_classes for c in resp["classes"])
    assert resp["probs"] == sorted(resp["probs"], reverse=True)
    # base64 JSON with a per-request topk
    resp2 = post_json(url + "/predict",
                      {"image": base64.b64encode(png_bytes(seed=2)).decode(),
                       "topk": 2})
    assert len(resp2["classes"]) == 2 and len(resp2["probs"]) == 2


def test_http_mixed_burst_zero_recompiles(served):
    """A concurrent burst of requests exercises multiple buckets through the
    batcher with zero recompiles (the acceptance-criteria check)."""
    cfg, engine, url, _ = served
    before = engine.compile_count
    results, errors = [], []
    lock = threading.Lock()

    def worker(seed):
        try:
            r = post_bytes(url + "/predict", png_bytes(seed=seed))
            with lock:
                results.append(r)
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert len(results) == 10
    assert engine.compile_count == before  # zero recompiles under load
    # the burst actually batched: fewer flushes than requests
    metrics = get_json(url + "/metrics")
    assert metrics["requests_total"] >= 10
    assert metrics["compile_count"] == before


def test_http_healthz_and_metrics(served):
    _, engine, url, _ = served
    health = get_json(url + "/healthz")
    assert health["status"] == "ok"
    assert health["buckets"] == list(engine.buckets)
    metrics = get_json(url + "/metrics")
    for key in ("requests_total", "errors_total", "requests_per_sec",
                "latency_s_p50", "latency_s_p95", "latency_s_p99",
                "batch_occupancy_mean", "queue_depth"):
        assert key in metrics, key


def test_http_bad_requests(served):
    _, _, url, _ = served
    with pytest.raises(urllib.error.HTTPError) as e:
        post_bytes(url + "/predict", b"not an image")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        post_bytes(url + "/nope", png_bytes())
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        post_json(url + "/predict",
                  {"image": base64.b64encode(png_bytes()).decode(),
                   "topk": 99})
    assert e.value.code == 400


# --- serve.jsonl contract + bench -------------------------------------------

# every serve_request record must carry these (vitax/serve/server.py
# REQUIRED_SERVE_KEYS + the Recorder envelope)
ENVELOPE_KEYS = ("schema", "time", "kind")


def test_serve_jsonl_contract(served):
    from vitax.serve import REQUIRED_SERVE_KEYS
    _, _, url, metrics_dir = served
    post_bytes(url + "/predict", png_bytes(seed=9))  # at least one record
    path = os.path.join(metrics_dir, "serve.jsonl")
    assert os.path.exists(path)
    deadline = time.time() + 5.0    # a record is written after its reply
    while True:
        records = [json.loads(line) for line in open(path) if line.strip()]
        kinds = {r["kind"] for r in records}
        if "serve_request" in kinds or time.time() >= deadline:
            break
        time.sleep(0.02)
    assert "serve_start" in kinds and "serve_request" in kinds
    reqs = [r for r in records if r["kind"] == "serve_request"]
    for rec in reqs:
        for key in ENVELOPE_KEYS + REQUIRED_SERVE_KEYS:
            assert key in rec, (key, rec)
        assert rec["schema"] == 1
        assert rec["batch_size"] <= rec["bucket"]
        assert rec["queue_wait_s"] <= rec["latency_s"]


def test_serve_bench_reports(served):
    """tools/serve_bench.py --json contract: throughput + p50/p95/p99 from
    both the client loop and the server's serve.jsonl records."""
    _, _, url, metrics_dir = served
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serve_bench
    finally:
        sys.path.pop(0)
    summary = serve_bench.run_bench(
        url, concurrency=4, requests_per_worker=3, image_size=20,
        timeout=60.0, serve_jsonl=os.path.join(metrics_dir, "serve.jsonl"))
    assert summary["completed"] == 12 and summary["errors"] == 0
    assert summary["throughput_rps"] > 0
    for key in ("latency_s_p50", "latency_s_p95", "latency_s_p99"):
        assert summary[key] > 0
        assert summary["server"][key] > 0
    assert summary["server"]["records"] >= 12
    assert 0 < summary["server"]["batch_occupancy_mean"] <= 1.0
    # --json emits one parseable object
    json.dumps(summary)


# --- eval top-5 + telemetry (satellite) -------------------------------------


def test_eval_event_in_metrics_jsonl(served):
    """The fixture's training run had --metrics_dir + test_epoch_interval=1,
    so eval_on_val must have emitted a kind:"eval" event (epoch, top1, top5,
    n) into metrics.jsonl — and metrics_report must surface it."""
    _, _, _, metrics_dir = served
    path = os.path.join(metrics_dir, "metrics.jsonl")
    assert os.path.exists(path)
    evals = [json.loads(line) for line in open(path)
             if line.strip() and '"eval"' in line]
    evals = [r for r in evals if r.get("kind") == "eval"]
    assert evals, "train() with test_epoch_interval=1 emitted no eval event"
    ev = evals[-1]
    assert ev["epoch"] == 1
    assert 0.0 <= ev["top1"] <= ev["top5"] <= 1.0
    assert ev["n"] > 0

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    summary = metrics_report.summarize(path)
    assert summary["eval_last"] == {k: ev[k]
                                    for k in ("epoch", "top1", "top5", "n")}


# --- config validation (satellite) ------------------------------------------


@pytest.mark.parametrize("kw,match", [
    (dict(eval_max_batches=-1), "eval_max_batches"),
    (dict(serve_port=-1), "serve_port"),
    (dict(serve_port=70000), "serve_port"),
    (dict(serve_max_batch=0), "serve_max_batch"),
    (dict(serve_max_batch=3), "power of two"),
    (dict(max_batch_wait_ms=-1.0), "max_batch_wait_ms"),
    (dict(serve_topk=0), "serve_topk"),
    (dict(serve_topk=-3), "serve_topk"),
])
def test_config_serve_validation_rejects(kw, match):
    with pytest.raises(AssertionError, match=match):
        tiny_cfg(**kw)


def test_config_serve_defaults_valid():
    cfg = Config().validate()
    assert cfg.serve_port == 8000 and cfg.serve_max_batch == 8
    assert cfg.serve_topk == 5 and cfg.max_batch_wait_ms == 5.0
