"""The server's own spans (vitax/serve: batcher.py, engine.py, server.py):
`serve_request` and `serve_batch` records in serve.jsonl carry absolute
`time.time()` marks for every phase of a request and of a batch, and cost
nothing but a few clock reads when `--metrics_dir` is unset.

One tiny real engine (seeded init, no training, no checkpoint) behind one
server with telemetry on; every statement about the records is one case of
one parametrised test, over the same burst of traffic.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.test_serve import png_bytes, post_bytes, tiny_cfg

BATCH_MARKS = ("t_collect", "t_stack", "t_put", "t_dispatch", "t_wait",
               "t_deliver", "t_end")
NEW_REQUEST_KEYS = ("batch_id", "t_start", "read_s", "decode_s", "wake_s",
                    "reply_s")


def tiny_engine(cfg):
    """Seeded parameters straight into an engine, as the benchmark's serve
    generator does (no train run, no checkpoint read)."""
    from vitax.parallel.mesh import build_mesh
    from vitax.parallel.sharding import param_specs, shardings_of
    from vitax.serve import engine as serve_engine
    mesh = build_mesh(cfg)
    model = serve_engine._build_model(cfg, mesh, quantized=False)
    sample = jnp.zeros((mesh.shape["dp"] * mesh.shape["fsdp"],
                        cfg.image_size, cfg.image_size, 3), jnp.float32)

    def init(rng):
        return model.init(rng, sample, True)

    abstract = jax.eval_shape(init, jax.random.key(cfg.seed))
    shardings = shardings_of(mesh, param_specs(abstract, cfg, mesh))
    params = jax.jit(init, out_shardings=shardings)(jax.random.key(cfg.seed))
    engine = serve_engine.InferenceEngine(cfg, mesh, model, params)
    engine.warmup()
    return engine


def read_events(metrics_dir):
    with open(os.path.join(metrics_dir, "serve.jsonl"), encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def burst(url, n, seed0=0):
    replies = [None] * n

    def one(i):
        replies[i] = post_bytes(url + "/predict", png_bytes(seed=seed0 + i))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(r is not None for r in replies)
    return replies


@pytest.fixture(scope="module")
def spans(devices8, tmp_path_factory):
    """(requests, batches, engine): the records of three bursts against a
    server with telemetry on, read after a drain (a record is written after
    its reply, so only the drain says that every one is on disk)."""
    from vitax.serve import start_server
    from vitax.serve.server import drain
    metrics_dir = str(tmp_path_factory.mktemp("spans") / "metrics")
    cfg = tiny_cfg(metrics_dir=metrics_dir, serve_port=0)
    engine = tiny_engine(cfg)
    httpd, ctx = start_server(cfg, engine, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        assert ctx.recorder is not None
        for round_ in range(3):
            burst(url, 6, seed0=10 * round_)
            time.sleep(0.03)        # past the 10 ms flush deadline
    finally:
        assert drain(httpd, ctx)
    events = read_events(metrics_dir)
    requests = [e for e in events if e["kind"] == "serve_request"]
    batches = sorted((e for e in events if e["kind"] == "serve_batch"),
                     key=lambda e: e["batch_id"])
    assert len(requests) == 18 and len(batches) >= 5    # buckets up to 4
    return requests, batches, engine


def check_request_fields(requests, batches):
    from vitax.serve import REQUIRED_SERVE_KEYS
    for r in requests:
        for key in ("schema", "time", "kind") + REQUIRED_SERVE_KEYS \
                + NEW_REQUEST_KEYS:
            assert key in r, (key, r)
        assert r["batch_size"] <= r["bucket"] and r["topk"] == 3
        assert r["queue_wait_s"] <= r["latency_s"]
        for key in ("read_s", "decode_s", "wake_s", "reply_s", "infer_s"):
            assert 0.0 <= r[key] < 60.0, (key, r)
        # written after the reply: the record's own time closes the request
        assert r["time"] >= r["t_start"] + r["latency_s"] + r["reply_s"] - 1e-3
        assert "req_id" not in r    # nothing read it: batch_id is the join


def check_latency_identity(requests, batches):
    """latency_s = (t_enqueue - t_start) + queue_wait_s + infer_s + wake_s,
    and read_s + decode_s is all of the first term but the brownout sample
    and the queue's lock: a few microseconds, milliseconds on a loaded
    machine with eight handler threads on one GIL."""
    for r in requests:
        rest = r["latency_s"] - (r["queue_wait_s"] + r["infer_s"]
                                 + r["wake_s"])
        assert rest >= r["read_s"] + r["decode_s"] - 1e-5, r
        assert rest - (r["read_s"] + r["decode_s"]) < 0.25, r


def check_batch_ids(requests, batches):
    by_id = {b["batch_id"]: b for b in batches}
    assert sorted(by_id) == list(range(len(batches)))      # count from 0
    riders = {}
    for r in requests:
        assert r["batch_id"] in by_id, r
        riders[r["batch_id"]] = riders.get(r["batch_id"], 0) + 1
        batch = by_id[r["batch_id"]]
        assert (r["batch_size"], r["bucket"], r["infer_s"]) == (
            batch["batch_size"], batch["bucket"], batch["infer_s"])
        # the request waited from before its batch's put to after its deliver
        assert r["t_start"] <= batch["t_put"]
        assert r["t_start"] + r["latency_s"] >= batch["t_deliver"] - 1e-5
    assert riders == {i: b["batch_size"] for i, b in by_id.items()}


def check_marks_ascend(requests, batches):
    for b in batches:
        marks = [b[m] for m in BATCH_MARKS]
        assert marks == sorted(marks), b
        assert b["infer_s"] == pytest.approx(b["t_deliver"] - b["t_put"],
                                             abs=2e-6)
        assert b["t_wait"] > b["t_put"]     # a real engine marks its phases


def check_no_holes(requests, batches):
    """A batch dispatched with nothing in flight begins where the batch
    before it ended; one dispatched behind a running batch (a burst of 6
    whose first requests went alone at the deadline leaves a full bucket of
    4 behind them) began before that batch was delivered."""
    for prev, nxt in zip(batches, batches[1:]):
        if nxt["overlapped"]:
            assert nxt["t_collect"] < prev["t_deliver"], (prev, nxt)
        else:
            assert nxt["t_collect"] == prev["t_end"], (prev, nxt)


def check_dead_field_gone(requests, batches):
    for b in batches:
        assert "queue_wait_s_max" not in b
        assert set(b) == {"schema", "time", "kind", "rank", "batch_id",
                          "batch_size", "bucket", "infer_s", "overlapped",
                          *BATCH_MARKS}


def check_off_builds_nothing(engine):
    """`--metrics_dir` unset: no recorder, the same answers from the same
    compiled buckets."""
    from vitax.serve import start_server, stop_server
    cfg = tiny_cfg(serve_port=0)
    images = np.random.default_rng(3).integers(
        0, 256, size=(3, cfg.image_size, cfg.image_size, 3), dtype=np.uint8)
    compiles = engine.compile_count
    want_ids, want_probs = engine.predict(images)
    httpd, ctx = start_server(cfg, engine, port=0)
    try:
        assert ctx.recorder is None
        assert ctx.batcher.on_batch is None     # so no stats dict is built
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        assert len(burst(url, 5)) == 5
        results = [ctx.batcher.submit(img).result(timeout=60)
                   for img in images]
    finally:
        stop_server(httpd, ctx)
    for row, res in enumerate(results):
        np.testing.assert_array_equal(res.classes, want_ids[row])
        np.testing.assert_allclose(res.probs, want_probs[row], rtol=1e-5)
        assert res.t_deliver > 0 and res.batch_id >= 0
    got_ids, got_probs = engine.predict(images)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_array_equal(got_probs, want_probs)
    assert engine.compile_count == compiles == len(engine.buckets)


STATEMENTS = {
    "request_fields": check_request_fields,
    "latency_identity": check_latency_identity,
    "batch_id_names_its_batch": check_batch_ids,
    "batch_marks_ascend": check_marks_ascend,
    "collect_starts_at_previous_end": check_no_holes,
    "queue_wait_s_max_gone": check_dead_field_gone,
}


@pytest.mark.parametrize("statement", sorted(STATEMENTS))
def test_serve_span_records(spans, statement):
    requests, batches, _ = spans
    STATEMENTS[statement](requests, batches)


def test_no_recorder_builds_nothing(spans):
    check_off_builds_nothing(spans[2])


def test_predict_batch_items_share_the_envelope(devices8, tmp_path):
    """The fleet's /predict_batch writes the same record through the same
    function: its items share `t_start` and `read_s`, each has its own
    `decode_s`, and an engine stand-in without phase marks gets an empty
    `put` and `dispatch`."""
    import base64

    from tests.test_fleet import FakeEngine
    from tests.test_serve import post_json
    from vitax.serve import start_server
    from vitax.serve.server import drain
    metrics_dir = str(tmp_path / "metrics")
    cfg = tiny_cfg(metrics_dir=metrics_dir, serve_port=0)
    httpd, ctx = start_server(cfg, FakeEngine(), port=0)
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        items = [base64.b64encode(png_bytes(seed=i)).decode() for i in range(3)]
        out = post_json(url + "/predict_batch", {"items": items})
        assert [r["status"] for r in out["results"]] == [200, 200, 200]
    finally:
        assert drain(httpd, ctx)
    events = read_events(metrics_dir)
    requests = [e for e in events if e["kind"] == "serve_request"]
    batches = [e for e in events if e["kind"] == "serve_batch"]
    assert len(requests) == 3 and all(r["batched"] for r in requests)
    assert len({(r["t_start"], r["read_s"]) for r in requests}) == 1
    for key in NEW_REQUEST_KEYS:
        assert all(key in r for r in requests), key
    for b in batches:
        assert b["t_put"] == b["t_dispatch"] == b["t_wait"] <= b["t_deliver"]
