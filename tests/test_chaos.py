"""Serve-path chaos layer: fault sites, breaker, retry budget, hedging,
brownout, and the end-to-end chaos drill.

Unit tier pins the CircuitBreaker state machine (open / half-open /
re-close, never opens under threshold) and RetryBudget token accounting
with injected clocks, the BrownoutController hysteresis, the health-loop
jitter seam, and the deterministic per-site firing indices of the new
serve fault sites (engine_predict, batcher_flush, replica_health,
router_dispatch). Router tier drives dispatch() over in-process fake
replicas: budget exhaustion -> fast 503 + Retry-After, hedges firing only
past the threshold and never double-counting, breaker containment of a
replica that fails every dispatch while answering health checks.

The drill (tier-1, real HTTP on ephemeral ports, fake predict_fn): a
3-replica fleet under a paced serve_bench burst with one replica
SIGKILLed, one predict-hung (batcher_flush hang), and one health-flapped
finishes with every client response inside the 200 / 429+Retry-After /
503+Retry-After envelope while the breaker opens and re-closes and the
retry budget stays within its fraction — plus a no-fault twin pinning
that an armed-but-never-firing plan changes nothing in the request path.
"""

import io
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from vitax import faults
from vitax.config import Config
from vitax.serve.batcher import DynamicBatcher
from vitax.serve.fleet import ReplicaManager, Router, start_router, stop_router
from vitax.serve.fleet.breaker import (CLOSED, HALF_OPEN, OPEN,
                                       CircuitBreaker, RetryBudget)
from vitax.serve.server import BrownoutController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test starts and ends with no plan armed (the registry is
    module-global, so a leaked plan would poison unrelated tests)."""
    faults.uninstall()
    yield
    faults.uninstall()


def tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=16, dtype="float32", lr=1e-3, warmup_steps=2,
        serve_max_batch=4, serve_topk=3, max_batch_wait_ms=10.0, seed=0,
    )
    base.update(kw)
    return Config(**base).validate()


def png_bytes(size: int = 16, seed: int = 0) -> bytes:
    from PIL import Image
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, size=(size, size, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr, "RGB").save(buf, "PNG")
    return buf.getvalue()


def post_bytes(url: str, body: bytes, content_type: str = "image/png",
               timeout: float = 30.0) -> dict:
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": content_type})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def get_json(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class DummyRecorder:
    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def event(self, kind, **payload):
        with self._lock:
            self.events.append((kind, payload))

    def of_kind(self, kind):
        with self._lock:
            return [p for k, p in self.events if k == kind]

    def close(self):
        pass


class FakeReplica:
    """In-process replica endpoint with failure dials (same shape as the
    test_fleet stand-in, plus a raw hit counter so breaker tests can pin
    that an OPEN breaker never even connects)."""

    def __init__(self, name: str):
        self.name = name
        self.fail_predicts = False
        self.queue_full = False
        self.hold = None             # Event: /predict blocks until set
        self.predict_started = threading.Event()
        self.predict_count = 0
        self.post_hits = 0           # every /predict arrival, any outcome
        self._lock = threading.Lock()
        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: A003
                pass

            def _reply(self, code, payload, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                if self.path == "/healthz":
                    self._reply(200, {"status": "ok", "ready": True})
                else:
                    self._reply(200, {"requests_total": fake.predict_count})

            def do_POST(self):  # noqa: N802
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                with fake._lock:
                    fake.post_hits += 1
                if fake.queue_full:
                    self._reply(503, {"error": "overloaded",
                                      "reason": "queue_full"},
                                headers={"Retry-After": "2"})
                    return
                if fake.fail_predicts:
                    self._reply(500, {"error": "replica exploded"})
                    return
                fake.predict_started.set()
                if fake.hold is not None:
                    fake.hold.wait(timeout=30)
                with fake._lock:
                    fake.predict_count += 1
                self._reply(200, {"classes": [1, 0, 2],
                                  "probs": [0.5, 0.3, 0.2],
                                  "latency_ms": 1.0,
                                  "replica": fake.name})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def fleet_factory():
    cleanup = []

    def build(n=2, recorder=None, **router_kw):
        fakes = [FakeReplica("abcdefgh"[i]) for i in range(n)]
        manager = ReplicaManager(recorder=recorder, fail_threshold=2,
                                 health_jitter=0.0)
        for f in fakes:
            manager.adopt(f.url, name=f.name)
        manager.poll_once()
        router_kw.setdefault("request_timeout_s", 10.0)
        router = Router(manager, recorder=recorder, **router_kw)
        cleanup.append(fakes)
        return manager, router, fakes

    yield build
    for fakes in cleanup:
        for f in fakes:
            f.stop()


# --- circuit breaker state machine -------------------------------------------


def test_breaker_never_opens_under_threshold():
    t = [0.0]
    br = CircuitBreaker("r", fail_threshold=3, cooldown_s=2.0,
                        clock=lambda: t[0])
    for _ in range(2):
        br.record_failure()
    assert br.state() == CLOSED and br.opens_total == 0
    br.record_success()  # consecutive counter resets
    for _ in range(2):
        br.record_failure()
    assert br.state() == CLOSED and br.opens_total == 0
    assert br.eligible() and br.begin()


def test_breaker_open_half_open_reclose_matrix():
    t = [0.0]
    events = []
    br = CircuitBreaker("r", fail_threshold=3, cooldown_s=2.0,
                        clock=lambda: t[0], on_event=events.append)
    for _ in range(3):
        br.record_failure()
    assert br.state() == OPEN and br.opens_total == 1
    assert not br.eligible() and not br.begin()  # cooling down
    # a straggler failure from a pre-trip dispatch is a no-op
    br.record_failure()
    assert br.state() == OPEN and br.opens_total == 1

    t[0] = 2.0  # cooldown elapsed: exactly one probe admitted
    assert br.eligible()
    assert br.begin() and br.state() == HALF_OPEN
    assert not br.eligible() and not br.begin()  # probe slot taken
    br.record_failure()  # probe failed -> reopen for another cooldown
    assert br.state() == OPEN and br.reopens_total == 1
    assert not br.begin()

    t[0] = 4.0
    assert br.begin() and br.state() == HALF_OPEN
    br.record_success()  # probe succeeded -> back in rotation
    assert br.state() == CLOSED and br.closes_total == 1
    assert [e["event"] for e in events] == \
        ["open", "half_open", "reopen", "half_open", "close"]
    assert all(e["replica"] == "r" for e in events)


def test_breaker_release_unused_frees_probe_slot():
    t = [0.0]
    br = CircuitBreaker("r", fail_threshold=1, cooldown_s=1.0,
                        clock=lambda: t[0])
    br.record_failure()
    t[0] = 1.0
    assert br.begin()           # claims the half-open probe
    br.release_unused()         # picked but never dispatched
    assert br.begin()           # slot is free again


def test_retry_budget_token_accounting():
    b = RetryBudget(ratio=0.25, cap=10.0)
    assert b.enabled
    for _ in range(10):          # starts full at cap
        assert b.withdraw()
    assert not b.withdraw()      # dry
    assert b.exhausted_total == 1 and b.granted_total == 10
    for _ in range(4):           # 4 requests earn one retry token
        b.deposit()
    assert b.withdraw() and not b.withdraw()
    snap = b.snapshot()
    assert snap["granted_total"] == 11 and snap["exhausted_total"] == 2
    # ratio 0 disables: every withdraw granted (pre-budget behavior)
    b0 = RetryBudget(ratio=0.0)
    assert not b0.enabled
    assert all(b0.withdraw() for _ in range(100))


# --- router: budget, breaker, hedging ----------------------------------------


def test_retry_budget_exhaustion_fast_503(fleet_factory):
    rec = DummyRecorder()
    _, router, fakes = fleet_factory(n=2, recorder=rec,
                                     retry_budget_ratio=0.1)
    for f in fakes:
        f.fail_predicts = True
    while router.budget.withdraw():  # drain the initial full bucket
        pass
    status, headers, payload = router.dispatch(png_bytes(), "image/png")
    assert status == 503
    assert payload["reason"] == "retry_budget_exhausted"
    assert headers["Retry-After"] == "1"
    # the first attempt went out, the RETRY did not: budget bounds
    # amplification, not first tries
    assert fakes[0].post_hits + fakes[1].post_hits == 1
    assert any(p.get("event") == "exhausted"
               for p in rec.of_kind("retry_budget"))
    snap = router.fleet_metrics()
    assert snap["retry_budget"]["exhausted_total"] >= 1


def test_breaker_contains_replica_that_fails_every_dispatch(fleet_factory):
    rec = DummyRecorder()
    _, router, fakes = fleet_factory(
        n=1, recorder=rec, breaker_threshold=2, breaker_cooldown_s=0.2)
    fakes[0].fail_predicts = True
    for _ in range(2):
        status, _, payload = router.dispatch(png_bytes(), "image/png")
        assert status == 503 and payload["reason"] == "dispatch_failed"
    br = router._breaker("a")
    assert br.state() == OPEN and br.opens_total == 1
    # while open the router never even connects (no timeout burned)
    hits = fakes[0].post_hits
    status, _, payload = router.dispatch(png_bytes(), "image/png")
    assert status == 503 and fakes[0].post_hits == hits
    # replica recovers; after the cooldown one probe re-admits it
    fakes[0].fail_predicts = False
    time.sleep(0.25)
    status, _, _ = router.dispatch(png_bytes(), "image/png")
    assert status == 200
    assert br.state() == CLOSED and br.closes_total == 1
    assert [p["event"] for p in rec.of_kind("breaker")] == \
        ["open", "half_open", "close"]
    snap = router.fleet_metrics()
    assert snap["breaker_opens"] == 1
    assert snap["breakers"]["a"]["state"] == CLOSED


def test_breaker_ignores_backpressure_and_client_errors(fleet_factory):
    """queue_full 503 and 4xx mean the replica ANSWERED: backpressure and
    client mistakes must never trip the breaker."""
    _, router, fakes = fleet_factory(n=1, breaker_threshold=2)
    fakes[0].queue_full = True
    for _ in range(4):
        status, headers, _ = router.dispatch(png_bytes(), "image/png")
        assert status == 429 and "Retry-After" in headers
    br = router._breaker("a")
    assert br.state() == CLOSED and br.opens_total == 0
    assert br.snapshot()["consecutive_failures"] == 0


def test_hedge_fires_only_past_threshold(fleet_factory):
    _, router, fakes = fleet_factory(n=2, hedge_after_ms=500.0)
    for _ in range(3):  # fast primaries: the hedge must stay holstered
        status, _, _ = router.dispatch(png_bytes(), "image/png")
        assert status == 200
    assert router.metrics.hedges_total == 0
    assert router.budget.snapshot()["granted_total"] == 0


def test_hedge_wins_and_never_double_counts(fleet_factory):
    rec = DummyRecorder()
    _, router, fakes = fleet_factory(n=2, recorder=rec, hedge_after_ms=50.0)
    fakes[0].hold = threading.Event()  # primary (first adopted) wedges
    status, _, payload = router.dispatch(png_bytes(), "image/png")
    assert status == 200
    assert json.loads(payload)["replica"] == "b"  # the hedge answered
    assert router.metrics.hedges_total == 1
    assert router.metrics.hedge_wins_total == 1
    assert router.metrics.requests_total == 1     # counted exactly once
    events = [p["event"] for p in rec.of_kind("hedge")]
    assert events == ["fired", "win"]
    # the losing primary lands later; per-request counters must not move
    fakes[0].hold.set()
    deadline = time.time() + 10
    while fakes[0].predict_count == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert fakes[0].predict_count == 1
    time.sleep(0.1)
    assert router.metrics.requests_total == 1
    assert router.metrics.errors_total == 0
    assert router.manager.total_in_flight() == 0  # both slots released


def test_hedge_bounded_by_retry_budget(fleet_factory):
    _, router, fakes = fleet_factory(n=2, hedge_after_ms=30.0,
                                     retry_budget_ratio=0.1)
    while router.budget.withdraw():
        pass
    fakes[0].hold = threading.Event()
    done = []
    t = threading.Thread(target=lambda: done.append(
        router.dispatch(png_bytes(), "image/png")))
    t.start()
    time.sleep(0.3)  # well past the hedge delay: a hedge WOULD have fired
    assert router.metrics.hedges_total == 0  # budget dry -> no hedge
    fakes[0].hold.set()
    t.join(timeout=10)
    assert done and done[0][0] == 200  # primary still answers


# --- brownout hysteresis ------------------------------------------------------


def test_brownout_hysteresis_with_injected_clock():
    entered, exited = [], []
    ctl = BrownoutController(
        queue_max=10, enter_frac=0.8, exit_frac=0.2, dwell_s=2.0,
        clock=lambda: 0.0, on_enter=lambda: entered.append(1),
        on_exit=exited.append)
    assert ctl.enabled
    # pressure must SUSTAIN the dwell: a blip never flips the mode
    assert ctl.observe(9, now=0.0) is False
    assert ctl.observe(9, now=1.0) is False
    assert ctl.observe(0, now=1.5) is False    # streak broken
    assert ctl.observe(9, now=2.0) is False    # new streak starts here
    assert ctl.observe(9, now=3.9) is False
    assert ctl.observe(9, now=4.0) is True     # dwell met -> degraded
    assert entered == [1] and ctl.enters_total == 1
    # depths between the thresholds hold the current state
    assert ctl.observe(5, now=5.0) is True
    # calm must also sustain the dwell
    assert ctl.observe(1, now=6.0) is True
    assert ctl.observe(3, now=7.0) is True     # calm streak broken (3 > 2)
    assert ctl.observe(1, now=8.0) is True
    assert ctl.observe(1, now=10.0) is False   # recovered
    assert len(exited) == 1
    assert exited[0] == pytest.approx(6.0)     # degraded t=4..10
    assert ctl.degraded_seconds(now=11.0) == pytest.approx(6.0)


def test_brownout_disabled_without_queue_bound():
    assert not BrownoutController(queue_max=0, enter_frac=0.8, exit_frac=0.2,
                                  dwell_s=1.0).enabled
    assert not BrownoutController(queue_max=10, enter_frac=0.0, exit_frac=0.0,
                                  dwell_s=1.0).enabled
    ctl = BrownoutController(queue_max=0, enter_frac=0.8, exit_frac=0.2,
                             dwell_s=0.0)
    assert ctl.observe(10 ** 6) is False and ctl.degraded_seconds() == 0.0


class FakeEngine:
    """InferenceEngine stand-in (same surface the server/batcher touch)."""

    def __init__(self):
        self.buckets = (1, 2, 4)
        self.topk = 3
        self.compile_count = 3
        self.ready = True
        self.hold = None
        self.predict_started = threading.Event()

    def predict(self, images):
        self.predict_started.set()
        if self.hold is not None:
            self.hold.wait(timeout=30)
        n = images.shape[0]
        return (np.tile(np.arange(3, dtype=np.int32), (n, 1)),
                np.tile(np.array([0.5, 0.3, 0.2], np.float32), (n, 1)))


def test_brownout_server_degrades_and_recovers():
    """Real server + FakeEngine: sustained queue pressure enters degraded
    (healthz advertises it, topk clamps to 1, batcher deadline shortens);
    drain + dwell exits and restores the tuning."""
    from vitax.serve import start_server, stop_server
    engine = FakeEngine()
    engine.hold = threading.Event()
    cfg = tiny_cfg(serve_max_batch=1, serve_queue_max=4,
                   max_batch_wait_ms=50.0, serve_brownout_enter_frac=0.5,
                   serve_brownout_exit_frac=0.25, serve_brownout_dwell_s=0.15,
                   serve_brownout_wait_ms=1.0)
    httpd, ctx = start_server(cfg, engine, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    results, threads = [], []

    def bg():
        results.append(post_bytes(url + "/predict", png_bytes()))

    try:
        assert get_json(url + "/healthz")["degraded"] is False
        for _ in range(3):  # 1 in predict + 2 queued >= enter depth 2
            t = threading.Thread(target=bg)
            t.start()
            threads.append(t)
        assert engine.predict_started.wait(timeout=10)
        deadline = time.time() + 10
        while (ctx.batcher.queue_depth() < 2 and time.time() < deadline):
            time.sleep(0.01)
        while (not get_json(url + "/healthz")["degraded"]
               and time.time() < deadline):
            time.sleep(0.02)  # healthz polls feed the pressure window
        health = get_json(url + "/healthz")
        assert health["degraded"] is True
        assert ctx.batcher.max_wait_s == pytest.approx(0.001)  # shortened
        snap = get_json(url + "/metrics")
        assert snap["degraded"] is True and snap["brownout_enters"] == 1
        assert snap["ready"] is True  # degraded != unready: still serving
        # a request admitted while degraded sheds optional work: topk -> 1
        t = threading.Thread(target=bg)
        t.start()
        threads.append(t)
        # recovery: drain, hold calm for the dwell, tuning restored
        engine.hold.set()
        for t in threads:
            t.join(timeout=30)
        assert len(results) == 4
        topks = sorted(len(r["classes"]) for r in results)
        assert topks[-1] == 3 and topks[0] == 1  # pre-brownout 3, degraded 1
        while (get_json(url + "/healthz")["degraded"]
               and time.time() < deadline):
            time.sleep(0.02)
        snap = get_json(url + "/metrics")
        assert snap["degraded"] is False
        assert snap["degraded_seconds"] > 0
        assert ctx.batcher.max_wait_s == pytest.approx(0.05)  # restored
    finally:
        engine.hold.set()
        stop_server(httpd, ctx)


# --- fault sites: wiring + determinism ---------------------------------------


def test_serve_fault_sites_registered():
    for site in ("engine_predict", "batcher_flush", "replica_health",
                 "router_dispatch"):
        assert site in faults.SITES


def test_fault_site_firing_index_deterministic_across_reinstalls():
    plan = '{"site": "router_dispatch", "at": 3, "action": "oserror"}'

    def firing_indices(calls=6):
        fired = []
        for i in range(1, calls + 1):
            try:
                faults.fire("router_dispatch")
            except OSError:
                fired.append(i)
        return fired

    faults.install(plan)
    first = firing_indices()
    faults.uninstall()
    faults.install(plan)  # fresh counters: the same plan replays exactly
    assert firing_indices() == first == [3]


def test_router_dispatch_site_deterministic_across_router_restarts(
        fleet_factory):
    """Same plan -> same firing index, through two router instances over
    the same fleet (each install resets the per-site counters)."""
    plan = '{"site": "router_dispatch", "at": 2, "action": "oserror"}'
    manager, router1, fakes = fleet_factory(n=2)
    rec = DummyRecorder()
    faults.set_reporter(lambda p: rec.event("serve_fault", **p))
    for router in (router1, Router(manager, request_timeout_s=10.0)):
        faults.install(plan)
        s1, _, _ = router.dispatch(png_bytes(), "image/png")
        s2, _, _ = router.dispatch(png_bytes(), "image/png")
        assert (s1, s2) == (200, 200)  # the injected failure was retried
        assert router.metrics.retries_total == 1
    fired = rec.of_kind("serve_fault")
    assert [p["index"] for p in fired] == [2, 2]
    assert all(p["site"] == "router_dispatch" for p in fired)


def test_replica_health_site_targets_by_sweep_order():
    """Probes sweep registration order, so with N replicas index k*N + i
    targets replica i — plans can flap ONE replica's health."""
    faults.install('{"site": "replica_health", "at": 3, "action": "oserror"}')
    manager = ReplicaManager(
        health_jitter=0.0,
        http_get=lambda url, timeout: {"status": "ok", "ready": True})
    ra = manager.adopt("http://x:1", name="a")
    rb = manager.adopt("http://x:2", name="b")
    manager.poll_once()   # indices 1, 2: both admitted
    assert ra.state == "ready" and rb.state == "ready"
    manager.poll_once()   # indices 3 (a: injected failure), 4 (b: ok)
    assert ra.health_failures == 1 and rb.health_failures == 0
    assert ra.state == "ready"  # one flap is below fail_threshold


def test_batcher_flush_site_fails_batch_without_killing_worker():
    faults.install('{"site": "batcher_flush", "at": 1, "action": "oserror"}')
    calls = []

    def predict(images):
        calls.append(images.shape[0])
        return (np.zeros((images.shape[0], 3), np.int32),
                np.zeros((images.shape[0], 3), np.float32))

    b = DynamicBatcher(predict, max_batch=2, max_wait_ms=1.0,
                       bucket_of=lambda n: 2)
    try:
        fut = b.submit(np.zeros((16, 16, 3), np.uint8))
        with pytest.raises(OSError, match="injected fault"):
            fut.result(timeout=10)
        assert calls == []  # the fault fired before predict
        # the worker survived: the next batch flows
        fut = b.submit(np.zeros((16, 16, 3), np.uint8))
        assert fut.result(timeout=10).batch_size == 1
        assert calls == [1]  # the engine pads to buckets, not the batcher
    finally:
        b.close()


def test_engine_predict_site_fires_before_any_work():
    """The engine hook is the first statement of predict(): with a plan
    armed it fires before shapes are even read (no jax needed to pin)."""
    from vitax.serve.engine import InferenceEngine
    faults.install('{"site": "engine_predict", "at": 1, "action": "oserror"}')
    with pytest.raises(OSError, match="injected fault"):
        InferenceEngine.predict(object.__new__(InferenceEngine), None)


# --- health-loop jitter (satellite) ------------------------------------------


def test_health_interval_jitter_bounded_and_seeded():
    m1 = ReplicaManager(health_interval_s=1.0, health_jitter=0.2,
                        rng=random.Random(7))
    intervals = [m1._next_interval() for _ in range(64)]
    assert all(0.8 <= v <= 1.2 for v in intervals)
    assert len(set(intervals)) > 1  # actually jittered
    m2 = ReplicaManager(health_interval_s=1.0, health_jitter=0.2,
                        rng=random.Random(7))
    assert [m2._next_interval() for _ in range(64)] == intervals  # seeded
    # jitter 0 restores the fixed cadence; invalid jitter refused
    m3 = ReplicaManager(health_interval_s=1.0, health_jitter=0.0)
    assert {m3._next_interval() for _ in range(8)} == {1.0}
    with pytest.raises(AssertionError):
        ReplicaManager(health_jitter=1.5)


# --- the chaos drill ---------------------------------------------------------


_STUB_SRC = r"""
import json, sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

class H(BaseHTTPRequestHandler):
    def log_message(self, fmt, *args):
        pass
    def _reply(self, code, payload):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
    def do_GET(self):
        self._reply(200, {"status": "ok", "ready": True})
    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self._reply(200, {"classes": [1, 0, 2], "probs": [0.5, 0.3, 0.2],
                          "latency_ms": 1.0})

httpd = ThreadingHTTPServer(("127.0.0.1", int(sys.argv[1])), H)
httpd.daemon_threads = True
print("ready", flush=True)
httpd.serve_forever()
"""


def _start_stub(port: int):
    proc = subprocess.Popen([sys.executable, "-c", _STUB_SRC, str(port)],
                            stdout=subprocess.PIPE, text=True)
    assert proc.stdout.readline().strip() == "ready"
    return proc


def _import_serve_bench():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serve_bench
        return serve_bench
    finally:
        sys.path.pop(0)


def test_chaos_drill_contract_under_kill_hang_and_flap():
    """The acceptance drill: 3 replicas under a paced burst — one
    SIGKILLed mid-burst, one predict-hung via batcher_flush, one
    health-flapped — and every client response stays inside the
    200 / 429+Retry-After / 503+Retry-After envelope while the breaker
    opens + re-closes and the retry budget holds its fraction."""
    from vitax.serve import start_server, stop_server
    serve_bench = _import_serve_bench()

    # the hang drill rides the real server's bounded request timeout: a
    # hung batch turns into fast 503s (dispatch failures) for the breaker
    engine = FakeEngine()
    cfg = tiny_cfg(serve_max_batch=4, max_batch_wait_ms=2.0,
                   serve_request_timeout_s=0.3)
    httpd_b, ctx_b = start_server(cfg, engine, port=0)
    url_b = f"http://127.0.0.1:{httpd_b.server_address[1]}"
    stub_a = _start_stub(free_port_a := free_port())
    stub_c = _start_stub(free_port_c := free_port())

    # one combined plan, disjoint sites, armed BEFORE any counter advances:
    # - B's 2nd batch flush hangs 1.2s (its requests 503 at the 0.3s
    #   timeout -> breaker failures while /healthz still answers)
    # - health sweeps are 3 probes in adoption order (a, b, c), so indices
    #   6 and 9 flap replica c on consecutive sweeps -> eject + re-admit
    faults.install(json.dumps({"faults": [
        {"site": "batcher_flush", "at": 2, "action": "hang", "seconds": 1.2},
        {"site": "replica_health", "at": 6, "action": "oserror"},
        {"site": "replica_health", "at": 9, "action": "oserror"},
    ]}))
    rec = DummyRecorder()
    faults.set_reporter(lambda p: rec.event("serve_fault", **p))

    manager = ReplicaManager(recorder=rec, fail_threshold=2,
                             health_jitter=0.0)
    manager.adopt(f"http://127.0.0.1:{free_port_a}", name="a")
    manager.adopt(url_b, name="b")
    manager.adopt(f"http://127.0.0.1:{free_port_c}", name="c")
    manager.poll_once()  # sweep 1 (indices 1-3): everyone admitted
    assert manager.ready_count() == 3

    router = Router(manager, recorder=rec, request_timeout_s=5.0,
                    breaker_threshold=2, breaker_cooldown_s=0.2,
                    retry_budget_ratio=0.5)
    httpd_r = start_router(router, 0)
    url = f"http://127.0.0.1:{httpd_r.server_address[1]}"

    def mid_burst_chaos():
        time.sleep(0.3)
        os.kill(stub_a.pid, signal.SIGKILL)  # replica a: gone, no drain
        stub_a.wait()
        for _ in range(3):                   # sweeps 2-4: flap + eject c
            time.sleep(0.25)
            manager.poll_once()

    chaos = threading.Thread(target=mid_burst_chaos)
    chaos.start()
    try:
        summary = serve_bench.run_bench(
            url, concurrency=4, requests_per_worker=10, image_size=16,
            timeout=10.0, target_rps=25.0, replicas=3)
        chaos.join(timeout=30)

        # the whole contract: nothing leaked past 200/429/503+Retry-After
        assert summary["errors"] == 0, summary["error_samples"]
        assert summary["errors_by_class"] == {}
        assert summary["completed"] > 0
        assert (summary["completed"] + summary["shed"]
                + summary["unavailable"]) == summary["requests"]

        # replica a died for real and left rotation
        assert manager.ready_count() == 2
        # replica c was flapped out and re-admitted
        ejects = [p for p in rec.of_kind("replica_eject")
                  if p["replica"] == "c"]
        admits = [p for p in rec.of_kind("replica_admit")
                  if p["replica"] == "c"]
        assert ejects and admits
        # the hang fired on b's batcher and the flap on the health probes
        fired_sites = {p["site"] for p in rec.of_kind("serve_fault")}
        assert fired_sites == {"batcher_flush", "replica_health"}

        # breaker engaged on the hung replica AND recovered. Least-loaded
        # selection prefers the healthy c (b's EWMA carries the timeout
        # spikes), so force the half-open probe: take c out of rotation
        # and drive traffic — b is the only candidate, the hang is long
        # over, and the probe re-closes the breaker.
        br = router._breaker("b")
        assert br.opens_total >= 1, br.snapshot()
        stub_c.kill()
        stub_c.wait()
        manager.poll_once()
        manager.poll_once()  # 2 failed probes = fail_threshold: c ejected
        deadline = time.time() + 10
        while br.state() != CLOSED and time.time() < deadline:
            post_bytes(url + "/predict", png_bytes(), timeout=10.0)
            time.sleep(0.05)
        assert br.state() == CLOSED and br.closes_total >= 1

        # retry budget held its fraction: grants never exceed the earned
        # tokens (initial bucket + ratio per dispatched request)
        budget = router.budget.snapshot()
        assert budget["granted_total"] <= (
            budget["cap"] + budget["ratio"] * budget["deposits_total"])
    finally:
        faults.uninstall()
        stop_router(httpd_r)
        stop_server(httpd_b, ctx_b)
        for proc in (stub_a, stub_c):
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def test_no_fault_plan_request_path_unchanged(fleet_factory):
    """The zero-overhead pin: an armed plan that never fires leaves the
    request path identical to no plan at all — same payload, no retries,
    no breaker movement, no budget spend. Single replica so load-balancing
    cannot alternate the serving replica between the two runs."""
    _, router, fakes = fleet_factory(n=1)
    httpd = start_router(router, 0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        def probe():
            resp = post_bytes(url + "/predict", png_bytes())
            resp.pop("latency_ms")  # wall-clock, not part of the contract
            return resp

        baseline = [probe() for _ in range(4)]
        faults.install(json.dumps(  # armed, but firing at call 10^9
            {"site": "router_dispatch", "at": 10 ** 9, "action": "crash"}))
        armed = [probe() for _ in range(4)]
        assert armed == baseline
        m = router.metrics.snapshot()
        assert m["requests_total"] == 8 and m["errors_total"] == 0
        assert m["retries_total"] == 0 and m["hedges_total"] == 0
        # closed breakers never moved and cost no dispatch
        assert all(b["state"] == CLOSED and b["opens_total"] == 0
                   for b in router.fleet_metrics()["breakers"].values())
        assert router.budget.snapshot()["granted_total"] == 0
    finally:
        stop_router(httpd)


# --- serve_bench error classes (satellite) -----------------------------------


def test_serve_bench_error_class_classifier():
    serve_bench = _import_serve_bench()
    classify = serve_bench.classify_error
    assert classify(urllib.error.URLError(
        ConnectionRefusedError(111, "refused"))) == "connection_refused"
    assert classify(urllib.error.URLError(
        ConnectionResetError(104, "reset"))) == "reset_mid_body"
    assert classify(ConnectionResetError(104, "reset")) == "reset_mid_body"
    assert classify(socket.timeout("timed out")) == "timeout"
    assert classify(TimeoutError("timed out")) == "timeout"
    assert classify(urllib.error.URLError(
        socket.timeout("timed out"))) == "timeout"
    err5 = urllib.error.HTTPError("u", 500, "boom", {}, None)
    assert classify(err5) == "http_5xx"
    err4 = urllib.error.HTTPError("u", 404, "nope", {}, None)
    assert classify(err4) == "other"


def test_serve_bench_buckets_unavailable_and_classes():
    """A 503 WITH Retry-After is the fleet's bounded-degradation contract
    (counted as `unavailable`, exit 0); a bare 500 is an http_5xx error."""
    serve_bench = _import_serve_bench()
    state = {"n": 0}

    class Flaky(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # noqa: A003
            pass

        def do_POST(self):  # noqa: N802
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            state["n"] += 1
            if state["n"] % 3 == 1:
                body = b'{"error": "boom"}'
                self.send_response(500)
            elif state["n"] % 3 == 2:
                body = (b'{"error": "retry budget exhausted",'
                        b' "reason": "retry_budget_exhausted"}')
                self.send_response(503)
                self.send_header("Retry-After", "0")
            else:
                body = (b'{"classes": [1], "probs": [0.9],'
                        b' "latency_ms": 1.0}')
                self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Flaky)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        summary = serve_bench.run_bench(
            url, concurrency=1, requests_per_worker=6, image_size=16,
            timeout=10.0)
        assert summary["completed"] == 2
        assert summary["unavailable"] == 2   # 503 + Retry-After: contract
        assert summary["errors"] == 2        # bare 500s are real errors
        assert summary["errors_by_class"] == {"http_5xx": 2}
        json.dumps(summary)  # --json stays one serializable object
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_chaos_endpoint_gated_and_installs():
    """POST /chaos: 403 without --serve_allow_chaos; with it, installs a
    plan (bad plans 400, empty body disarms)."""
    from vitax.serve import start_server, stop_server
    engine = FakeEngine()
    httpd, ctx = start_server(tiny_cfg(), engine, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    plan = b'{"site": "engine_predict", "at": 5, "action": "oserror"}'
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            post_bytes(url + "/chaos", plan, "application/json")
        assert e.value.code == 403
        assert not faults.active()
    finally:
        stop_server(httpd, ctx)

    httpd, ctx = start_server(tiny_cfg(serve_allow_chaos=True), engine,
                              port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        out = post_bytes(url + "/chaos", plan, "application/json")
        assert "engine_predict:oserror(at=5)" in out["installed"]
        assert faults.active()
        with pytest.raises(urllib.error.HTTPError) as e:
            post_bytes(url + "/chaos", b'{"site": "nope", "action": "hang"}',
                       "application/json")
        assert e.value.code == 400
        assert post_bytes(url + "/chaos", b"",
                          "application/json") == {"installed": None}
        assert not faults.active()
    finally:
        stop_server(httpd, ctx)


def test_serve_bench_chaos_forwarding(fleet_factory):
    """serve_bench --chaos discovers replica URLs from the router's
    /metrics and POSTs the plan to each /chaos endpoint."""
    from vitax.serve import start_server, stop_server
    serve_bench = _import_serve_bench()
    engine = FakeEngine()
    httpd_b, ctx_b = start_server(tiny_cfg(serve_allow_chaos=True), engine,
                                  port=0)
    url_b = f"http://127.0.0.1:{httpd_b.server_address[1]}"
    manager = ReplicaManager(health_jitter=0.0)
    manager.adopt(url_b, name="b")
    manager.poll_once()
    router = Router(manager, request_timeout_s=10.0)
    httpd_r = start_router(router, 0)
    url = f"http://127.0.0.1:{httpd_r.server_address[1]}"
    try:
        plan = '{"site": "engine_predict", "at": 7, "action": "oserror"}'
        results = serve_bench.install_chaos(url, plan)
        assert results == {
            "b": {"installed": "engine_predict:oserror(at=7)"}}
        assert faults.active()  # the replica shares this process
    finally:
        stop_router(httpd_r)
        stop_server(httpd_b, ctx_b)
