"""HTTP front end: stdlib ThreadingHTTPServer over the engine + batcher.

Endpoints:
- POST /predict  — body is raw image bytes (any PIL-decodable format) or
                   JSON {"image": <base64 image bytes>, "topk": <optional,
                   <= --serve_topk>}; the image runs the SAME eval
                   transforms training validation uses
                   (vitax/data/transforms.py ValTransform), then the
                   dynamic batcher; response is
                   {"classes": [...], "probs": [...], "latency_ms": ...}.
- GET /healthz   — liveness + readiness: the server is LIVE once it binds
                   (status "ok") but READY only after AOT bucket warmup
                   completes and while not draining — a fleet router
                   (vitax/serve/fleet/) keys rotation off "ready".
- GET /metrics   — aggregate counters: requests/s, latency p50/p95/p99,
                   queue wait, batch occupancy, queue depth, the configured
                   request timeout, readiness/drain state.

Overload and shutdown semantics:
- a full batcher queue (--serve_queue_max) answers 503 with JSON reason
  "queue_full" and Retry-After — the fleet router maps that to an
  admission shed (429);
- **brownout** (BrownoutController): queue depth sustained at or above
  --serve_brownout_enter_frac of --serve_queue_max for
  --serve_brownout_dwell_s enters DEGRADED mode — optional work is shed
  (topk clamped to 1, the batcher deadline shortened to
  --serve_brownout_wait_ms so queued work drains in smaller waits) and
  /healthz + /metrics advertise `degraded: true` (the fleet router folds
  the count into its aggregate). Recovery is hysteretic: depth must hold
  at or below --serve_brownout_exit_frac for the same dwell. Degraded is
  NOT unready — a browned-out replica still serves;
- SIGTERM drains gracefully (python -m vitax.serve): stop accepting new
  work (ready: false, new /predict -> 503), answer every in-flight
  request, flush the batcher, exit 0 — so a ReplicaManager restart never
  drops an accepted request.

Chaos: --fault_plan (or VITAX_FAULT_PLAN) arms the serve fault sites
(vitax/faults.py: engine_predict, batcher_flush) at startup; with
--serve_allow_chaos, POST /chaos installs a plan into a RUNNING replica
(tools/serve_bench.py --chaos drives this). Fired faults surface as
kind:"serve_fault" telemetry events.

Observability rides the existing vitax.telemetry Recorder/sinks: with
--metrics_dir set, schema-versioned JSONL records land in
<metrics_dir>/serve.jsonl (summarized by tools/serve_bench.py --json for
CI); unset, there is no recorder and nothing is built or written. Every
stamp is time.time() seconds, the clock a device trace shares:
- kind "serve_request", one per answered request, written after its reply:
  batch_id, t_start, read_s, decode_s, latency_s, queue_wait_s, infer_s,
  wake_s, reply_s, batch_size, bucket, topk[, batched]
  (latency_s = enqueue - t_start + queue_wait_s + infer_s + wake_s, stamped
  when the handler runs again after its future; reply_s comes after it);
- kind "serve_batch", one per engine batch, the worker thread's timeline
  (vitax/serve/batcher.py): batch_id, batch_size, bucket, infer_s,
  overlapped (1: dispatched while the batch before it was still in flight,
  so the two timelines overlap), t_collect, t_stack, t_put, t_dispatch,
  t_wait, t_deliver, t_end;
- lifecycle events: serve_start, serve_drain, brownout, serve_fault, ...
"""

from __future__ import annotations

import base64
import io
import json
import os
import signal
import sys
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from vitax import faults
from vitax.config import Config
from vitax.serve.engine import InferenceEngine
from vitax.serve.batcher import DynamicBatcher, QueueFull
from vitax.platform import device_kind
from vitax.telemetry.threads import install_thread_excepthook
from vitax.utils.logging import master_print

# acceptance contract of a serve_request record: tools/serve_bench.py and
# tests/test_serve.py key off this exact set (beyond the Recorder's own
# schema/time/kind/rank envelope)
REQUIRED_SERVE_KEYS = (
    "latency_s", "queue_wait_s", "infer_s", "batch_size", "bucket", "topk",
)

# a request outlives at most: its batcher deadline + one engine batch +
# generous slack — beyond that the handler answers 503 instead of hanging
# the client forever. Default for --serve_request_timeout_s (and the
# fallback when a Config predates the flag).
REQUEST_TIMEOUT_S = 60.0


class ServeMetrics:
    """Thread-safe aggregate counters behind GET /metrics."""

    def __init__(self, window: int = 2048):
        self._lock = threading.Lock()
        self.started = time.time()
        self.requests_total = 0
        self.errors_total = 0
        self._latency = deque(maxlen=window)
        self._wait = deque(maxlen=window)
        self._occupancy = deque(maxlen=window)  # batch_size / bucket
        self._times = deque(maxlen=window)      # completion timestamps

    def observe(self, latency_s: float, queue_wait_s: float,
                batch_size: int, bucket: int) -> None:
        with self._lock:
            self.requests_total += 1
            self._latency.append(latency_s)
            self._wait.append(queue_wait_s)
            self._occupancy.append(batch_size / max(bucket, 1))
            self._times.append(time.time())

    def error(self) -> None:
        with self._lock:
            self.errors_total += 1

    @staticmethod
    def _pct(sorted_vals, q: float) -> Optional[float]:
        if not sorted_vals:
            return None
        pos = (len(sorted_vals) - 1) * q
        lo = int(pos)
        hi = min(lo + 1, len(sorted_vals) - 1)
        frac = pos - lo
        return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)

    def snapshot(self) -> dict:
        with self._lock:
            lat = sorted(self._latency)
            waits = list(self._wait)
            occ = list(self._occupancy)
            times = list(self._times)
            total, errors = self.requests_total, self.errors_total
        now = time.time()
        recent = [t for t in times if now - t <= 60.0]
        return {
            "requests_total": total,
            "errors_total": errors,
            "uptime_s": round(now - self.started, 3),
            "requests_per_sec": round(total / max(now - self.started, 1e-9), 3),
            "requests_per_sec_60s": round(len(recent) / 60.0, 3),
            "latency_s_p50": self._pct(lat, 0.50),
            "latency_s_p95": self._pct(lat, 0.95),
            "latency_s_p99": self._pct(lat, 0.99),
            "queue_wait_s_mean": (round(sum(waits) / len(waits), 6)
                                  if waits else None),
            "batch_occupancy_mean": (round(sum(occ) / len(occ), 4)
                                     if occ else None),
        }


class BrownoutController:
    """Hysteretic degraded mode keyed on batcher queue depth.

    Pressure (depth >= enter_depth) sustained for `dwell_s` enters
    DEGRADED; calm (depth <= exit_depth) sustained for the same dwell
    exits. The dwell window means blips never flip the mode, and the
    enter/exit gap means depths between the thresholds hold the current
    state — the two classic chatter guards composed. `clock` is
    injectable so tests drive transitions without real time.

    The controller only decides; the owner passes `on_enter`/`on_exit`
    callbacks for the actual shedding (topk clamp, batcher deadline) and
    telemetry. Disabled (never degrades) when queue_max or enter_frac
    is 0."""

    def __init__(self, queue_max: int, enter_frac: float, exit_frac: float,
                 dwell_s: float,
                 clock: Callable[[], float] = time.monotonic,
                 on_enter: Optional[Callable[[], None]] = None,
                 on_exit: Optional[Callable[[float], None]] = None):
        self.enabled = queue_max > 0 and enter_frac > 0
        self.enter_depth = enter_frac * queue_max
        self.exit_depth = exit_frac * queue_max
        self.dwell_s = dwell_s
        self._clock = clock
        self._on_enter = on_enter
        self._on_exit = on_exit
        self._lock = threading.Lock()
        self.degraded = False
        self._streak_since: Optional[float] = None  # pressure/calm streak
        self._entered_at: Optional[float] = None
        self.enters_total = 0
        self._degraded_s = 0.0  # accumulated across COMPLETED episodes

    def observe(self, depth: int, now: Optional[float] = None) -> bool:
        """Feed one queue-depth sample; returns the (possibly updated)
        degraded state. Called from /predict and /healthz handlers — the
        health poll keeps recovery moving when traffic stops entirely."""
        if not self.enabled:
            return False
        now = self._clock() if now is None else now
        transition = None
        with self._lock:
            if not self.degraded:
                if depth >= self.enter_depth:
                    if self._streak_since is None:
                        self._streak_since = now
                    if now - self._streak_since >= self.dwell_s:
                        self.degraded = True
                        self.enters_total += 1
                        self._entered_at = now
                        self._streak_since = None
                        transition = ("enter", depth)
                else:
                    self._streak_since = None
            else:
                if depth <= self.exit_depth:
                    if self._streak_since is None:
                        self._streak_since = now
                    if now - self._streak_since >= self.dwell_s:
                        self.degraded = False
                        episode_s = now - (self._entered_at or now)
                        self._degraded_s += episode_s
                        self._entered_at = None
                        self._streak_since = None
                        transition = ("exit", episode_s)
                else:
                    self._streak_since = None
            degraded = self.degraded
        # callbacks outside the lock: they touch the batcher and telemetry
        if transition is not None:
            kind, arg = transition
            if kind == "enter" and self._on_enter is not None:
                self._on_enter()
            elif kind == "exit" and self._on_exit is not None:
                self._on_exit(arg)
        return degraded

    def degraded_seconds(self, now: Optional[float] = None) -> float:
        """Total time spent degraded, including the live episode."""
        with self._lock:
            total = self._degraded_s
            if self._entered_at is not None:
                total += (self._clock() if now is None else now) \
                    - self._entered_at
            return total


def build_serve_recorder(cfg: Config):
    """Recorder writing schema-versioned serve.jsonl records through the
    existing telemetry sinks, or None when --metrics_dir is unset. Fail-soft
    like training telemetry: an unwritable dir disables recording, never
    serving."""
    metrics_dir = getattr(cfg, "metrics_dir", "") or ""
    if not metrics_dir:
        return None
    import jax
    from vitax.telemetry.record import Recorder
    from vitax.telemetry.sinks import JsonlSink
    try:
        os.makedirs(metrics_dir, exist_ok=True)
        sinks = [JsonlSink(os.path.join(metrics_dir, "serve.jsonl"))]
    except OSError as e:
        print(f"vitax.serve: --metrics_dir {metrics_dir!r} is not writable "
              f"({e}); serve telemetry disabled", file=sys.stderr, flush=True)
        return None
    return Recorder(cfg, sinks, jax.device_count(),
                    device_kind(), rank=0)


def decode_image_bytes(raw: bytes, transform):
    """One /predict image body -> transformed HWC array.

    JPEG bodies route through the native in-memory pipeline
    (vitax/data/native.py process_bytes — libjpeg decode + the PIL-parity
    resize, one C call, no per-request Python decode tax); anything else, or
    a native failure/missing library, falls back to PIL. The two paths apply
    the SAME eval transform (tests/test_stream.py pins resize-path parity)."""
    from vitax.data import native
    if (native.is_jpeg_bytes(raw) and hasattr(transform, "native_params")
            and native.mem_available()):
        arr = native.process_bytes(
            raw, transform.native_params(0, 0, 0), transform.image_size,
            getattr(transform, "resize_to", 0),
            normalize=getattr(transform, "normalize", True))
        if arr is not None:
            return arr
    from PIL import Image
    img = Image.open(io.BytesIO(raw)).convert("RGB")
    return transform(img)


class ServeContext:
    """Everything a handler thread needs, wired once at startup."""

    def __init__(self, cfg: Config, engine: InferenceEngine, recorder=None):
        from vitax.data.transforms import val_transform
        self.cfg = cfg
        self.engine = engine
        self.recorder = recorder
        self.metrics = ServeMetrics()
        self.request_timeout_s = float(
            getattr(cfg, "serve_request_timeout_s", REQUEST_TIMEOUT_S))
        # drain/readiness state: handlers enter through enter_request() so a
        # drain can wait for the in-flight count to reach zero before the
        # batcher is flushed and the process exits
        self.draining = False
        self._inflight = 0
        self._flight_cond = threading.Condition()
        # normalize=False: the eval stack emits uint8 HWC and the engine's
        # compiled program normalizes on device (vitax/train/step.py
        # prepare_images) — the same split training uses
        self.transform = val_transform(cfg.image_size, normalize=False)
        from vitax.serve.engine import next_bucket
        self.batcher = DynamicBatcher(
            engine.predict, max_batch=cfg.serve_max_batch,
            max_wait_ms=cfg.max_batch_wait_ms,
            bucket_of=lambda n: next_bucket(n, engine.buckets),
            # no recorder: no hook, and the batcher's record goes nowhere
            on_batch=self._record_batch if recorder is not None else None,
            queue_max=getattr(cfg, "serve_queue_max", 0),
            # the engine's two-phase call lets the batcher queue one batch
            # behind the one that runs; a stand-in with `predict` alone is
            # wrapped by the batcher and never overlaps
            dispatch_fn=getattr(engine, "dispatch", None))
        # brownout: shed optional work under sustained queue pressure
        # instead of tipping into queue-full sheds (degraded != unready:
        # a browned-out replica still serves)
        self.brownout = BrownoutController(
            queue_max=getattr(cfg, "serve_queue_max", 0),
            enter_frac=getattr(cfg, "serve_brownout_enter_frac", 0.0),
            exit_frac=getattr(cfg, "serve_brownout_exit_frac", 0.0),
            dwell_s=getattr(cfg, "serve_brownout_dwell_s", 2.0),
            on_enter=self._brownout_enter, on_exit=self._brownout_exit)

    def _brownout_enter(self) -> None:
        # shorten the flush deadline: under pressure, smaller faster
        # batches drain the queue instead of waiting out the full deadline
        self.batcher.set_max_wait_ms(
            getattr(self.cfg, "serve_brownout_wait_ms", 1.0))
        if self.recorder is not None:
            self.recorder.event("brownout", event="enter",
                                queue_depth=self.batcher.queue_depth())

    def _brownout_exit(self, degraded_s: float) -> None:
        self.batcher.set_max_wait_ms(self.cfg.max_batch_wait_ms)
        if self.recorder is not None:
            self.recorder.event("brownout", event="exit",
                                degraded_s=round(degraded_s, 6))

    def degraded(self) -> bool:
        """Current brownout verdict, refreshed with a live depth sample
        (handlers call this, so /healthz polls keep recovery moving even
        with zero traffic)."""
        return self.brownout.observe(self.batcher.queue_depth())

    def is_ready(self) -> bool:
        """READY = warmed up and not draining. Distinct from liveness: a
        warming or draining server still answers /healthz (live) but must
        not receive routed traffic."""
        return not self.draining and getattr(self.engine, "ready", True)

    def enter_request(self) -> bool:
        """Admit one /predict into the in-flight set; False when the server
        is warming or draining (the handler answers 503)."""
        with self._flight_cond:
            if not self.is_ready():
                return False
            self._inflight += 1
            return True

    def exit_request(self) -> None:
        with self._flight_cond:
            self._inflight -= 1
            self._flight_cond.notify_all()

    def inflight(self) -> int:
        with self._flight_cond:
            return self._inflight

    def wait_idle(self, timeout_s: float) -> bool:
        """Block until every in-flight request is answered (drain step 2);
        False if `timeout_s` elapsed with requests still in flight."""
        deadline = time.monotonic() + timeout_s
        with self._flight_cond:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._flight_cond.wait(timeout=remaining)
            return True

    def _record_batch(self, stats: dict) -> None:
        """The batcher's record of one batch (its seven marks, the engine's
        two among them, and `overlapped`) as one `serve_batch` span record.
        Only hooked up where there is a recorder."""
        self.recorder.event("serve_batch", **{
            k: round(v, 6) if isinstance(v, float) else v
            for k, v in stats.items()})

    def record_request(self, result, topk: int, t_start: float,
                       read_s: float, decode_s: float, t_woke: float,
                       t_replied: float, **extra) -> None:
        """One `serve_request` record, written after the reply; `/predict`
        and `/predict_batch` both come through here, so it has one shape.
        `t_woke` is the handler's stamp right after `fut.result()`."""
        if self.recorder is None:
            return
        self.recorder.event(
            "serve_request", batch_id=result.batch_id,
            t_start=round(t_start, 6), read_s=round(read_s, 6),
            decode_s=round(decode_s, 6),
            latency_s=round(t_woke - t_start, 6),
            queue_wait_s=round(result.queue_wait_s, 6),
            infer_s=round(result.infer_s, 6),
            wake_s=round(t_woke - result.t_deliver, 6),
            reply_s=round(t_replied - t_woke, 6),
            batch_size=result.batch_size, bucket=result.bucket,
            topk=topk, **extra)

    def decode(self, body: bytes, content_type: str):
        """(uint8 HWC image, requested topk) from a /predict body."""
        topk = self.engine.topk
        if "application/json" in content_type:
            payload = json.loads(body.decode("utf-8"))
            raw = base64.b64decode(payload["image"])
            if "topk" in payload:
                topk = int(payload["topk"])
                if not 1 <= topk <= self.engine.topk:
                    raise ValueError(
                        f"topk must be in [1, {self.engine.topk}] "
                        f"(--serve_topk caps the compiled top-k)")
        else:
            raw = body
        return decode_image_bytes(raw, self.transform), topk

    def close(self) -> None:
        self.batcher.close()
        if self.recorder is not None:
            self.recorder.close()


def _make_handler(ctx: ServeContext):
    class Handler(BaseHTTPRequestHandler):
        # per-request access logging off: at serving rates stderr chatter is
        # a throughput bug, and telemetry owns the durable record
        def log_message(self, fmt, *args):  # noqa: A003
            pass

        def _reply(self, code: int, payload: dict,
                   headers: Optional[dict] = None) -> None:
            body = json.dumps(payload).encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for key, value in (headers or {}).items():
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
            if self.path == "/healthz":
                self._reply(200, {
                    "status": "ok",                 # liveness: we answered
                    "ready": ctx.is_ready(),        # routable: warmed + not draining
                    "draining": ctx.draining,
                    "degraded": ctx.degraded(),     # brownout: serving, but shedding optional work
                    "degraded_seconds": round(
                        ctx.brownout.degraded_seconds(), 3),
                    "buckets": list(ctx.engine.buckets),
                    "topk": ctx.engine.topk,
                    "compile_count": ctx.engine.compile_count,
                })
            elif self.path == "/metrics":
                snap = ctx.metrics.snapshot()
                snap["queue_depth"] = ctx.batcher.queue_depth()
                snap["queue_max"] = ctx.batcher.queue_max
                # batches dispatched, and how many of them went to the device
                # while the batch before was still in flight
                snap["batches_total"] = ctx.batcher.batches_total
                snap["batches_overlapped"] = ctx.batcher.batches_overlapped
                snap["compile_count"] = ctx.engine.compile_count
                snap["request_timeout_s"] = ctx.request_timeout_s
                snap["ready"] = ctx.is_ready()
                snap["draining"] = ctx.draining
                snap["degraded"] = ctx.degraded()
                snap["degraded_seconds"] = round(
                    ctx.brownout.degraded_seconds(), 3)
                snap["brownout_enters"] = ctx.brownout.enters_total
                # device-resident weight footprint (vitax/serve/quant.py):
                # the per-replica HBM number serve_bench and the fleet
                # router's capacity math read; only-when-reported so
                # engine-shaped stand-ins without the accounting still serve
                if hasattr(ctx.engine, "weights_dtype"):
                    snap["weights_dtype"] = ctx.engine.weights_dtype
                if hasattr(ctx.engine, "param_bytes"):
                    snap["param_bytes"] = ctx.engine.param_bytes()
                # how much of param_bytes is the engine's own copy of leaves
                # cast to the compute dtype once, at warm-up (engine.py
                # `compute_params`); 0 on float32 and quantized engines
                if hasattr(ctx.engine, "precast_leaves"):
                    snap["precast_leaves"] = ctx.engine.precast_leaves
                    snap["precast_bytes"] = ctx.engine.precast_bytes
                # tier-2 quant mode flags (PR 16): which activation-quant
                # and fused-dequant policy this replica's program compiled
                # with — the fleet router surfaces mixed values during a
                # rollout
                if hasattr(ctx.engine, "act_quant"):
                    snap["act_quant"] = ctx.engine.act_quant
                if hasattr(ctx.engine, "fused_dequant"):
                    snap["fused_dequant"] = ctx.engine.fused_dequant
                self._reply(200, snap)
            else:
                self._reply(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):  # noqa: N802
            if self.path == "/chaos":
                self._chaos()
                return
            if self.path not in ("/predict", "/predict_batch"):
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            if not ctx.enter_request():
                reason = "draining" if ctx.draining else "warming_up"
                ctx.metrics.error()
                self._reply(503, {"error": f"not ready: {reason}",
                                  "reason": reason},
                            headers={"Retry-After": "1"})
                return
            try:
                if self.path == "/predict_batch":
                    self._predict_batch()
                else:
                    self._predict()
            finally:
                ctx.exit_request()

        def _chaos(self) -> None:
            """Install a fault plan into this running replica (the drill
            transport behind tools/serve_bench.py --chaos). Gated hard on
            --serve_allow_chaos: an open chaos endpoint on a production
            replica would be remote code-adjacent sabotage, so without the
            opt-in the path answers 403 and changes nothing. An empty body
            disarms."""
            if not getattr(ctx.cfg, "serve_allow_chaos", False):
                self._reply(403, {
                    "error": "chaos endpoint disabled "
                             "(start with --serve_allow_chaos to arm)"})
                return
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length).decode("utf-8").strip()
            if not body:
                faults.uninstall()
                self._reply(200, {"installed": None})
                return
            try:
                plan = faults.install(body)
            except ValueError as e:
                self._reply(400, {"error": str(e)})
                return
            if ctx.recorder is not None:
                rec = ctx.recorder
                faults.set_reporter(
                    lambda p: rec.event("serve_fault", **p))
            if ctx.recorder is not None:
                ctx.recorder.event("chaos_install", plan=plan.describe())
            self._reply(200, {"installed": plan.describe()})

        def _predict(self) -> None:
            t0 = time.time()
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                t_read = time.time()
                image, topk = ctx.decode(
                    body, self.headers.get("Content-Type", ""))
                t_decoded = time.time()
            except Exception as e:  # noqa: BLE001 — client error, not ours
                ctx.metrics.error()
                self._reply(400, {"error": f"bad request: {e}"})
                return
            # brownout: this sample feeds the pressure window, and while
            # degraded the optional work (top-k beyond 1) is shed
            if ctx.degraded():
                topk = 1
            try:
                fut = ctx.batcher.submit(image)
            except QueueFull as e:
                # typed overload: the fleet router maps this reason to an
                # admission shed (429); a bare client just backs off
                ctx.metrics.error()
                self._reply(503, {"error": f"overloaded: {e}",
                                  "reason": "queue_full"},
                            headers={"Retry-After": "1"})
                return
            try:
                result = fut.result(timeout=ctx.request_timeout_s)
            except Exception as e:  # noqa: BLE001
                ctx.metrics.error()
                self._reply(503, {"error": f"inference failed: {e}"})
                return
            t_woke = time.time()
            latency_s = t_woke - t0
            ctx.metrics.observe(latency_s, result.queue_wait_s,
                                result.batch_size, result.bucket)
            self._reply(200, {
                "classes": [int(c) for c in result.classes[:topk]],
                "probs": [float(p) for p in result.probs[:topk]],
                "latency_ms": round(latency_s * 1000.0, 3),
            })
            ctx.record_request(result, topk, t0, t_read - t0,
                               t_decoded - t_read, t_woke, time.time())

        def _predict_batch(self) -> None:
            """Composed dispatch from the fleet router (BatchComposer in
            vitax/serve/fleet/router.py): decode every item, submit ALL
            of them to the batcher BEFORE waiting on any future — the
            group lands in the queue together, so the DynamicBatcher
            flushes it as one bucket instead of trickling singles through
            its max_batch_wait_ms window. Each item's `body` is the exact
            JSON a lone /predict would have produced (same engine, same
            formatting), so composed and direct dispatch are
            indistinguishable to clients. Per-item failures (bad image,
            queue full, inference error) settle that item only; the
            batch call itself only 400s on an unparseable envelope."""
            t0 = time.time()
            try:
                length = int(self.headers.get("Content-Length", 0))
                wire = json.loads(self.rfile.read(length).decode("utf-8"))
                bodies = [base64.b64decode(s) for s in wire["items"]]
                ctypes = wire.get("content_types") or [""] * len(bodies)
                if len(ctypes) != len(bodies):
                    raise ValueError("content_types/items length mismatch")
            except Exception as e:  # noqa: BLE001 — client error, not ours
                ctx.metrics.error()
                self._reply(400, {"error": f"bad batch request: {e}"})
                return
            read_s = time.time() - t0   # the envelope: shared by its items
            results = [None] * len(bodies)
            waiting = []  # (index, topk, future, decode_s)
            answered = []  # (result, topk, decode_s, t_woke)
            for i, (body, ctype) in enumerate(zip(bodies, ctypes)):
                try:
                    t_decode = time.time()
                    image, topk = ctx.decode(body, ctype)
                    decode_s = time.time() - t_decode
                except Exception as e:  # noqa: BLE001 — client error
                    ctx.metrics.error()
                    results[i] = {"status": 400, "body": json.dumps(
                        {"error": f"bad request: {e}"})}
                    continue
                if ctx.degraded():
                    topk = 1
                try:
                    fut = ctx.batcher.submit(image)
                except QueueFull as e:
                    ctx.metrics.error()
                    results[i] = {"status": 503, "reason": "queue_full",
                                  "body": json.dumps(
                                      {"error": f"overloaded: {e}",
                                       "reason": "queue_full"})}
                    continue
                waiting.append((i, topk, fut, decode_s))
            for i, topk, fut, decode_s in waiting:
                try:
                    result = fut.result(timeout=ctx.request_timeout_s)
                except Exception as e:  # noqa: BLE001
                    ctx.metrics.error()
                    results[i] = {"status": 503, "body": json.dumps(
                        {"error": f"inference failed: {e}"})}
                    continue
                t_woke = time.time()
                latency_s = t_woke - t0
                ctx.metrics.observe(latency_s, result.queue_wait_s,
                                    result.batch_size, result.bucket)
                if ctx.recorder is not None:
                    answered.append((result, topk, decode_s, t_woke))
                results[i] = {"status": 200, "body": json.dumps({
                    "classes": [int(c) for c in result.classes[:topk]],
                    "probs": [float(p) for p in result.probs[:topk]],
                    "latency_ms": round(latency_s * 1000.0, 3),
                })}
            self._reply(200, {"results": results})
            t_replied = time.time()
            for result, topk, decode_s, t_woke in answered:
                ctx.record_request(result, topk, t0, read_s, decode_s,
                                   t_woke, t_replied, batched=True)

    return Handler


def start_server(cfg: Config, engine: InferenceEngine,
                 port: Optional[int] = None):
    """Warmed engine -> listening server (background thread).

    Returns (httpd, ctx): httpd.server_address[1] is the bound port (pass
    port=0 / --serve_port 0 for an ephemeral one — tests do). Call
    `stop_server(httpd, ctx)` to drain and shut down."""
    recorder = build_serve_recorder(cfg)
    # arm the serve-path chaos sites (engine_predict, batcher_flush) when a
    # plan is named; left untouched otherwise so embedding tests that
    # installed a plan directly keep it
    if getattr(cfg, "fault_plan", "") or os.environ.get(faults.ENV_VAR, ""):
        faults.install_from_config(cfg)
    if faults.active() and recorder is not None:
        faults.set_reporter(lambda p: recorder.event("serve_fault", **p))
    # batcher worker + HTTP handler threads: crashes become thread_crash
    # events in serve.jsonl instead of silent 500s-forever
    install_thread_excepthook(recorder, rank=0)
    ctx = ServeContext(cfg, engine, recorder=recorder)
    bind_port = cfg.serve_port if port is None else port
    httpd = ThreadingHTTPServer(("0.0.0.0", bind_port), _make_handler(ctx))
    httpd.daemon_threads = True
    thread = threading.Thread(  # vtx: ignore[VTX205] stop_server's httpd.shutdown() ends serve_forever
        target=httpd.serve_forever, daemon=True, name="vitax-serve-http")
    thread.start()
    if recorder is not None:
        recorder.event("serve_start", port=httpd.server_address[1],
                       buckets=list(engine.buckets), topk=engine.topk,
                       max_batch_wait_ms=cfg.max_batch_wait_ms,
                       compile_count=engine.compile_count,
                       precast_leaves=getattr(engine, "precast_leaves", 0),
                       precast_bytes=getattr(engine, "precast_bytes", 0))
    master_print(f"serve: listening on :{httpd.server_address[1]} "
                 f"(buckets {list(engine.buckets)}, "
                 f"wait {cfg.max_batch_wait_ms}ms, top-{engine.topk})")
    return httpd, ctx


def stop_server(httpd, ctx: ServeContext) -> None:
    httpd.shutdown()
    httpd.server_close()
    ctx.close()


def drain(httpd, ctx: ServeContext, timeout_s: float = 30.0) -> bool:
    """Graceful shutdown: stop accepting, answer in-flight, flush, close.

    The SIGTERM contract a ReplicaManager restart relies on — an accepted
    request is never dropped:
      1. mark draining (healthz reports ready: false; new /predict -> 503)
         and stop the accept loop;
      2. wait for every in-flight request to be answered (their batch
         futures resolve through the still-running batcher worker);
      3. close the batcher (flushes anything still queued) and telemetry.
    Returns True when the in-flight set drained inside `timeout_s`."""
    with ctx._flight_cond:
        ctx.draining = True
    httpd.shutdown()
    idle = ctx.wait_idle(timeout_s)
    httpd.server_close()
    if ctx.recorder is not None:
        ctx.recorder.event("serve_drain", clean=idle,
                           inflight_left=ctx.inflight())
    ctx.close()
    if not idle:
        master_print(f"serve: drain timed out after {timeout_s:.0f}s with "
                     f"{ctx.inflight()} requests in flight")
    return idle


def serve_forever(cfg: Config, engine: InferenceEngine) -> None:
    """Blocking entry point (python -m vitax.serve).

    Binds FIRST, then warms up: /healthz is answerable (live, ready: false)
    while the AOT buckets compile, so a fleet router can watch a replica
    warm without routing to it. SIGTERM/SIGINT trigger the graceful drain
    and the function returns (the CLI exits 0)."""
    httpd, ctx = start_server(cfg, engine)
    stop = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001 — handler signature
        stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_signal)
        except ValueError:
            pass  # not the main thread (embedded use): Ctrl-C unavailable
    if not getattr(engine, "ready", True):
        engine.warmup()
    while not stop.wait(timeout=0.5):
        pass
    master_print("serve: draining (SIGTERM/SIGINT)")
    drain(httpd, ctx)
