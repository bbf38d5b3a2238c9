"""ReplicaManager: spawn/adopt N serve replicas, health-driven rotation.

The process layer of the fleet (Clipper-style layered serving, NSDI '17):
each replica is one `python -m vitax.serve` engine on its own port — its
adaptive batching, AOT buckets, and telemetry are untouched — and this
module decides which replicas are routable:

- **spawn or adopt**: `manage()` launches a replica subprocess and owns its
  lifecycle (restart-with-backoff on death, SIGTERM drain on shutdown —
  both through the vitax.supervise seams: `backoff_delay`,
  `terminate_child`); `adopt()` registers an externally started endpoint
  (another host, or an in-process stub in tests) that is health-checked
  but never restarted.
- **rotation**: a replica is dispatched to only while READY. The health
  loop polls `GET /healthz`; `ready: false` (warming after restart, or
  draining) or `fail_threshold` consecutive failed polls EJECT it from
  rotation, and a later live-and-ready poll re-admits it. A managed
  replica whose process died is respawned after capped exponential
  backoff and re-enters rotation only once its warmup completes — the
  router never sees a cold replica.
- **load accounting**: the router's least-loaded pick reads the per-replica
  in-flight counter and EWMA latency maintained here via
  `acquire()`/`release()`.

All state transitions emit schema-1 telemetry events (kinds
"replica_spawn" / "replica_exit" / "replica_restart" / "replica_eject" /
"replica_admit") through the shared Recorder when one is attached, so
`tools/metrics_report.py` can fold restart counts out of serve.jsonl.

Stdlib-only by design: the router tier must run on a box with no jax.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence

from vitax import faults
from vitax.supervise import backoff_delay, terminate_child

# rotation states
STARTING = "starting"   # spawned/adopted, live but not yet warmed
READY = "ready"         # in rotation: healthz answered ready: true
EJECTED = "ejected"     # live but out of rotation (failing or not ready)
DEAD = "dead"           # managed process exited; awaiting backoff + respawn

DEFAULT_HEALTH_INTERVAL_S = 0.5
DEFAULT_HEALTH_TIMEOUT_S = 5.0
DEFAULT_FAIL_THRESHOLD = 2
DEFAULT_BACKOFF_S = 0.5
DEFAULT_BACKOFF_MAX_S = 30.0
DEFAULT_MAX_RESTARTS = 10
DEFAULT_TERM_GRACE_S = 30.0
DEFAULT_EWMA_ALPHA = 0.2
DEFAULT_HEALTH_JITTER = 0.2  # +-20% per-sweep jitter on the health interval


def http_get_json(url: str, timeout: float) -> dict:
    """Default health/metrics probe (injectable for tests)."""
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


class Replica:
    """One serve endpoint and its rotation/load state. All mutable fields
    are guarded by the owning ReplicaManager's lock."""

    def __init__(self, name: str, url: str,
                 argv: Optional[Sequence[str]] = None, proc=None):
        self.name = name
        self.url = url.rstrip("/")
        self.argv = list(argv) if argv is not None else None
        self.proc = proc                 # None for adopted replicas
        self.state = STARTING
        self.retired = False             # scale-in: out of rotation for good
        self.in_flight = 0
        self.ewma_latency_s: Optional[float] = None
        self.requests_total = 0
        self.dispatch_failures = 0       # router-side failed dispatches
        self.health_failures = 0         # consecutive failed health polls
        self.restarts = 0
        self.exit_code: Optional[int] = None
        self.restart_not_before = 0.0    # monotonic clock gate (backoff)
        self.last_health: dict = {}

    @property
    def managed(self) -> bool:
        return self.argv is not None

    def snapshot(self) -> dict:
        return {
            "url": self.url,
            "state": self.state,
            "retired": self.retired,
            "managed": self.managed,
            "in_flight": self.in_flight,
            "ewma_latency_s": (round(self.ewma_latency_s, 6)
                               if self.ewma_latency_s is not None else None),
            "requests_total": self.requests_total,
            "dispatch_failures": self.dispatch_failures,
            "health_failures": self.health_failures,
            "restarts": self.restarts,
            "exit_code": self.exit_code,
        }


class ReplicaManager:
    """Fleet rotation + lifecycle. `spawn`, `http_get`, `sleep` and `clock`
    are injectable so ejection/re-admission/restart logic is unit-testable
    with no real processes or sockets (tests/test_fleet.py)."""

    def __init__(self, recorder=None,
                 health_interval_s: float = DEFAULT_HEALTH_INTERVAL_S,
                 health_timeout_s: float = DEFAULT_HEALTH_TIMEOUT_S,
                 fail_threshold: int = DEFAULT_FAIL_THRESHOLD,
                 backoff_s: float = DEFAULT_BACKOFF_S,
                 backoff_max_s: float = DEFAULT_BACKOFF_MAX_S,
                 max_restarts: int = DEFAULT_MAX_RESTARTS,
                 term_grace_s: float = DEFAULT_TERM_GRACE_S,
                 ewma_alpha: float = DEFAULT_EWMA_ALPHA,
                 health_jitter: float = DEFAULT_HEALTH_JITTER,
                 spawn: Optional[Callable] = None,
                 http_get: Optional[Callable[[str, float], dict]] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic,
                 rng: Optional[random.Random] = None):
        assert fail_threshold >= 1, fail_threshold
        assert max_restarts >= 0, max_restarts
        assert 0.0 <= health_jitter < 1.0, health_jitter
        self.recorder = recorder
        self.health_interval_s = health_interval_s
        self.health_timeout_s = health_timeout_s
        self.fail_threshold = fail_threshold
        self.backoff_s = backoff_s
        self.backoff_max_s = backoff_max_s
        self.max_restarts = max_restarts
        self.term_grace_s = term_grace_s
        self.ewma_alpha = ewma_alpha
        self.health_jitter = health_jitter
        self._rng = rng or random.Random()
        self.replicas: List[Replica] = []
        self._name_seq = 0               # monotonic: discard never recycles names
        self.restart_total = 0
        self.started = time.time()
        self._lock = threading.Lock()
        # a chip belongs to one process at a time: this parent imports JAX
        # only transitively and never initializes a backend, so each replica
        # child gets its chip (tests/test_entrypoints.py pins it)
        self._spawn = spawn or (lambda argv: subprocess.Popen(argv))
        self._http_get = http_get or http_get_json
        self._sleep = sleep
        self._clock = clock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- registration --------------------------------------------------------

    def manage(self, argv: Sequence[str], url: str,
               name: Optional[str] = None) -> Replica:
        """Spawn a replica subprocess and own its lifecycle (restart on
        death, SIGTERM drain on stop)."""
        with self._lock:
            name = name or f"replica_{self._name_seq}"
            self._name_seq += 1
        replica = Replica(name, url, argv=argv, proc=self._spawn(list(argv)))
        with self._lock:
            self.replicas.append(replica)
        self._event("replica_spawn", replica=name, url=url)
        return replica

    def adopt(self, url: str, name: Optional[str] = None) -> Replica:
        """Register an externally started replica: health-checked and
        rotated, never restarted (its lifecycle belongs to someone else)."""
        with self._lock:
            name = name or f"replica_{self._name_seq}"
            self._name_seq += 1
        replica = Replica(name, url)
        with self._lock:
            self.replicas.append(replica)
        self._event("replica_spawn", replica=name, url=url, adopted=True)
        return replica

    # -- rotation / load accounting ------------------------------------------

    def ready_replicas(self) -> List[Replica]:
        with self._lock:
            return [r for r in self.replicas
                    if r.state == READY and not r.retired]

    def ready_count(self) -> int:
        return len(self.ready_replicas())

    def warming_count(self) -> int:
        """Live-but-warming replicas: spawned/adopted/restarted, not yet
        admitting traffic (STARTING until their own /healthz turns ready).
        Admission control counts these at --warming_capacity_frac so an
        in-progress scale-out relieves the predicted wait instead of the
        fleet shedding at the old capacity estimate."""
        with self._lock:
            return sum(1 for r in self.replicas
                       if r.state == STARTING and not r.retired)

    def active_count(self) -> int:
        """Fleet size for scaling decisions: every replica not retired
        (STARTING/READY/EJECTED/DEAD-awaiting-restart all count — they are
        capacity the fleet still owns or will recover)."""
        with self._lock:
            return sum(1 for r in self.replicas if not r.retired)

    def total_in_flight(self) -> int:
        with self._lock:
            return sum(r.in_flight for r in self.replicas)

    def in_flight_of(self, replica: Replica) -> int:
        with self._lock:
            return replica.in_flight

    def find(self, name: str) -> Optional[Replica]:
        with self._lock:
            for r in self.replicas:
                if r.name == name:
                    return r
            return None

    def degraded_count(self) -> int:
        """Replicas whose last /healthz advertised brownout (degraded:
        true) — serving, but shedding optional work. The router folds this
        into the fleet aggregate."""
        with self._lock:
            return sum(1 for r in self.replicas
                       if bool(r.last_health.get("degraded")))

    def degraded_seconds(self) -> float:
        """Fleet-wide brownout time: sum of each replica's advertised
        degraded_seconds (its BrownoutController odometer) at last poll."""
        with self._lock:
            return round(sum(
                float(r.last_health.get("degraded_seconds") or 0.0)
                for r in self.replicas), 3)

    def acquire(self, exclude: Sequence[str] = ()) -> Optional[Replica]:
        """Least-loaded pick: the READY replica with the fewest in-flight
        requests, ties broken by EWMA latency. Increments its in-flight
        count — pair every acquire with a release()."""
        with self._lock:
            candidates = [r for r in self.replicas
                          if r.state == READY and not r.retired
                          and r.name not in exclude]
            if not candidates:
                return None
            best = min(candidates,
                       key=lambda r: (r.in_flight, r.ewma_latency_s or 0.0))
            best.in_flight += 1
            return best

    def release(self, replica: Replica, latency_s: Optional[float] = None,
                ok: bool = True, counted: bool = True) -> None:
        """Pair of acquire(). `counted=False` undoes the acquire without
        charging a success or failure (the router's breaker uses it when it
        returns a picked replica unused — e.g. losing a half-open probe
        race — so accounting reflects only real dispatches)."""
        with self._lock:
            replica.in_flight = max(replica.in_flight - 1, 0)
            if not counted:
                return
            if ok:
                replica.requests_total += 1
                if latency_s is not None:
                    prev = replica.ewma_latency_s
                    replica.ewma_latency_s = (
                        latency_s if prev is None else
                        self.ewma_alpha * latency_s
                        + (1.0 - self.ewma_alpha) * prev)
            else:
                replica.dispatch_failures += 1

    # -- scale-in lifecycle ----------------------------------------------------

    def retire(self, replica: Replica) -> None:
        """Take a replica out of rotation for good (scale-in step 1): no
        new dispatches, and the health loop will never re-admit it. Its
        in-flight requests keep draining — pair with discard() once
        in_flight reaches zero."""
        with self._lock:
            if replica.retired:
                return
            replica.retired = True
            if replica.state == READY:
                replica.state = EJECTED
        self._event("replica_retire", replica=replica.name)

    def discard(self, replica: Replica) -> Optional[int]:
        """Remove a replica from the fleet (scale-in step 2). A managed
        process still alive is SIGTERM-drained through terminate_child —
        the replica's own drain contract answers anything left in flight
        before it exits. Returns the exit code (None for adopted
        replicas, whose processes belong to someone else)."""
        rc = None
        if replica.proc is not None and replica.proc.poll() is None:
            rc = terminate_child(replica.proc, self.term_grace_s,
                                 sleep=self._sleep)
        with self._lock:
            replica.state = DEAD
            replica.retired = True
            if rc is not None:
                replica.exit_code = rc
            if replica in self.replicas:
                self.replicas.remove(replica)
        self._event("replica_discard", replica=replica.name, exit_code=rc)
        return rc

    # -- health loop ----------------------------------------------------------

    def poll_once(self, now: Optional[float] = None) -> None:
        """One health sweep over the fleet (the background loop calls this
        every health_interval_s; tests call it directly). Retired replicas
        are skipped: they are draining toward discard() and must never be
        re-admitted or respawned."""
        now = self._clock() if now is None else now
        with self._lock:  # manage()/adopt() append concurrently
            fleet = [r for r in self.replicas if not r.retired]
        for replica in fleet:
            self._poll_replica(replica, now)

    def _poll_replica(self, r: Replica, now: float) -> None:
        if r.proc is not None:
            rc = r.proc.poll()
            if rc is not None:
                self._handle_dead(r, rc, now)
                return
        try:
            # chaos hook: `oserror` here is one flaky probe — probes sweep
            # the fleet in registration order, so with N replicas index
            # k*N + i targets replica i deterministically
            faults.fire("replica_health")
            payload = self._http_get(r.url + "/healthz",
                                     self.health_timeout_s)
            live = payload.get("status") == "ok"
            # replicas predating the liveness/readiness split have no
            # "ready" key: live implies routable for them
            ready = bool(payload.get("ready", True))
        except Exception:  # noqa: BLE001 — any probe failure means not live
            payload, live, ready = {}, False, False
        if live and ready:
            with self._lock:
                previous, r.state = r.state, READY
                r.health_failures = 0
                r.last_health = payload
            if previous != READY:
                self._event("replica_admit", replica=r.name,
                            previous_state=previous)
        elif live:
            # warming (after spawn/restart) or draining: out of rotation,
            # but alive — not a health FAILURE, so no failure count
            with self._lock:
                previous = r.state
                if r.state == READY:
                    r.state = EJECTED
                r.health_failures = 0
                r.last_health = payload
            if previous == READY:
                self._event("replica_eject", replica=r.name,
                            reason="not_ready")
        else:
            with self._lock:
                r.health_failures += 1
                eject = (r.state == READY
                         and r.health_failures >= self.fail_threshold)
                if eject:
                    r.state = EJECTED
                failures = r.health_failures
            if eject:
                self._event("replica_eject", replica=r.name,
                            reason=f"{failures} consecutive healthz failures")

    def _handle_dead(self, r: Replica, rc: int, now: float) -> None:
        with self._lock:
            first = r.state != DEAD
            if first:
                r.state = DEAD
                r.in_flight = 0
                r.exit_code = rc
                r.health_failures = 0
                r.restart_not_before = now + backoff_delay(
                    r.restarts + 1, self.backoff_s, self.backoff_max_s)
        if first:
            self._event("replica_exit", replica=r.name, exit_code=rc,
                        restarts=r.restarts)
            return
        if r.restarts >= self.max_restarts or now < r.restart_not_before:
            return
        proc = self._spawn(list(r.argv))
        with self._lock:
            r.proc = proc
            r.state = STARTING       # re-warms; re-admitted via healthz
            r.restarts += 1
            r.exit_code = None
            self.restart_total += 1
            restart = r.restarts
        self._event("replica_restart", replica=r.name, restart=restart)

    def start(self) -> None:
        """Launch the background health loop."""
        assert self._thread is None, "health loop already running"
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="vitax-fleet-health")
        self._thread.start()

    def _next_interval(self) -> float:
        """Jittered sleep before the next health sweep: uniform in
        health_interval_s * [1 - jitter, 1 + jitter]. Without jitter every
        manager in a deployment polls on the same cadence and a slow fleet
        sees synchronized probe bursts (a thundering herd against replicas
        already struggling to answer)."""
        if self.health_jitter <= 0.0:
            return self.health_interval_s
        spread = self.health_jitter * (2.0 * self._rng.random() - 1.0)
        return self.health_interval_s * (1.0 + spread)

    def _loop(self) -> None:
        while not self._stop.wait(timeout=self._next_interval()):
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — health loop must survive
                print(f"[vitax.fleet] health sweep failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)

    def stop(self) -> None:
        """Stop the health loop, then SIGTERM-drain every managed replica
        (their serve drain answers in-flight requests and exits 0)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.health_interval_s * 4 + 5.0)
            self._thread = None
        with self._lock:
            fleet = list(self.replicas)
        for r in fleet:
            if r.proc is not None and r.proc.poll() is None:
                rc = terminate_child(r.proc, self.term_grace_s,
                                     sleep=self._sleep)
                with self._lock:
                    r.state = DEAD
                    r.exit_code = rc
                self._event("replica_exit", replica=r.name, exit_code=rc,
                            drained=True)

    # -- observability ---------------------------------------------------------

    def snapshot(self) -> Dict[str, dict]:
        """Per-replica rotation/load state for the router's /metrics."""
        now = time.time()
        uptime = max(now - self.started, 1e-9)
        with self._lock:
            out = {}
            for r in self.replicas:
                snap = r.snapshot()
                snap["requests_per_sec"] = round(
                    r.requests_total / uptime, 3)
                out[r.name] = snap
            return out

    def _event(self, kind: str, **payload) -> None:
        if self.recorder is not None:
            try:
                self.recorder.event(kind, **payload)
            except Exception:  # noqa: BLE001 # vtx: ignore[VTX106] telemetry must not kill the fleet
                pass
