#!/usr/bin/env python3
"""Closed-loop load generator for the vitax serving stack (vitax/serve/).

Each worker thread issues POST /predict requests back-to-back (closed loop:
a worker's next request starts when its previous response lands), so
`--concurrency` bounds the in-flight requests and the dynamic batcher's
occupancy. Reports throughput and client-side p50/p95/p99 latency; when the
server ran with --metrics_dir, point --serve_jsonl at its serve.jsonl to
fold in the server-side per-request records (queue wait, engine latency,
batch occupancy) for the same window.

    python tools/serve_bench.py --url http://127.0.0.1:8000 \
        --concurrency 8 --requests 200 --image_size 224
    python tools/serve_bench.py ... --serve_jsonl /runs/s/serve.jsonl --json

Fleet mode (target = a vitax.serve.fleet router):
- `--target_rps N` paces the closed loop to an offered rate (each worker
  sleeps out the remainder of its share of 1/N between requests) so the
  bench exercises an SLO contract instead of saturating;
- 429 responses (admission sheds) are counted separately from errors —
  they ARE the overload contract — and the worker honors Retry-After
  (capped at 1s so benches stay short);
- `--slo_p99_ms D` adds an SLO verdict to the summary: attained iff the
  client p99 of successful requests is within D and errors == 0;
- `--replicas N` samples the router's /metrics during the run and reports
  rotation (ready_min/ready_end) and replica_restarts — a kill-a-replica
  drill shows up here, not in the error count — plus the containment
  counters (hedged, breaker_opens, degraded_seconds, retry budget) and
  the fleet-growth counters (cache_hits, cache_hit_rate, scale_events,
  ready_max) when the router runs with a cache/autoscaler;
- `--ramp "rps:secs,rps:secs,..."` replaces the fixed request count with
  a staged offered-load profile (each stage paces to its rps for its
  duration) — the autoscale acceptance drill's load shape. The summary
  gains a per-stage breakdown under "ramp";
- errors are classified: `errors_by_class` buckets connection_refused /
  reset_mid_body / timeout / http_5xx / other, so a drill can assert
  *which* failure mode leaked to clients, not just how many;
- 503s that carry Retry-After are `unavailable`, not errors: like 429
  sheds they are the fleet's bounded-degradation contract (retry budget
  exhausted, no ready replicas) and the worker honors the backoff.

Chaos mode (`--chaos '<fault plan json>'`): before the burst, POST the
plan to every replica's /chaos endpoint (URLs discovered from the
router's /metrics; replicas must run with --serve_allow_chaos) so a
drill can crash/hang/flap replicas mid-burst and assert the client view
stayed inside the 200/429/503+Retry-After contract. See vitax/faults.py
for the plan grammar and site names.

stdlib-only (urllib + threading): the bench must run on bare CI hosts.
Exit status: 0 when every request succeeded (sheds are not errors),
2 otherwise.
"""

from __future__ import annotations

import argparse
import io
import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request


def percentile(sorted_vals, q: float):
    """Linear-interpolated percentile of an ascending list (shared shape
    with tools/metrics_report.py percentile — numpy-free)."""
    if not sorted_vals:
        return None
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (len(sorted_vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac)


def make_image_bytes(image_size: int, seed: int = 0) -> bytes:
    """One PNG request body (random noise — serving cost is content-free)."""
    import numpy as np
    from PIL import Image
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, size=(image_size, image_size, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr, "RGB").save(buf, "PNG")
    return buf.getvalue()


def classify_error(exc: Exception) -> str:
    """Bucket a client-visible failure for `errors_by_class`: the drill
    question is WHICH mechanism leaked (a refused connect means routing
    sent traffic to a corpse; a reset mid-body means a replica died while
    answering; a timeout means a hang was not contained)."""
    if isinstance(exc, urllib.error.HTTPError):
        return "http_5xx" if exc.code >= 500 else "other"
    if isinstance(exc, (socket.timeout, TimeoutError)):
        return "timeout"
    # urllib wraps socket errors in URLError(reason=<OSError>)
    reason = getattr(exc, "reason", exc)
    if isinstance(reason, ConnectionRefusedError):
        return "connection_refused"
    if isinstance(reason, (ConnectionResetError, ConnectionAbortedError)):
        return "reset_mid_body"
    if isinstance(reason, (socket.timeout, TimeoutError)):
        return "timeout"
    text = str(exc).lower()
    if "refused" in text:
        return "connection_refused"
    if "reset" in text or "aborted" in text:
        return "reset_mid_body"
    if "timed out" in text or "timeout" in text:
        return "timeout"
    return "other"


def _retry_after_s(e: urllib.error.HTTPError) -> float:
    try:
        return float(e.headers.get("Retry-After", "1"))
    except (TypeError, ValueError):
        return 1.0


def parse_ramp(spec: str):
    """"rps:secs,rps:secs,..." -> [(rps, secs), ...] with validation."""
    stages = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            rps_s, secs_s = part.split(":", 1)
            rps, secs = float(rps_s), float(secs_s)
        except ValueError:
            raise ValueError(
                f"bad --ramp stage {part!r}: want 'rps:secs'") from None
        if rps <= 0 or secs <= 0:
            raise ValueError(f"--ramp stage {part!r}: rps and secs must be "
                             f"> 0")
        stages.append((rps, secs))
    if not stages:
        raise ValueError(f"--ramp {spec!r} has no stages")
    return stages


def run_worker(url: str, body: bytes, n_requests: int, timeout: float,
               latencies: list, errors: list, lock: threading.Lock,
               sheds: list = None, interval_s: float = 0.0,
               unavailable: list = None, deadline: float = 0.0) -> None:
    """One closed-loop worker. `interval_s` > 0 paces to an offered rate
    (open-ish loop: sleep out the remainder of the interval after each
    response); `sheds` collects 429 admission responses separately from
    errors — shedding under overload is contract behavior, not failure —
    and `unavailable` likewise collects 503+Retry-After (the fleet's
    bounded-degradation answer: retry budget dry, no ready replicas).
    `errors` entries are (class, detail) pairs — see classify_error.
    `deadline` > 0 switches to time-bounded mode (ramp stages): loop
    until the wall clock passes it, ignoring n_requests."""
    sent = 0
    while ((time.time() < deadline) if deadline > 0
           else (sent < n_requests)):
        sent += 1
        req = urllib.request.Request(
            url + "/predict", data=body,
            headers={"Content-Type": "image/png"})
        t0 = time.time()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                payload = json.load(resp)
                assert "classes" in payload and "probs" in payload
            with lock:
                latencies.append(time.time() - t0)
        except urllib.error.HTTPError as e:
            if e.code == 429 and sheds is not None:
                retry_after = _retry_after_s(e)
                with lock:
                    sheds.append(retry_after)
                time.sleep(min(max(retry_after, 0.0), 1.0))
            elif (e.code == 503 and unavailable is not None
                    and e.headers is not None
                    and e.headers.get("Retry-After") is not None):
                # contract degradation, not failure: back off as told
                retry_after = _retry_after_s(e)
                with lock:
                    unavailable.append(retry_after)
                time.sleep(min(max(retry_after, 0.0), 1.0))
            else:
                with lock:
                    errors.append((classify_error(e), f"HTTPError: {e.code}"))
        except Exception as e:  # noqa: BLE001 — count, keep loading
            with lock:
                errors.append(
                    (classify_error(e), f"{type(e).__name__}: {e}"))
        if interval_s > 0:
            leftover = interval_s - (time.time() - t0)
            if leftover > 0:
                time.sleep(leftover)


class FleetSampler:
    """Polls the router's GET /metrics during the bench to observe rotation:
    minimum ready count seen (did the fleet lose replicas?), final ready
    count (did they come back?), and restarts performed."""

    def __init__(self, url: str, period_s: float = 0.5):
        self.url = url
        self.period_s = period_s
        self.ready_min = None
        self.ready_max = None
        self.ready_end = None
        self.fleet_size = None
        self.restarts_end = 0
        self.hedged = 0
        self.hedge_wins = 0
        self.breaker_opens = 0
        self.degraded_seconds = 0.0
        self.retry_budget_exhausted = 0
        # fleet-growth counters (PR 17): absent keys stay at their zeros,
        # so benching a cache-less/static fleet still reports cleanly
        self.cache_hits = 0
        self.cache_hit_rate = None
        self.scale_events = 0
        self.scale_out = 0
        self.scale_in = 0
        # _sample runs on both the poll thread and the start/stop callers
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _sample(self) -> None:
        try:
            with urllib.request.urlopen(self.url + "/metrics",
                                        timeout=5.0) as resp:
                snap = json.load(resp)
        except Exception:  # noqa: BLE001 — sampling is best-effort
            return
        fleet = snap.get("fleet") or {}
        ready = fleet.get("ready")
        budget = snap.get("retry_budget") or {}
        with self._lock:
            if ready is not None:
                self.ready_end = ready
                self.ready_min = (ready if self.ready_min is None
                                  else min(self.ready_min, ready))
                self.ready_max = (ready if self.ready_max is None
                                  else max(self.ready_max, ready))
            self.fleet_size = fleet.get("size", self.fleet_size)
            self.restarts_end = fleet.get("replica_restarts",
                                          self.restarts_end)
            # containment counters (monotone on the router; keep the max
            # so a failed final scrape never rolls them back)
            self.hedged = max(self.hedged,
                              snap.get("hedges_total", 0))
            self.hedge_wins = max(self.hedge_wins,
                                  snap.get("hedge_wins_total", 0))
            self.breaker_opens = max(self.breaker_opens,
                                     snap.get("breaker_opens", 0))
            self.degraded_seconds = max(
                self.degraded_seconds,
                float(fleet.get("degraded_seconds") or 0.0))
            self.retry_budget_exhausted = max(
                self.retry_budget_exhausted,
                budget.get("exhausted_total", 0))
            self.cache_hits = max(self.cache_hits,
                                  snap.get("cache_hits", 0))
            rate = snap.get("cache_hit_rate")
            if rate is not None:
                self.cache_hit_rate = rate
            self.scale_events = max(self.scale_events,
                                    snap.get("scale_events", 0))
            auto = snap.get("autoscale") or {}
            self.scale_out = max(self.scale_out,
                                 auto.get("scale_out_total", 0))
            self.scale_in = max(self.scale_in,
                                auto.get("scale_in_total", 0))

    def _loop(self) -> None:
        while not self._stop.wait(timeout=self.period_s):
            self._sample()

    def start(self) -> None:
        self._sample()
        self._thread.start()

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._sample()
        with self._lock:
            return {
                "replicas": self.fleet_size,
                "ready_min": self.ready_min,
                "ready_max": self.ready_max,
                "ready_end": self.ready_end,
                "replica_restarts": self.restarts_end,
                "hedged": self.hedged,
                "hedge_wins": self.hedge_wins,
                "breaker_opens": self.breaker_opens,
                "degraded_seconds": round(self.degraded_seconds, 3),
                "retry_budget_exhausted": self.retry_budget_exhausted,
                "cache_hits": self.cache_hits,
                "cache_hit_rate": self.cache_hit_rate,
                "scale_events": self.scale_events,
                "scale_out": self.scale_out,
                "scale_in": self.scale_in,
            }


def install_chaos(router_url: str, plan_json: str,
                  timeout: float = 5.0) -> dict:
    """Forward a fault plan (vitax/faults.py grammar) to every replica's
    POST /chaos endpoint. Replica URLs come from the router's /metrics
    snapshot; replicas must run with --serve_allow_chaos or they answer
    403. Returns {replica_name: install result or error string}."""
    with urllib.request.urlopen(router_url + "/metrics",
                                timeout=timeout) as resp:
        snap = json.load(resp)
    replicas = snap.get("replicas") or {}
    assert replicas, f"no replicas in {router_url}/metrics — not a fleet?"
    results = {}
    body = plan_json.encode("utf-8")
    for name, info in sorted(replicas.items()):
        url = info.get("url")
        if not url:
            results[name] = "no url in router snapshot"
            continue
        req = urllib.request.Request(
            url + "/chaos", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                results[name] = json.load(resp)
        except Exception as e:  # noqa: BLE001 — report per replica
            results[name] = f"{type(e).__name__}: {e}"
    return results


def summarize_serve_jsonl(path: str, since: float) -> dict:
    """Server-side view from serve.jsonl: per-request records written by
    vitax/serve/server.py (kind "serve_request") in the bench window."""
    lat, wait, infer, occ = [], [], [], []
    corrupt = 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                corrupt += 1
                continue
            if (not isinstance(rec, dict)
                    or rec.get("kind") != "serve_request"
                    or rec.get("time", 0) < since):
                continue
            lat.append(rec["latency_s"])
            wait.append(rec["queue_wait_s"])
            infer.append(rec["infer_s"])
            occ.append(rec["batch_size"] / max(rec["bucket"], 1))
    lat.sort()
    return {
        "records": len(lat),
        "corrupt_lines": corrupt,
        "latency_s_p50": percentile(lat, 0.50),
        "latency_s_p95": percentile(lat, 0.95),
        "latency_s_p99": percentile(lat, 0.99),
        "queue_wait_s_mean": (round(sum(wait) / len(wait), 6)
                              if wait else None),
        "infer_s_mean": (round(sum(infer) / len(infer), 6)
                         if infer else None),
        "batch_occupancy_mean": (round(sum(occ) / len(occ), 4)
                                 if occ else None),
    }


def scrape_weights(url: str, timeout: float = 2.0):
    """Weight-footprint keys from a server or router /metrics: the single
    engine reports weights_dtype/param_bytes at top level, the fleet router
    aggregates them under "fleet" (vitax/serve/quant.py export path). None
    when the endpoint (or an older server) doesn't report them."""
    try:
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=timeout) as resp:
            snap = json.loads(resp.read())
    except Exception:  # noqa: BLE001  scrape is best-effort
        return None
    for scope in (snap, snap.get("fleet") or {}):
        if "param_bytes" in scope:
            return {
                "param_bytes": int(scope["param_bytes"]),
                "weights_dtype": scope.get("weights_dtype",
                                           scope.get("weights_dtypes")),
                "act_quant": scope.get("act_quant",
                                       scope.get("act_quants", "off")),
                "fused_dequant": scope.get("fused_dequant",
                                           scope.get("fused_dequants",
                                                     False)),
            }
    return None


def run_bench(url: str, concurrency: int, requests_per_worker: int,
              image_size: int, timeout: float, serve_jsonl: str = "",
              target_rps: float = 0.0, slo_p99_ms: float = 0.0,
              replicas: int = 0, chaos: str = "", ramp: str = "") -> dict:
    body = make_image_bytes(image_size)
    latencies: list = []
    errors: list = []
    sheds: list = []
    unavailable: list = []
    lock = threading.Lock()
    stages = parse_ramp(ramp) if ramp else []
    # pacing: each of C workers owns 1/C of the offered rate
    interval_s = concurrency / target_rps if target_rps > 0 else 0.0
    chaos_installed = install_chaos(url, chaos) if chaos else None
    sampler = FleetSampler(url) if replicas > 0 else None
    if sampler is not None:
        sampler.start()
    t_start = time.time()
    stage_reports = []
    if stages:
        # staged offered-load profile: each stage paces its own workers
        # against a wall-clock deadline; the aggregate lists span all
        # stages so the overall summary covers the whole profile
        for rps, secs in stages:
            stage_interval = concurrency / rps
            counts0 = (len(latencies), len(sheds), len(unavailable),
                       len(errors))
            deadline = time.time() + secs
            workers = [threading.Thread(
                target=run_worker,
                args=(url, body, 0, timeout, latencies, errors, lock,
                      sheds, stage_interval, unavailable, deadline),
                daemon=True) for _ in range(concurrency)]
            for w in workers:
                w.start()
            for w in workers:
                w.join()
            stage_lat = sorted(latencies[counts0[0]:])
            report = {
                "target_rps": rps,
                "duration_s": secs,
                "completed": len(stage_lat),
                "shed": len(sheds) - counts0[1],
                "unavailable": len(unavailable) - counts0[2],
                "errors": len(errors) - counts0[3],
                "latency_s_p50": percentile(stage_lat, 0.50),
                "latency_s_p99": percentile(stage_lat, 0.99),
            }
            if slo_p99_ms > 0:
                # per-stage SLO verdict: a surge stage that missed while
                # the fleet grew is visible even when the whole-profile
                # aggregate attains (and vice versa)
                stage_p99 = report["latency_s_p99"]
                report["slo_attained"] = bool(
                    stage_lat and report["errors"] == 0
                    and stage_p99 is not None
                    and stage_p99 * 1000.0 <= slo_p99_ms)
            stage_reports.append(report)
    else:
        workers = [threading.Thread(
            target=run_worker,
            args=(url, body, requests_per_worker, timeout, latencies,
                  errors, lock, sheds, interval_s, unavailable),
            daemon=True) for _ in range(concurrency)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    elapsed = time.time() - t_start
    lat = sorted(latencies)
    by_class: dict = {}
    for cls, _ in errors:
        by_class[cls] = by_class.get(cls, 0) + 1
    attempted = (len(lat) + len(errors) + len(sheds) + len(unavailable)
                 if stages else concurrency * requests_per_worker)
    summary = {
        "url": url,
        "concurrency": concurrency,
        "requests": attempted,
        "completed": len(lat),
        "errors": len(errors),
        "errors_by_class": by_class,
        "error_samples": [msg for _, msg in errors[:3]],
        "shed": len(sheds),
        "unavailable": len(unavailable),
        "shed_fraction": round(len(sheds) / max(attempted, 1), 4),
        "elapsed_s": round(elapsed, 3),
        "throughput_rps": round(len(lat) / max(elapsed, 1e-9), 3),
        "achieved_rps": round(
            (len(lat) + len(sheds)) / max(elapsed, 1e-9), 3),
        "latency_s_p50": percentile(lat, 0.50),
        "latency_s_p95": percentile(lat, 0.95),
        "latency_s_p99": percentile(lat, 0.99),
        "latency_s_mean": (round(sum(lat) / len(lat), 6) if lat else None),
    }
    if stage_reports:
        summary["ramp"] = stage_reports
    if slo_p99_ms > 0:
        p99 = summary["latency_s_p99"]
        summary["slo"] = {
            "p99_ms": slo_p99_ms,
            "target_rps": target_rps,
            "attained": bool(lat and not errors
                             and p99 is not None
                             and p99 * 1000.0 <= slo_p99_ms),
        }
    if sampler is not None:
        summary["fleet"] = sampler.stop()
    weights = scrape_weights(url, timeout=min(timeout, 5.0))
    if weights is not None:
        summary["weights"] = weights
    if chaos_installed is not None:
        summary["chaos"] = chaos_installed
    if serve_jsonl:
        # the server writes a request's record after its reply: give the
        # last handlers a moment to reach the file before counting
        deadline = time.time() + 2.0
        while True:
            server = summarize_serve_jsonl(serve_jsonl, since=t_start)
            if server["records"] >= len(lat) or time.time() >= deadline:
                break
            time.sleep(0.02)
        summary["server"] = server
    return summary


def print_human(s: dict) -> None:
    print(f"bench: {s['url']} x{s['concurrency']} closed-loop")
    print(f"  {s['completed']}/{s['requests']} ok ({s['errors']} errors, "
          f"{s['shed']} shed, {s['unavailable']} unavailable) in "
          f"{s['elapsed_s']:.2f}s -> {s['throughput_rps']:.1f} req/s")
    if s["errors_by_class"]:
        buckets = "  ".join(f"{k} {v}" for k, v
                            in sorted(s["errors_by_class"].items()))
        print(f"  errors by class: {buckets}")
    if s["latency_s_p50"] is not None:
        print(f"  client latency: p50 {1e3 * s['latency_s_p50']:.1f}ms  "
              f"p95 {1e3 * s['latency_s_p95']:.1f}ms  "
              f"p99 {1e3 * s['latency_s_p99']:.1f}ms")
    slo = s.get("slo")
    if slo:
        print(f"  SLO p99 <= {slo['p99_ms']:.0f}ms: "
              f"{'ATTAINED' if slo['attained'] else 'MISSED'}")
    fleet = s.get("fleet")
    if fleet:
        print(f"  fleet: {fleet['ready_end']}/{fleet['replicas']} ready at "
              f"end (min {fleet['ready_min']}), "
              f"{fleet['replica_restarts']} restarts")
        if (fleet.get("hedged") or fleet.get("breaker_opens")
                or fleet.get("degraded_seconds")
                or fleet.get("retry_budget_exhausted")):
            print(f"  containment: {fleet['hedged']} hedged "
                  f"({fleet['hedge_wins']} wins), "
                  f"{fleet['breaker_opens']} breaker opens, "
                  f"{fleet['retry_budget_exhausted']} budget-exhausted, "
                  f"degraded {fleet['degraded_seconds']:.1f}s")
        if fleet.get("scale_events") or fleet.get("cache_hits"):
            rate = fleet.get("cache_hit_rate")
            print(f"  growth: {fleet.get('scale_events', 0)} scale events "
                  f"({fleet.get('scale_out', 0)} out, "
                  f"{fleet.get('scale_in', 0)} in, ready peaked at "
                  f"{fleet.get('ready_max')}), "
                  f"{fleet.get('cache_hits', 0)} cache hits"
                  + (f" (rate {rate:.2f})" if rate is not None else ""))
    for i, st in enumerate(s.get("ramp") or []):
        p99 = st["latency_s_p99"]
        print(f"  ramp[{i}] {st['target_rps']:g} rps x "
              f"{st['duration_s']:g}s: {st['completed']} ok, "
              f"{st['shed']} shed, {st['unavailable']} unavailable, "
              f"{st['errors']} errors"
              + (f", p99 {1e3 * p99:.1f}ms" if p99 is not None else "")
              + ("" if "slo_attained" not in st else
                 f", slo {'ATTAINED' if st['slo_attained'] else 'MISSED'}"))
    weights = s.get("weights")
    if weights:
        print(f"  weights: {weights['weights_dtype']} "
              f"({weights['param_bytes']:,} B device-resident)  "
              f"act_quant {weights.get('act_quant', 'off')}  "
              f"fused_dequant {weights.get('fused_dequant', False)}")
    srv = s.get("server")
    if srv and srv["records"]:
        print(f"  server ({srv['records']} records): "
              f"p50 {1e3 * srv['latency_s_p50']:.1f}ms  "
              f"p99 {1e3 * srv['latency_s_p99']:.1f}ms  "
              f"queue {1e3 * srv['queue_wait_s_mean']:.1f}ms  "
              f"infer {1e3 * srv['infer_s_mean']:.1f}ms  "
              f"occupancy {srv['batch_occupancy_mean']:.2f}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="closed-loop load generator for vitax.serve")
    p.add_argument("--url", type=str, default="http://127.0.0.1:8000")
    p.add_argument("--concurrency", type=int, default=8,
                   help="closed-loop worker threads")
    p.add_argument("--requests", type=int, default=100,
                   help="requests per worker")
    p.add_argument("--image_size", type=int, default=224,
                   help="request image size (must match the served model)")
    p.add_argument("--timeout", type=float, default=90.0,
                   help="per-request client timeout (s)")
    p.add_argument("--serve_jsonl", type=str, default="",
                   help="server's serve.jsonl (--metrics_dir) to fold "
                        "server-side latency/queue/occupancy into the report")
    p.add_argument("--target_rps", type=float, default=0.0,
                   help="pace the offered load to this rate (0 = saturate)")
    p.add_argument("--slo_p99_ms", type=float, default=0.0,
                   help="add an SLO verdict: attained iff client p99 is "
                        "within this and errors == 0")
    p.add_argument("--replicas", type=int, default=0,
                   help="expected fleet size: sample the router's /metrics "
                        "during the run and report rotation + restarts")
    p.add_argument("--chaos", type=str, default="",
                   help="fault plan JSON (vitax/faults.py grammar) POSTed "
                        "to every replica's /chaos before the burst — "
                        "replicas must run with --serve_allow_chaos")
    p.add_argument("--ramp", type=str, default="",
                   help="staged offered-load profile 'rps:secs,rps:secs,"
                        "...' (replaces --requests/--target_rps; the "
                        "autoscale drill's load shape)")
    p.add_argument("--json", action="store_true",
                   help="emit the summary as one JSON object (CI mode)")
    args = p.parse_args(argv)

    summary = run_bench(args.url, args.concurrency, args.requests,
                        args.image_size, args.timeout, args.serve_jsonl,
                        target_rps=args.target_rps,
                        slo_p99_ms=args.slo_p99_ms, replicas=args.replicas,
                        chaos=args.chaos, ramp=args.ramp)
    if args.json:
        print(json.dumps(summary, sort_keys=True))
    else:
        print_human(summary)
    return 0 if summary["errors"] == 0 and summary["completed"] else 2


if __name__ == "__main__":
    sys.exit(main())
