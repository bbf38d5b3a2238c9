"""vitax.arbiter: chip-ledger arbitration for co-located train + serve.

Fast tier pins the whole subsystem socketless (injected clocks, fake
procs, recorded seams — the test_autoscale.py discipline): the versioned
host ledger with atomic persistence and restart recovery, the hysteretic
borrow/return policy in all three modes, the TrainDirector's
drain-then-relaunch resize over supervise.topology_env, the Arbiter's
borrow/return executor with rollback and deny-dedupe, the train-side
ArbiterReporter heartbeat, the real-HTTP daemon surface, a two-agent
placement soak (round-robin boots, AgentFullError on a full pod,
release-on-drain slot accounting), and the metrics_report / serve_bench
schema growth. One `slow` drill runs the acceptance scenario end to end:
a chaos-armed serve_bench ramp against a live 2-process fake-data
training job; the surge borrows one host (agreed-preemption drain, 2->1
elastic resume from peer stores with zero Orbax reads, replica
provisioned + adopted), the ramp ends, the host returns and training
re-expands to 2 — all visible in one metrics_report.
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from vitax.arbiter import Arbiter, ArbiterPolicy, HostLedger, TrainDirector
from vitax.arbiter.daemon import (JsonlRecorder, free_port, start_arbiter,
                                  stop_arbiter)
from vitax.arbiter.ledger import LEDGER_SCHEMA
from vitax.arbiter.policy import POLICIES, _QUIET_MULT
from vitax.config import Config
from vitax.serve.fleet import (AdmissionController, Autoscaler,
                               PlacementAgent, PlacementClient,
                               ReplicaManager, Router, start_agent,
                               start_router, stop_agent, stop_router)
from vitax.serve.fleet.placement import AgentFullError
from vitax.train.control import ArbiterReporter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_COUNTS = {"train": 2, "serve": 0, "free": 0}


def _import_tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


class DummyRecorder:
    def __init__(self):
        self.events = []

    def event(self, kind, **payload):
        self.events.append((kind, payload))

    def of(self, event):
        return [p for k, p in self.events
                if k == "arbiter" and p.get("event") == event]


class FakeProc:
    """Popen stand-in; exits with `exit_code` on the first SIGTERM."""

    def __init__(self, exit_code=0, on_signal=None):
        self.rc = None
        self.signals = []
        self._exit_code = exit_code
        self._on_signal = on_signal

    def poll(self):
        return self.rc

    def send_signal(self, sig):
        self.signals.append(sig)
        if self._on_signal is not None:
            self._on_signal(self)
        self.rc = self._exit_code

    def kill(self):
        self.rc = -9


class FakeTrain:
    """TrainDirector stand-in recording resize() calls."""

    term_grace_s = 5.0

    def __init__(self, n=2):
        self.n = n
        self.resizes = []
        self.is_healthy = True

    @property
    def process_count(self):
        return self.n

    def alive(self):
        return self.n

    def healthy(self):
        return self.is_healthy

    def resize(self, n):
        self.resizes.append(n)
        self.n = n
        return {"to_processes": n}


def _never(url, timeout):
    raise ConnectionError("unreachable")


# --- host ledger -------------------------------------------------------------

def test_ledger_seed_counts_and_owner():
    led = HostLedger(["h0", "h1"], owner="train")
    assert led.counts() == {"train": 2, "serve": 0, "free": 0}
    assert led.owner_of("h0") == "train"
    assert led.owner_of("nope") is None
    assert led.version == 2
    assert led.recovered is False
    snap = led.snapshot()
    assert snap["schema"] == LEDGER_SCHEMA
    assert set(snap["hosts"]) == {"h0", "h1"}


def test_ledger_assign_bumps_version_and_lease():
    led = HostLedger(["h0", "h1"])
    lease = led.assign("h1", "serve")
    assert lease["owner"] == "serve"
    assert lease["version"] == lease["lease_version"] == 3
    assert lease["host"] == "h1"
    assert led.counts() == {"train": 1, "serve": 1, "free": 0}
    with pytest.raises(KeyError):
        led.assign("nope", "serve")
    with pytest.raises(AssertionError):
        led.assign("h0", "cryptominer")


def test_ledger_hosts_owned_is_lease_ordered():
    """Oldest lease first; the borrow path peels hosts_owned()[-1], so a
    host that bounced through serve and back is the NEXT borrow victim."""
    led = HostLedger(["h0", "h1", "h2"])
    assert led.hosts_owned("train") == ["h0", "h1", "h2"]
    led.assign("h0", "serve")
    led.assign("h0", "train")   # h0 now holds the newest train lease
    assert led.hosts_owned("train") == ["h1", "h2", "h0"]
    assert led.hosts_owned("serve") == []


def test_ledger_persists_and_recovers(tmp_path):
    path = str(tmp_path / "ledger.json")
    led = HostLedger(["h0", "h1"], path=path)
    led.assign("h1", "serve")
    with open(path, encoding="utf-8") as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == LEDGER_SCHEMA
    assert on_disk["version"] == 3
    assert on_disk["hosts"]["h1"]["owner"] == "serve"
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic: no torn temps

    # a restarted arbiter re-derives the exact granted state
    led2 = HostLedger(path=path)
    assert led2.recovered is True
    assert led2.version == 3
    assert led2.owner_of("h1") == "serve"
    assert led2.counts() == led.counts()


def test_ledger_recovery_merges_new_hosts(tmp_path):
    path = str(tmp_path / "ledger.json")
    HostLedger(["h0"], path=path).assign("h0", "serve")
    led = HostLedger(["h0", "h1"], path=path)
    assert led.recovered is True
    assert led.owner_of("h0") == "serve"   # recovered lease wins
    assert led.owner_of("h1") == "train"   # new host seeded fresh


def test_ledger_corrupt_file_starts_fresh(tmp_path):
    path = str(tmp_path / "ledger.json")
    with open(path, "w", encoding="utf-8") as f:
        f.write("{not json")
    led = HostLedger(["h0"], path=path)
    assert led.recovered is False
    assert led.owner_of("h0") == "train"
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"hosts": [], "version": "x"}, f)  # wrong shapes
    assert HostLedger(["h0"], path=path).recovered is False


def test_ledger_in_memory_mode(tmp_path):
    led = HostLedger(["h0"])  # path="" -> no persistence
    led.assign("h0", "free")
    assert led.counts()["free"] == 1
    assert not list(tmp_path.iterdir())


# --- policy ------------------------------------------------------------------

def test_policy_dwell_then_borrow_then_cooldown():
    pol = ArbiterPolicy("slo_bounded", dwell_s=2.0, cooldown_s=5.0)
    sig = {"shed_rate_per_s": 3.0}
    d = pol.tick(sig, TRAIN_COUNTS, 0, 0.0)
    assert (d.action, d.reason, d.deny) == (None, "dwell", False)
    d = pol.tick(sig, TRAIN_COUNTS, 0, 2.0)
    assert (d.action, d.reason) == ("borrow", "shed_rate")
    pol.action_taken(2.0)   # executed: cooldown until 7.0, streaks reset
    assert pol.tick(sig, TRAIN_COUNTS, 1, 2.5).reason == "dwell"
    d = pol.tick(sig, TRAIN_COUNTS, 1, 4.5)   # dwell met, cooldown open
    assert (d.reason, d.deny) == ("cooldown", True)
    assert pol.tick(sig, TRAIN_COUNTS, 1, 7.5).action == "borrow"


def test_policy_deny_reasons_ordered():
    pol = ArbiterPolicy("slo_bounded", dwell_s=0.0, min_train_hosts=1)
    sig = {"shed_rate_per_s": 9.0, "train_progressing": False}
    # the floor outranks everything: a one-host train job is never drained
    one = {"train": 1, "serve": 1, "free": 0}
    d = pol.tick(sig, one, 1, 0.0)
    assert (d.reason, d.deny) == ("min_train_hosts", True)
    # above the floor, a stalled step loop blocks the drain
    d = pol.tick(sig, TRAIN_COUNTS, 0, 1.0)
    assert (d.reason, d.deny) == ("train_stalled", True)


def test_policy_train_priority_requires_backed_escalation():
    pol = ArbiterPolicy("train_priority", dwell_s=0.0)
    assert pol.tick({"shed_rate_per_s": 9.0}, TRAIN_COUNTS,
                    0, 0.0).reason == "idle"
    assert pol.tick({"escalations": 1}, TRAIN_COUNTS, 0, 1.0).reason == "idle"
    d = pol.tick({"escalations": 1, "shed_rate_per_s": 9.0},
                 TRAIN_COUNTS, 0, 2.0)
    assert (d.action, d.reason) == ("borrow", "escalation")


def test_policy_quiet_dwell_multiples():
    for name in POLICIES:
        pol = ArbiterPolicy(name, dwell_s=2.0)
        assert pol.quiet_dwell_s == 2.0 * _QUIET_MULT[name], name
    assert ArbiterPolicy(dwell_s=2.0, quiet_dwell_s=1.5).quiet_dwell_s == 1.5


def test_policy_return_after_quiet_streak():
    pol = ArbiterPolicy("slo_bounded", dwell_s=1.0)   # quiet dwell 2.0
    assert pol.tick({}, TRAIN_COUNTS, 0, 0.0).reason == "idle"
    assert pol.tick({}, TRAIN_COUNTS, 1, 0.0).reason == "quiet_dwell"
    assert pol.tick({}, TRAIN_COUNTS, 1, 1.5).reason == "quiet_dwell"
    d = pol.tick({}, TRAIN_COUNTS, 1, 2.0)
    assert (d.action, d.reason) == ("return", "pressure_cleared")
    # pressure mid-streak resets the quiet clock
    pol.tick({"predicted_wait_overshoot": True}, TRAIN_COUNTS, 1, 2.5)
    assert pol.tick({}, TRAIN_COUNTS, 1, 3.0).reason == "quiet_dwell"


def test_policy_set_policy_resets_streaks_and_snapshot():
    pol = ArbiterPolicy("slo_bounded", dwell_s=2.0, cooldown_s=5.0)
    sig = {"shed_rate_per_s": 9.0}
    pol.tick(sig, TRAIN_COUNTS, 0, 0.0)
    pol.set_policy("serve_priority")
    assert pol.tick(sig, TRAIN_COUNTS, 0, 3.0).reason == "dwell"  # re-earned
    assert pol.snapshot() == {
        "policy": "serve_priority", "min_train_hosts": 1, "dwell_s": 2.0,
        "quiet_dwell_s": 8.0, "cooldown_s": 5.0, "cooldown_until": 0.0}


# --- TrainDirector -----------------------------------------------------------

def mk_director(exit_code=0, argv=("train.py",), order=None):
    spawned = []
    order = order if order is not None else []

    def spawn(child_argv, env, tag):
        proc = FakeProc(exit_code,
                        on_signal=lambda p: order.append(spawned_index(p)))
        spawned.append({"argv": list(child_argv), "env": env, "tag": tag,
                        "proc": proc})
        return proc

    def spawned_index(proc):
        return next(i for i, s in enumerate(spawned) if s["proc"] is proc)

    director = TrainDirector(list(argv), term_grace_s=2.0,
                             env={"BASE": "1"}, spawn=spawn,
                             sleep=lambda s: None, port_fn=lambda: 4321)
    return director, spawned, order


def test_director_start_builds_topology_env():
    director, spawned, _ = mk_director()
    director.start(2)
    assert [s["tag"] for s in spawned] == ["g0_p0", "g0_p1"]
    for pid, s in enumerate(spawned):
        assert s["env"]["JAX_COORDINATOR_ADDRESS"] == "localhost:4321"
        assert s["env"]["JAX_NUM_PROCESSES"] == "2"
        assert s["env"]["JAX_PROCESS_ID"] == str(pid)
        assert s["env"]["BASE"] == "1"
        # ensure_auto_resume: a relaunch must adopt the committed epoch
        assert s["argv"][-2:] == ["--resume_epoch", "-1"]
    assert director.process_count == 2
    assert director.alive() == 2 and director.healthy()


def test_director_resize_signals_all_before_waiting():
    """The preemption fold needs every rank alive to agree: drain SIGTERMs
    ALL processes first, then waits each out; the relaunch drops the
    coordinator vars for a 1-process topology."""
    director, spawned, order = mk_director()
    director.start(2)
    out = director.resize(1)
    assert out == {"from_processes": 2, "to_processes": 1,
                   "exit_codes": [0, 0]}
    # first wave hits both procs before any terminate-wait re-signals
    assert order[:2] == [0, 1]
    assert director.process_count == 1 and director.resizes_total == 1
    new = spawned[2]
    assert new["tag"] == "g1_p0"
    assert "JAX_NUM_PROCESSES" not in new["env"]
    assert "JAX_COORDINATOR_ADDRESS" not in new["env"]


def test_director_resize_relaunches_old_count_on_dirty_exit():
    """A dirty drain raises AND restores the previous topology: the last
    committed checkpoint is intact, and a director left at zero processes
    would make every later resize compute from 0."""
    director, spawned, _ = mk_director(exit_code=1)
    director.start(2)
    with pytest.raises(RuntimeError, match="exit codes.*relaunched at 2"):
        director.resize(1)
    assert director.process_count == 2   # relaunched, not left empty
    assert [s["tag"] for s in spawned[2:]] == ["g1_p0", "g1_p1"]
    assert director.last_start_t is not None


def test_director_healthy_sees_dead_rank():
    director, spawned, _ = mk_director()
    director.start(2)
    spawned[0]["proc"].rc = 1   # one rank crashed
    assert director.alive() == 1
    assert director.healthy() is False


# --- arbiter executor (socketless) -------------------------------------------

def mk_arbiter(hosts=("h0", "h1"), n_train=2, policy="slo_bounded",
               dwell_s=0.0, cooldown_s=0.0, quiet_dwell_s=0.0,
               min_train_hosts=1, clock=None, **seams):
    ledger = HostLedger(list(hosts))
    pol = ArbiterPolicy(policy, min_train_hosts=min_train_hosts,
                        dwell_s=dwell_s, cooldown_s=cooldown_s,
                        quiet_dwell_s=quiet_dwell_s)
    train = FakeTrain(n_train)
    rec = DummyRecorder()
    arb = Arbiter(ledger, pol, train=train, recorder=rec,
                  clock=clock or (lambda: 0.0), **seams)
    return arb, train, rec


def test_arbiter_borrow_then_return_full_sequence():
    order = []
    arb, train, rec = mk_arbiter(
        provision=lambda host: (order.append(("provision", host))
                                or "http://b:1"),
        release=lambda host, url: order.append(("release", host, url)),
        fleet_adopt=lambda url: order.append(("adopt", url)),
        fleet_release=lambda url: order.append(("fleet_release", url)),
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    assert arb.tick(now=0.0) == "borrow"
    # serve side engaged in order, against the NEWEST train lease
    assert order == [("provision", "h1"), ("adopt", "http://b:1")]
    assert train.resizes == [1]
    assert arb.ledger.owner_of("h1") == "serve"
    m = arb.metrics()
    assert m["borrows_total"] == 1
    assert m["borrowed"] == {"h1": "http://b:1"}
    assert [p["event"] for p in rec.of("borrow")] == ["borrow"]
    assert rec.of("borrow")[0]["ledger_version"] == arb.ledger.version

    # pressure gone: drain the loan back in reverse order of acquisition
    order.clear()
    arb._signals_fn = lambda: {}
    assert arb.tick(now=1.0) == "return"
    assert order == [("fleet_release", "http://b:1"),
                     ("release", "h1", "http://b:1")]
    assert train.resizes == [1, 2]
    assert arb.ledger.owner_of("h1") == "train"
    assert arb.metrics()["returns_total"] == 1
    assert arb.metrics()["borrowed"] == {}


def test_arbiter_borrow_rollback_on_provision_failure():
    def provision(host):
        raise RuntimeError("agent down")

    arb, train, rec = mk_arbiter(
        provision=provision,
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    assert arb.tick(now=0.0) is None
    # unwound: ledger restored, training re-expanded, loudly reported
    assert arb.ledger.owner_of("h1") == "train"
    assert train.resizes == [1, 2]
    assert arb.metrics()["borrows_total"] == 0
    fails = rec.of("borrow_failed")
    assert fails and "RuntimeError: agent down" in fails[0]["detail"]


def test_arbiter_borrow_rollback_releases_provisioned_replica():
    order = []
    arb, train, _ = mk_arbiter(
        provision=lambda host: "http://b:1",
        release=lambda host, url: order.append(("release", host, url)),
        fleet_adopt=lambda url: (_ for _ in ()).throw(OSError("router")),
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    assert arb.tick(now=0.0) is None
    # the orphaned replica is released before the ledger flips back
    assert order == [("release", "h1", "http://b:1")]
    assert arb.ledger.owner_of("h1") == "train"
    assert train.resizes == [1, 2]


def test_arbiter_deny_dedupe_and_cooldown_after_failure():
    attempts = []

    def provision(host):
        attempts.append(host)
        if len(attempts) == 1:
            raise RuntimeError("first attempt dies")
        return "http://b:1"

    arb, _, rec = mk_arbiter(provision=provision, cooldown_s=10.0,
                             signals_fn=lambda: {"shed_rate_per_s": 9.0})
    assert arb.tick(now=0.0) is None          # borrow_failed -> cooldown
    assert arb.tick(now=1.0) is None          # denied: cooldown
    assert arb.tick(now=2.0) is None          # same reason: deduped
    assert arb.metrics()["denies_total"] == 1
    assert len(rec.of("deny")) == 1
    assert rec.of("deny")[0]["reason"] == "cooldown"
    assert arb.tick(now=11.0) == "borrow"     # cooldown over: retried
    assert arb.metrics()["borrows_total"] == 1


def test_arbiter_escalation_drives_borrow_and_clears():
    arb, _, rec = mk_arbiter(provision=lambda host: "http://b:1")
    out = arb.request_capacity("autoscaler_max")
    assert out == {"accepted": True, "status": "pending"}
    assert arb.metrics()["requests_total"] == 1
    assert rec.of("request")[0]["reason"] == "autoscaler_max"
    assert arb.tick(now=0.0) == "borrow"
    assert rec.of("borrow")[0]["reason"] == "escalation"
    # the escalation was consumed: next tick sees quiet and returns
    assert arb.tick(now=1.0) == "return"


def test_arbiter_return_failure_keeps_loan_then_retries():
    state = {"fail": True}

    def fleet_release(url):
        if state["fail"]:
            raise OSError("router drain wedged")

    arb, train, rec = mk_arbiter(
        provision=lambda host: "http://b:1", fleet_release=fleet_release,
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    assert arb.tick(now=0.0) == "borrow"
    arb._signals_fn = lambda: {}
    assert arb.tick(now=1.0) is None           # return failed: loan kept
    assert rec.of("return_failed")
    assert arb.metrics()["borrowed"] == {"h1": "http://b:1"}
    assert arb.metrics()["returns_total"] == 0
    state["fail"] = False
    assert arb.tick(now=2.0) == "return"
    assert train.resizes == [1, 2]


def test_arbiter_telemetry_outranks_director_liveness():
    """A fresh step heartbeat proves progress even when the director's
    process view says unhealthy (mid-recovery); a stale one falls back."""
    clock = lambda: 100.0  # noqa: E731 — trivially injected clock
    arb, train, rec = mk_arbiter(
        hosts=("h0", "h1", "h2"), n_train=3, clock=clock,
        provision=lambda host: "http://b:1", cooldown_s=0.0,
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    train.is_healthy = False
    assert arb.tick(now=0.0) is None
    assert rec.of("deny")[0]["reason"] == "train_stalled"

    arb.observe_train({"step": 7, "epoch": 0, "process_count": 3,
                       "junk": "dropped"})
    tel = arb.metrics()["train_telemetry"]
    assert tel["step"] == 7 and "junk" not in tel
    assert tel["observed_at"] == 100.0
    assert arb.tick(now=110.0) == "borrow"     # heartbeat 10s old: fresh
    assert arb.tick(now=200.0) is None         # 100s old: stale again
    assert rec.of("deny")[-1]["reason"] == "train_stalled"


def test_arbiter_heartbeat_must_postdate_generation():
    """After a resize, the PREVIOUS generation's heartbeat no longer
    vouches for progress: the relaunched ranks must post a step of their
    own before any further drain — a booting rank has no preemption
    handler installed and would die dirty on SIGTERM."""
    t = {"now": 100.0}
    arb, train, rec = mk_arbiter(
        hosts=("h0", "h1", "h2"), n_train=3, cooldown_s=0.0,
        clock=lambda: t["now"], provision=lambda host: "http://b:1",
        signals_fn=lambda: {"shed_rate_per_s": 9.0})
    arb.observe_train({"step": 9, "epoch": 0, "process_count": 3})
    t["now"] = 105.0                         # clock at the resize moment
    assert arb.tick(now=110.0) == "borrow"   # stamps _gen_start_t = 105
    assert arb._gen_start_t == 105.0
    assert arb.tick(now=111.0) is None       # fresh, but pre-resize post
    deny = rec.of("deny")[-1]
    assert deny["reason"] == "train_stalled"
    # the deny carries its inputs: the heartbeat predates the resize by 5s
    assert deny["generation_lag_s"] == 5.0
    assert deny["telemetry_age_s"] == 11.0
    t["now"] = 120.0
    arb.observe_train({"step": 1, "epoch": 0, "process_count": 2})
    assert arb.tick(now=121.0) == "borrow"  # the new generation reported


def test_policy_return_blocked_while_train_stalled():
    """The return's re-expand drains the current generation too, so a
    stalled (or still-booting) train job defers the return as well."""
    pol = ArbiterPolicy("slo_bounded", dwell_s=1.0)   # quiet dwell 2.0
    pol.tick({}, TRAIN_COUNTS, 1, 0.0)
    d = pol.tick({"train_progressing": False}, TRAIN_COUNTS, 1, 2.5)
    assert (d.reason, d.deny) == ("train_stalled", True)
    d = pol.tick({}, TRAIN_COUNTS, 1, 3.0)
    assert (d.action, d.reason) == ("return", "pressure_cleared")


def test_arbiter_metrics_shape_and_policy_gate():
    arb, _, rec = mk_arbiter()
    assert set(arb.metrics()) == {
        "borrows_total", "returns_total", "denies_total", "requests_total",
        "borrowed", "last_event", "train_telemetry", "policy", "ledger",
        "train_processes", "train_alive"}
    with pytest.raises(ValueError, match="unknown policy"):
        arb.set_policy("cryptomining")
    assert arb.set_policy("serve_priority") == {"policy": "serve_priority"}
    assert arb.metrics()["policy"]["policy"] == "serve_priority"
    assert rec.of("policy_change")[0]["policy"] == "serve_priority"


# --- train-side heartbeat (ArbiterReporter) ----------------------------------

def test_arbiter_reporter_posts_latest_and_dedupes():
    posts = []
    reporter = ArbiterReporter(
        "http://a:1/", process_count=2,
        http_json=lambda url, payload, timeout: posts.append((url, payload)))
    assert reporter.post_once() is False       # nothing observed yet
    reporter.update(5, 0)
    reporter.update(6, 0)                      # only the LATEST posts
    assert reporter.post_once() is True
    assert posts == [("http://a:1/telemetry",
                      {"step": 6, "epoch": 0, "process_count": 2})]
    assert reporter.post_once() is False       # unchanged: deduped
    reporter.update(7, 0)
    assert reporter.post_once() is True
    assert reporter.posts_total == 2
    # the heartbeat refresh: an UNCHANGED snapshot still re-posts on
    # force — a slow trainer must not read as a stalled one
    assert reporter.post_once() is False
    assert reporter.post_once(force=True) is True
    assert posts[-1][1] == {"step": 7, "epoch": 0, "process_count": 2}
    assert reporter.posts_total == 3


def test_arbiter_reporter_swallows_transport_failures():
    reporter = ArbiterReporter("http://a:1", http_json=_never)
    reporter.update(1, 0)
    assert reporter.post_once() is False
    assert reporter.post_failures == 1
    assert reporter.posts_total == 0


def test_arbiter_reporter_thread_flushes_on_stop():
    posts = []
    reporter = ArbiterReporter(
        "http://a:1", interval_s=30.0,   # too slow to fire: stop() flushes
        http_json=lambda url, payload, timeout: posts.append(payload))
    reporter.start()
    reporter.update(3, 1)
    reporter.stop()
    assert posts == [{"step": 3, "epoch": 1, "process_count": 1}]
    assert not any(t.name == "vitax-arbiter-report"
                   for t in threading.enumerate())


# --- daemon HTTP surface -----------------------------------------------------

def _http(url, payload=None, timeout=10.0):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def test_arbiter_http_surface():
    ledger = HostLedger(["h0", "h1"])
    arb = Arbiter(ledger, ArbiterPolicy(dwell_s=3600.0), interval_s=3600.0)
    httpd = start_arbiter(arb, 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        assert _http(base + "/healthz") == {"status": "ok"}
        led = _http(base + "/ledger")
        assert led["schema"] == LEDGER_SCHEMA and set(led["hosts"]) == {
            "h0", "h1"}
        out = _http(base + "/request", {"reason": "surge"})
        assert out == {"accepted": True, "status": "pending"}
        assert _http(base + "/telemetry",
                     {"step": 3, "epoch": 0,
                      "process_count": 2}) == {"ok": True}
        m = _http(base + "/metrics")
        assert m["requests_total"] == 1
        assert m["train_telemetry"]["step"] == 3
        # POST /policy is an operator action: hard 403 until opted in
        with pytest.raises(urllib.error.HTTPError) as err:
            _http(base + "/policy", {"policy": "serve_priority"})
        assert err.value.code == 403
        arb.allow_admin = True
        assert _http(base + "/policy", {"policy": "serve_priority"}) == {
            "policy": "serve_priority"}
        with pytest.raises(urllib.error.HTTPError) as err:
            _http(base + "/policy", {"policy": "bogus"})
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _http(base + "/nope")
        assert err.value.code == 404
    finally:
        stop_arbiter(httpd, arb)


# --- two-agent placement soak (multi-host pod) -------------------------------

def _mk_loopback_agent(max_slots=1):
    """A real-HTTP placement agent whose manager spawns FakeProcs (no
    health loop verdicts: http_get always fails, states stay STARTING —
    slot accounting is what this soak pins)."""
    spawned = []

    def spawn(argv):
        proc = FakeProc()
        spawned.append((argv, proc))
        return proc

    manager = ReplicaManager(spawn=spawn, http_get=_never,
                             health_interval_s=0.05)
    agent = PlacementAgent(advertise_host="127.0.0.1", base_port=9300,
                           manager=manager, max_slots=max_slots)
    httpd = start_agent(agent, port=0)
    client = PlacementClient(
        f"http://127.0.0.1:{httpd.server_address[1]}")
    return agent, httpd, client, spawned


def test_two_agent_soak_round_robin_full_pod_and_release():
    """Two loopback `fleet.agent` instances, one slot each: round-robin
    boots land one replica per host, a third provision 409s on BOTH
    agents (AgentFullError — the autoscaler's escalation trigger), and a
    release-on-drain frees the slot for the next provision. Slot
    accounting (/healthz "slots") pins every transition."""
    agent_a, httpd_a, client_a, spawned_a = _mk_loopback_agent()
    agent_b, httpd_b, client_b, spawned_b = _mk_loopback_agent()
    clients = [client_a, client_b]

    def spawn_replica(i, start):
        # the fleet CLI's placement loop: round-robin start, try every
        # agent, surface AgentFullError only when the whole pod is full
        last_full = None
        for k in range(len(clients)):
            client = clients[(start + k) % len(clients)]
            try:
                return client, client.provision(["--dtype", "float32"],
                                                name=f"replica_{i}")
            except AgentFullError as e:
                last_full = e
        raise last_full

    try:
        # boot: one replica per agent
        used_a = spawn_replica(0, 0)
        used_b = spawn_replica(1, 1)
        assert used_a[0] is client_a and used_b[0] is client_b
        assert agent_a.manager.find("replica_0") is not None
        assert agent_b.manager.find("replica_1") is not None
        assert client_a.healthz()["slots"] == {"used": 1, "max": 1}
        assert client_b.healthz()["slots"] == {"used": 1, "max": 1}

        # the pod is full: every agent 409s, the loop surfaces the error
        with pytest.raises(AgentFullError):
            spawn_replica(2, 0)
        # and the wire contract really is a 409, not a generic failure
        with pytest.raises(urllib.error.HTTPError) as err:
            client_a._http_json(client_a.agent_url + "/provision",
                                {"argv": ["--x", "y"]}, 5.0)
        assert err.value.code == 409

        # release-on-drain: slot freed, process SIGTERM-drained
        assert client_a.release("replica_0") == {"released": "replica_0"}
        assert 15 in spawned_a[0][1].signals
        assert client_a.healthz()["slots"] == {"used": 0, "max": 1}

        # next provision starts at the FULL agent and wraps to the free one
        client, out = spawn_replica(3, 1)
        assert client is client_a
        assert agent_a.manager.find("replica_3") is not None
        assert out["url"].startswith("http://127.0.0.1:")

        assert agent_a.provisions_total == 2
        assert agent_a.releases_total == 1
        assert agent_b.provisions_total == 1
    finally:
        stop_agent(httpd_a, agent_a)
        stop_agent(httpd_b, agent_b)


def test_agent_cli_exposes_max_replicas_flag():
    from vitax.serve.fleet.agent import build_agent_parser
    ns = build_agent_parser().parse_args([])
    assert ns.agent_max_replicas == 0   # default: unbounded (historical)
    ns = build_agent_parser().parse_args(["--agent_max_replicas", "2"])
    assert ns.agent_max_replicas == 2


# --- metrics_report + serve_bench schema growth ------------------------------

def test_metrics_report_arbiter_sections(tmp_path):
    metrics_report = _import_tool("metrics_report")
    path = tmp_path / "arbiter.jsonl"
    records = [
        {"kind": "arbiter", "event": "request", "reason": "escalation"},
        {"kind": "arbiter", "event": "deny", "reason": "min_train_hosts"},
        {"kind": "arbiter", "event": "deny", "reason": "min_train_hosts"},
        {"kind": "arbiter", "event": "deny", "reason": "cooldown"},
        {"kind": "arbiter", "event": "borrow_start", "host": "h1"},
        {"kind": "arbiter", "event": "borrow", "host": "h1"},
        {"kind": "arbiter", "event": "borrow_failed", "host": "h1"},
        {"kind": "arbiter", "event": "return", "host": "h1"},
        {"kind": "autoscale", "event": "scale_out", "outcome": "escalated"},
        {"kind": "autoscale", "event": "scale_out", "replica": "r1"},
        {"kind": "control", "event": "elastic_resume",
         "from_processes": 2, "to_processes": 1},
        {"kind": "control", "event": "topology_change",
         "from_processes": 1, "to_processes": 2},
    ]
    path.write_text("\n".join(
        json.dumps(dict({"schema": 1, "time": float(i), "rank": 0}, **r))
        for i, r in enumerate(records)) + "\n")
    summary = metrics_report.summarize(str(path))
    assert summary["arbiter_events"] == {
        "requests": 1, "borrows": 1, "returns": 1, "borrow_failures": 1,
        "return_failures": 0,
        "denies": {"min_train_hosts": 2, "cooldown": 1}}
    assert summary["autoscale_events"]["escalations"] == 1
    assert summary["train_topology_timeline"] == [
        {"event": "elastic_resume", "from_processes": 2, "to_processes": 1},
        {"event": "topology_change", "from_processes": 1,
         "to_processes": 2}]
    metrics_report.print_human(summary)   # human arm renders without error


def test_serve_bench_ramp_stage_slo_verdict():
    """Each ramp stage now carries its own SLO verdict, so a surge-stage
    miss is visible even when the whole-profile aggregate attains."""
    serve_bench = _import_tool("serve_bench")

    class Instant(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # noqa: A003
            pass

        def do_POST(self):  # noqa: N802
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            body = json.dumps({"classes": [0], "probs": [1.0]}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Instant)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        summary = serve_bench.run_bench(
            url, concurrency=2, requests_per_worker=0, image_size=16,
            timeout=10.0, slo_p99_ms=5000.0, ramp="4:1")
        stage = summary["ramp"][0]
        assert stage["slo_attained"] is True
        assert stage["errors"] == 0 and stage["completed"] > 0
        # without an SLO the per-stage verdict stays absent (old schema)
        bare = serve_bench.run_bench(
            url, concurrency=2, requests_per_worker=0, image_size=16,
            timeout=10.0, slo_p99_ms=0.0, ramp="4:1")
        assert "slo_attained" not in bare["ramp"][0]
    finally:
        httpd.shutdown()
        httpd.server_close()


# --- compiled-program identity ----------------------------------------------

def test_arbiter_plane_identical_step_program(devices8):
    """--arbiter_url is host-side machinery (a reporter thread): the
    lowered train-step program must be bit-identical with the arbiter
    plane on or off — same pin control knobs and telemetry carry."""
    import jax
    from tests.test_checkpoint import tiny_cfg
    from tests.test_train_smoke import build_train_objects, random_batch

    def lowered(cfg):
        mesh, state, step_fn, _ = build_train_objects(cfg)
        batch = random_batch(cfg, mesh)
        return step_fn.lower(state, batch, jax.random.key(0)).as_text()

    assert lowered(tiny_cfg()) == lowered(
        tiny_cfg(arbiter_url="http://127.0.0.1:9"))


# --- the acceptance drill ----------------------------------------------------

def _drill_tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=16, dtype="float32", lr=1e-3,
        warmup_steps=2, serve_max_batch=4, serve_topk=3,
        max_batch_wait_ms=10.0, seed=0,
    )
    base.update(kw)
    return Config(**base).validate()


def _drill_train_argv(ckpt_dir, peers, metrics_dir, arbiter_url):
    return [
        sys.executable, os.path.join(REPO, "run_vit_training.py"),
        "--fake_data", "--image_size", "32", "--patch_size", "8",
        "--embed_dim", "32", "--num_heads", "2", "--num_blocks", "2",
        "--num_classes", "4", "--batch_size", "16", "--dtype", "float32",
        "--num_epochs", "1", "--steps_per_epoch", "100000",
        "--log_step_interval", "1", "--warmup_steps", "0",
        "--eval_max_batches", "1", "--test_epoch_interval", "99",
        "--ckpt_epoch_interval", "99", "--ckpt_dir", str(ckpt_dir),
        "--zero_stall_ckpt", "--replicate_steps", "2",
        "--peer_dir", str(peers), "--metrics_dir", str(metrics_dir),
        "--control_sync_steps", "2",
        "--arbiter_url", arbiter_url,
    ]


def _wait_for(predicate, deadline_s, what):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.5)
    raise AssertionError(f"timed out after {deadline_s}s waiting for {what}")


@pytest.mark.slow
def test_arbiter_borrow_return_drill(devices8, tmp_path_factory):
    """The tentpole acceptance drill. A chaos-armed serve_bench ramp
    overloads a one-replica fleet whose autoscaler is at max_replicas;
    the escalation reaches the arbiter, which borrows a host from a LIVE
    2-process fake-data training job: agreed-preemption drain (both ranks
    exit 0 on a joint checkpoint), 2->1 elastic resume from the surviving
    peer store with ZERO Orbax reads, a real replica provisioned on the
    freed host through the placement agent and adopted by the router.
    The ramp's quiet tail holds the SLO on the grown fleet; once pressure
    clears the arbiter returns the host (router release -> agent drain ->
    ledger flip) and training re-expands 1->2 — the whole story visible
    in one shared metrics_report."""
    from vitax.train.loop import train
    serve_bench = _import_tool("serve_bench")
    metrics_report = _import_tool("metrics_report")

    root = tmp_path_factory.mktemp("arbiter_drill")
    metrics_dir = root / "metrics"
    cache_dir = root / "xla_cache"
    os.makedirs(metrics_dir, exist_ok=True)

    # a committed tiny checkpoint for the serve replicas
    serve_ckpt = str(root / "serve_ckpt")
    train(_drill_tiny_cfg(fake_data=True, num_epochs=1, steps_per_epoch=2,
                          log_step_interval=1, ckpt_dir=serve_ckpt,
                          ckpt_epoch_interval=1, num_workers=2,
                          eval_max_batches=1))
    model_flags = [
        "--image_size", "16", "--patch_size", "8", "--embed_dim", "32",
        "--num_heads", "2", "--num_blocks", "2", "--num_classes", "4",
        "--dtype", "float32", "--serve_max_batch", "4", "--serve_topk", "3",
        "--max_batch_wait_ms", "10.0", "--ckpt_dir", serve_ckpt,
        "--epoch", "1",
    ]
    # the seed replica is a slow accelerator: every predict hangs 250ms,
    # so ramp load beyond ~1 batch in flight predictably sheds
    slow_plan = json.dumps({"site": "engine_predict", "at": 1,
                            "times": 1000000, "action": "hang",
                            "seconds": 0.25})

    jrec = JsonlRecorder(str(metrics_dir))   # shared stream with the ranks
    arb_port = free_port()
    arb_url = f"http://127.0.0.1:{arb_port}"

    # the live tenant: 2-process training, peer-replicated, heartbeating
    director = TrainDirector(
        _drill_train_argv(root / "train_ckpt", root / "peers", metrics_dir,
                          arb_url),
        term_grace_s=240.0, log_dir=str(root / "train_logs"),
        # the compile cache is placed from outside (vitax/platform.py)
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(cache_dir),
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))

    # the serving tenant: router + admission + maxed-out autoscaler
    manager = ReplicaManager(health_interval_s=0.25, backoff_s=0.5)
    admission = AdmissionController(deadline_ms=400.0, ewma_alpha=0.0)
    admission.observe(0.2)

    def request_capacity(reason):
        return _http(arb_url + "/request", {"reason": reason}, timeout=5.0)

    autoscaler = Autoscaler(manager, admission=admission, min_replicas=1,
                            max_replicas=1, interval_s=0.25, dwell_s=0.75,
                            cooldown_s=2.0, shed_rate_per_s=0.5,
                            request_capacity=request_capacity, recorder=jrec)
    router = Router(manager, admission=admission, autoscaler=autoscaler,
                    request_timeout_s=60.0)

    # the freed host's replica factory: one real placement agent
    agent_manager = ReplicaManager(health_interval_s=0.5, backoff_s=1.0)
    agent = PlacementAgent(advertise_host="127.0.0.1",
                           base_port=free_port(), manager=agent_manager,
                           max_slots=1)
    agent_httpd = start_agent(agent, port=0)
    agent_client = PlacementClient(
        f"http://127.0.0.1:{agent_httpd.server_address[1]}")

    def provision(host):
        return agent_client.provision(model_flags,
                                      name=f"borrow_{host}")["url"]

    def release(host, url):
        for name, snap in agent_client.replicas()["replicas"].items():
            if snap.get("url") == url:
                agent_client.release(name)
                return
        raise RuntimeError(f"no agent replica at {url}")

    adopt_seq = {"n": 0}

    def fleet_adopt(url):
        adopt_seq["n"] += 1
        manager.adopt(url, name=f"borrowed_{adopt_seq['n']}")

    def fleet_release(url):
        target = next((manager.find(name)
                       for name, snap in manager.snapshot().items()
                       if snap.get("url") == url), None)
        if target is None:
            return  # already out of rotation (a prior partial return)
        manager.retire(target)
        deadline = time.time() + 60.0
        while manager.in_flight_of(target) > 0 and time.time() < deadline:
            time.sleep(0.05)
        manager.discard(target)

    from vitax.arbiter.daemon import FleetSignals
    ledger = HostLedger(["h0", "h1"], path=str(root / "ledger.json"))
    policy = ArbiterPolicy("slo_bounded", min_train_hosts=1, dwell_s=1.0,
                           cooldown_s=5.0, quiet_dwell_s=6.0,
                           shed_rate_per_s=0.5)
    arb = Arbiter(ledger, policy, train=director, provision=provision,
                  release=release, fleet_adopt=fleet_adopt,
                  fleet_release=fleet_release, recorder=jrec,
                  interval_s=0.5)

    router_httpd = None
    try:
        # seed replica on h0's chips, then open the router
        port = free_port()
        manager.manage([sys.executable, "-m", "vitax.serve"] + model_flags
                       + ["--serve_port", str(port), "--fault_plan",
                          slow_plan],
                       f"http://127.0.0.1:{port}", name="replica_0")
        manager.start()
        _wait_for(lambda: manager.ready_count() >= 1, 300,
                  "seed replica ready")
        router_httpd = start_router(router, 0)
        fleet_url = f"http://127.0.0.1:{router_httpd.server_address[1]}"
        arb._signals_fn = FleetSignals(fleet_url)
        autoscaler.start()

        arb_httpd = start_arbiter(arb, arb_port)
        try:
            director.start(2)
            # training must be PROGRESSING (heartbeats landing) before the
            # surge: the policy's train_stalled gate reads this telemetry
            _wait_for(
                lambda: arb.metrics()["train_telemetry"] is not None,
                600, "first train step heartbeat")

            # surge long enough for escalation -> borrow -> drain ->
            # provision -> AOT warmup; then a quiet tail on the grown fleet
            summary = serve_bench.run_bench(
                fleet_url, concurrency=6, requests_per_worker=0,
                image_size=16, timeout=60.0, slo_p99_ms=5000.0, replicas=2,
                ramp="40:150,2:45")

            # the surge really overloaded the seed replica...
            assert summary["ramp"][0]["shed"] > 0, summary["ramp"]
            # ...the maxed-out autoscaler escalated instead of stalling...
            assert autoscaler.escalations_total >= 1
            # ...and the arbiter borrowed the host for serving
            assert arb.borrows_total >= 1, arb.metrics()
            assert summary["errors"] == 0, summary["error_samples"]
            # SLO verdict on the grown fleet: the quiet tail attains
            assert summary["ramp"][-1]["slo_attained"] is True, (
                summary["ramp"])

            # pressure is gone: the loan comes home and training re-expands
            _wait_for(lambda: arb.returns_total >= 1, 300,
                      "the borrowed host to return")
            _wait_for(lambda: director.process_count == 2
                      and director.alive() == 2, 300,
                      "training re-expanded to 2 processes")
            assert ledger.counts()["train"] == 2
            assert len(agent_manager.snapshot()) == 0  # replica drained
        finally:
            stop_arbiter(arb_httpd, arb)

        # drain the training job deliberately: every rank exits 0
        codes = director.stop()
        assert codes == [0, 0], codes
    finally:
        autoscaler.stop()
        if router_httpd is not None:
            stop_router(router_httpd)
        manager.stop()
        stop_agent(agent_httpd, agent)
        director.stop()

    # one report tells the whole story: the ranks, the autoscaler and the
    # arbiter all appended to the same metrics.jsonl
    summary = metrics_report.summarize(str(metrics_dir / "metrics.jsonl"))
    arb_ev = summary["arbiter_events"]
    assert arb_ev["borrows"] >= 1 and arb_ev["returns"] >= 1, arb_ev
    assert summary["autoscale_events"]["escalations"] >= 1

    # topology timeline: the pod shrank to 1 and grew back to 2
    timeline = summary["train_topology_timeline"]
    tos = [t["to_processes"] for t in timeline]
    assert 1 in tos and tos[-1] == 2, timeline

    # the 2->1 resume came from the surviving peer store: ZERO committed
    # steps lost, ZERO shared-storage checkpoint reads
    with open(metrics_dir / "metrics.jsonl") as f:
        events = [json.loads(line) for line in f if line.strip()]
    peer_restores = [e for e in events if e.get("kind") == "restore"
                     and e.get("path") == "peer"]
    assert peer_restores, [e for e in events if e.get("kind") == "restore"]
    assert all(e["orbax_reads"] == 0 for e in peer_restores)
    assert all(e["resume_step"] > 0 for e in peer_restores)


# --- borrowed-host int8 warm boot (PR 19 residue, exercised) -----------------

@pytest.mark.slow
def test_borrowed_host_boots_int8_npz_replica(devices8, tmp_path):
    """Warming int8 images on borrowed hosts: the freed host's replica
    factory (the arbiter's `provision` callback is exactly
    `agent.provision(model_flags, ...)`) boots a REAL `python -m
    vitax.serve` replica from a quantized consolidated npz, through the
    registry's engine constructor (vitax/programs/builder.py:build_engine).
    The replica warms, and its /metrics pins weights_dtype == "int8" —
    the borrowed chips hold int8 weights, not a full-precision fallback."""
    import numpy as np
    from vitax.checkpoint.consolidate import flatten_tree, save_npz
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.parallel.mesh import build_mesh
    from vitax.train.state import build_optimizer, make_train_state

    cfg = _drill_tiny_cfg()
    mesh = build_mesh(cfg)
    model = build_model(cfg)
    tx, _ = build_optimizer(cfg, max_iteration=10)
    import jax
    state, _, _ = make_train_state(cfg, model, tx, mesh, jax.random.key(0))
    npz = str(tmp_path / "int8.npz")
    save_npz(npz, {k: np.asarray(v)
                   for k, v in flatten_tree(state.params).items()},
             dtype="int8")

    model_flags = [
        "--image_size", "16", "--patch_size", "8", "--embed_dim", "32",
        "--num_heads", "2", "--num_blocks", "2", "--num_classes", "4",
        "--dtype", "float32", "--serve_max_batch", "4", "--serve_topk", "3",
        "--max_batch_wait_ms", "10.0",
        "--npz", npz, "--serve_quant_dtype", "int8",
    ]
    agent = PlacementAgent(advertise_host="127.0.0.1",
                           base_port=free_port(),
                           manager=ReplicaManager(health_interval_s=0.5,
                                                  backoff_s=1.0),
                           max_slots=1)
    try:
        out = agent.provision(model_flags, name="borrow_int8")
        url = out["url"]

        def ready():
            try:
                return _http(url + "/healthz", timeout=5.0)["ready"]
            except Exception:
                return False

        _wait_for(ready, 240.0, "int8 replica warm")
        snap = _http(url + "/metrics", timeout=5.0)
        assert snap["weights_dtype"] == "int8", snap
    finally:
        agent.release("borrow_int8")
