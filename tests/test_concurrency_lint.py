"""vitax.analysis.concurrency: VTX200-series thread-safety lint + the
vitax.telemetry.threads crash/join primitives + thread-fuzz stress.

Every rule gets one fixture that fires and one that stays silent; the
firing fixtures double as the "deliberately-broken negative arms" of the
CI pin — un-suppressed they fail, suppressed with a reason they pass.
The stress tests pin DynamicBatcher and SnapshotPipeline end-to-end
under forced GIL churn (sys.setswitchinterval(1e-5)) with barrier-started
submitters: every future resolves and every save lands exactly once.
"""

import os
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from vitax.analysis import concurrency
from vitax.serve.batcher import DynamicBatcher
from vitax.telemetry import threads as vthreads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src):
    return concurrency.lint_source(textwrap.dedent(src), "fixture.py")


def codes(findings):
    return sorted({f.code for f in findings})


# --- VTX200: unguarded shared attribute --------------------------------------

VTX200_FIRING = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def _run(self):
            self._count += 1

        def read(self):
            return self._count

        def stop(self):
            self._t.join(timeout=1.0)
"""


def test_vtx200_fires_on_unguarded_shared_attr():
    findings = lint(VTX200_FIRING)
    assert codes(findings) == ["VTX200"]
    assert "_count" in findings[0].message


def test_vtx200_silent_when_both_sides_hold_the_lock():
    findings = lint("""
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                with self._lock:
                    self._count += 1

            def read(self):
                with self._lock:
                    return self._count

            def stop(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []


def test_vtx200_silent_for_init_only_writes():
    # config attrs written once in __init__ and read everywhere are the
    # happens-before-publish pattern, not a race
    findings = lint("""
        import threading

        class Reader:
            def __init__(self):
                self.limit = 7
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                return self.limit

            def stop(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []


def test_vtx200_guard_context_propagates_through_calls():
    # the helper never takes the lock itself — every call site does; the
    # call-context fixpoint must see that and stay silent
    findings = lint("""
        import threading

        class Ctx:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _bump(self):
                self._n += 1

            def _run(self):
                with self._lock:
                    self._bump()

            def bump(self):
                with self._lock:
                    self._bump()

            def stop(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []


# --- VTX201: Condition.wait outside a while loop -----------------------------

VTX201_FIRING = """
    import threading

    class Waiter:
        def __init__(self):
            self._cond = threading.Condition()
            self._ready = False

        def get(self):
            with self._cond:
                if not self._ready:
                    self._cond.wait()
                return self._ready
"""


def test_vtx201_fires_on_if_guarded_wait():
    findings = lint(VTX201_FIRING)
    assert codes(findings) == ["VTX201"]


def test_vtx201_silent_inside_while():
    findings = lint("""
        import threading

        class Waiter:
            def __init__(self):
                self._cond = threading.Condition()
                self._ready = False

            def get(self):
                with self._cond:
                    while not self._ready:
                        self._cond.wait(timeout=1.0)
                    return self._ready
    """)
    assert findings == []


# --- VTX202: lock-order cycle ------------------------------------------------

VTX202_FIRING = """
    import threading

    class TwoLocks:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:
                    pass
"""


def test_vtx202_fires_on_opposite_order():
    findings = lint(VTX202_FIRING)
    assert codes(findings) == ["VTX202"]


def test_vtx202_fires_transitively_through_a_helper():
    findings = lint("""
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def _take_a(self):
                with self._a:
                    pass

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    self._take_a()
    """)
    assert codes(findings) == ["VTX202"]


def test_vtx202_silent_on_consistent_order():
    findings = lint("""
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._a:
                    with self._b:
                        pass
    """)
    assert findings == []


# --- VTX203: blocking call while holding a lock ------------------------------

VTX203_FIRING = """
    import threading

    class Joiner:
        def __init__(self):
            self._lock = threading.Lock()
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def _run(self):
            pass

        def stop(self):
            with self._lock:
                self._t.join()
"""


def test_vtx203_fires_on_join_under_lock():
    findings = lint(VTX203_FIRING)
    assert codes(findings) == ["VTX203"]


def test_vtx203_fires_on_blocking_queue_get_under_lock():
    findings = lint("""
        import queue
        import threading

        class Drainer:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def drain_one(self):
                with self._lock:
                    return self._q.get()
    """)
    assert codes(findings) == ["VTX203"]


def test_vtx203_silent_with_timeout_or_without_lock():
    findings = lint("""
        import queue
        import threading

        class Joiner:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass

            def drain_one(self):
                with self._lock:
                    return self._q.get(timeout=1.0)

            def stop(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []


# --- VTX204: JAX dispatch on a thread path -----------------------------------

VTX204_FIRING = """
    import threading
    import jax

    class Dispatcher:
        def __init__(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def _run(self):
            jax.device_put(1)

        def stop(self):
            self._t.join(timeout=1.0)
"""


def test_vtx204_fires_on_thread_side_jax():
    findings = lint(VTX204_FIRING)
    assert codes(findings) == ["VTX204"]
    assert "jax.device_put" in findings[0].message


def test_vtx204_silent_for_caller_side_jax():
    findings = lint("""
        import threading
        import jax

        class Dispatcher:
            def __init__(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass

            def predict(self, x):
                return jax.device_put(x)

            def stop(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []


# --- VTX205: leaked thread ---------------------------------------------------

VTX205_FIRING = """
    import threading

    class Leaker:
        def start(self):
            self._t = threading.Thread(target=self._run)
            self._t.start()

        def _run(self):
            pass
"""


def test_vtx205_fires_on_never_joined_attr_thread():
    findings = lint(VTX205_FIRING)
    assert codes(findings) == ["VTX205"]


def test_vtx205_fires_on_local_and_anonymous_threads():
    findings = lint("""
        import threading

        def fire_and_forget(fn):
            t = threading.Thread(target=fn)
            t.start()
    """)
    assert codes(findings) == ["VTX205"]
    findings = lint("""
        import threading

        def fire_and_forget(fn):
            threading.Thread(target=fn).start()
    """)
    assert codes(findings) == ["VTX205"]


def test_vtx205_silent_with_join_or_stop_event():
    findings = lint("""
        import threading

        class Joined:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass

            def close(self):
                self._t.join(timeout=1.0)
    """)
    assert findings == []
    findings = lint("""
        import threading

        class Evented:
            def start(self):
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                while not self._stop.wait(0.1):
                    pass

            def shutdown(self):
                self._stop.set()
    """)
    assert findings == []
    # a joined local thread in a module function is fine too
    findings = lint("""
        import threading

        def run_and_wait(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join(timeout=5.0)
    """)
    assert findings == []


# --- suppression machinery ---------------------------------------------------

def test_suppression_with_reason_silences_and_wrong_code_does_not():
    src = VTX200_FIRING.replace(
        "self._count += 1",
        "self._count += 1  # vtx: ignore[VTX200] fixture: benign test race")
    assert lint(src) == []
    wrong = VTX200_FIRING.replace(
        "self._count += 1",
        "self._count += 1  # vtx: ignore[VTX205] wrong code, still fires")
    assert codes(lint(wrong)) == ["VTX200"]


def test_every_firing_fixture_fails_unsuppressed():
    # the acceptance contract: each deliberately-broken arm fails CI until
    # it carries a reasoned suppression on the reported line
    for src, code in [(VTX200_FIRING, "VTX200"), (VTX201_FIRING, "VTX201"),
                      (VTX202_FIRING, "VTX202"), (VTX203_FIRING, "VTX203"),
                      (VTX204_FIRING, "VTX204"), (VTX205_FIRING, "VTX205")]:
        findings = lint(src)
        assert codes(findings) == [code]
        lines = textwrap.dedent(src).splitlines()
        lines[findings[0].line - 1] += (
            f"  # vtx: ignore[{code}] fixture: deliberately broken")
        assert concurrency.lint_source("\n".join(lines), "fixture.py") == []


def test_bare_suppressions_are_not_reported_here():
    # VTX100 policing belongs to ast_lint (which runs first in lint.sh);
    # the concurrency pass must not double-report it
    findings = lint("""
        x = 1  # vtx: ignore[]
    """)
    assert findings == []


# --- repo pin ----------------------------------------------------------------

def test_repo_and_tools_are_clean():
    findings = concurrency.lint_paths([os.path.join(REPO, "vitax"),
                                       os.path.join(REPO, "tools")])
    assert [f.format() for f in findings] == []


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(VTX205_FIRING), encoding="utf-8")
    assert concurrency.main([str(bad)]) == 1
    assert concurrency.main([str(bad), "--json"]) == 1
    good = tmp_path / "good.py"
    good.write_text("x = 1\n", encoding="utf-8")
    assert concurrency.main([str(good)]) == 0


# --- telemetry.threads: excepthook + bounded joins ---------------------------

class _Recorder:
    def __init__(self):
        self.events = []

    def event(self, kind, **payload):
        self.events.append((kind, payload))


def test_thread_excepthook_records_crash(capfd):
    rec = _Recorder()
    vthreads.install_thread_excepthook(rec, rank=3)
    before = vthreads.thread_crash_count()
    t = threading.Thread(target=lambda: 1 / 0, name="crasher")
    t.start()
    t.join(timeout=5.0)
    assert vthreads.thread_crash_count() == before + 1
    assert rec.events and rec.events[-1][0] == "thread_crash"
    payload = rec.events[-1][1]
    assert payload["rank"] == 3 and payload["thread"] == "crasher"
    assert "ZeroDivisionError" in payload["error"]
    err = capfd.readouterr().err
    assert "rank 3" in err and "crasher" in err and "ZeroDivisionError" in err


def test_thread_excepthook_ignores_system_exit(capfd):
    vthreads.install_thread_excepthook(None, rank=0)
    before = vthreads.thread_crash_count()
    t = threading.Thread(target=lambda: sys.exit(1))
    t.start()
    t.join(timeout=5.0)
    assert vthreads.thread_crash_count() == before
    assert "uncaught exception" not in capfd.readouterr().err


def test_join_or_warn_bounds_a_wedged_join(capfd):
    release = threading.Event()
    t = threading.Thread(target=release.wait, name="wedged")
    t.start()
    try:
        assert vthreads.join_or_warn(t, timeout=0.05) is False
        err = capfd.readouterr().err
        assert "wedged" in err and "still alive" in err
    finally:
        release.set()
        t.join(timeout=5.0)
    assert vthreads.join_or_warn(t, timeout=1.0) is True


# --- thread-fuzz stress ------------------------------------------------------

@pytest.fixture
def gil_churn():
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    yield
    sys.setswitchinterval(old)


def test_batcher_exactly_once_under_contention(gil_churn):
    def predict(images):
        n = len(images)
        time.sleep(0.0005)  # widen the flush window the races live in
        return (np.tile(np.arange(3, dtype=np.int32), (n, 1)),
                np.ones((n, 3), np.float32))

    batcher = DynamicBatcher(predict, max_batch=4, max_wait_ms=1.0)
    n_threads, per_thread = 8, 25
    barrier = threading.Barrier(n_threads)
    futures = [[] for _ in range(n_threads)]

    def submitter(i):
        barrier.wait()
        for _ in range(per_thread):
            futures[i].append(batcher.submit(np.zeros((2, 2, 3), np.float32)))

    workers = [threading.Thread(target=submitter, args=(i,))
               for i in range(n_threads)]
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=30.0)
    flat = [f for per in futures for f in per]
    assert len(flat) == n_threads * per_thread
    # exactly once: every future resolves (a double set_result would crash
    # the worker with InvalidStateError and strand the rest on timeout)
    results = [f.result(timeout=30.0) for f in flat]
    assert all(1 <= r.batch_size <= 4 for r in results)
    batcher.close()
    assert not batcher._worker.is_alive()


def test_snapshot_pipeline_exactly_once_under_contention(
        gil_churn, tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from vitax.checkpoint import snapshot as snap_mod
    import vitax.checkpoint.orbax_io as orbax_io_mod

    lock = threading.Lock()
    saved = []

    def fake_save(ckpt_dir, epoch, tree, **kw):
        with lock:
            saved.append(int(epoch))

    monkeypatch.setattr(orbax_io_mod, "save_state", fake_save)
    state = {"w": jax.device_put(np.arange(8, dtype=np.float32))}
    pipe = snap_mod.SnapshotPipeline(max_buffer_sets=2)
    # the first stage() reads the tree's layout into the pipeline, from the
    # loop's thread before any other calls (the class's contract); what is
    # contended here is the buffer pool and the worker's queue after it
    pipe.stage(state, epoch=-1).release()
    n_threads, per_thread = 4, 6
    barrier = threading.Barrier(n_threads)
    errors = []

    def submitter(i):
        barrier.wait()
        for j in range(per_thread):
            try:
                pipe.submit(state, epoch=i * 100 + j,
                            persist_to=str(tmp_path))
            except Exception as e:  # noqa: BLE001 — collected and asserted
                with lock:
                    errors.append(e)

    workers = [threading.Thread(target=submitter, args=(i,))
               for i in range(n_threads)]
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=60.0)
    pipe.drain()
    pipe.close()
    assert errors == []
    expected = sorted(i * 100 + j for i in range(n_threads)
                      for j in range(per_thread))
    assert sorted(saved) == expected  # every save exactly once, none lost
