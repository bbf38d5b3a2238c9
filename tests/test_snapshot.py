"""Zero-stall checkpointing + peer-replicated state (PR 11).

Covers vitax/checkpoint/snapshot.py (staged device->host snapshots, the
background write pipeline, the ckpt_stall_s accounting pin) and
vitax/checkpoint/peer.py (pack/unpack, the local PeerStore, restore
negotiation, checksum-failure fallback to Orbax), plus the satellites:
checkpoint GC (--keep_checkpoints), the ControlPlane's default exit
deadline, the VTX108 ast-lint rule, metrics_report's new fields, and the
supervisor's peer-aware progress frontier. The slow 2-process drill at the
bottom is the acceptance test: SIGKILL one of two hosts mid-epoch, resume
from peer shards with ZERO shared-storage checkpoint reads, and pin bitwise
parameter equality against the uninterrupted run.
"""

import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time
import zlib

import jax
import numpy as np
import pytest

from tests.test_checkpoint import abstract_of, make_state, tiny_cfg
from tests.test_multiprocess import (REPO, _free_port, _tiny_train_argv,
                                     _two_proc_env)
from vitax.checkpoint import peer, snapshot
from vitax.checkpoint.orbax_io import (
    committed_epochs, epoch_ckpt_path, prune_checkpoints, restore_state,
    save_state)
from vitax.train.control import (
    BIT_PEER_RESTORE, EXIT_HANG, ControlPlane, agree_peer_restore)


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _loop_common(tmp_path, **kw):
    base = dict(
        fake_data=True, steps_per_epoch=4, log_step_interval=1,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_epoch_interval=1,
        test_epoch_interval=99, num_workers=2, eval_max_batches=1,
        metrics_dir=str(tmp_path / "metrics"),
    )
    base.update(kw)
    return base


def _read_metrics(tmp_path):
    recs = []
    with open(tmp_path / "metrics" / "metrics.jsonl") as f:
        for line in f:
            recs.append(json.loads(line))
    steps = [r for r in recs if not r.get("kind") and "loss" in r]
    events = [r for r in recs if r.get("kind")]
    return steps, events


# --- unit: ring math, progress keys, the agreement fold ----------------------

def test_ring_buddy_and_guard():
    assert peer.ring_buddy(0, 2) == 1 and peer.ring_buddy(1, 2) == 0
    assert peer.ring_guard(0, 2) == 1 and peer.ring_guard(1, 2) == 0
    # at n=4 the ring is a proper cycle: buddy(guard(i)) == i
    for i in range(4):
        assert peer.ring_buddy(peer.ring_guard(i, 4), 4) == i
    assert peer.ring_buddy(3, 4) == 0  # wraps


def test_progress_key_orders_boundary_above_mid_epoch():
    # boundary save of epoch e (step 0) means e is COMPLETE
    assert peer.progress_key(2, 0) == (3, 0)
    assert peer.progress_key(2, 7) == (2, 7)
    assert peer.progress_key(2, 0) > peer.progress_key(2, 99)
    assert peer.progress_key(3, 1) > peer.progress_key(2, 0)


def test_agree_peer_restore_fold():
    # single process: the local verdict stands, no collective
    assert agree_peer_restore(True, process_count=1)
    assert not agree_peer_restore(False, process_count=1)
    # multi process: one raised veto bit in the OR-fold kills the restore
    assert agree_peer_restore(
        True, process_count=2, collective=lambda w: w | 0)
    assert not agree_peer_restore(
        True, process_count=2, collective=lambda w: w | BIT_PEER_RESTORE)
    assert not agree_peer_restore(
        False, process_count=2, collective=lambda w: w)


def test_bit_peer_restore_is_out_of_band():
    """The veto bit must NOT join the in-loop signal word: unpack_word still
    rejects it (it never travels on the step-boundary cadence)."""
    from vitax.train.control import _ALL_BITS, unpack_word
    assert not (BIT_PEER_RESTORE & _ALL_BITS)
    with pytest.raises(ValueError):
        unpack_word(BIT_PEER_RESTORE)


# --- staging + pipeline ------------------------------------------------------

def test_staging_roundtrip_reuses_buffers(devices8):
    cfg = tiny_cfg()
    _, state, _ = make_state(cfg)
    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=1, step_in_epoch=3)
        assert snap.version == (1, 3, 1)
        _leaves_equal(state, snap.rebuild())
        # the staged copies are OWNED buffers, not views of device memory:
        # a post-stage state update must not leak into the snapshot
        saved = np.array(snap.buffers(0)[0], copy=True)
        bufs_first = [id(snap.buffers(i)[0])
                      for i in range(len(snap.specs))]
        snap.release()
        # the freed buffer set is REUSED by the next stage (no per-save
        # allocation churn — the CheckFreq staging discipline)
        snap2 = pipe.stage(state, epoch=1, step_in_epoch=4)
        assert [id(snap2.buffers(i)[0])
                for i in range(len(snap2.specs))] == bufs_first
        np.testing.assert_array_equal(snap2.buffers(0)[0], saved)
        snap2.release()
    finally:
        pipe.close()


def test_pipeline_persist_matches_state(devices8, tmp_path):
    """submit(persist_to=...) + drain commits an Orbax checkpoint equal to
    the live state — the background write path loses nothing."""
    cfg = tiny_cfg(ckpt_dir=str(tmp_path))
    mesh, state, sspecs = make_state(cfg)
    pipe = snapshot.SnapshotPipeline()
    try:
        pipe.submit(state, epoch=3, persist_to=cfg.ckpt_dir)
        pipe.drain()
    finally:
        pipe.close()
    from vitax.checkpoint.orbax_io import wait_until_finished
    wait_until_finished()
    assert committed_epochs(cfg.ckpt_dir) == [3]
    restored = restore_state(cfg.ckpt_dir, 3, abstract_of(state, mesh, sspecs))
    _leaves_equal(state, restored)


def test_submit_returns_before_slow_write(devices8, tmp_path, monkeypatch):
    """The zero-stall contract at the API level: with the Orbax write made
    artificially slow, submit() must still return in staging time (the loop
    dispatches step N+1 immediately), and drain() must still commit."""
    cfg = tiny_cfg(ckpt_dir=str(tmp_path))
    _, state, _ = make_state(cfg)
    calls = []

    def slow_save(ckpt_dir, epoch, tree, **kw):
        time.sleep(0.5)
        calls.append((ckpt_dir, epoch))

    import vitax.checkpoint.orbax_io as orbax_io_mod
    monkeypatch.setattr(orbax_io_mod, "save_state", slow_save)
    pipe = snapshot.SnapshotPipeline()
    try:
        t0 = time.perf_counter()
        pipe.submit(state, epoch=1, persist_to=cfg.ckpt_dir)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.25, (
            f"submit took {elapsed:.3f}s — the slow write leaked onto the "
            f"loop thread")
        assert pipe.last_stall_s < 0.25
        assert not calls  # the write had not even started synchronously
        pipe.drain()
        assert calls == [(cfg.ckpt_dir, 1)]
        # VITAX_CKPT_SYNC=1 forces the old synchronous behavior (debug seam)
        monkeypatch.setenv("VITAX_CKPT_SYNC", "1")
        t0 = time.perf_counter()
        pipe.submit(state, epoch=2, persist_to=cfg.ckpt_dir)
        assert time.perf_counter() - t0 >= 0.5
        assert len(calls) == 2
    finally:
        pipe.close()


def test_step_program_identical_with_snapshot_flags(devices8):
    """Snapshotting is host-side by construction: the lowered step program
    must be bit-identical with --zero_stall_ckpt/--replicate_steps on or
    off (the same pin telemetry and the control plane carry)."""
    from tests.test_train_smoke import build_train_objects, random_batch

    def lowered(cfg):
        mesh, state, step_fn, _ = build_train_objects(cfg)
        batch = random_batch(cfg, mesh)
        return step_fn.lower(state, batch, jax.random.key(0)).as_text()

    assert lowered(tiny_cfg()) == lowered(
        tiny_cfg(zero_stall_ckpt=True, replicate_steps=2))


# --- peer store + negotiation ------------------------------------------------

def test_peer_store_roundtrip_and_checksum_failure(devices8, tmp_path):
    cfg = tiny_cfg()
    _, state, _ = make_state(cfg)
    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=1, step_in_epoch=2)
        meta, payload = peer.pack_snapshot(snap, src=0)
        snap.release()
    finally:
        pipe.close()
    store = peer.PeerStore(str(tmp_path / "store"))
    store.put(meta, payload)
    assert tuple(store.holdings()[0]["version"]) == (1, 2, 1)
    got_meta, got_payload = store.load(0, expect_version=(1, 2, 1))
    parts = peer.unpack_payload(got_meta, got_payload)
    want_keys = {sh["key"] for leaf in meta["leaves"] for sh in leaf["shards"]}
    assert set(parts) == want_keys

    # version mismatch is loud
    with pytest.raises(peer.PeerRestoreError):
        store.load(0, expect_version=(9, 9, 1))
    # flipped payload bytes fail the crc32 end-to-end check
    blob = store_path = os.path.join(store.root, "host_0", "shard.npz")
    raw = bytearray(open(blob, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(store_path, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(peer.PeerRestoreError):
        store.load(0)


def test_negotiate_single_proc_respects_frontier(devices8, tmp_path):
    cfg = tiny_cfg()
    mesh, state, sspecs = make_state(cfg)
    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=2, step_in_epoch=6)
        meta, payload = peer.pack_snapshot(snap, src=0)
        snap.release()
    finally:
        pipe.close()
    store = peer.PeerStore(str(tmp_path / "store"))
    store.put(meta, payload)

    # peer version (2, 6) loses to an Orbax frontier already past it
    assert peer.negotiate_restore(
        store, process_index=0, process_count=1,
        orbax_frontier=peer.progress_key(2, 0)) is None
    # ...and wins against an older frontier; the plan restores bitwise
    plan = peer.negotiate_restore(
        store, process_index=0, process_count=1,
        orbax_frontier=peer.progress_key(2, 3))
    assert plan is not None and plan.version == (2, 6, 1)
    assert plan.epoch == 2 and plan.meta["step_in_epoch"] == 6
    restored = peer.restore_from_store(
        store, plan, abstract_of(state, mesh, sspecs))
    _leaves_equal(state, restored)


def test_restore_falls_back_to_orbax_on_bad_peer(devices8, tmp_path):
    """Satellite 3, unit half: a buddy shard failing its checksum must fall
    back LOUDLY to the last committed Orbax epoch — kind:"control" event,
    info records the fallback — and still return a usable state."""
    cfg = tiny_cfg(ckpt_dir=str(tmp_path / "ckpt"))
    mesh, state, sspecs = make_state(cfg)
    save_state(cfg.ckpt_dir, 1, state, wait=True)

    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=1, step_in_epoch=2)
        meta, payload = peer.pack_snapshot(snap, src=0)
        snap.release()
    finally:
        pipe.close()
    store = peer.PeerStore(str(tmp_path / "store"))
    store.put(meta, payload)
    # corrupt the stored payload AFTER the meta committed
    blob = os.path.join(store.root, "host_0", "shard.npz")
    raw = bytearray(open(blob, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(blob, "wb") as f:
        f.write(bytes(raw))

    plan = peer.negotiate_restore(store, process_index=0, process_count=1)
    assert plan is not None  # negotiation reads metas, not payloads
    events = []
    restored, info = peer.restore_state_preferring_peers(
        store, plan, cfg.ckpt_dir, 1, abstract_of(state, mesh, sspecs),
        on_event=lambda kind, payload: events.append((kind, payload)))
    assert info["path"] == "orbax" and info["epoch"] == 1
    assert "fallback_from" in info
    _leaves_equal(state, restored)
    kinds = [(k, p.get("event")) for k, p in events]
    assert ("control", "peer_restore_failed") in kinds

    # with NO Orbax epoch to fall back to, the failure is fatal (loud, not
    # a silent from-scratch restart)
    with pytest.raises(RuntimeError):
        peer.restore_state_preferring_peers(
            store, plan, cfg.ckpt_dir, 0, abstract_of(state, mesh, sspecs))


# --- multi-host negotiation (fake KV + OR-fold, two threads) -----------------

class _FakeKV:
    """In-memory stand-in for the coordination-service KV client."""

    def __init__(self):
        self._d = {}
        self._cond = threading.Condition()

    def key_value_set(self, key, value, allow_overwrite=False):
        with self._cond:
            self._d[key] = value
            self._cond.notify_all()

    def blocking_key_value_get(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        with self._cond:
            while key not in self._d:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(key)
                self._cond.wait(remaining)
            return self._d[key]


class _OrFold:
    """A per-round OR collective across n threads (the BIT_PEER_RESTORE
    agreement fold) — every participant blocks until all n contributed."""

    def __init__(self, n):
        self.n = n
        self._cond = threading.Condition()
        self._words = []
        self._done = []

    def __call__(self, word):
        with self._cond:
            rnd = len(self._done)
            self._words.append(int(word))
            if len(self._words) == self.n:
                folded = 0
                for w in self._words:
                    folded |= w
                self._done.append(folded)
                self._words = []
                self._cond.notify_all()
            else:
                if not self._cond.wait_for(lambda: len(self._done) > rnd,
                                           timeout=30):
                    raise TimeoutError("OR-fold never completed")
            return self._done[rnd]


def _put_fake_shard(store, src, version, corrupt=False):
    """A minimal valid peer blob (negotiation only reads meta + crc32)."""
    payload = json.dumps({"src": src, "v": list(version)}).encode() * 7
    store.put({"version": list(version), "src": int(src),
               "step_in_epoch": int(version[1]),
               "process_count": int(version[2]), "leaves": [],
               "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
               "nbytes": len(payload)}, payload)
    if corrupt:
        blob = os.path.join(store.root, f"host_{src}", "shard.npz")
        raw = bytearray(open(blob, "rb").read())
        raw[0] ^= 0xFF
        with open(blob, "wb") as f:
            f.write(bytes(raw))


def _negotiate_two(stores, timeout_s=5.0):
    kv, fold = _FakeKV(), _OrFold(2)
    results, errors = [None, None], [None, None]

    def run(pid):
        try:
            results[pid] = peer.negotiate_restore(
                stores[pid], process_index=pid, process_count=2,
                client=kv, collective=fold, timeout_s=timeout_s)
        except BaseException as e:  # noqa: BLE001 — surfaced by the assert below
            errors[pid] = e

    threads = [threading.Thread(target=run, args=(pid,)) for pid in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert errors == [None, None], errors
    return results


def test_negotiate_verifies_held_shards_and_refetches(tmp_path):
    """A host whose LOCALLY HELD copy of the agreed version is corrupt must
    detect it during negotiation and replace it from the serving holder —
    not sail through the agreement and then strand itself alone on the
    Orbax fallback at restore time (the divergent-replica hazard the
    BIT_PEER_RESTORE gate exists to prevent)."""
    v = (1, 4, 2)
    stores = [peer.PeerStore(str(tmp_path / "p0")),
              peer.PeerStore(str(tmp_path / "p1"))]
    _put_fake_shard(stores[0], 0, v)
    _put_fake_shard(stores[0], 1, v)          # host 0 guards host 1 too
    _put_fake_shard(stores[1], 1, v, corrupt=True)  # host 1's own copy rots

    plans = _negotiate_two(stores)
    assert all(p is not None and p.version == v for p in plans), plans
    # the corrupt copy was REPLACED during negotiation: every shard in
    # every store now load-verifies for the agreed version
    for store in stores:
        for src in (0, 1):
            if src in store.holdings():
                store.load(src, expect_version=v)
    stores[1].load(1, expect_version=v)  # specifically the refetched one


def test_negotiate_declines_together_when_sole_copy_corrupt(tmp_path):
    """When the ONLY copy of a shard is corrupt, no host can serve it: both
    hosts must decline the peer path together (None == Orbax fallback for
    the whole pod), not split."""
    v = (1, 4, 2)
    stores = [peer.PeerStore(str(tmp_path / "p0")),
              peer.PeerStore(str(tmp_path / "p1"))]
    _put_fake_shard(stores[0], 0, v)
    _put_fake_shard(stores[1], 1, v, corrupt=True)  # sole copy of shard 1
    plans = _negotiate_two(stores, timeout_s=1.0)
    assert plans == [None, None], plans


def test_negotiate_counts_mixed_version_coverage(tmp_path):
    """The common steady state: each host's self-spill is one replication
    window ahead of the replica it mirrors for its guard. The newest
    version IS fully covered across hosts — negotiation must find it
    rather than flattening each host to a single version and declining."""
    v_new, v_old = (1, 4, 2), (1, 2, 2)
    stores = [peer.PeerStore(str(tmp_path / "p0")),
              peer.PeerStore(str(tmp_path / "p1"))]
    _put_fake_shard(stores[0], 0, v_new)  # fresh self-spill
    _put_fake_shard(stores[0], 1, v_old)  # buddy replica lags one window
    _put_fake_shard(stores[1], 1, v_new)
    _put_fake_shard(stores[1], 0, v_old)

    plans = _negotiate_two(stores)
    assert all(p is not None and p.version == v_new for p in plans), plans
    # both hosts completed their stores: every shard of v_new everywhere
    for store in stores:
        for src in (0, 1):
            store.load(src, expect_version=v_new)


def test_post_agreement_veto_drops_to_orbax(devices8, tmp_path):
    """The second fold: even when THIS host's peer load succeeds, a peer's
    post-agreement veto must drop it to the Orbax fallback with the pod —
    and with no veto the peer path stands."""
    cfg = tiny_cfg(ckpt_dir=str(tmp_path / "ckpt"))
    mesh, state, sspecs = make_state(cfg)
    save_state(cfg.ckpt_dir, 1, state, wait=True)
    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=1, step_in_epoch=2)
        meta, payload = peer.pack_snapshot(snap, src=0)
        snap.release()
    finally:
        pipe.close()
    store = peer.PeerStore(str(tmp_path / "store"))
    store.put(meta, payload)
    plan = peer.negotiate_restore(store, process_index=0, process_count=1)
    assert plan is not None

    events = []
    restored, info = peer.restore_state_preferring_peers(
        store, plan, cfg.ckpt_dir, 1, abstract_of(state, mesh, sspecs),
        on_event=lambda kind, payload: events.append((kind, payload)),
        process_count=2, collective=lambda w: w | BIT_PEER_RESTORE)
    assert info["path"] == "orbax" and info["epoch"] == 1
    assert "fallback_from" in info
    _leaves_equal(state, restored)
    assert ("control", "peer_restore_failed") in [
        (k, p.get("event")) for k, p in events]

    restored2, info2 = peer.restore_state_preferring_peers(
        store, plan, cfg.ckpt_dir, 1, abstract_of(state, mesh, sspecs),
        process_count=2, collective=lambda w: w)
    assert info2["path"] == "peer"
    _leaves_equal(state, restored2)


# --- rebuild HBM gate --------------------------------------------------------

def test_rebuild_gates_on_hbm_headroom(devices8, monkeypatch):
    """The persist path's transient second device copy must be refused —
    loudly, with guidance — when device memory_stats say it cannot fit;
    the escape hatch and the roomy case both proceed."""
    cfg = tiny_cfg()
    _, state, _ = make_state(cfg)
    pipe = snapshot.SnapshotPipeline()
    try:
        snap = pipe.stage(state, epoch=1)
        monkeypatch.setenv("VITAX_SNAPSHOT_HBM_WAIT_S", "0")
        monkeypatch.setattr(
            snapshot, "_device_memory_stats",
            lambda device: {"bytes_limit": 1024, "bytes_in_use": 1024})
        with pytest.raises(RuntimeError, match="HBM"):
            snap.rebuild()
        monkeypatch.setenv("VITAX_SNAPSHOT_HBM_CHECK", "0")
        _leaves_equal(state, snap.rebuild())
        monkeypatch.delenv("VITAX_SNAPSHOT_HBM_CHECK")
        monkeypatch.setattr(
            snapshot, "_device_memory_stats",
            lambda device: {"bytes_limit": 1 << 40, "bytes_in_use": 0})
        _leaves_equal(state, snap.rebuild())
        snap.release()
    finally:
        pipe.close()


# --- checkpoint GC (--keep_checkpoints) --------------------------------------

def _fake_committed(ckpt_dir, epoch, sidecar=False):
    d = epoch_ckpt_path(str(ckpt_dir), epoch)
    os.makedirs(d)
    open(os.path.join(d, "_CHECKPOINT_METADATA"), "w").close()
    if sidecar:
        with open(d + ".resume.json", "w") as f:
            json.dump({"step_in_epoch": 3}, f)


def test_prune_checkpoints_spares_torn_dirs(tmp_path):
    ckpt = tmp_path / "ckpt"
    for ep in (1, 2, 3, 4):
        _fake_committed(ckpt, ep, sidecar=(ep == 2))
    torn = epoch_ckpt_path(str(ckpt), 5)  # crashed mid-write: NO marker
    os.makedirs(torn)
    open(os.path.join(torn, "partial.bin"), "w").close()

    assert prune_checkpoints(str(ckpt), 2) == [1, 2]
    assert committed_epochs(str(ckpt)) == [3, 4]
    assert not os.path.exists(epoch_ckpt_path(str(ckpt), 1))
    assert not os.path.exists(epoch_ckpt_path(str(ckpt), 2) + ".resume.json")
    # the torn dir is crash forensics — GC must never touch it
    assert os.path.exists(os.path.join(torn, "partial.bin"))
    # keep <= 0 keeps everything; keep >= count prunes nothing
    assert prune_checkpoints(str(ckpt), 0) == []
    assert prune_checkpoints(str(ckpt), 5) == []
    assert committed_epochs(str(ckpt)) == [3, 4]


def test_loop_gc_keeps_newest(devices8, tmp_path, monkeypatch):
    from vitax.train.loop import train
    monkeypatch.setenv("VITAX_CKPT_SYNC", "1")  # GC needs committed dirs
    torn = epoch_ckpt_path(str(tmp_path / "ckpt"), 9)
    os.makedirs(torn)
    common = _loop_common(tmp_path, keep_checkpoints=1, metrics_dir="")
    train(tiny_cfg(num_epochs=3, **common))
    assert committed_epochs(common["ckpt_dir"]) == [3]
    assert os.path.isdir(torn)


# --- ControlPlane default exit deadline (satellite 1) ------------------------

def test_arm_exit_deadline_default_bounded():
    exits = []
    plane = ControlPlane(process_index=0, process_count=2,
                         collective=lambda w: w,
                         hard_exit=lambda code: exits.append(code))
    plane.arm_exit_deadline(deadline_s=0.05)
    first = plane._exit_timer
    assert first is not None
    plane.arm_exit_deadline(deadline_s=99.0)  # idempotent: first timer wins
    assert plane._exit_timer is first
    deadline = time.monotonic() + 5.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.01)
    assert exits == [EXIT_HANG]


def test_arm_exit_deadline_prefers_running_watchdog():
    class FakeWatchdog:
        running = True
        armed = 0

        def arm_exit_deadline(self):
            self.armed += 1

    wd = FakeWatchdog()
    plane = ControlPlane(process_index=0, process_count=2,
                         watchdog=wd, collective=lambda w: w,
                         hard_exit=lambda code: pytest.fail("own timer used"))
    plane.arm_exit_deadline()
    assert wd.armed == 1 and plane._exit_timer is None


def test_arm_exit_deadline_noop_and_cancel():
    exits = []
    # single host: nothing to wait on, no timer
    solo = ControlPlane(process_index=0, process_count=1,
                        hard_exit=lambda code: exits.append(code))
    solo.arm_exit_deadline(deadline_s=0.01)
    assert solo._exit_timer is None
    # stop() cancels an armed timer before it fires
    plane = ControlPlane(process_index=0, process_count=2,
                         collective=lambda w: w,
                         hard_exit=lambda code: exits.append(code))
    plane.arm_exit_deadline(deadline_s=0.2)
    plane.stop()
    time.sleep(0.3)
    assert exits == []


# --- VTX108 lint rule (satellite 6) ------------------------------------------

def test_vtx108_flags_synchronous_save_in_loop():
    from vitax.analysis.ast_lint import lint_source
    src = (
        "def run(state):\n"
        "    for step in range(10):\n"
        "        save_state(d, 1, state, wait=True)\n"
    )
    findings = lint_source(src, "vitax/train/loop.py")
    assert [f.code for f in findings] == ["VTX108"]
    assert findings[0].severity == "ERROR" and findings[0].line == 3


def test_vtx108_escapes_and_non_matches():
    from vitax.analysis.ast_lint import lint_source
    clean = (
        "def run(state):\n"
        "    save_state(d, 1, state, wait=True)\n"       # not in a loop
        "    for step in range(10):\n"
        "        save_state(d, 1, state, wait=False)\n"  # async: fine
        "        save_state(d, 1, state, wait=w)\n"      # variable: fine
        "        orbax_io.save_state(d, 1, state, wait=True)"
        "  # vtx: ignore[VTX108] drill needs the stall\n"
    )
    assert lint_source(clean, "vitax/train/loop.py") == []
    # attribute-qualified calls in a while loop are still caught
    caught = (
        "def run(state):\n"
        "    while True:\n"
        "        orbax_io.save_state(d, 1, state, wait=True)\n"
    )
    assert [f.code for f in lint_source(caught, "x.py")] == ["VTX108"]


# --- metrics_report fields (satellite 4) -------------------------------------

def test_metrics_report_surfaces_ckpt_fields(tmp_path):
    path = tmp_path / "metrics.jsonl"
    records = [
        {"schema": 1, "step": 1, "loss": 2.0, "sec_per_iter": 0.1,
         "data_wait_s": 0.0, "ckpt_stall_s": 0.001},
        {"schema": 1, "step": 2, "loss": 1.9, "sec_per_iter": 0.1,
         "data_wait_s": 0.0, "ckpt_stall_s": 0.003},
        {"schema": 1, "kind": "peer_replication", "bytes": 1000,
         "version": [1, 2, 2], "src": 0, "buddy": 1},
        {"schema": 1, "kind": "peer_replication", "bytes": 2000,
         "version": [1, 4, 2], "src": 0, "buddy": 1},
        {"schema": 1, "kind": "restore", "path": "peer", "epoch": 1,
         "orbax_reads": 0},
        {"schema": 1, "kind": "control", "event": "peer_restore_failed",
         "version": [1, 4, 2], "error": "crc32 mismatch",
         "fallback_epoch": 1},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    r = subprocess.run(
        [sys.executable, os.path.join("tools", "metrics_report.py"),
         str(path), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout)
    assert summary["ckpt_stall_s_p50"] == pytest.approx(0.002)
    assert summary["ckpt_stall_s_p95"] == pytest.approx(0.0029, abs=1e-4)
    assert summary["peer_replication_bytes"] == 3000
    assert summary["peer_replication_windows"] == 2
    assert summary["peer_restores"] == 1
    assert summary["restore_path"] == "peer"
    assert summary["control_events"]["peer_restore_failures"] == 1

    human = subprocess.run(
        [sys.executable, os.path.join("tools", "metrics_report.py"),
         str(path)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert human.returncode == 0
    assert "ckpt stall: p50" in human.stdout
    assert "peer replication: 2 window(s)" in human.stdout
    assert "restore path: peer (1 peer restore(s))" in human.stdout
    assert "peer restores that fell back to Orbax: 1" in human.stdout


# --- supervisor peer-aware progress frontier ---------------------------------

def test_supervisor_counts_peer_progress(tmp_path):
    from vitax.supervise import peer_store_root, run_progress
    root = tmp_path / "peers"
    host = root / "p0" / "host_0"
    os.makedirs(host)
    with open(host / "meta.json", "w") as f:
        json.dump({"version": [3, 5, 2], "src": 0}, f)
    ckpt = tmp_path / "ckpt"  # no Orbax commits at all
    assert run_progress(str(ckpt)) == (0, 0)
    assert run_progress(str(ckpt), str(root)) == (3, 5)

    # gating: the root only resolves for commands that replicate
    child = ["run.py", "--replicate_steps", "2", "--peer_dir", str(root)]
    assert peer_store_root(child, str(ckpt)) == str(root)
    assert peer_store_root(["run.py"], str(ckpt)) == ""
    assert peer_store_root(["run.py", "--replicate_steps", "0"],
                           str(ckpt)) == ""
    assert peer_store_root(["run.py", "--replicate_steps=2"],
                           str(ckpt)).endswith("peerstore")


def test_run_progress_normalizes_boundary_saves(tmp_path):
    """A peer BOUNDARY version (e, 0) means epoch e is COMPLETE: it must
    outrank a stale mid-epoch Orbax frontier (e, s) — both sides of the
    crash-loop progress check compare in progress_key space."""
    from vitax.supervise import run_progress
    ckpt = tmp_path / "ckpt"
    _fake_committed(ckpt, 3)
    with open(epoch_ckpt_path(str(ckpt), 3) + ".resume.json", "w") as f:
        json.dump({"step_in_epoch": 5}, f)  # mid-epoch-3 Orbax frontier
    root = tmp_path / "peers"
    host = root / "p1" / "host_1"
    os.makedirs(host)
    with open(host / "meta.json", "w") as f:
        json.dump({"version": [3, 0, 2], "src": 1}, f)  # epoch 3 COMPLETE

    assert peer.store_frontier(str(root)) == (4, 0)
    assert run_progress(str(ckpt)) == (3, 5)
    # the epoch-completing peer version wins over the mid-epoch frontier
    assert run_progress(str(ckpt), str(root)) == (4, 0)
    # an empty store still reads as no progress, not as (1, 0)
    assert run_progress(str(tmp_path / "none"), str(tmp_path / "no_peers")) \
        == (0, 0)


# --- loop integration --------------------------------------------------------

def test_loop_zero_stall_pin_and_peer_resume(devices8, tmp_path):
    """The in-loop acceptance pins: (a) every step record carries a
    ckpt_stall_s under the stall budget even with per-epoch saves and
    2-step replication windows; (b) a fresh auto-resume prefers the peer
    store and touches shared storage ZERO times (the counter seam)."""
    from vitax.train.loop import train
    common = _loop_common(tmp_path, zero_stall_ckpt=True, replicate_steps=2)
    # the budget below is the staging copy's, not the collector's: a full
    # collection (of what this process's earlier tests left, and of what
    # tracing the step leaves) takes over 0.1 s when it lands inside a copy
    gc.collect()
    gc.disable()
    try:
        state = train(tiny_cfg(num_epochs=2, **common))
    finally:
        gc.enable()
    assert int(jax.device_get(state.step)) == 8

    steps, events = _read_metrics(tmp_path)
    assert len(steps) == 8
    # per-step: <5% of step time with an absolute floor (tiny CPU steps are
    # dominated by scheduler jitter, not the staging copy); the central pin
    # is tight — a synchronous Orbax write leaking onto the loop thread
    # costs hundreds of ms and fails both
    stalls = sorted(r["ckpt_stall_s"] for r in steps)
    for r in steps:
        budget = max(0.05 * r["sec_per_iter"], 0.1)
        assert r["ckpt_stall_s"] <= budget, (
            f"step {r['step']}: stall {r['ckpt_stall_s']:.4f}s over "
            f"{budget:.4f}s budget")
    assert stalls[len(stalls) // 2] <= 0.02
    repl = [e for e in events if e["kind"] == "peer_replication"]
    # 2 epochs x 2 in-loop windows, plus the 2 boundary saves mirror too
    assert len(repl) >= 4
    assert all(e["bytes"] > 0 for e in repl)
    assert os.path.isdir(os.path.join(common["ckpt_dir"], "peerstore", "p0"))

    # resume: the peer store's frontier matches the final boundary save, so
    # the restore comes from the LOCAL store — zero Orbax reads
    state2 = train(tiny_cfg(num_epochs=2, resume_epoch=-1, **common))
    assert int(jax.device_get(state2.step)) == 8
    _leaves_equal(state.params, state2.params)
    _, events2 = _read_metrics(tmp_path)
    restores = [e for e in events2 if e["kind"] == "restore"]
    assert restores and restores[-1]["path"] == "peer"
    assert restores[-1]["orbax_reads"] == 0


def test_loop_checksum_fallback_completes(devices8, tmp_path):
    """Satellite 3, integration half: resume with a CORRUPTED peer store
    must fall back to the last committed Orbax epoch, emit the control
    event, and still complete the run."""
    import glob

    from vitax.train.loop import train
    common = _loop_common(tmp_path, zero_stall_ckpt=True, replicate_steps=2)
    train(tiny_cfg(num_epochs=1, **common))

    for blob in glob.glob(os.path.join(common["ckpt_dir"], "peerstore",
                                       "p*", "host_*", "shard.npz")):
        raw = bytearray(open(blob, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        with open(blob, "wb") as f:
            f.write(bytes(raw))

    state = train(tiny_cfg(num_epochs=2, resume_epoch=-1, **common))
    assert int(jax.device_get(state.step)) == 8  # epoch 2 ran to completion
    _, events = _read_metrics(tmp_path)
    failed = [e for e in events if e.get("kind") == "control"
              and e.get("event") == "peer_restore_failed"]
    assert failed, "checksum failure must surface as a control event"
    restores = [e for e in events if e.get("kind") == "restore"]
    assert restores and restores[-1]["path"] == "orbax"
    assert restores[-1]["epoch"] == 1


# --- the acceptance drill: kill a host, resume from peers, bitwise ----------

def _consolidated(ckpt_dir, epoch, out):
    """Host-side full-param export of a committed epoch (runs in THIS
    process — single host, no mesh: consolidate host-restores the shards)."""
    from vitax.checkpoint.consolidate import consolidate
    consolidate(str(ckpt_dir), epoch, str(out), params_only=True)
    return {k: v for k, v in np.load(str(out)).items()}


def _drill_argv(ckpt_dir, peers, metrics_dir):
    return _tiny_train_argv(12, ckpt_dir) + [
        "--zero_stall_ckpt", "--replicate_steps", "2",
        "--peer_dir", str(peers), "--metrics_dir", str(metrics_dir)]


@pytest.mark.slow
def test_two_process_kill_and_peer_restore_bitwise(tmp_path):
    """The PR's acceptance drill. Baseline: an uninterrupted 2-process run.
    Drill: the same run with host 1 SIGKILLed right after dispatching step 5
    (both hosts mirrored the step-4 window; host 0 then wedges in step 5/6's
    collective and the liveness monitor exits it 42, well before any Orbax
    commit), host 1's LOCAL store deleted (the lost machine's scratch is
    gone), then a 2-process relaunch that must restore host 1's shard from
    host 0's surviving replica — ZERO shared-storage checkpoint reads (no
    committed Orbax dir even exists) — and finish the epoch with final
    parameters BITWISE equal to the baseline's."""
    # baseline ---------------------------------------------------------------
    port = _free_port()
    base_ckpt = tmp_path / "base_ckpt"
    base_argv = _drill_argv(base_ckpt, tmp_path / "base_peers",
                            tmp_path / "base_metrics")
    procs, logs = _spawn_two(base_argv, port, tmp_path, prefix="base")
    _wait_all(procs, logs)
    base_params = _consolidated(base_ckpt, 1, tmp_path / "base.npz")

    # interrupted run --------------------------------------------------------
    port = _free_port()
    ckpt = tmp_path / "ckpt"
    peers = tmp_path / "peers"
    argv = _drill_argv(ckpt, peers, tmp_path / "metrics") + [
        "--fault_plan",
        '[{"site": "step", "action": "peer_loss", "at": 5, "process": 1}]',
        "--peer_heartbeat_s", "0.5", "--peer_grace_s", "5.0"]
    env = {"VITAX_PEER_POLL_S": "0.05"}
    procs, logs = _spawn_two(argv, port, tmp_path, extra_env=env,
                             prefix="drill")
    try:
        procs[1].wait(timeout=540)
        assert procs[1].returncode == -signal.SIGKILL, \
            logs[1].read_text()[-3000:]
        procs[0].wait(timeout=120)  # bounded by liveness grace + deadline
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    out0 = logs[0].read_text()
    assert procs[0].returncode == EXIT_HANG == 42, out0[-3000:]
    assert "peer 1 lost" in out0, out0[-3000:]
    # no Orbax COMMIT ever happened — the run died mid-epoch (a torn
    # emergency-save dir without the commit marker is fine)
    assert committed_epochs(str(ckpt)) == []
    # host 0's store holds BOTH shards of the step-4 window: its own spill
    # plus the replica it received as host 1's ring guard
    holdings = peer.PeerStore(str(peers / "p0")).holdings()
    assert tuple(holdings[0]["version"]) == (1, 4, 2), holdings
    assert tuple(holdings[1]["version"]) == (1, 4, 2), holdings

    # the lost host's scratch dies with it
    import shutil
    shutil.rmtree(peers / "p1")

    # relaunch: same topology, no fault plan ---------------------------------
    port = _free_port()
    resume_argv = _drill_argv(ckpt, peers, tmp_path / "metrics2") + [
        "--resume_epoch", "-1"]
    procs, logs = _spawn_two(resume_argv, port, tmp_path, prefix="resume")
    _wait_all(procs, logs)

    steps, events = [], []
    with open(tmp_path / "metrics2" / "metrics.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            (events if rec.get("kind") else steps).append(rec)
    restores = [e for e in events if e["kind"] == "restore"]
    assert restores and restores[-1]["path"] == "peer", restores
    assert restores[-1]["orbax_reads"] == 0  # the counter seam: ZERO reads
    assert restores[-1]["resume_step"] == 4
    # only steps 5..12 re-ran
    assert [r["step_in_epoch"] for r in steps
            if "loss" in r] == list(range(5, 13))

    drill_params = _consolidated(ckpt, 1, tmp_path / "drill.npz")
    assert set(drill_params) == set(base_params)
    for key in base_params:
        assert np.array_equal(base_params[key], drill_params[key]), (
            f"{key}: peer-restored run diverged from the baseline")


def _spawn_two(argv, port, tmp_path, extra_env=None, prefix="rank"):
    logs = [tmp_path / f"{prefix}{i}.log" for i in range(2)]
    procs = []
    for pid in range(2):
        env = _two_proc_env(port, pid)
        env.update(extra_env or {})
        with open(logs[pid], "w") as log_f:
            procs.append(subprocess.Popen(
                argv, cwd=REPO, env=env, stdout=log_f,
                stderr=subprocess.STDOUT, text=True))
    return procs, logs


def _wait_all(procs, logs, timeout=540):
    try:
        for p in procs:
            p.wait(timeout=timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, lg) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, (
            f"process {pid} failed:\n{lg.read_text()[-3000:]}")
