"""Zero-stall snapshot pipeline (ROADMAP item 3a; CheckFreq, FAST'21).

`save_state` already returns at the device->host snapshot, but that snapshot
itself is synchronous inside Orbax and re-allocates host memory every save —
at 10B scale the train loop still stalls for the full D2H of its shard, and
every emergency save serializes on the loop thread. This module splits the
save into the only part that MUST block the step dispatch and everything
else:

  stage()    synchronous, on the loop thread: fence the state (pipeline
             drain, accounted separately — waiting for step N to finish is
             not snapshot cost) then memcpy each host's unique addressable
             shards into a PREALLOCATED, REUSED staging buffer set. This is
             the only window where the live buffers are read: the moment
             stage() returns, the caller may dispatch step N+1 and donate
             the state. The copy time is the per-step `ckpt_stall_s`
             telemetry (consume_stall_s: read and reset by the loop at each
             step record) — the acceptance harness pins it ~0.

  worker     one background thread owns EVERYTHING downstream: rebuilding
             device arrays from the staged copies and handing them to
             `orbax_io.save_state` (persist jobs — sharing its retry /
             sidecar / commit / GC machinery), and mirroring the staged
             bytes to the ring-buddy host (replicate jobs,
             vitax/checkpoint/peer.py). One thread, one queue: Orbax's
             async checkpointer is a per-process singleton and two
             concurrent save() calls race its internal state, so when the
             pipeline is on, ALL saves route through it — including the
             wait=True emergency/final paths, which just drain the queue.
             CAVEAT — a persist job rebuilds a TRANSIENT SECOND device copy
             of this host's state shard (device_put of the staged buffers)
             while the next training steps are running; the pre-pipeline
             path saved the live arrays with no extra device allocation.
             rebuild() gates that allocation on available HBM headroom
             (device memory_stats, where the backend exposes them) and
             fails the job with a clear error rather than risk an
             allocator OOM or a defragmentation stall in the middle of a
             dispatched step. VITAX_SNAPSHOT_HBM_CHECK=0 disables the
             gate; VITAX_SNAPSHOT_HBM_WAIT_S (default 10) bounds how long
             the job re-polls for headroom before giving up.

Staging buffers live in a small free-list (at most `max_buffer_sets`,
default 2): steady state allocates nothing and touches the same pages every
snapshot (the host-pinning analog under PJRT — page-warm, allocator-free).
If every set is in flight the next stage() blocks until one frees — that
wait is charged to ckpt_stall_s honestly rather than hidden by unbounded
allocation.

Nothing here traces or compiles: the step program is bit-identical with the
pipeline on or off (pinned by tests/test_snapshot.py).
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from typing import Any, List, Optional, Tuple

import jax
import numpy as np

from vitax.telemetry.threads import join_or_warn

PyTree = Any


def _index_key(index, shape) -> Tuple[Tuple[int, int], ...]:
    """A shard's global placement as a hashable ((start, stop), ...) tuple —
    the dedup key for replicated shards and the serialized form the peer
    protocol ships (vitax/checkpoint/peer.py)."""
    return tuple((int(s.start or 0),
                  int(s.stop if s.stop is not None else dim))
                 for s, dim in zip(index, shape))


def _device_memory_stats(device) -> Optional[dict]:
    """device.memory_stats() as a dict, or None when the backend exposes
    none (CPU, some PJRT plugins). A seam so tests can fake HBM pressure."""
    try:
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — stats are best-effort, never fatal
        return None
    return stats if isinstance(stats, dict) else None


def _path_str(key_path) -> str:
    """tree_flatten_with_path key -> stable "/"-joined string (same
    convention as consolidate.flatten_tree, so peer shards and npz
    consolidation name leaves identically)."""
    return "/".join(str(getattr(p, "key", getattr(p, "name",
                                                  getattr(p, "idx", p))))
                    for p in key_path)


class _LeafSpec:
    """Static per-leaf layout, computed once per run (the state structure
    and sharding never change between steps)."""

    __slots__ = ("path", "shape", "dtype", "sharding", "index_slot",
                 "indices", "placements")

    def __init__(self, path, leaf):
        self.path = path
        self.shape = tuple(leaf.shape)
        self.dtype = np.dtype(leaf.dtype)
        self.sharding = leaf.sharding
        self.index_slot = {}
        self.indices: List[Tuple] = []
        self.placements: List[Tuple[Any, int]] = []  # (device, unique slot)
        for sh in leaf.addressable_shards:
            key = _index_key(sh.index, self.shape)
            slot = self.index_slot.get(key)
            if slot is None:
                slot = len(self.indices)
                self.index_slot[key] = slot
                self.indices.append(key)
            self.placements.append((sh.device, slot))


class HostSnapshot:
    """One staged copy of this host's state shards: everything a persist or
    replicate job needs, with zero references to live device buffers."""

    def __init__(self, pipeline, buffer_set, specs, treedef, *, epoch,
                 step_in_epoch, process_count, stream_cursor):
        self._pipeline = pipeline
        self._buffer_set = buffer_set
        self._refs = 1
        self._lock = threading.Lock()
        self.specs = specs
        self.treedef = treedef
        self.epoch = int(epoch)
        self.step_in_epoch = int(step_in_epoch)
        self.process_count = int(process_count)
        self.stream_cursor = stream_cursor
        self.nbytes = sum(b.nbytes for leaf in buffer_set for b in leaf)

    @property
    def version(self) -> Tuple[int, int, int]:
        """(epoch, step_in_epoch, topology) — the replication version tag."""
        return (self.epoch, self.step_in_epoch, self.process_count)

    def buffers(self, leaf_i: int) -> List[np.ndarray]:
        return self._buffer_set[leaf_i]

    def retain(self) -> None:
        with self._lock:
            self._refs += 1

    def release(self) -> None:
        with self._lock:
            self._refs -= 1
            done = self._refs == 0
        if done:
            self._pipeline._return_buffers(self._buffer_set)

    def rebuild(self) -> PyTree:
        """Global device arrays from the staged host copies — what the
        persist job hands Orbax. Each host contributes exactly its
        addressable shards (device_put per placement), so the write path is
        identical to saving the live state.

        This allocates a TRANSIENT SECOND device copy of this host's state
        shard while the next training steps run (it is freed once Orbax's
        own host snapshot is taken and the persist job drops the tree), so
        the allocation is gated on available HBM headroom first — a persist
        job failing with a clear error beats an allocator OOM or a
        defragmentation stall hitting a dispatched step."""
        self._gate_on_hbm()
        leaves = []
        for i, spec in enumerate(self.specs):
            bufs = self.buffers(i)
            # device_put an OWNED copy (.copy(), unconditionally), not the
            # staging buffer itself: the CPU backend may zero-copy-adopt an
            # aligned numpy buffer, and these buffers are RECYCLED — the
            # next stage() would overwrite them under Orbax's still-running
            # async write (torn checkpoint). Same aliasing hazard as
            # peer.assemble_state's restore callback.
            arrays = [jax.device_put(bufs[slot].copy(), device)
                      for device, slot in spec.placements]
            leaves.append(jax.make_array_from_single_device_arrays(
                spec.shape, spec.sharding, arrays))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def _transient_device_bytes(self) -> dict:
        """{device: bytes rebuild() will place on it} — the extra HBM the
        persist path borrows on top of the live training state."""
        per_dev: dict = {}
        for i, spec in enumerate(self.specs):
            bufs = self.buffers(i)
            for device, slot in spec.placements:
                per_dev[device] = per_dev.get(device, 0) + bufs[slot].nbytes
        return per_dev

    def _gate_on_hbm(self) -> None:
        """Refuse rebuild()'s device allocation when it clearly cannot fit.
        Best-effort: backends without memory_stats (CPU) skip the check;
        headroom is re-polled for a short window first (a running step's
        temporaries come and go). VITAX_SNAPSHOT_HBM_CHECK=0 forces the
        attempt anyway; VITAX_SNAPSHOT_HBM_WAIT_S bounds the re-poll."""
        import os
        if os.environ.get("VITAX_SNAPSHOT_HBM_CHECK", "1") == "0":
            return
        deadline = time.monotonic() + float(
            os.environ.get("VITAX_SNAPSHOT_HBM_WAIT_S", 10.0))
        while True:
            blocked = None
            for device, incoming in self._transient_device_bytes().items():
                stats = _device_memory_stats(device)
                if not stats:
                    continue
                limit = int(stats.get("bytes_limit") or 0)
                free = limit - int(stats.get("bytes_in_use") or 0)
                if limit and incoming > free:
                    blocked = (device, incoming, free, limit)
                    break
            if blocked is None:
                return
            if time.monotonic() >= deadline:
                device, incoming, free, limit = blocked
                raise RuntimeError(
                    f"snapshot persist needs a transient second copy of "
                    f"this host's state shard on {device} "
                    f"({incoming / 2**20:.0f} MiB) but only "
                    f"{max(free, 0) / 2**20:.0f} of {limit / 2**20:.0f} MiB "
                    f"HBM are free — refusing the allocation (an OOM or "
                    f"defrag stall would hit the running step). Free HBM, "
                    f"lower the save/replication cadence, or set "
                    f"VITAX_SNAPSHOT_HBM_CHECK=0 to force the attempt.")
            time.sleep(0.2)


class SnapshotPipeline:
    """stage-on-the-loop-thread, persist/replicate-on-a-worker. See module
    docstring. Thread-safe for the loop's usage: submit()/drain()/close()
    from the loop thread, jobs on the single worker."""

    def __init__(self, max_buffer_sets: int = 2):
        assert max_buffer_sets >= 1, max_buffer_sets
        self.max_buffer_sets = int(max_buffer_sets)
        self._specs: Optional[List[_LeafSpec]] = None
        self._treedef = None
        self._free: List[list] = []
        self._allocated = 0
        self._cond = threading.Condition()
        self._q: queue.Queue = queue.Queue()
        self._errors: List[BaseException] = []
        self._stall_s = 0.0
        self.last_stall_s = 0.0
        self.last_fence_s = 0.0
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="vitax-snapshot-writer")
        self._worker.start()
        self._closed = False

    # -- staging (loop thread; the only part that may stall the step) -------
    def stage(self, state: PyTree, *, epoch: int, step_in_epoch: int = 0,
              stream_cursor: Optional[dict] = None) -> HostSnapshot:
        self.raise_pending()
        leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(state)
        leaves = [leaf for _, leaf in leaves_kp]
        if self._specs is None:
            self._specs = [_LeafSpec(_path_str(kp), leaf)
                           for kp, leaf in leaves_kp]
            self._treedef = treedef
        # fence OUTSIDE the stall clock: step N must complete before its
        # result can be copied — that wait is pipeline drain the loop would
        # pay at the next fence anyway, not snapshot cost
        t_fence = time.perf_counter()
        jax.block_until_ready(leaves)
        self.last_fence_s = time.perf_counter() - t_fence

        t0 = time.perf_counter()
        buffer_set = self._acquire_buffers()
        # overlap the D2H transfers across leaves before the blocking copies
        for leaf, spec in zip(leaves, self._specs):
            seen = set()
            for sh in leaf.addressable_shards:
                key = _index_key(sh.index, spec.shape)
                if key in seen:
                    continue
                seen.add(key)
                start = getattr(sh.data, "copy_to_host_async", None)
                if start is not None:
                    start()
        for leaf_i, (leaf, spec) in enumerate(zip(leaves, self._specs)):
            bufs = buffer_set[leaf_i]
            filled = set()
            for sh in leaf.addressable_shards:
                slot = spec.index_slot[_index_key(sh.index, spec.shape)]
                if slot in filled:
                    continue
                filled.add(slot)
                # an explicit copy INTO the owned buffer: np.asarray of a
                # host-committed jax array may be a zero-copy view of
                # memory the next train step will donate and overwrite
                np.copyto(bufs[slot], np.asarray(sh.data))
        snapshot = HostSnapshot(
            self, buffer_set, self._specs, self._treedef, epoch=epoch,
            step_in_epoch=step_in_epoch, process_count=jax.process_count(),
            stream_cursor=stream_cursor)
        self.last_stall_s = time.perf_counter() - t0
        self._stall_s += self.last_stall_s
        return snapshot

    def consume_stall_s(self) -> float:
        """Accumulated staging stall since the last call (the loop divides
        by the steps of its record window)."""
        s, self._stall_s = self._stall_s, 0.0
        return s

    # -- dispatch ------------------------------------------------------------
    def submit(self, state: PyTree, *, epoch: int, step_in_epoch: int = 0,
               stream_cursor: Optional[dict] = None,
               persist_to: Optional[str] = None, keep: int = 0,
               extra_meta: Optional[dict] = None,
               replicator=None, wait: bool = False) -> HostSnapshot:
        """stage() + enqueue the requested background jobs. `persist_to`
        writes an Orbax checkpoint for `epoch` through orbax_io.save_state
        (retries, sidecar, GC included); `replicator` mirrors the staged
        bytes to the ring buddy. wait=True (or VITAX_CKPT_SYNC=1) drains the
        queue before returning — the final/emergency save semantics."""
        import os
        wait = wait or os.environ.get("VITAX_CKPT_SYNC", "") == "1"
        snapshot = self.stage(state, epoch=epoch,
                              step_in_epoch=step_in_epoch,
                              stream_cursor=stream_cursor)
        jobs = []
        if persist_to is not None:
            jobs.append(lambda: self._persist(snapshot, persist_to,
                                              keep=keep,
                                              extra_meta=extra_meta,
                                              wait=wait))
        if replicator is not None:
            jobs.append(lambda: replicator.replicate(snapshot))
        for _ in jobs[1:]:
            snapshot.retain()
        if not jobs:
            snapshot.release()
            return snapshot
        for job in jobs:
            self._q.put((job, snapshot))
        if wait:
            self.drain()
        return snapshot

    @staticmethod
    def _persist(snapshot: HostSnapshot, ckpt_dir: str, *, keep: int,
                 extra_meta: Optional[dict], wait: bool) -> None:
        from vitax.checkpoint import orbax_io
        tree = snapshot.rebuild()
        orbax_io.save_state(  # vtx: ignore[VTX108] the worker thread IS the zero-stall path, off the step loop
            ckpt_dir, snapshot.epoch, tree, wait=wait,
            step_in_epoch=snapshot.step_in_epoch or None,
            stream_cursor=snapshot.stream_cursor, keep=keep,
            extra_meta=extra_meta)

    def drain(self) -> None:
        """Block until every queued job ran; surface any worker error."""
        self._q.join()
        self.raise_pending()

    def raise_pending(self) -> None:
        with self._cond:  # vs the worker's append in _run
            err = self._errors.pop(0) if self._errors else None
        if err is not None:
            raise RuntimeError(
                "snapshot pipeline: a background save/replicate job "
                "failed") from err

    def close(self) -> None:
        """Drain and stop the worker. Never raises (callers sit in finally
        blocks); pending errors are printed — the wait=True paths already
        surfaced anything fatal."""
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        join_or_warn(self._worker, timeout=60.0)
        with self._cond:
            errors = list(self._errors)
        for err in errors:
            print(f"vitax.snapshot: background job failed "
                  f"({type(err).__name__}: {err})", file=sys.stderr,
                  flush=True)

    # -- internals -----------------------------------------------------------
    def _acquire_buffers(self) -> list:
        with self._cond:
            while not self._free and self._allocated >= self.max_buffer_sets:
                # every set is in flight: wait for the worker to finish one.
                # Counted inside the stall clock — honest backpressure.
                self._cond.wait(timeout=1.0)
            if self._free:
                return self._free.pop()
            self._allocated += 1
        return [[np.empty(tuple(stop - start for start, stop in key),
                          dtype=spec.dtype)
                 for key in spec.indices]
                for spec in self._specs]

    def _return_buffers(self, buffer_set: list) -> None:
        with self._cond:
            self._free.append(buffer_set)
            self._cond.notify()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            job, snapshot = item
            try:
                job()
            except BaseException as e:  # noqa: BLE001 — surfaced at the next submit/drain, never lost
                with self._cond:
                    self._errors.append(e)
                print(f"vitax.snapshot: background job failed "
                      f"({type(e).__name__}: {e})", file=sys.stderr,
                      flush=True)
            finally:
                snapshot.release()
                self._q.task_done()
