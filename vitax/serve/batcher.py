"""Dynamic micro-batcher: requests -> futures -> bucketed engine batches.

The Orca/Clipper-style adaptive batching core: requests enqueue with a
Future and a single worker thread flushes them as one engine batch when
either the largest bucket fills (`max_batch`) or the oldest queued request
has waited `max_batch_wait_ms` — whichever comes first. Under load the
batcher runs full buckets back-to-back (throughput); a lone request waits
at most the deadline (bounded tail latency).

A batch passes the engine in two phases (vitax/serve/engine.py):
`dispatch_fn(images)` hands it to the device and returns a handle without
waiting, `handle.result()` blocks on its answers. The worker keeps at most
ONE undelivered handle, and so at most one batch queued on the device behind
the one that runs. What it does next depends on two things it can see, that
batch in flight and the depth of the queue:

- nothing in flight: the rule above, then dispatch;
- a batch still running and a full largest bucket pending: stack, put and
  dispatch that bucket BEFORE fetching the batch in flight, so the device
  goes from one to the next without waiting for this thread (its fetch, its
  8 futures, its turn at the GIL);
- a batch still running and less than a full bucket pending: the deadline
  alone flushes nothing, because the device is busy anyway and a partial
  batch queued behind a running one only locks in a worse occupancy. The
  worker waits for "bucket full or batch in flight done", whichever first
  (a submit wakes it; `done()` it asks every DONE_POLL_S); if the bucket
  filled it is the case above;
- the batch in flight is done: its answers are delivered first, and the
  worker is back at the first case.

One queued batch is the whole of it: a third could only wait longer. A plain
`predict_fn(images) -> (ids, probs)` is a `dispatch_fn` whose handle is
already finished when it returns (`finished`); the loop does not tell the
two apart: by the last case such an engine simply never overlaps. Batches
are numbered (`batch_id`, from 0) and delivered in the order of dispatch. An
exception from `dispatch_fn` or from `result()` goes to the futures of that
batch only.

Thread-safe by construction: HTTP handler threads only append under the
condition lock and block on their Future; all engine work, dispatch and
fetch of every batch, happens on the one worker thread, so the engine needs
no internal locking.

The worker stamps each batch's timeline (`time.time()`; a phase lasts from
its mark to the next) and hands it to `on_batch`:

  t_collect  the worker turned to this batch: waiting for the bucket to fill
             or the deadline
  t_stack    batch popped from the queue; `np.stack`
  t_put      stacked; fault hook, then `dispatch_fn`: padding, `device_put`
  t_dispatch the handle's: `device_put` returned; the compiled call, and
             then whatever the worker does before it turns to this batch's
             answers (the batch ahead's fetch and futures, the next one's
             dispatch)
  t_wait     the handle's: `result()` began to block
  t_deliver  `result()` returned; futures resolved
  t_end      last future resolved (`on_batch` runs after it)

With nothing else in flight a batch's timeline has no holes and `t_collect`
of batch n+1 is `t_end` of batch n. A batch dispatched behind a running one
(`overlapped` 1 in its record, counted in `batches_overlapped`) starts at
the `t_end` of batch n-1: its `collect`, `stack`, `put` and `dispatch` lie
inside batch n's device time, and its own `dispatch` phase stretches over
batch n's `wait` and `deliver`, so two timelines cover such a moment. A
handle that marks nothing (a plain `predict_fn`) gives `t_put` for both of
its marks: all of predict is `wait`.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable, Optional

import numpy as np

from vitax import faults


class QueueFull(RuntimeError):
    """submit() against a batcher whose pending queue is at queue_max.

    The typed overload signal of the serve path: the single-engine HTTP
    server maps it to 503 (reason "queue_full"), the fleet router
    (vitax/serve/fleet/router.py) maps a replica's queue-full 503 to an
    admission shed (429 + Retry-After). Before the bound existed the deque
    grew without limit under overload and every queued request eventually
    timed out — now the queue depth is bounded by --serve_queue_max."""


class BatchResult:
    """Per-request slice of a flushed batch, plus the batch's accounting
    (queue wait, engine latency, occupancy) for telemetry."""

    __slots__ = ("classes", "probs", "queue_wait_s", "infer_s",
                 "batch_size", "bucket", "batch_id", "t_deliver")

    def __init__(self, classes, probs, queue_wait_s, infer_s, batch_size,
                 bucket, batch_id=0, t_deliver=0.0):
        self.classes = classes            # (k,) int32 class ids
        self.probs = probs                # (k,) float32 probabilities
        self.queue_wait_s = queue_wait_s  # this request's time in queue
        self.infer_s = infer_s            # its batch's put to answers (holds the wait behind a batch ahead)
        self.batch_size = batch_size      # real requests in the batch
        self.bucket = bucket              # padded bucket it executed in
        self.batch_id = batch_id          # names its batch's serve_batch span
        self.t_deliver = t_deliver        # time.time() its results reached the host


def finished(predict_fn: Callable) -> Callable:
    """`predict_fn(images) -> (ids, probs)`, which blocks, as a `dispatch_fn`
    whose handle is finished when it returns and marks nothing."""
    return lambda images: _Finished(predict_fn(images))


class _Finished:
    __slots__ = ("_outputs",)
    t_dispatch = t_wait = None

    def __init__(self, outputs):
        self._outputs = outputs

    def done(self) -> bool:
        return True

    def result(self):
        return self._outputs


# how often the worker asks a handle whether it is done while it waits for a
# bucket to fill behind it (a submit wakes it at once; only `done` is polled)
DONE_POLL_S = 0.0005


class DynamicBatcher:
    """Queue + worker thread around an engine.

    `dispatch_fn(images) -> handle` receives a stacked (n, H, W, 3) array
    with n <= max_batch and returns without waiting for the device; the
    handle's `result()` blocks and returns per-row top-k ids/probs, `done()`
    tells whether it would block, and `t_dispatch` / `t_wait` are its marks.
    Without one, `predict_fn(images) -> (ids, probs)` is wrapped as a
    dispatch that is finished when it returns. The engine pads n to its
    bucket internally and reports the bucket via `bucket_of` (so telemetry
    can record occupancy = batch_size / bucket).
    """

    def __init__(self, predict_fn: Optional[Callable], max_batch: int,
                 max_wait_ms: float,
                 bucket_of: Optional[Callable[[int], int]] = None,
                 on_batch: Optional[Callable[[dict], None]] = None,
                 queue_max: int = 0,
                 dispatch_fn: Optional[Callable] = None):
        assert max_batch >= 1
        assert queue_max >= 0, queue_max
        self.dispatch_fn = dispatch_fn or finished(predict_fn)
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1000.0
        self.queue_max = queue_max        # 0 = unbounded (pre-bound behavior)
        self.bucket_of = bucket_of or (lambda n: n)
        self.on_batch = on_batch          # telemetry hook, called per batch
        self.batches_total = 0            # dispatched; the next batch_id
        self.batches_overlapped = 0       # ... behind a batch still in flight
        self._pending: deque = deque()    # (image, Future, t_enqueue)
        self._cond = threading.Condition()
        self._closed = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="vitax-serve-batcher")
        self._worker.start()

    def submit(self, image: np.ndarray) -> Future:
        """Enqueue one (H, W, 3) image; resolves to a BatchResult.

        Raises QueueFull when `queue_max` requests are already pending —
        overload is answered at admission time, not by letting the deque
        grow until every queued request times out."""
        fut: Future = Future()
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if self.queue_max and len(self._pending) >= self.queue_max:
                raise QueueFull(
                    f"{len(self._pending)} requests already pending "
                    f"(--serve_queue_max {self.queue_max})")
            self._pending.append((image, fut, time.time()))
            self._cond.notify()
        return fut

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    def set_max_wait_ms(self, max_wait_ms: float) -> None:
        """Retune the flush deadline at runtime (brownout mode shortens it
        to drain the queue faster, then restores it on recovery). The worker
        recomputes its deadline from `max_wait_s` every cycle, so the new
        value takes effect at the next flush decision."""
        assert max_wait_ms >= 0, max_wait_ms
        with self._cond:
            self.max_wait_s = max_wait_ms / 1000.0
            self._cond.notify()

    def close(self, timeout: float = 10.0) -> None:
        """Stop accepting work, deliver the batch in flight, flush what is
        queued, join the worker."""
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._worker.join(timeout=timeout)

    # --- worker -----------------------------------------------------------

    def _run(self) -> None:
        flying = None            # the one dispatched, undelivered batch:
                                 # (requests, handle, its record so far)
        t_free = time.time()     # the worker's last dispatch or delivery ended
        while True:
            batch = self._collect(flying and flying[1])
            if not batch and flying is None:
                return           # closed, and the queue is flushed
            ahead = None
            if batch:
                ahead = self._dispatch(batch, t_free, time.time(),
                                       overlapped=int(flying is not None))
                t_free = time.time()
            if flying is not None:
                t_free = self._deliver(*flying)
            flying = ahead

    def _collect(self, running) -> list:
        """Pop the batch to dispatch next; `running` is the handle of the
        batch in flight, if there is one. Empty: deliver that batch first
        or, with nothing in flight, the batcher is closed and has flushed."""
        with self._cond:
            if running is None:
                while not self._pending and not self._closed:
                    self._cond.wait()
                # flush when the largest bucket fills or the OLDEST request
                # hits the deadline, whichever first (deadline recomputed
                # each wait so set_max_wait_ms() applies to queued work too)
                while (len(self._pending) < self.max_batch
                       and not self._closed):
                    deadline = self._pending[0][2] + self.max_wait_s
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
            else:
                # while the batch in flight runs, the device is busy: only a
                # full bucket goes behind it. A partial one waits for company
                # until that batch is done, not for its deadline. Once it is
                # done its answers come first (a handle finished from the
                # start, a plain predict_fn's, never has a batch behind it)
                while (len(self._pending) < self.max_batch
                       and not self._closed and not running.done()):
                    self._cond.wait(timeout=DONE_POLL_S)
                if len(self._pending) < self.max_batch or running.done():
                    return []
            return [self._pending.popleft()
                    for _ in range(min(len(self._pending), self.max_batch))]

    @staticmethod
    def _fail(batch, error) -> None:
        for _, fut, _ in batch:
            if not fut.cancelled():
                fut.set_exception(error)

    def _dispatch(self, batch, t_collect, t_stack, overlapped):
        """Hand one popped batch to the engine; returns what `_deliver`
        takes (the requests, the handle, the batch's record so far), or None
        where the dispatch failed and the batch's futures have the
        exception."""
        images = np.stack([img for img, _, _ in batch])
        t_put = time.time()
        try:
            # chaos hook on the worker thread: `hang` stalls the whole batch
            # (the predict-hang drill), `oserror` fails it — delivered to
            # every request future of this batch, never killing the worker
            faults.fire("batcher_flush")
            handle = self.dispatch_fn(images)
        except Exception as e:  # noqa: BLE001 — deliver, don't kill the worker
            self._fail(batch, e)
            return None
        record = {"batch_id": self.batches_total, "batch_size": len(batch),
                  "bucket": self.bucket_of(len(batch)),
                  "overlapped": overlapped, "t_collect": t_collect,
                  "t_stack": t_stack, "t_put": t_put}
        self.batches_total += 1
        self.batches_overlapped += overlapped
        return batch, handle, record

    def _deliver(self, batch, handle, record) -> float:
        """Fetch a dispatched batch's answers and resolve its futures;
        returns its `t_end`."""
        try:
            ids, probs = handle.result()
        except Exception as e:  # noqa: BLE001 — deliver, don't kill the worker
            self._fail(batch, e)
            return time.time()
        t_deliver = time.time()
        t_put = record["t_put"]
        infer_s = t_deliver - t_put
        for row, (_, fut, t_enq) in enumerate(batch):
            if not fut.cancelled():
                fut.set_result(BatchResult(
                    classes=ids[row], probs=probs[row],
                    queue_wait_s=t_put - t_enq, infer_s=infer_s,
                    batch_size=record["batch_size"], bucket=record["bucket"],
                    batch_id=record["batch_id"], t_deliver=t_deliver))
        t_end = time.time()
        if self.on_batch is not None:
            record.update(infer_s=infer_s,
                          t_dispatch=handle.t_dispatch or t_put,
                          t_wait=handle.t_wait or t_put,
                          t_deliver=t_deliver, t_end=t_end)
            try:
                self.on_batch(record)
            except Exception:  # noqa: BLE001 # vtx: ignore[VTX106] telemetry must not kill serving
                pass
        return t_end
