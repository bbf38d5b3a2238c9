"""Dynamic micro-batcher: requests -> futures -> bucketed engine batches.

The Orca/Clipper-style adaptive batching core: requests enqueue with a
Future and a single worker thread flushes them as one engine batch when
either the largest bucket fills (`max_batch`) or the oldest queued request
has waited `max_batch_wait_ms` — whichever comes first. Under load the
batcher runs full buckets back-to-back (throughput); a lone request waits
at most the deadline (bounded tail latency).

Thread-safe by construction: HTTP handler threads only append under the
condition lock and block on their Future; all engine work happens on the
one worker thread, so the engine needs no internal locking.

The worker stamps its own timeline (`time.time()`, no holes: each phase
lasts from its mark to the next, and `t_collect` of batch n+1 is `t_end` of
batch n) and hands it to `on_batch` with a `batch_id` counting from 0:

  t_collect  waiting for the bucket to fill or the deadline
  t_stack    batch popped from the queue; `np.stack`
  t_put      stacked; fault hook, then `predict_fn` (which may mark phases
             of its own inside: the engine's `t_dispatch`, `t_wait`)
  t_deliver  `predict_fn` returned; futures resolved
  t_end      last future resolved (`on_batch` runs after it, so a telemetry
             write of batch n falls into `collect` of batch n+1)
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
        self.infer_s = infer_s            # engine latency of its batch
        self.batch_size = batch_size      # real requests in the batch
        self.bucket = bucket              # padded bucket it executed in
        self.batch_id = batch_id          # names its batch's serve_batch span
        self.t_deliver = t_deliver        # time.time() its results reached the host


class DynamicBatcher:
    """Queue + worker thread around `predict_fn(images) -> (ids, probs)`.

    `predict_fn` receives a stacked (n, H, W, 3) array with n <= max_batch
    and returns per-row top-k ids/probs; the engine pads n to its bucket
    internally and reports the bucket via `bucket_of` (so telemetry can
    record occupancy = batch_size / bucket).
    """

    def __init__(self, predict_fn: Callable, max_batch: int,
                 max_wait_ms: float,
                 bucket_of: Optional[Callable[[int], int]] = None,
                 on_batch: Optional[Callable[[dict], None]] = None,
                 queue_max: int = 0):
        assert max_batch >= 1
        assert queue_max >= 0, queue_max
        self.predict_fn = predict_fn
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1000.0
        self.queue_max = queue_max        # 0 = unbounded (pre-bound behavior)
        self.bucket_of = bucket_of or (lambda n: n)
        self.on_batch = on_batch          # telemetry hook, called per flush
        self.batches_flushed = 0
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
        """Stop accepting work, flush what is queued, join the worker."""
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._worker.join(timeout=timeout)

    # --- worker -----------------------------------------------------------

    def _run(self) -> None:
        t_collect = time.time()
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait()
                if not self._pending and self._closed:
                    return
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
                batch = [self._pending.popleft()
                         for _ in range(min(len(self._pending),
                                            self.max_batch))]
            t_collect = self._flush(batch, t_collect, time.time())

    def _flush(self, batch, t_collect,  # vtx: ignore[VTX103] predict_fn fences internally (np.asarray on outputs)
               t_stack) -> float:
        """Run one popped batch; returns its `t_end`, which the worker takes
        as the next batch's `t_collect`."""
        images = np.stack([img for img, _, _ in batch])
        t_flush = time.time()   # the `t_put` mark
        try:
            # chaos hook on the worker thread: `hang` stalls the whole batch
            # (the predict-hang drill), `oserror` fails it — delivered to
            # every request future below, never killing the worker
            faults.fire("batcher_flush")
            ids, probs = self.predict_fn(images)
        except Exception as e:  # noqa: BLE001 — deliver, don't kill the worker
            for _, fut, _ in batch:
                if not fut.cancelled():
                    fut.set_exception(e)
            return time.time()
        t_deliver = time.time()
        infer_s = t_deliver - t_flush
        n = len(batch)
        bucket = self.bucket_of(n)
        batch_id = self.batches_flushed
        self.batches_flushed += 1
        for row, (_, fut, t_enq) in enumerate(batch):
            if not fut.cancelled():
                fut.set_result(BatchResult(
                    classes=ids[row], probs=probs[row],
                    queue_wait_s=t_flush - t_enq, infer_s=infer_s,
                    batch_size=n, bucket=bucket, batch_id=batch_id,
                    t_deliver=t_deliver))
        t_end = time.time()
        if self.on_batch is not None:
            try:
                self.on_batch({"batch_id": batch_id, "batch_size": n,
                               "bucket": bucket, "infer_s": infer_s,
                               "t_collect": t_collect, "t_stack": t_stack,
                               "t_put": t_flush, "t_deliver": t_deliver,
                               "t_end": t_end})
            except Exception:  # noqa: BLE001 # vtx: ignore[VTX106] telemetry must not kill serving
                pass
        return t_end
