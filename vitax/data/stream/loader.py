"""Streaming dataset + loader: shard records -> native decode -> device.

Mirrors ShardedLoader's interface (vitax/data/loader.py) so train/loop.py
consumes either transparently — `epoch(epoch, start_step)`, `steps_per_epoch`,
`t_got`, `close()` — with three streaming-specific upgrades:

- records arrive as in-memory bytes from the shard reader (ONE open handle,
  sequential shard consumption) and decode through the native memory-source
  batch call (`vitax/data/native.py process_batch_bytes`): one GIL-free C++
  call per local batch, no filesystem round-trip per sample;
- the host->device stage is explicitly double-buffered: the transfer of
  batch k+1 is ISSUED before batch k is yielded to the step loop, so H2D
  overlaps step k even on transports whose device_put is lazier than XLA's
  async dispatch suggests;
- `cursor_for_step` / `check_cursor` expose the deterministic mid-epoch
  resume cursor (vitax/data/stream/sampler.py) that train/loop.py stores in
  the checkpoint sidecar.
"""

from __future__ import annotations

import io
import queue
import threading
import time
import traceback
from typing import Dict, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from vitax.data.loader import LoaderWorkerError, _ProducerFailure
from vitax.data.stream.format import ShardReader, load_split_meta
from vitax.data.stream.sampler import StreamSampler
from vitax.parallel.mesh import batch_pspec


class StreamDataset:
    """Decodes (shard_id, record_id) entries from one split's shard set.

    `use_native=None` (auto) routes JPEG records through the C++
    memory-source pipeline when available; anything else (non-JPEG payloads,
    corrupt records, stale .so without the mem API) falls back to PIL per
    record — the same degradation ladder as ImageFolderDataset."""

    def __init__(self, split_dir: str, transform=None,
                 use_native: Optional[bool] = None):
        from vitax.data import native
        self._native = native
        self.split_dir = split_dir
        self.transform = transform
        self.meta = load_split_meta(split_dir)
        self.reader = ShardReader(split_dir, self.meta)
        self.classes = list(self.meta.get("classes", []))
        self.num_records = int(self.meta["num_records"])
        if use_native is None:
            use_native = native.mem_available()
        self.use_native = (use_native and transform is not None
                           and hasattr(transform, "native_params"))
        self._normalize = getattr(transform, "normalize", True)

    def set_epoch(self, epoch: int) -> None:
        if self.transform is not None and hasattr(self.transform, "set_epoch"):
            self.transform.set_epoch(epoch)

    def __len__(self) -> int:
        return self.num_records

    def __repr__(self) -> str:
        return (f"StreamDataset(split_dir={self.split_dir!r}, "
                f"classes={len(self.classes)}, records={self.num_records}, "
                f"shards={len(self.meta['shards'])})")

    def _shape_args(self) -> Tuple[int, int]:
        return self.transform.image_size, getattr(self.transform, "resize_to", 0)

    def _pil_decode(self, payload: bytes, global_id: int) -> np.ndarray:
        from PIL import Image
        with Image.open(io.BytesIO(payload)) as img:
            img = img.convert("RGB")
            if self.transform is not None:
                return self.transform(img, index=global_id)
            return np.asarray(img, np.float32) / 255.0

    def load_entries(self, entries: Sequence[Tuple[int, int, int]],
                     n_threads: int = 8) -> Tuple[np.ndarray, np.ndarray]:
        """One local batch: entries = (shard_id, record_id, global_id)
        triples in plan order (grouped by shard — the reader advances
        sequentially). Returns (images, labels) like
        ImageFolderDataset.load_batch: normalized float32 or raw uint8 when
        the transform has normalize=False (device-side normalization)."""
        payloads, labels = [], []
        for shard_id, record_id, _ in entries:
            payload, label = self.reader.read_record(int(shard_id),
                                                     int(record_id))
            payloads.append(payload)
            labels.append(label)
        labels_arr = np.asarray(labels, np.int32)
        out_size, resize_to = self._shape_args()
        dtype = np.float32 if self._normalize else np.uint8
        images = np.empty((len(entries), out_size, out_size, 3), dtype)

        native_pos, params = [], []
        if self.use_native:
            for pos, (_, _, global_id) in enumerate(entries):
                payload = payloads[pos]
                if not self._native.is_jpeg_bytes(payload):
                    continue
                size = self._native.jpeg_size_bytes(payload)
                if size is None:
                    continue
                native_pos.append(pos)
                params.append(self.transform.native_params(
                    size[0], size[1], int(global_id)))

        native_set = set(native_pos)
        fallback = [p for p in range(len(entries)) if p not in native_set]
        if native_pos:
            batch, failed = self._native.process_batch_bytes(
                [payloads[p] for p in native_pos], params, out_size,
                resize_to, n_threads, normalize=self._normalize)
            if batch is None:
                fallback = list(range(len(entries)))
            else:
                failed_set = set(failed)
                for j, pos in enumerate(native_pos):
                    if j in failed_set:
                        fallback.append(pos)
                    else:
                        images[pos] = batch[j]
        for pos in fallback:
            images[pos] = self._pil_decode(payloads[pos],
                                           int(entries[pos][2]))
        return images, labels_arr

    def close(self) -> None:
        self.reader.close()


class StreamLoader:
    """Iterates global batches as sharded device arrays: background producer
    thread (shard read + native decode), double-buffered H2D on the consumer
    thread, deterministic mid-epoch cursor."""

    def __init__(self, dataset: StreamDataset, sampler: StreamSampler,
                 mesh: Mesh, num_workers: int = 4, prefetch: int = 2):
        self.dataset = dataset
        self.sampler = sampler
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, batch_pspec())
        self.label_sharding = NamedSharding(mesh, batch_pspec())
        self.num_workers = max(num_workers, 1)
        self.prefetch = max(prefetch, 1)
        self.steps_per_epoch = sampler.steps_per_epoch
        self.t_got = 0.0   # the loop's `t_got` mark, as ShardedLoader's

    def cursor_for_step(self, epoch: int, step: int) -> Dict:
        """Resume cursor after `step` consumed batches — what train/loop.py
        stores in the mid-epoch checkpoint sidecar."""
        return self.sampler.cursor_for_step(epoch, step)

    def check_cursor(self, cursor: Dict, resume_step: int) -> None:
        """Validate a restored sidecar cursor against this run's derived
        resume position (shard-set drift detection)."""
        self.sampler.check_cursor(cursor, int(cursor.get("epoch", 0)),
                                  resume_step)

    def _load_local(self, rows: np.ndarray) -> Dict[str, np.ndarray]:
        entries = [(int(s), int(r), self.sampler.global_id(int(s), int(r)))
                   for s, r in rows]
        images, labels = self.dataset.load_entries(entries, self.num_workers)
        return {"image": images, "label": labels}

    def _to_device(self, local: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        return {
            "image": jax.make_array_from_process_local_data(
                self.sharding, local["image"]),
            "label": jax.make_array_from_process_local_data(
                self.label_sharding, local["label"]),
        }

    def epoch(self, epoch: int, start_step: int = 0
              ) -> Iterator[Dict[str, jax.Array]]:
        """Yield device batches for one epoch. `start_step` skips the first N
        batches EXACTLY (the plan is a pure function of (seed, epoch), so no
        skipped record is read) — mid-epoch resume lands on precisely the
        not-yet-seen records."""
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)
        plan = self.sampler.epoch_entries(epoch)[start_step:]
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def producer():
            # Host-side work only (shard read + decode). ALL JAX dispatch
            # stays on the consumer thread — a second dispatch thread can
            # interleave compiled collectives and deadlock their rendezvous
            # (see ShardedLoader.epoch).
            try:
                for rows in plan:
                    if stop.is_set():
                        return
                    q.put(self._load_local(rows))
            except BaseException as e:
                q.put(_ProducerFailure(e, traceback.format_exc()))
            finally:
                q.put(None)

        t = threading.Thread(target=producer, daemon=True,
                             name="vitax-stream-prefetch")
        t.start()
        pending: Optional[Dict[str, jax.Array]] = None
        try:
            while True:
                item = q.get()
                self.t_got = time.time()
                if item is None:
                    break
                if isinstance(item, _ProducerFailure):
                    raise LoaderWorkerError(
                        f"stream worker failed while producing epoch {epoch}:"
                        f" {type(item.exc).__name__}: {item.exc}\n"
                        f"--- worker traceback (vitax-stream-prefetch thread)"
                        f" ---\n{item.tb}") from item.exc
                # double buffer: ISSUE the transfer of this batch, then yield
                # the previous one — batch k+1's H2D is in flight while the
                # step loop consumes batch k
                device_batch = self._to_device(item)
                if pending is not None:
                    yield pending
                pending = device_batch
            if pending is not None:
                yield pending
        finally:
            stop.set()
            # drain until the producer actually exits (a producer blocked in
            # q.put needs the consumer to free a slot — see ShardedLoader)
            deadline = time.monotonic() + 10.0
            while t.is_alive() and time.monotonic() < deadline:
                try:
                    q.get(timeout=0.05)
                except queue.Empty:
                    pass
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def close(self) -> None:
        self.dataset.close()
