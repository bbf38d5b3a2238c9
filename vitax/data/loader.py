"""Sharded host input pipeline with device prefetch.

Replaces the reference's DistributedSampler + DataLoader + MpDeviceLoader stack
(reference run_vit_training.py:62-88; SURVEY.md section 2.2):

- `ShardedSampler`    — per-process disjoint index shard with epoch-seeded
                        reshuffle and drop-last (DistributedSampler parity,
                        including the rank::world_size interleaving).
- worker pool         — parallel __getitem__ (decode + augment) on host CPU
                        threads (PIL releases the GIL during JPEG decode).
- `ShardedLoader`     — assembles the *global* batch as one sharded jax.Array
                        via make_array_from_process_local_data and
                        double-buffers device transfer on a background thread
                        (MpDeviceLoader parity: async host->device staging,
                        run_vit_training.py:74,88 — without the implicit
                        mark_step, which has no jit equivalent or need).

There is no per-core process fan-out (xmp.spawn): one process per host feeds
all its local devices through the sharded global array.
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from vitax import faults
from vitax.config import Config
from vitax.parallel.mesh import batch_pspec


class LoaderWorkerError(RuntimeError):
    """A data-worker (producer-thread) failure, re-raised on the CONSUMING
    host with the worker's own traceback attached. Without this, a dead
    producer just starves the consumer until the watchdog fires a dump with
    no cause in it — the stall is visible, the broken sample is not."""


class _ProducerFailure:
    """Queue envelope for a producer exception + its formatted traceback
    (the traceback object itself must not cross threads via re-raise: the
    consumer's `raise` would show the consumer's stack, not the worker's)."""

    __slots__ = ("exc", "tb")

    def __init__(self, exc: BaseException, tb: str):
        self.exc = exc
        self.tb = tb


class ShardedSampler:
    """Epoch-seeded, per-process index shard (DistributedSampler parity,
    reference run_vit_training.py:62-64,76-78 and set_epoch at :258)."""

    def __init__(self, dataset_len: int, global_batch: int, shuffle: bool,
                 seed: int, process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        self.dataset_len = dataset_len
        self.global_batch = global_batch
        self.shuffle = shuffle
        self.seed = seed
        self.process_index = jax.process_index() if process_index is None else process_index
        self.process_count = jax.process_count() if process_count is None else process_count
        assert global_batch % self.process_count == 0
        self.local_batch = global_batch // self.process_count
        # drop_last at the global-batch level: identical step count on every
        # process (reference drop_last=True on sampler AND loader, :63-69)
        self.steps_per_epoch = dataset_len // global_batch

    def epoch_indices(self, epoch: int) -> np.ndarray:
        """(steps_per_epoch, local_batch) index matrix for this process."""
        if self.shuffle:
            order = np.random.default_rng(
                np.random.SeedSequence([self.seed, epoch])).permutation(self.dataset_len)
        else:
            order = np.arange(self.dataset_len)
        usable = self.steps_per_epoch * self.global_batch
        order = order[:usable].reshape(self.steps_per_epoch, self.global_batch)
        # rank-interleaved split of each global batch (DistributedSampler's
        # indices[rank::world] layout)
        return order[:, self.process_index::self.process_count]


class ShardedLoader:
    """Iterates global batches as sharded device arrays, with background
    prefetch (double buffering)."""

    def __init__(self, dataset, sampler: ShardedSampler, mesh: Mesh,
                 num_workers: int = 4, prefetch: int = 2):
        self.dataset = dataset
        self.sampler = sampler
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, batch_pspec())
        self.label_sharding = NamedSharding(mesh, batch_pspec())
        self.num_workers = max(num_workers, 1)
        self.prefetch = max(prefetch, 1)
        self.steps_per_epoch = sampler.steps_per_epoch
        # time.time() at which the prefetch queue last handed a host batch
        # to the TRAINING THREAD (stamped in epoch(), on that thread): the
        # loop's `t_got` mark. Before it the thread was blocked on the queue
        # (`wait`: queue time, which the loop's run-ahead hides from the
        # device while the log step's `fence` outlasts it; a run whose wait
        # tracks sec/iter with no fence left is input-bound), after it it
        # hands the batch to the device (`put`) — vitax/train/loop.py,
        # module docstring.
        self.t_got = 0.0
        self._pool = ThreadPoolExecutor(max_workers=self.num_workers,
                                        thread_name_prefix="vitax-data")

    def _load_local(self, indices: Sequence[int]) -> Dict[str, np.ndarray]:
        if getattr(self.dataset, "use_native", False):
            # whole-batch native path: one GIL-free C++ call, its own thread pool
            images, labels = self.dataset.load_batch(indices, self.num_workers)
            return {"image": images, "label": labels}
        items = list(self._pool.map(self.dataset.__getitem__, indices))
        images = np.stack([it[0] for it in items])
        if images.dtype != np.uint8:  # uint8 = device-side normalization path
            images = images.astype(np.float32)
        labels = np.asarray([it[1] for it in items], np.int32)
        return {"image": images, "label": labels}

    def _to_device(self, local: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        # Builds the GLOBAL (B, ...) array from each process's local shard; on a
        # single host this is a plain sharded device_put over the mesh.
        return {
            "image": jax.make_array_from_process_local_data(self.sharding, local["image"]),
            "label": jax.make_array_from_process_local_data(self.label_sharding, local["label"]),
        }

    def epoch(self, epoch: int, start_step: int = 0) -> Iterator[Dict[str, jax.Array]]:
        """Yield device batches for one epoch. `epoch` seeds the shuffle
        (train_sampler.set_epoch parity, reference run_vit_training.py:258)
        and the per-sample augmentation randomness. `start_step` skips the
        first N global batches exactly (the index matrix is a pure function
        of (seed, epoch), so no data is loaded for the skipped steps) —
        step-granular preemption resume (vitax/train/loop.py)."""
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)
        index_matrix = self.sampler.epoch_indices(epoch)[start_step:]
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def producer():
            # Host-side work only (decode + stack). ALL JAX dispatch happens on
            # the consumer thread: a second dispatch thread can interleave
            # compiled programs containing collectives and deadlock their
            # rendezvous (observed on XLA:CPU's in-process communicator).
            try:
                for row in index_matrix:
                    if stop.is_set():
                        return
                    faults.fire("loader")  # host-side drill point: a `stall`
                    # here starves the consumer; an `oserror` exercises the
                    # worker-traceback surfacing below
                    q.put(self._load_local(row))
            except BaseException as e:  # surface worker errors to the consumer
                q.put(_ProducerFailure(e, traceback.format_exc()))
            finally:
                q.put(None)

        t = threading.Thread(target=producer, daemon=True, name="vitax-prefetch")
        t.start()
        try:
            while True:
                item = q.get()
                self.t_got = time.time()
                if item is None:
                    return
                if isinstance(item, _ProducerFailure):
                    raise LoaderWorkerError(
                        f"data worker failed while producing epoch {epoch}: "
                        f"{type(item.exc).__name__}: {item.exc}\n"
                        f"--- worker traceback (vitax-prefetch thread) ---\n"
                        f"{item.tb}") from item.exc
                # device transfer is async in JAX — this enqueues the copies
                # and returns; compute/transfer overlap still happens
                yield self._to_device(item)
        finally:
            stop.set()
            # Drain until the producer thread actually exits: a producer
            # blocked in q.put never observes `stop` on its own — it needs
            # the consumer to free a slot first. Breaking on the first empty
            # read (the old behavior) races exactly that window: the
            # producer is awake between puts, the queue is momentarily
            # empty, the drain stops, and the next q.put blocks forever —
            # leaking the thread (and with it a reference to the dataset)
            # every time an epoch iterator is abandoned early. Bounded so a
            # wedged worker can't hang shutdown.
            deadline = time.monotonic() + 10.0
            while t.is_alive() and time.monotonic() < deadline:
                try:
                    q.get(timeout=0.05)
                except queue.Empty:
                    pass
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def close(self):
        # cancel queued decode work, then wait: a shutdown(wait=False) can
        # drop the pool while __getitem__ calls are mid-flight, and their
        # exceptions land in dead futures nobody observes
        try:
            self._pool.shutdown(wait=True, cancel_futures=True)
        except TypeError:  # cancel_futures needs python>=3.9
            self._pool.shutdown(wait=True)


def build_datasets(cfg: Config, mesh: Mesh):
    """Build (train_dataset, train_loader, val_dataset, val_loader)
    (reference build_datasets parity, run_vit_training.py:30-96)."""
    from vitax.data.fake import TRAIN_SPLIT_LEN, VAL_SPLIT_LEN, FakeImageNetDataset

    world = jax.process_count()
    assert cfg.batch_size % world == 0, (
        f"batch_size {cfg.batch_size} not divisible by process count {world}")

    if cfg.data_format == "stream":
        # .vtxshard streaming containers (vitax/data/stream/): same return
        # contract, sharded-streaming input plane (config.validate() already
        # rejected stream+fake_data)
        from vitax.data.stream import build_stream_datasets
        return build_stream_datasets(cfg, mesh)

    if cfg.decoder:
        assert cfg.fake_data, (
            "--model_family decoder trains on --fake_data documents only: no "
            "text loader is built")
        from vitax.data.fake import FakeDocumentLoader
        train_loader = FakeDocumentLoader(cfg, mesh, TRAIN_SPLIT_LEN)
        val_loader = FakeDocumentLoader(cfg, mesh, VAL_SPLIT_LEN)
        return train_loader, train_loader, val_loader, val_loader

    if cfg.packed:
        # packed native-resolution batches: fake data only so far (packing in
        # the ImageFolder and stream loaders is a later issue, PERF.md s.7)
        assert cfg.fake_data, (
            "--pack_tokens trains on --fake_data only: packing real images "
            "in the ImageFolder / stream loaders is not built yet")
        from vitax.data.fake import FakePackedLoader
        train_loader = FakePackedLoader(cfg, mesh, TRAIN_SPLIT_LEN)
        val_loader = FakePackedLoader(cfg, mesh, VAL_SPLIT_LEN)
        return train_loader, train_loader, val_loader, val_loader

    if cfg.fake_data:
        train_ds = FakeImageNetDataset(cfg.image_size, TRAIN_SPLIT_LEN)
        val_ds = FakeImageNetDataset(cfg.image_size, VAL_SPLIT_LEN)
    else:
        from vitax.data.imagefolder import ImageFolderDataset
        from vitax.data.transforms import train_transform, val_transform
        import os
        # device_normalize: transforms emit raw uint8 and the jitted step
        # normalizes on-device (step.py:prepare_images)
        norm_on_host = not cfg.device_normalize
        train_ds = ImageFolderDataset(
            os.path.join(cfg.data_dir, "train"),
            train_transform(cfg.image_size, cfg.seed, normalize=norm_on_host))
        val_ds = ImageFolderDataset(
            os.path.join(cfg.data_dir, "val"),
            val_transform(cfg.image_size, normalize=norm_on_host))

    train_sampler = ShardedSampler(len(train_ds), cfg.batch_size, shuffle=True, seed=cfg.seed)
    val_sampler = ShardedSampler(len(val_ds), cfg.batch_size, shuffle=False, seed=cfg.seed)
    train_loader = ShardedLoader(train_ds, train_sampler, mesh, cfg.num_workers,
                                 prefetch=cfg.prefetch_batches)
    val_loader = ShardedLoader(val_ds, val_sampler, mesh, cfg.num_workers,
                               prefetch=cfg.prefetch_batches)
    return train_ds, train_loader, val_ds, val_loader
