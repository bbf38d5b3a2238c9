"""Fake ImageNet dataset (reference FakeImageNetDataset parity, utils.py:46-55).

Zero-filled images, label 0, real ImageNet split lengths (1,281,167 train /
50,000 val — reference run_vit_training.py:59-60). This is the fixture that
validates the whole distributed graph — compile, collectives, memory — without
any data on disk (reference README.md:76; SURVEY.md section 4).

Images are NHWC (TPU-native layout; XLA convolutions want channels-last),
vs the reference's CHW torch tensors.

`FakePackedLoader` is the same fixture for the native-resolution packed model
(--pack_tokens): zero pixels and label 0 again, but images of seeded,
different grids, packed first-fit into rows (vitax/data/packing.py).
"""

from __future__ import annotations

import numpy as np

TRAIN_SPLIT_LEN = 1_281_167
VAL_SPLIT_LEN = 50_000


class FakeImageNetDataset:
    def __init__(self, image_size: int, length: int):
        self.image_size = image_size
        self.length = length

    def __getitem__(self, idx: int):
        s = self.image_size
        return np.zeros((s, s, 3), np.float32), 0

    def __len__(self) -> int:
        return self.length

    def __repr__(self) -> str:
        return f"FakeImageNetDataset(image_size={self.image_size}, length={self.length})"


class FakePackedLoader:
    """Packed batches of zero-pixel images for `--fake_data --pack_tokens`:
    the loop's loader surface (`epoch`, `steps_per_epoch`, `close`; no
    prefetch queue, so no `t_got`: all its time is the loop's `put`). Each
    step draws grids (even sides up to the position table's, redrawn while
    over the per-image limit) from (seed, epoch, step, process) until one
    fits no row, first-fit packs them, and hands the loop one batch-sharded
    device batch."""

    def __init__(self, cfg, mesh, length: int):
        from jax.sharding import NamedSharding
        from vitax.parallel.mesh import batch_pspec
        import jax
        self.cfg = cfg
        self.sharding = NamedSharding(mesh, batch_pspec())
        self.process_index = jax.process_index()
        assert cfg.batch_size % jax.process_count() == 0
        self.local_rows = cfg.batch_size // jax.process_count()
        self.steps_per_epoch = max(
            length // (cfg.batch_size * cfg.pack_images), 1)

    def draw(self, epoch: int, step: int):
        """(batch of NumPy arrays, the packer's counts) for one step."""
        from vitax.data.packing import first_fit, pack_batch
        cfg = self.cfg
        rng = np.random.default_rng(np.random.SeedSequence(
            [cfg.seed, epoch, step, self.process_index]))
        shape = dict(rows=self.local_rows, row_tokens=cfg.pack_tokens,
                     images_per_row=cfg.pack_images)
        grids = []
        while True:
            h, w = (2 * rng.integers(1, cfg.pos_grid // 2 + 1, 2)).tolist()
            if h * w > cfg.max_image_tokens:
                continue
            if first_fit(grids + [(h, w)], **shape)[1]:
                break
            grids.append((h, w))
        return pack_batch(grids, [0] * len(grids), None, **shape,
                          patch_dim=3 * cfg.patch_size ** 2)

    def epoch(self, epoch: int, start_step: int = 0):
        import jax
        for step in range(start_step, self.steps_per_epoch):
            local, _ = self.draw(epoch, step)
            yield {k: jax.make_array_from_process_local_data(self.sharding, v)
                   for k, v in local.items()}

    def close(self) -> None:
        pass

    def __repr__(self) -> str:
        c = self.cfg
        return (f"FakePackedLoader(rows={c.batch_size}, row_tokens="
                f"{c.pack_tokens}, images_per_row={c.pack_images}, "
                f"steps_per_epoch={self.steps_per_epoch})")


class FakeDocumentLoader(FakePackedLoader):
    """Packed batches of random token documents for `--fake_data
    --model_family decoder`: the same loader surface. Each step draws
    document lengths (a long one with probability 1/4, uniform up to the row;
    else uniform up to an eighth of it) and ids uniform over the vocabulary
    rows held, from (seed, epoch, step, process), until a document fits no
    row, and first-fit packs them."""

    def draw(self, epoch: int, step: int):
        from vitax.data.packing import first_fit, pack_documents
        cfg = self.cfg
        rng = np.random.default_rng(np.random.SeedSequence(
            [cfg.seed, epoch, step, self.process_index]))
        shape = dict(rows=self.local_rows, row_tokens=cfg.pack_tokens,
                     docs_per_row=cfg.pack_images)
        lengths = []
        while True:
            top = cfg.pack_tokens if rng.random() < 0.25 \
                else max(cfg.pack_tokens // 8, 2)
            n = int(rng.integers(2, top + 1))
            if first_fit([(m, 1) for m in lengths + [n]], shape["rows"],
                         cfg.pack_tokens, cfg.pack_images)[1]:
                break
            lengths.append(n)
        docs = [rng.integers(0, cfg.vocab_rows, n, dtype=np.int32)
                for n in lengths]
        return pack_documents(docs, **shape)

    def __repr__(self) -> str:
        c = self.cfg
        return (f"FakeDocumentLoader(rows={c.batch_size}, row_tokens="
                f"{c.pack_tokens}, docs_per_row={c.pack_images}, "
                f"steps_per_epoch={self.steps_per_epoch})")
