"""Token-budget packing of whole images into rows (native-resolution models).

A packed batch is R rows of T pre-cut patches. A row holds up to S whole
images of different grids back to back; what is left of the row is padding.
The arrays (all static shapes; the model's input, vitax/models/vit.py):

  patches      uint8 [R, T, 3*p*p]  a patch's pixels as (row, column, channel)
  segment_ids  int32 [R, T]         0 = padding, 1.. = the image within its row
  positions    int32 [R, T, 2]      (row, column) in the image's own grid
  grid_hw      int32 [R, S, 2]      each image's grid; 0 where there is none
  label        int32 [R, S]
  label_mask   float32 [R, S]       1 where an image exists

A packed batch of token DOCUMENTS (the decoder family, vitax/models/
decoder.py) is the same in one dimension: `document_layout` / `pack_documents`

  tokens       int32 [R, T]         token ids; 0 at padding
  segment_ids  int32 [R, T]         0 = padding, 1.. = the document in its row
  positions    int32 [R, T]         the token's position in its own document

NumPy only: this runs in a loader's host thread.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Grid = Tuple[int, int]


def cut_patches(image: np.ndarray, patch: int) -> np.ndarray:
    """(H, W, C) -> (H/p * W/p, p*p*C): patches in raster order, each
    flattened as (row, column, channel) — the order in which a p x p
    stride-p convolution's kernel (p, p, C, D), reshaped to (p*p*C, D),
    multiplies them."""
    h, w, c = image.shape
    assert h % patch == 0 and w % patch == 0, (image.shape, patch)
    x = image.reshape(h // patch, patch, w // patch, patch, c)
    return x.transpose(0, 2, 1, 3, 4).reshape(-1, patch * patch * c)


def first_fit(grids: Sequence[Grid], rows: int, row_tokens: int,
              images_per_row: int) -> Tuple[List[List[int]], List[int]]:
    """Each image, in order, into the first row with room for all of its
    tokens and a free image slot. Returns (the image indices of each row,
    the images that fitted nowhere). No image is ever split."""
    placed: List[List[int]] = [[] for _ in range(rows)]
    free = [row_tokens] * rows
    left = []
    for i, (h, w) in enumerate(grids):
        for r in range(rows):
            if h * w <= free[r] and len(placed[r]) < images_per_row:
                placed[r].append(i)
                free[r] -= h * w
                break
        else:
            left.append(i)
    return placed, left


def row_layout(rows_of_grids: Sequence[Sequence[Grid]], row_tokens: int,
               images_per_row: int) -> Dict[str, np.ndarray]:
    """segment_ids, positions, grid_hw and label_mask for rows whose images
    (their grids, in packing order) are already chosen."""
    r = len(rows_of_grids)
    seg = np.zeros((r, row_tokens), np.int32)
    pos = np.zeros((r, row_tokens, 2), np.int32)
    hw = np.zeros((r, images_per_row, 2), np.int32)
    mask = np.zeros((r, images_per_row), np.float32)
    for i, grids in enumerate(rows_of_grids):
        assert len(grids) <= images_per_row, (len(grids), images_per_row)
        at = 0
        for s, (h, w) in enumerate(grids):
            n = h * w
            assert at + n <= row_tokens, (
                f"row {i}: {at + n} tokens exceed the row's {row_tokens}")
            seg[i, at:at + n] = s + 1
            pos[i, at:at + n, 0] = np.repeat(np.arange(h), w)
            pos[i, at:at + n, 1] = np.tile(np.arange(w), h)
            hw[i, s] = (h, w)
            mask[i, s] = 1.0
            at += n
    return {"segment_ids": seg, "positions": pos, "grid_hw": hw,
            "label_mask": mask}


def pack_batch(grids: Sequence[Grid], labels: Sequence[int],
               patches: Optional[Sequence[np.ndarray]], *, rows: int,
               row_tokens: int, images_per_row: int, patch_dim: int,
               ) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
    """First-fit pack images (their grids, labels and cut patches; patches
    None = zero pixels) into a batch. Returns (the arrays above, counts:
    `tokens` valid, `padding_tokens`, `images` placed, `left` unplaced)."""
    placed, left = first_fit(grids, rows, row_tokens, images_per_row)
    batch = row_layout([[grids[i] for i in row] for row in placed],
                       row_tokens, images_per_row)
    batch["patches"] = np.zeros((rows, row_tokens, patch_dim), np.uint8)
    batch["label"] = np.zeros((rows, images_per_row), np.int32)
    for r, row in enumerate(placed):
        at = 0
        for s, i in enumerate(row):
            n = grids[i][0] * grids[i][1]
            if patches is not None:
                batch["patches"][r, at:at + n] = patches[i]
            batch["label"][r, s] = labels[i]
            at += n
    tokens = int((batch["segment_ids"] > 0).sum())
    return batch, {"tokens": tokens,
                   "padding_tokens": rows * row_tokens - tokens,
                   "images": sum(len(row) for row in placed), "left": left}


def document_layout(rows_of_lengths: Sequence[Sequence[int]], row_tokens: int,
                    docs_per_row: int) -> Dict[str, np.ndarray]:
    """segment_ids and positions for rows whose documents (their lengths, in
    packing order) are already chosen. No document is ever split."""
    r = len(rows_of_lengths)
    seg = np.zeros((r, row_tokens), np.int32)
    pos = np.zeros((r, row_tokens), np.int32)
    for i, lengths in enumerate(rows_of_lengths):
        assert len(lengths) <= docs_per_row, (len(lengths), docs_per_row)
        at = 0
        for s, n in enumerate(lengths):
            assert n >= 1 and at + n <= row_tokens, (
                f"row {i}: {at + n} tokens exceed the row's {row_tokens}")
            seg[i, at:at + n] = s + 1
            pos[i, at:at + n] = np.arange(n)
            at += n
    return {"segment_ids": seg, "positions": pos}


def pack_documents(docs: Sequence[np.ndarray], *, rows: int, row_tokens: int,
                   docs_per_row: int
                   ) -> Tuple[Dict[str, np.ndarray], Dict[str, object]]:
    """First-fit pack documents (arrays of token ids) into a batch. Returns
    (the arrays above, counts: `tokens` valid, `padding_tokens`, `documents`
    placed, `left` unplaced)."""
    placed, left = first_fit([(len(d), 1) for d in docs], rows, row_tokens,
                             docs_per_row)
    batch = document_layout([[len(docs[i]) for i in row] for row in placed],
                            row_tokens, docs_per_row)
    batch["tokens"] = np.zeros((rows, row_tokens), np.int32)
    for r, row in enumerate(placed):
        at = 0
        for i in row:
            batch["tokens"][r, at:at + len(docs[i])] = docs[i]
            at += len(docs[i])
    tokens = int((batch["segment_ids"] > 0).sum())
    return batch, {"tokens": tokens,
                   "padding_tokens": rows * row_tokens - tokens,
                   "documents": sum(len(row) for row in placed), "left": left}
