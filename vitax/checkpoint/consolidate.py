"""Offline checkpoint consolidation: sharded epoch checkpoint -> one .npz file.

Parity with `python3 -m torch_xla.distributed.fsdp.consolidate_sharded_ckpts`
(cited at reference utils.py:27-29): produces a single-file, framework-neutral
export of the full (unsharded) parameters for serving/analysis.

Unlike the reference's tool, no shard metadata is needed — Orbax checkpoints are
already topology-independent; this tool simply restores on host and flattens.

The export is the direct input to the serving stack:
`vitax.serve.InferenceEngine.from_npz` restores the exact param tree from it
via the shared `flatten_tree` / `unflatten_tree` helpers below (see the
README "Serving" section and vitax/serve/engine.py).

Usage:
    python -m vitax.checkpoint.consolidate --ckpt_dir /path --epoch 10 --out full.npz
    python -m vitax.checkpoint.consolidate ... --params_only
    python -m vitax.checkpoint.consolidate ... --dtype bfloat16   # half-size export
    python -m vitax.checkpoint.consolidate ... --dtype int8       # quantized export
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, Optional, Tuple

import numpy as np

from vitax.checkpoint.orbax_io import epoch_ckpt_path

# npz has no native bfloat16: bf16 arrays are stored as uint16 bit-views and
# their keys recorded under this manifest entry, so load_npz can restore the
# exact dtype. The key cannot collide with a param path ("/"-joined names).
BF16_MANIFEST_KEY = "__bfloat16_keys__"

# --dtype int8/float8_e4m3 manifest: a JSON document under this key records
# which leaves were quantized, keyed BY QUANTIZED DTYPE:
#     {"schema": 1, "dtypes": {"int8": ["params/head/kernel", ...]}}
# Each quantized leaf's per-output-channel float32 scales live beside it at
# QUANT_SCALE_PREFIX + key. Neither key can collide with a param path
# ("/"-joined names never start with "__"). fp8 leaves are stored as uint8
# bit-views (npz has no fp8 dtype — same trick as the bf16 uint16 views) and
# restored by dtype from this manifest.
QUANT_MANIFEST_KEY = "__quant__"
QUANT_SCALE_PREFIX = "__scale__/"
QUANT_SCHEMA_VERSION = 1
QUANT_DTYPES = ("int8", "float8_e4m3")

# Leaves never quantized, by path name: the MoE router and every LayerNorm —
# the same names vitax/parallel/sharding.py KEEP_F32_PARAMS keeps out of the
# bf16 comm cast, MINUS "head": the head kernel is a full (d, num_classes)
# matmul weight and dequantizes back to f32 at use, so int8 storage does not
# change where its compute happens (tests/test_quant.py pins the relation to
# KEEP_F32_PARAMS).
QUANT_SKIP_NAMES = ("router", "norm", "norm1", "norm2")

# matmul weight leaf names: Dense/Conv kernels plus the MoE expert matrices
# (vitax/models/moe.py w1/w2). Biases, LN params, pos_embed and every other
# 1-D/scalar leaf stay f32.
QUANT_WEIGHT_NAMES = ("kernel", "w1", "w2")


def _is_float(v: np.ndarray) -> bool:
    """Floating leaves only — integer/bool leaves (step counters, already-
    quantized int8 weights) must never be touched by a --dtype cast."""
    import ml_dtypes
    return bool(np.issubdtype(v.dtype, np.floating)
                or v.dtype == ml_dtypes.bfloat16)


def should_quantize(key: str, v: np.ndarray) -> bool:
    """Whether a quantized --dtype quantizes this leaf: a 2-D+ floating matmul
    weight (patchify/QKV/proj/MLP/head) not under a skip name."""
    parts = key.split("/")
    return (_is_float(v) and v.ndim >= 2
            and parts[-1] in QUANT_WEIGHT_NAMES
            and not any(p in QUANT_SKIP_NAMES for p in parts))


def _contraction_axes(key: str, ndim: int) -> Tuple[int, ...]:
    """Axes reduced by the absmax scale: everything except the output-channel
    (last) axis and any leading stacking axes — the scan-stacked layer dim of
    block params ("blocks" in the path) and the experts dim of MoE w1/w2 —
    so scales stay per (layer[, expert], out_channel)."""
    parts = key.split("/")
    stack = 1 if "blocks" in parts else 0
    if parts[-1] in ("w1", "w2"):
        stack += 1  # (…, E, in, out): experts are independent matmuls
    return tuple(range(stack, ndim - 1))


def quant_max(dtype: str) -> float:
    """The largest magnitude the quantized dtype represents: 127 for int8,
    the max FINITE fp8 value for float8_e4m3 (240 for ml_dtypes' IEEE-style
    e4m3 — absmax maps onto it exactly, so no leaf element ever rounds to
    inf)."""
    if dtype == "int8":
        return 127.0
    import ml_dtypes
    return float(ml_dtypes.finfo(ml_dtypes.float8_e4m3).max)


def quantize_leaf(key: str, v: np.ndarray,
                  dtype: str = "int8") -> Tuple[np.ndarray, np.ndarray]:
    """Per-output-channel symmetric absmax quantization to int8 or fp8.

    scale = absmax / quant_max(dtype) over the contraction axes (keepdims,
    so dequant is the broadcast `w_q * scale`); int8 rounds to
    [-127, 127], float8_e4m3 rounds to the nearest fp8 value (the mantissa
    rounding IS the quantization — fp8 keeps per-element exponents, so its
    relative error is flat across each channel instead of absolute).
    All-zero channels get scale 1.0 (they quantize and dequantize to 0)."""
    assert dtype in QUANT_DTYPES, dtype
    w = np.asarray(v, dtype=np.float32)
    axes = _contraction_axes(key, w.ndim)
    absmax = np.max(np.abs(w), axis=axes, keepdims=True) if axes else np.abs(w)
    scale = (absmax / quant_max(dtype)).astype(np.float32)
    scale = np.where(scale == 0.0, np.float32(1.0), scale)
    if dtype == "int8":
        q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    else:
        import ml_dtypes
        q = (w / scale).astype(ml_dtypes.float8_e4m3)
    return q, scale


def quantize_flat(flat: Dict[str, np.ndarray], dtype: str = "int8") -> Tuple[
        Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Quantize every eligible leaf of a flat tree to `dtype`.

    Returns (flat with quantized leaves substituted, {key: float32 scales}).
    Ineligible leaves pass through untouched."""
    out, scales = {}, {}
    for k, v in flat.items():
        if should_quantize(k, v):
            out[k], scales[k] = quantize_leaf(k, v, dtype)
        else:
            out[k] = v
    return out, scales


def quant_manifest(scales_keys, dtype: str = "int8") -> str:
    """The dtype-keyed JSON manifest body for a set of quantized keys."""
    assert dtype in QUANT_DTYPES, dtype
    return json.dumps({"schema": QUANT_SCHEMA_VERSION,
                       "dtypes": {dtype: sorted(scales_keys)}})


def parse_quant_manifest(doc: str) -> Dict[str, str]:
    """{key: quantized dtype} from a manifest JSON document (dtype-keyed on
    disk; inverted here because consumers look leaves up by key)."""
    parsed = json.loads(doc)
    assert parsed.get("schema") == QUANT_SCHEMA_VERSION, (
        f"unknown quant manifest schema {parsed.get('schema')!r} "
        f"(this build reads schema {QUANT_SCHEMA_VERSION})")
    out: Dict[str, str] = {}
    for dtype, keys in parsed.get("dtypes", {}).items():
        assert dtype in QUANT_DTYPES, (
            f"quantized dtype {dtype!r} not supported by this build "
            f"(implemented: {QUANT_DTYPES})")
        for k in keys:
            out[k] = dtype
    return out


def flatten_tree(tree, sep: str = "/") -> Dict[str, np.ndarray]:
    """Flatten a (nested-dict) param tree to {"a/b/c": np.ndarray}.

    The inverse of `unflatten_tree`: consolidate writes with this and
    `InferenceEngine.from_npz` reads with that, so the two sides share one
    key convention by construction."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = sep.join(
            str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))
            for p in path)
        out[key] = np.asarray(leaf)
    return out


def unflatten_tree(flat: Dict[str, np.ndarray], sep: str = "/") -> dict:
    """Rebuild the nested dict tree from flatten_tree's "/"-joined keys."""
    tree: dict = {}
    for key, leaf in flat.items():
        parts = key.split(sep)
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def save_npz(out: str, flat: Dict[str, np.ndarray],
             dtype: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Write a flat tree as .npz, optionally casting/quantizing float arrays.

    dtype "bfloat16" halves the export; bf16 has no npz dtype, so those
    arrays are stored as uint16 bit-views plus a key manifest
    (BF16_MANIFEST_KEY) that load_npz uses to restore them exactly.

    dtype "int8" / "float8_e4m3" quantizes every eligible matmul weight
    (should_quantize) per output channel and records the key set under
    QUANT_MANIFEST_KEY with the float32 scales at QUANT_SCALE_PREFIX + key;
    ineligible float leaves stay at their stored dtype, so a quantized
    export of a bf16 tree carries both manifests in one file. fp8 leaves
    have no npz dtype either — they are stored as uint8 bit-views and
    restored by manifest dtype, the same trick as the bf16 uint16 views.
    Casts touch FLOATING leaves only — integer/bool leaves (step counters,
    pre-quantized int8 weights) round-trip exactly under every --dtype."""
    import ml_dtypes
    scales: Dict[str, np.ndarray] = {}
    if dtype in QUANT_DTYPES:
        flat, scales = quantize_flat(flat, dtype)
    elif dtype:
        target = (ml_dtypes.bfloat16 if dtype == "bfloat16"
                  else np.dtype(dtype))
        flat = {k: v.astype(target) if _is_float(v) else v
                for k, v in flat.items()}
    bf16_keys = sorted(k for k, v in flat.items()
                       if v.dtype == ml_dtypes.bfloat16)
    fp8_keys = {k for k, v in flat.items()
                if v.dtype == ml_dtypes.float8_e4m3}
    payload = {k: (v.view(np.uint16) if k in bf16_keys
                   else v.view(np.uint8) if k in fp8_keys else v)
               for k, v in flat.items()}
    if bf16_keys:
        payload[BF16_MANIFEST_KEY] = np.asarray(bf16_keys)
    if scales:
        payload[QUANT_MANIFEST_KEY] = np.asarray(quant_manifest(scales, dtype))
        for k, s in scales.items():
            payload[QUANT_SCALE_PREFIX + k] = s
    np.savez(out, **payload)
    return flat


def load_npz_raw(path: str) -> Tuple[Dict[str, np.ndarray],
                                     Dict[str, np.ndarray],
                                     Dict[str, str]]:
    """Read a save_npz export without dequantizing.

    Returns (flat, scales, manifest): `flat` holds quantized leaves at their
    stored quantized dtype (bf16 and fp8 bit-views restored), `scales` the
    per-key float32 scale arrays, `manifest` {key: quantized dtype} — all
    empty dicts but `flat` for an unquantized file. This is the serving load
    path: InferenceEngine.from_npz device_puts quantized leaves verbatim."""
    import ml_dtypes
    with np.load(path) as data:
        bf16 = (set(str(k) for k in data[BF16_MANIFEST_KEY])
                if BF16_MANIFEST_KEY in data.files else set())
        manifest = (parse_quant_manifest(str(data[QUANT_MANIFEST_KEY]))
                    if QUANT_MANIFEST_KEY in data.files else {})
        flat, scales = {}, {}
        for k in data.files:
            if k in (BF16_MANIFEST_KEY, QUANT_MANIFEST_KEY):
                continue
            if k.startswith(QUANT_SCALE_PREFIX):
                scales[k[len(QUANT_SCALE_PREFIX):]] = data[k]
            elif k in bf16:
                flat[k] = data[k].view(ml_dtypes.bfloat16)
            elif manifest.get(k) == "float8_e4m3":
                flat[k] = data[k].view(ml_dtypes.float8_e4m3)
            else:
                flat[k] = data[k]
        assert set(manifest) == set(scales), (
            f"quant manifest/scale mismatch in {path}: manifest names "
            f"{sorted(set(manifest) ^ set(scales))} without scales (or "
            f"vice versa)")
        return flat, scales, manifest


def load_npz(path: str) -> Dict[str, np.ndarray]:
    """Read a save_npz export back to {key: array}, restoring bf16 views and
    dequantizing int8/fp8 leaves to float32 (key set == the saved tree's;
    generic consumers never see scales). Serving wants the quantized leaves
    verbatim — use load_npz_raw there."""
    flat, scales, manifest = load_npz_raw(path)
    for k in manifest:
        flat[k] = (flat[k].astype(np.float32) * scales[k]).astype(np.float32)
    return flat


def consolidate(ckpt_dir: str, epoch: int, out: str, params_only: bool = True,
                dtype: Optional[str] = None) -> dict:
    import jax
    import orbax.checkpoint as ocp

    from vitax.checkpoint.orbax_io import wait_until_finished
    wait_until_finished()  # same-process async save of this epoch must commit
    path = epoch_ckpt_path(ckpt_dir, epoch)
    # Restore every leaf as a plain numpy array (restore_type=np.ndarray).
    # A targetless restore would instead rebuild the SAVED device mesh from
    # the sharding file — impossible on this host for a checkpoint written
    # by a multi-host run (its device ids don't exist here). Consolidation
    # must work from any single machine regardless of save topology.
    with ocp.PyTreeCheckpointer() as ckptr:
        # metadata() is one StepMetadata object; the saved tree's per-leaf
        # view lives under item_metadata.tree
        meta_tree = ckptr.metadata(path).item_metadata.tree
        restore_args = jax.tree.map(
            lambda _: ocp.RestoreArgs(restore_type=np.ndarray), meta_tree)
        state = ckptr.restore(path, restore_args=restore_args)
    tree = state["params"] if params_only and "params" in state else state
    flat = save_npz(out, flatten_tree(tree), dtype=dtype)
    total = sum(v.size for v in flat.values())
    print(f"consolidated {len(flat)} arrays ({total:,} elements"
          + (f", cast to {dtype}" if dtype else "")
          + f") from {path} -> {out}")
    return flat


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt_dir", type=str, required=True)
    p.add_argument("--epoch", type=int, required=True)
    p.add_argument("--out", type=str, required=True)
    p.add_argument("--full_state", action="store_false", dest="params_only",
                   help="include optimizer state and step, not just params")
    p.add_argument("--dtype", type=str, default=None,
                   choices=["float32", "bfloat16", "int8", "float8_e4m3"],
                   help="cast float arrays for the export (default: keep "
                        "the stored dtype). bfloat16 halves the file — the "
                        "serving engine computes in bf16 anyway "
                        "(vitax/serve/engine.py from_npz). int8/float8_e4m3 "
                        "quantize every matmul weight per output channel "
                        "(symmetric absmax, float32 scales under the "
                        "__quant__ manifest) for ~4x smaller serve weights; "
                        "LN/bias/router leaves stay f32 (see README "
                        "'Quantized serving')")
    args = p.parse_args(argv)
    consolidate(args.ckpt_dir, args.epoch, args.out, args.params_only,
                dtype=args.dtype)


if __name__ == "__main__":
    main()
