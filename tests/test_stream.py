"""Streaming data plane (vitax/data/stream/): container round-trip, per-host
disjointness, epoch-seeded shuffle determinism, mid-epoch cursor resume
(loader-level exact-record-set and full kill-and-resume through train()),
native-vs-PIL decode parity for the serve path, the stream_read fault drill,
and the ImageFolder-equivalence guard (streaming and directory-scan pipelines
deliver identical sample sets per epoch).
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest
from PIL import Image

import jax

from vitax import faults
from vitax.config import Config
from vitax.data.loader import LoaderWorkerError
from vitax.data.stream.format import (MAGIC, ShardFormatError, ShardReader,
                                      ShardWriter, load_split_meta)
from vitax.data.stream.sampler import StreamSampler, assign_shards

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_N = 32          # per split sizes are divisible by the global batch so
VAL_N = 16            # every record is consumed each epoch (drop_last == nothing)
BATCH = 8
SEED = 3


def _make_imagefolder(root, n_per_class, classes=("cat", "dog"), seed=0,
                      size=40):
    """Tiny ImageFolder tree of unique random JPEGs (pixels identify records)."""
    rng = np.random.default_rng(seed)
    for cls in classes:
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.integers(0, 255, (size, size + 4, 3), np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"img_{i:03d}.jpg"),
                                      quality=90)


@pytest.fixture(scope="module")
def data_dirs(tmp_path_factory):
    """(imagefolder_root, shard_root) with train/ + val/ splits, packed small
    enough that each split spans several shards."""
    src = tmp_path_factory.mktemp("imagefolder")
    dst = tmp_path_factory.mktemp("shards")
    _make_imagefolder(str(src / "train"), TRAIN_N // 2, seed=1)
    _make_imagefolder(str(src / "val"), VAL_N // 2, seed=2)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import make_shards
    finally:
        sys.path.pop(0)
    for split in ("train", "val"):
        make_shards.pack_split(str(src / split), str(dst / split),
                               shard_size_mb=0.01, quiet=True)
    return str(src), str(dst)


def _tiny_cfg(**kw):
    base = dict(
        image_size=16, patch_size=8, embed_dim=32, num_heads=2, num_blocks=2,
        num_classes=4, batch_size=BATCH, dtype="float32", lr=1e-3,
        warmup_steps=2, clip_grad_norm=1.0, seed=SEED, num_workers=2,
    )
    base.update(kw)
    return Config(**base).validate()


def _batch_hashes(batch):
    """One hash per (image, label) sample of a host batch dict."""
    images = np.asarray(batch["image"])
    labels = np.asarray(batch["label"])
    return [hashlib.sha1(images[i].tobytes()
                         + int(labels[i]).to_bytes(4, "little")).hexdigest()
            for i in range(images.shape[0])]


def _build_stream(cfg, split="train"):
    from vitax.parallel.mesh import build_mesh
    from vitax.data.stream import build_stream_datasets
    mesh = build_mesh(cfg)
    train_ds, train_loader, val_ds, val_loader = build_stream_datasets(cfg,
                                                                       mesh)
    if split == "train":
        val_loader.close()
        return train_ds, train_loader
    train_loader.close()
    return val_ds, val_loader


# --- container format ------------------------------------------------------


def test_writer_reader_round_trip(data_dirs):
    """Every payload byte and label comes back exactly, in listing order,
    across shard boundaries."""
    src, dst = data_dirs
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from make_shards import list_imagefolder
    finally:
        sys.path.pop(0)
    classes, samples = list_imagefolder(os.path.join(src, "train"))
    reader = ShardReader(os.path.join(dst, "train"))
    assert len(reader.shards) > 1, "fixture should span multiple shards"
    got = []
    for sid in range(len(reader.shards)):
        got.extend(reader.iter_shard(sid))
    assert len(got) == len(samples) == TRAIN_N
    for (payload, label), (path, want_label) in zip(got, samples):
        with open(path, "rb") as f:
            assert payload == f.read()
        assert label == want_label
    meta = reader.meta
    assert meta["classes"] == classes
    assert meta["num_records"] == TRAIN_N
    reader.close()


def test_reader_rejects_torn_shard(tmp_path):
    split = tmp_path / "train"
    writer = ShardWriter(str(split))
    writer.add(b"payload-bytes", 1)
    writer.close()
    reader = ShardReader(str(split))
    assert reader.read_record(0, 0) == (b"payload-bytes", 1)
    reader.close()
    # corrupt the magic -> loud format error, not garbage pixels
    shard_path = split / reader.shards[0]["name"]
    data = shard_path.read_bytes()
    shard_path.write_bytes(b"X" * len(MAGIC) + data[len(MAGIC):])
    reader2 = ShardReader(str(split))
    with pytest.raises(ShardFormatError, match="bad magic"):
        reader2.read_record(0, 0)
    reader2.close()


def test_missing_meta_is_loud(tmp_path):
    with pytest.raises(FileNotFoundError, match="make_shards"):
        load_split_meta(str(tmp_path))


def test_make_shards_cli(tmp_path, data_dirs):
    src, _ = data_dirs
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import make_shards
    finally:
        sys.path.pop(0)
    rc = make_shards.main(["--src", src, "--dst", str(tmp_path / "out"),
                           "--shard_size_mb", "0.01"])
    assert rc == 0
    for split in ("train", "val"):
        meta = load_split_meta(str(tmp_path / "out" / split))
        assert meta["num_records"] == (TRAIN_N if split == "train" else VAL_N)
    with pytest.raises(SystemExit):
        make_shards.main(["--src", src, "--dst", str(tmp_path / "bad"),
                          "--shard_size_mb", "0"])


# --- sampler: disjointness, determinism, cursor ----------------------------


def test_two_process_disjointness(data_dirs):
    """Fake 2-process topology: shard assignment and the per-epoch record
    streams are disjoint and jointly cover the shard set (ShardedSampler
    contract at shard granularity)."""
    _, dst = data_dirs
    meta = load_split_meta(os.path.join(dst, "train"))
    s0 = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                       process_index=0, process_count=2)
    s1 = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                       process_index=1, process_count=2)
    assert set(s0.my_shards).isdisjoint(s1.my_shards)
    assert sorted(s0.my_shards + s1.my_shards) == list(
        range(len(meta["shards"])))
    assert s0.steps_per_epoch == s1.steps_per_epoch
    for epoch in (1, 2):
        g0 = {s0.global_id(s, r)
              for s, r in s0.epoch_entries(epoch).reshape(-1, 2)}
        g1 = {s1.global_id(s, r)
              for s, r in s1.epoch_entries(epoch).reshape(-1, 2)}
        assert g0.isdisjoint(g1)
    counts = [int(s["records"]) for s in meta["shards"]]
    for world in (2, 3, 4):
        hosts = assign_shards(counts, world)
        flat = sorted(i for h in hosts for i in h)
        assert flat == list(range(len(counts)))


def test_epoch_shuffle_determinism(data_dirs):
    """Same (seed, epoch) -> identical plan; different epoch reshuffles both
    the shard order and the within-shard record order."""
    _, dst = data_dirs
    meta = load_split_meta(os.path.join(dst, "train"))
    s = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                      process_index=0, process_count=1)
    twin = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                         process_index=0, process_count=1)
    assert np.array_equal(s.epoch_entries(1), twin.epoch_entries(1))
    assert not np.array_equal(s.epoch_entries(1), s.epoch_entries(2))
    assert s.shard_order(1) != s.shard_order(2) or not np.array_equal(
        s.record_order(1, s.my_shards[0]), s.record_order(2, s.my_shards[0]))
    # both epochs cover the same record SET (a permutation, not a resample)
    ids1 = sorted(s.global_id(a, b)
                  for a, b in s.epoch_entries(1).reshape(-1, 2))
    ids2 = sorted(s.global_id(a, b)
                  for a, b in s.epoch_entries(2).reshape(-1, 2))
    assert ids1 == ids2 == list(range(TRAIN_N))
    noshuffle = StreamSampler(meta, BATCH, shuffle=False, seed=SEED,
                              process_index=0, process_count=1)
    flat = noshuffle.epoch_entries(1).reshape(-1, 2)
    assert [noshuffle.global_id(a, b) for a, b in flat] == list(range(TRAIN_N))


def test_cursor_roundtrip_and_drift(data_dirs):
    _, dst = data_dirs
    meta = load_split_meta(os.path.join(dst, "train"))
    s = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                      process_index=0, process_count=1)
    plan = s.epoch_entries(2)
    for step in range(s.steps_per_epoch + 1):
        cur = s.cursor_for_step(2, step)
        s.check_cursor(cur, 2, step)  # self-consistent
        if step < s.steps_per_epoch:
            # the cursor names exactly the next record the plan serves
            order = s.shard_order(2)
            shard = order[cur["shard_cursor"]]
            rec = s.record_order(2, shard)[cur["record_offset"]]
            assert plan[step][0][0] == shard and plan[step][0][1] == rec
    drifted = dict(s.cursor_for_step(2, 1))
    drifted["record_offset"] += 1
    with pytest.raises(RuntimeError, match="cursor mismatch"):
        s.check_cursor(drifted, 2, 1)
    # another host's cursor is not comparable -> ignored, not a false alarm
    other = dict(s.cursor_for_step(2, 1))
    other["process_index"] = 7
    other["record_offset"] += 1
    s.check_cursor(other, 2, 1)


# --- loader: resume equivalence, ImageFolder guard -------------------------


def test_midepoch_resume_exact_records(devices8, data_dirs):
    """Kill-mid-epoch-and-resume at loader level: consume k batches, "die",
    rebuild everything from scratch (a new process would), verify the stored
    cursor, resume at start_step=k — union(seen-before, seen-after) is
    exactly one full epoch with no duplicates."""
    _, dst = data_dirs
    cfg = _tiny_cfg(data_dir=dst, data_format="stream", fake_data=False)
    epoch, kill_at = 2, 2

    _, loader = _build_stream(cfg)
    full = []
    for batch in loader.epoch(epoch):
        full.extend(_batch_hashes(batch))
    loader.close()
    assert len(full) == len(set(full)) == TRAIN_N  # divisible: full coverage

    _, loader1 = _build_stream(cfg)  # the run that gets killed
    before = []
    it = loader1.epoch(epoch)
    for _ in range(kill_at):
        before.extend(_batch_hashes(next(it)))
    cursor = loader1.cursor_for_step(epoch, kill_at)  # what the sidecar keeps
    it.close()
    loader1.close()

    _, loader2 = _build_stream(cfg)  # the resumed run (fresh build)
    loader2.check_cursor(cursor, kill_at)  # shard set unchanged -> passes
    after = []
    for batch in loader2.epoch(epoch, start_step=kill_at):
        after.extend(_batch_hashes(batch))
    loader2.close()

    assert set(before).isdisjoint(after), "resume replayed seen records"
    assert sorted(before + after) == sorted(full), (
        "union(before-kill, after-resume) != one full epoch")
    assert before == full[:len(before)] and after == full[len(before):]


def test_stream_matches_imagefolder_samples(devices8, data_dirs):
    """The equivalence guard: for the same (seed, epoch), streaming and
    ImageFolder deliver IDENTICAL sample sets — same decoded+augmented
    pixels, same labels — differing only in order (the two samplers shuffle
    differently). Val (no shuffle) matches in exact order."""
    from vitax.parallel.mesh import build_mesh
    from vitax.data.loader import ShardedLoader, ShardedSampler
    from vitax.data.imagefolder import ImageFolderDataset
    from vitax.data.transforms import train_transform, val_transform
    src, dst = data_dirs
    cfg = _tiny_cfg(data_dir=dst, data_format="stream", fake_data=False)
    mesh = build_mesh(cfg)

    _, s_loader = _build_stream(cfg)
    stream_set = []
    for batch in s_loader.epoch(1):
        stream_set.extend(_batch_hashes(batch))
    s_loader.close()

    folder_ds = ImageFolderDataset(
        os.path.join(src, "train"),
        train_transform(cfg.image_size, cfg.seed, normalize=False))
    folder_loader = ShardedLoader(
        folder_ds, ShardedSampler(len(folder_ds), BATCH, shuffle=True,
                                  seed=cfg.seed), mesh, num_workers=2)
    folder_set = []
    for batch in folder_loader.epoch(1):
        folder_set.extend(_batch_hashes(batch))
    folder_loader.close()
    assert sorted(stream_set) == sorted(folder_set)
    assert len(set(stream_set)) == TRAIN_N

    _, sv_loader = _build_stream(cfg, split="val")
    stream_val = []
    for batch in sv_loader.epoch(0):
        stream_val.extend(_batch_hashes(batch))
    sv_loader.close()
    val_ds = ImageFolderDataset(
        os.path.join(src, "val"),
        val_transform(cfg.image_size, normalize=False))
    val_loader = ShardedLoader(
        val_ds, ShardedSampler(len(val_ds), BATCH, shuffle=False,
                               seed=cfg.seed), mesh, num_workers=2)
    folder_val = []
    for batch in val_loader.epoch(0):
        folder_val.extend(_batch_hashes(batch))
    val_loader.close()
    assert stream_val == folder_val  # no shuffle: exact order too


# --- native decode parity (serve satellite) --------------------------------


def test_native_bytes_decode_parity(tmp_path):
    """The in-memory native pipeline is BITWISE-identical to the file-based
    one (same bytes, same params) — the property that lets shard records and
    /predict bodies reuse the training decode path."""
    from vitax.data import native
    from vitax.data.transforms import val_transform
    if not native.mem_available():
        pytest.skip("native memory-source API unavailable")
    rng = np.random.default_rng(7)
    arr = rng.integers(0, 255, (50, 62, 3), np.uint8)
    path = str(tmp_path / "img.jpg")
    Image.fromarray(arr).save(path, quality=92)
    with open(path, "rb") as f:
        raw = f.read()
    assert native.is_jpeg_bytes(raw)
    assert native.jpeg_size_bytes(raw) == native.jpeg_size(path) == (62, 50)
    t = val_transform(16, normalize=False)
    params = t.native_params(0, 0, 0)
    from_bytes = native.process_bytes(raw, params, 16, t.resize_to,
                                      normalize=False)
    from_file = native.process_file(path, params, 16, t.resize_to,
                                    normalize=False)
    assert from_bytes is not None and from_file is not None
    assert np.array_equal(from_bytes, from_file)
    # batch mem call agrees with per-item mem calls
    batch, failed = native.process_batch_bytes([raw, raw], [params, params],
                                               16, t.resize_to, n_threads=2,
                                               normalize=False)
    assert failed == []
    assert np.array_equal(batch[0], from_bytes)
    assert np.array_equal(batch[1], from_bytes)


def test_serve_decode_native_vs_pil(tmp_path):
    """serve decode_image_bytes: JPEG bodies take the native resize path
    (within the established native-vs-PIL resample tolerance of the training
    pipeline), non-JPEG bodies fall back to PIL exactly."""
    from vitax.data import native
    from vitax.data.transforms import val_transform
    from vitax.serve.server import decode_image_bytes
    rng = np.random.default_rng(11)
    arr = rng.integers(0, 255, (48, 56, 3), np.uint8)
    jpg = str(tmp_path / "img.jpg")
    Image.fromarray(arr).save(jpg, quality=92)
    with open(jpg, "rb") as f:
        raw = f.read()
    t = val_transform(16, normalize=False)
    out = decode_image_bytes(raw, t)
    assert out.shape == (16, 16, 3) and out.dtype == np.uint8
    with Image.open(jpg) as img:
        pil = t(img.convert("RGB"))
    if native.mem_available():
        # bitwise vs the file-based native path training eval uses...
        params = t.native_params(0, 0, 0)
        want = native.process_file(jpg, params, 16, t.resize_to,
                                   normalize=False)
        assert np.array_equal(out, want)
        # ...and within the PIL resample tolerance (test_native.py LSB bound)
        diff = np.abs(out.astype(np.int32) - pil.astype(np.int32))
        assert diff.mean() <= 255 * 0.018
    else:
        assert np.array_equal(out, pil)
    # PNG body: PIL fallback, exact
    png = str(tmp_path / "img.png")
    Image.fromarray(arr).save(png)
    with open(png, "rb") as f:
        raw_png = f.read()
    assert not native.is_jpeg_bytes(raw_png)
    with Image.open(png) as img:
        want_png = t(img.convert("RGB"))
    assert np.array_equal(decode_image_bytes(raw_png, t), want_png)


# --- fault drill -----------------------------------------------------------


def test_stream_read_fault_drill(data_dirs):
    """stream_read oserror x2 exhausts the single retry and surfaces
    LoaderWorkerError carrying the shard path; x1 is absorbed by the retry."""
    _, dst = data_dirs
    split = os.path.join(dst, "train")
    try:
        faults.install(json.dumps(
            {"site": "stream_read", "at": 1, "times": 2,
             "action": "oserror"}))
        reader = ShardReader(split)
        with pytest.raises(LoaderWorkerError) as exc_info:
            reader.read_record(0, 0)
        assert reader.shards[0]["name"] in str(exc_info.value)
        reader.close()
    finally:
        faults.uninstall()
    try:
        faults.install(json.dumps(
            {"site": "stream_read", "at": 1, "times": 1,
             "action": "oserror"}))
        reader = ShardReader(split)
        payload, label = reader.read_record(0, 0)  # retry absorbed it
        assert len(payload) > 0
        reader.close()
    finally:
        faults.uninstall()


def test_stream_read_fault_through_loader(devices8, data_dirs):
    """The same drill through the producer thread: the consumer gets a
    LoaderWorkerError with the worker traceback, not a silent stall."""
    _, dst = data_dirs
    cfg = _tiny_cfg(data_dir=dst, data_format="stream", fake_data=False)
    try:
        faults.install(json.dumps(
            {"site": "stream_read", "at": 1, "times": 2,
             "action": "oserror"}))
        _, loader = _build_stream(cfg)
        with pytest.raises(LoaderWorkerError, match="stream worker failed"):
            for _ in loader.epoch(1):
                pass
        loader.close()
    finally:
        faults.uninstall()


# --- config + tooling satellites -------------------------------------------


def test_config_validation(data_dirs):
    _, dst = data_dirs
    with pytest.raises(AssertionError, match="stream_prefetch"):
        _tiny_cfg(stream_prefetch=0)
    with pytest.raises(AssertionError, match="data_format"):
        _tiny_cfg(data_format="webdataset")
    with pytest.raises(AssertionError, match="fake_data"):
        _tiny_cfg(data_format="stream", fake_data=True)
    with pytest.raises(AssertionError, match="shard root"):
        _tiny_cfg(data_format="stream", data_dir="")
    cfg = _tiny_cfg(data_format="stream", data_dir=dst, stream_prefetch=3)
    assert cfg.stream_prefetch == 3
    # the CLI surface carries both flags
    from vitax.config import build_parser
    ns = build_parser().parse_args(
        ["--data_format", "stream", "--stream_prefetch", "4"])
    assert ns.data_format == "stream" and ns.stream_prefetch == 4


def test_metrics_report_input_bound(tmp_path, capsys):
    """--json gains input_bound: the fraction of steps whose data wait
    exceeds 10% of the step — the streaming plane's acceptance metric."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for i in range(1, 11):
            f.write(json.dumps({
                "schema": 1, "time": 1000.0 + i, "step": i, "epoch": 1,
                "step_in_epoch": i, "loss": 2.0, "lr": 1e-3,
                "sec_per_iter": 1.0,
                # 3 of 10 steps input-bound (wait > 10% of the step)
                "data_wait_s": 0.5 if i <= 3 else 0.01}) + "\n")
    summary = metrics_report.summarize(str(path))
    assert summary["input_bound"] == pytest.approx(0.3)
    metrics_report.print_human(summary)
    out = capsys.readouterr().out
    assert "input-bound steps" in out and "30.0%" in out
    # with the loop's marks, a wait that the fence after it outlasts is time
    # the run-ahead hid from the device (two steps a record: waits of 0.3 s
    # against fences of 1.0 s in all): only a wait with no backlog behind it
    # counts (records 4 and 5)
    with open(path, "w") as f:
        for i in range(1, 6):
            fence = 0.0 if i >= 4 else 1.0
            f.write(json.dumps({
                "schema": 1, "time": 1000.0 + 2 * i, "step": 2 * i,
                "epoch": 1, "step_in_epoch": 2 * i, "loss": 2.0, "lr": 1e-3,
                "sec_per_iter": 1.0, "data_wait_s": 0.3,
                "loop_marks": [
                    [2 * i - 1] + [1000.0 + 2 * i + t
                                   for t in (0.0, 0.3, 0.35, 0.4, 0.4)],
                    [2 * i] + [1001.0 + 2 * i + t
                               for t in (0.0, 0.3, 0.35, 0.4, 0.4 + fence)]],
            }) + "\n")
    assert metrics_report.summarize(str(path))["input_bound"] \
        == pytest.approx(0.4)
    # a healthy run reports 0.0, and human mode drops the (!!) flag
    with open(path, "w") as f:
        f.write(json.dumps({
            "schema": 1, "time": 1.0, "step": 1, "epoch": 1,
            "step_in_epoch": 1, "loss": 2.0, "lr": 1e-3,
            "sec_per_iter": 1.0, "data_wait_s": 0.0}) + "\n")
    healthy = metrics_report.summarize(str(path))
    assert healthy["input_bound"] == 0.0
    metrics_report.print_human(healthy)
    assert "(!!)" not in capsys.readouterr().out


# --- end-to-end through train() --------------------------------------------


def test_step_program_identical_stream_vs_imagefolder(devices8, data_dirs):
    """The input pipeline is host-side only: the compiled train-step program
    is bit-identical between --data_format stream and imagefolder configs."""
    from test_train_smoke import build_train_objects
    _, dst = data_dirs
    cfg_folder = _tiny_cfg()
    cfg_stream = _tiny_cfg(data_format="stream", data_dir=dst,
                           stream_prefetch=4)
    mesh, state, step_fn, _ = build_train_objects(cfg_folder)
    _, state2, step_fn2, _ = build_train_objects(cfg_stream)
    from test_train_smoke import random_batch
    batch = random_batch(cfg_folder, mesh)
    rng = jax.random.key(0)
    text1 = step_fn.lower(state, batch, rng).as_text()
    text2 = step_fn2.lower(state2, batch, rng).as_text()
    assert text1 == text2


def test_train_e2e_stream(devices8, data_dirs, tmp_path):
    """--data_format stream trains end-to-end through the full train()
    orchestration (epoch accounting, telemetry data_wait_s wiring, eval over
    the streaming val split, checkpoint save)."""
    from vitax.train.loop import train
    _, dst = data_dirs
    metrics_dir = str(tmp_path / "metrics")
    cfg = _tiny_cfg(
        data_format="stream", data_dir=dst, fake_data=False, num_epochs=1,
        log_step_interval=1, ckpt_dir=str(tmp_path / "ckpt"),
        ckpt_epoch_interval=1, test_epoch_interval=1, eval_max_batches=1,
        metrics_dir=metrics_dir)
    state = train(cfg)
    assert int(jax.device_get(state.step)) == TRAIN_N // BATCH
    assert os.path.isdir(os.path.join(str(tmp_path / "ckpt"), "epoch_1"))
    records = []
    with open(os.path.join(metrics_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "step" in rec and not rec.get("kind"):
                records.append(rec)
    assert records and all("data_wait_s" in r for r in records)


def test_kill_midepoch_and_resume_e2e(devices8, data_dirs, tmp_path):
    """The full story: SIGTERM mid-epoch -> committed checkpoint whose
    sidecar carries the (epoch, shard_cursor, record_offset) cursor ->
    auto-resume verifies the cursor and consumes exactly the not-yet-seen
    steps (total step count proves no batch was repeated or skipped)."""
    import signal
    from vitax.checkpoint.orbax_io import load_resume_step, load_stream_cursor
    from vitax.train import preempt
    from vitax.train.loop import train
    _, dst = data_dirs
    ckpt = str(tmp_path / "ckpt")
    steps_per_epoch = TRAIN_N // BATCH

    preempt.reset()
    assert preempt.install()
    os.kill(os.getpid(), signal.SIGTERM)
    try:
        cfg = _tiny_cfg(
            data_format="stream", data_dir=dst, fake_data=False,
            num_epochs=2, log_step_interval=99, ckpt_dir=ckpt,
            ckpt_epoch_interval=99, test_epoch_interval=99,
            eval_max_batches=1)
        state = train(cfg)
        assert int(jax.device_get(state.step)) == 1  # killed after one step
    finally:
        preempt.uninstall()
        preempt.reset()

    assert load_resume_step(ckpt, 1) == 1
    cursor = load_stream_cursor(ckpt, 1)
    assert cursor is not None
    assert cursor["epoch"] == 1 and cursor["step"] == 1
    # the sidecar cursor is exactly what the epoch plan derives for step 1
    meta = load_split_meta(os.path.join(dst, "train"))
    sampler = StreamSampler(meta, BATCH, shuffle=True, seed=SEED,
                            process_index=0, process_count=1)
    sampler.check_cursor(cursor, 1, 1)

    cfg2 = _tiny_cfg(
        data_format="stream", data_dir=dst, fake_data=False, num_epochs=2,
        resume_epoch=-1, log_step_interval=99, ckpt_dir=ckpt,
        ckpt_epoch_interval=99, test_epoch_interval=99, eval_max_batches=1)
    state2 = train(cfg2)
    # 1 step before the kill + the rest of epoch 1 + all of epoch 2
    assert int(jax.device_get(state2.step)) == 2 * steps_per_epoch


def test_epoch_rounded_resume_reruns_the_partial_epoch(devices8, data_dirs,
                                                       tmp_path, capsys):
    """Loop integration of the EPOCH-ROUNDED elastic path (the planner alone
    is covered in tests/test_control.py): a mid-epoch stream checkpoint whose
    sidecar records a different topology must RE-ENTER the checkpointed
    epoch from step 0 — re-running the partial epoch as announced — not
    treat resume_step=0 as 'epoch done' and skip its remaining records."""
    import signal
    from vitax.train import preempt
    from vitax.train.loop import train
    _, dst = data_dirs
    ckpt = str(tmp_path / "ckpt")
    steps_per_epoch = TRAIN_N // BATCH

    # 1) SIGTERM mid-epoch: commits a step-1 checkpoint with a stream cursor
    preempt.reset()
    assert preempt.install()
    os.kill(os.getpid(), signal.SIGTERM)
    try:
        cfg = _tiny_cfg(
            data_format="stream", data_dir=dst, fake_data=False,
            num_epochs=2, log_step_interval=99, ckpt_dir=ckpt,
            ckpt_epoch_interval=99, test_epoch_interval=99,
            eval_max_batches=1)
        state = train(cfg)
        assert int(jax.device_get(state.step)) == 1
    finally:
        preempt.uninstall()
        preempt.reset()

    # 2) simulate the topology change: rewrite the sidecar's recorded
    # process_count (this single-process harness cannot really re-launch
    # under N=2; the loop only ever sees the sidecar, so this exercises
    # exactly the rounded branch a real N->M restart takes)
    sidecar = os.path.join(ckpt, "epoch_1.resume.json")
    with open(sidecar) as f:
        meta = json.load(f)
    assert meta["step_in_epoch"] == 1 and "stream_cursor" in meta
    meta["process_count"] = 2
    with open(sidecar, "w") as f:
        json.dump(meta, f)

    # 3) auto-resume under 1 process: cursor invalidated -> epoch-rounded
    cfg2 = _tiny_cfg(
        data_format="stream", data_dir=dst, fake_data=False, num_epochs=2,
        resume_epoch=-1, log_step_interval=99, ckpt_dir=ckpt,
        ckpt_epoch_interval=99, test_epoch_interval=99, eval_max_batches=1)
    state2 = train(cfg2)
    out = capsys.readouterr().out
    assert "epoch-rounding the resume (re-running 1 mid-epoch steps)" in out
    assert "epoch-rounded resume: re-running epoch 1 from step 1" in out
    # 1 pre-kill step + ALL of epoch 1 re-run from its boundary + epoch 2
    # (before the fix the loop started at epoch 2 and this read
    # 1 + steps_per_epoch: the checkpointed epoch's remainder was skipped)
    assert int(jax.device_get(state2.step)) == 1 + 2 * steps_per_epoch
