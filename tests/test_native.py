"""Native (C++) data-path tests: PIL parity of the libjpeg decode + bicubic
resample pipeline, batch API with fallback, and loader integration.

The native library replaces the reference's DataLoader worker-process decode
(reference run_vit_training.py:65-73 + torchvision transforms :39-55); these
tests pin its numerics to the PIL implementation within 1 uint8 LSB.
"""

import os

import numpy as np
import pytest
from PIL import Image

from vitax.data import native
from vitax.data.imagefolder import ImageFolderDataset
from vitax.data.transforms import train_transform, val_transform

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++/libjpeg)")

# 1 uint8 LSB after normalization: (1/255)/min(std) = 0.0171..., rounded up
LSB_TOL = 0.018


def _save_jpeg(path, w, h, seed=0, quality=95):
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
    Image.fromarray(arr).save(path, quality=quality)


def test_jpeg_size(tmp_path):
    p = str(tmp_path / "x.jpg")
    _save_jpeg(p, 317, 211)
    assert native.jpeg_size(p) == (317, 211)
    assert native.jpeg_size(str(tmp_path / "missing.jpg")) is None


# (512, 1025) pins the resize-shorter rounding: 256*1025/512 = 512.5 must
# round half-to-even (512) like Python round(), not half-away (513)
@pytest.mark.parametrize("w,h", [(400, 300), (180, 523), (224, 224), (97, 101),
                                 (512, 1025)])
def test_val_pipeline_matches_pil(tmp_path, w, h):
    p = str(tmp_path / "x.jpg")
    _save_jpeg(p, w, h, seed=w)
    vt = val_transform(224)
    with Image.open(p) as im:
        ref = vt(im.convert("RGB"))
    out = native.process_file(p, vt.native_params(w, h, 0), 224, vt.resize_to)
    assert out is not None and out.shape == (224, 224, 3)
    assert np.abs(out - ref).max() <= LSB_TOL


def test_train_pipeline_matches_pil(tmp_path):
    p = str(tmp_path / "x.jpg")
    _save_jpeg(p, 400, 300)
    tt = train_transform(224, seed=3)
    tt.set_epoch(2)
    for index in (0, 7, 123):
        with Image.open(p) as im:
            ref = tt(im.convert("RGB"), index=index)
        out = native.process_file(p, tt.native_params(400, 300, index), 224, 0)
        assert out is not None
        assert np.abs(out - ref).max() <= LSB_TOL


def test_train_params_shared_with_pil_path(tmp_path):
    """native_params must consume the SAME rng stream as the PIL __call__ —
    same (crop, flip) decisions for the same (seed, epoch, index)."""
    tt = train_transform(224, seed=11)
    a = tt.native_params(640, 480, 5)
    b = tt.native_params(640, 480, 5)
    assert a == b  # deterministic per (seed, epoch, index)
    tt.set_epoch(1)
    assert tt.native_params(640, 480, 5) != a  # varies across epochs


def test_process_file_corrupt_returns_none(tmp_path):
    p = str(tmp_path / "bad.jpg")
    with open(p, "wb") as f:
        f.write(b"\xff\xd8\xff\xe0 this is not a real jpeg")
    assert native.process_file(p, (1, 0, 0, 0, 0, 0), 224, 256) is None


def test_batch_matches_single_calls(tmp_path):
    paths = []
    vt = val_transform(64)
    for i in range(6):
        p = str(tmp_path / f"{i}.jpg")
        _save_jpeg(p, 100 + 17 * i, 120 + 11 * i, seed=i)
        paths.append(p)
    params = [vt.native_params(0, 0, i) for i in range(6)]
    batch, failed = native.process_batch(paths, params, 64, vt.resize_to, n_threads=3)
    assert failed == []
    for i, p in enumerate(paths):
        single = native.process_file(p, params[i], 64, vt.resize_to)
        np.testing.assert_array_equal(batch[i], single)


def test_batch_reports_failures(tmp_path):
    good = str(tmp_path / "good.jpg")
    bad = str(tmp_path / "bad.jpg")
    _save_jpeg(good, 128, 128)
    with open(bad, "wb") as f:
        f.write(b"nope")
    vt = val_transform(64)
    params = [vt.native_params(0, 0, i) for i in range(2)]
    batch, failed = native.process_batch([good, bad], params, 64, vt.resize_to)
    assert failed == [1]
    assert np.isfinite(batch[0]).all()


def test_uint8_output_matches_device_normalize(tmp_path):
    """Raw-uint8 output + on-device normalization == float output: the
    device_normalize transport optimization must not change numerics."""
    import jax.numpy as jnp
    from vitax.train.step import prepare_images

    p = str(tmp_path / "x.jpg")
    _save_jpeg(p, 200, 150)
    vt = val_transform(64)
    params = vt.native_params(200, 150, 0)
    f32 = native.process_file(p, params, 64, vt.resize_to, normalize=True)
    u8 = native.process_file(p, params, 64, vt.resize_to, normalize=False)
    assert u8.dtype == np.uint8
    on_device = np.asarray(prepare_images(jnp.asarray(u8)))
    np.testing.assert_allclose(on_device, f32, atol=1e-6)
    # float input passes through untouched
    assert prepare_images(jnp.asarray(f32)).dtype == jnp.float32


def test_uint8_pil_and_native_paths_agree(tmp_path):
    root = tmp_path / "train"
    os.makedirs(root / "a")
    _save_jpeg(str(root / "a" / "0.jpg"), 300, 200, seed=1)
    tt = train_transform(64, seed=0, normalize=False)
    ds_native = ImageFolderDataset(str(root), tt, use_native=True)
    ds_pil = ImageFolderDataset(str(root), tt, use_native=False)
    img_n, _ = ds_native[0]
    img_p, _ = ds_pil[0]
    assert img_n.dtype == np.uint8 and img_p.dtype == np.uint8
    assert np.abs(img_n.astype(int) - img_p.astype(int)).max() <= 1  # 1 LSB
    imgs, _ = ds_native.load_batch([0])
    assert imgs.dtype == np.uint8


def test_imagefolder_native_matches_pil_dataset(tmp_path):
    root = tmp_path / "train"
    for cls in ("a", "b"):
        os.makedirs(root / cls)
    _save_jpeg(str(root / "a" / "0.jpg"), 300, 200, seed=1)
    _save_jpeg(str(root / "b" / "0.jpg"), 250, 260, seed=2)
    # non-JPEG falls back to PIL inside the native dataset
    Image.fromarray(np.zeros((90, 90, 3), np.uint8)).save(root / "b" / "1.png")

    tt = train_transform(64, seed=0)
    ds_native = ImageFolderDataset(str(root), tt, use_native=True)
    ds_pil = ImageFolderDataset(str(root), tt, use_native=False)
    assert ds_native.use_native and not ds_pil.use_native
    assert len(ds_native) == 3

    for i in range(3):
        img_n, lbl_n = ds_native[i]
        img_p, lbl_p = ds_pil[i]
        assert lbl_n == lbl_p
        assert np.abs(img_n - img_p).max() <= LSB_TOL

    imgs, labels = ds_native.load_batch([2, 0, 1], n_threads=2)
    assert imgs.shape == (3, 64, 64, 3) and labels.tolist() == [1, 0, 1]
    assert np.abs(imgs[1] - ds_pil[0][0]).max() <= LSB_TOL
    assert np.abs(imgs[0] - ds_pil[2][0]).max() <= LSB_TOL  # the PNG fallback slot


def test_decode_releases_gil(tmp_path):
    """A pure-Python counter thread must keep advancing while the main thread
    runs native decode: ctypes CDLL calls drop the GIL, which is what makes
    the loader's in-process thread pool a valid substitute for the
    reference's DataLoader worker processes (run_vit_training.py:65-73).
    Even on one core, OS timeslicing keeps the counter at a healthy fraction
    of its idle rate (~0.5 measured); a GIL-holding decode pins it near 0."""
    import threading
    import time

    def counter_rate(work, min_time: float = 0.5) -> float:
        """Counts/sec of a pure-Python spin thread while `work()` runs
        repeatedly on the calling thread for >= min_time."""
        box = {"n": 0, "stop": False}

        def spin():
            n = 0
            while not box["stop"]:
                n += 1
            box["n"] = n

        t = threading.Thread(target=spin, daemon=True)
        t.start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < min_time:
            work()
        dt = time.perf_counter() - t0
        box["stop"] = True
        t.join(timeout=10)
        assert not t.is_alive()
        return box["n"] / dt

    paths, params = [], []
    tt = train_transform(224, seed=0)
    for i in range(32):
        p = str(tmp_path / f"{i}.jpg")
        _save_jpeg(p, 350, 300, seed=i)
        paths.append(p)
        params.append(tt.native_params(350, 300, i))

    idle = counter_rate(lambda: time.sleep(0.02), min_time=0.4)
    during = counter_rate(
        lambda: native.process_batch(paths, params, 224, 0, n_threads=1),
        min_time=0.4)
    # 0.15 is deliberately far below the ~0.5 timeslicing expectation to
    # stay robust under CI load; a held GIL measures < 0.01
    assert during / idle > 0.15, (
        f"counter starved during native decode: {during:.0f}/s vs "
        f"{idle:.0f}/s idle — is the GIL being held across the C call?")
