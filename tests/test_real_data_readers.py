"""Real-data format readers: idx(.gz) MNIST and CIFAR-10 python pickles.

Round-1 gap: the real parse paths (`_read_idx`, the CIFAR
pickle branch) were dead code in tests — only the synthetic surrogate ever
ran. These tests write byte-exact fixture files in the standard formats
(IDX magic/dims/payload per Yann LeCun's spec; CIFAR's pickled
``{b'data', b'labels'}`` batches, row-major CHW uint8) and assert the
loaders parse them into the documented NHWC float32 [0,1] + int32 labels.
"""

import gzip
import os
import pickle
import struct
import tarfile

import numpy as np

from pytorch_distributed_training_tutorials_tpu.data.datasets import (
    _read_idx,
    cifar10,
    mnist,
)


def _write_idx_images(path, arr: np.ndarray, compress: bool) -> None:
    """IDX3 (unsigned byte, 3 dims): magic 0x00000803, dims, raw bytes."""
    payload = struct.pack(">I", 0x00000803)
    payload += struct.pack(">III", *arr.shape)
    payload += arr.astype(np.uint8).tobytes()
    opener = gzip.open if compress else open
    with opener(path, "wb") as f:
        f.write(payload)


def _write_idx_labels(path, labels: np.ndarray, compress: bool) -> None:
    """IDX1 (unsigned byte, 1 dim): magic 0x00000801."""
    payload = struct.pack(">I", 0x00000801)
    payload += struct.pack(">I", len(labels))
    payload += labels.astype(np.uint8).tobytes()
    opener = gzip.open if compress else open
    with opener(path, "wb") as f:
        f.write(payload)


def _mnist_fixture(data_dir, n=32, compress=True):
    rng = np.random.Generator(np.random.PCG64(5))
    images = rng.integers(0, 256, (n, 28, 28)).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.uint8)
    ext = ".gz" if compress else ""
    _write_idx_images(
        os.path.join(data_dir, f"train-images-idx3-ubyte{ext}"),
        images, compress,
    )
    _write_idx_labels(
        os.path.join(data_dir, f"train-labels-idx1-ubyte{ext}"),
        labels, compress,
    )
    return images, labels


def test_read_idx_roundtrip(tmp_path):
    arr = np.arange(2 * 3 * 4, dtype=np.uint8).reshape(2, 3, 4)
    p = str(tmp_path / "t-idx3")
    _write_idx_images(p, arr, compress=False)
    np.testing.assert_array_equal(_read_idx(p), arr)
    pgz = str(tmp_path / "t-idx3.gz")
    _write_idx_images(pgz, arr, compress=True)
    np.testing.assert_array_equal(_read_idx(pgz), arr)


def test_mnist_parses_idx_gz_fixture(tmp_path):
    images, labels = _mnist_fixture(str(tmp_path), n=32, compress=True)
    ds = mnist("train", data_dir=str(tmp_path))
    assert not ds.synthetic  # the REAL path ran
    x, y = ds.arrays
    assert x.shape == (32, 28, 28, 1) and x.dtype == np.float32
    assert y.dtype == np.int32
    np.testing.assert_allclose(x[..., 0], images.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(y, labels.astype(np.int32))
    assert 0.0 <= x.min() and x.max() <= 1.0


def test_mnist_parses_uncompressed_idx(tmp_path):
    images, labels = _mnist_fixture(str(tmp_path), n=8, compress=False)
    ds = mnist("train", data_dir=str(tmp_path))
    assert not ds.synthetic
    np.testing.assert_array_equal(ds.arrays[1], labels.astype(np.int32))


def test_mnist_falls_back_synthetic_when_absent(tmp_path):
    ds = mnist("train", data_dir=str(tmp_path / "empty"))
    assert ds.synthetic
    assert ds.arrays[0].shape == (60000, 28, 28, 1)


def _cifar_fixture(data_dir, n_per_batch=8):
    """The real layout: cifar-10-batches-py/data_batch_{1..5} + test_batch,
    each a bytes-keyed pickle of (N, 3072) uint8 rows (CHW order)."""
    batch_dir = os.path.join(data_dir, "cifar-10-batches-py")
    os.makedirs(batch_dir)
    rng = np.random.Generator(np.random.PCG64(6))
    all_imgs, all_labels = [], []
    for name in [f"data_batch_{i}" for i in range(1, 6)] + ["test_batch"]:
        data = rng.integers(0, 256, (n_per_batch, 3072)).astype(np.uint8)
        labels = rng.integers(0, 10, n_per_batch).astype(np.int64)
        with open(os.path.join(batch_dir, name), "wb") as f:
            pickle.dump({b"data": data, b"labels": labels.tolist()}, f)
        if name != "test_batch":
            all_imgs.append(data)
            all_labels.extend(labels.tolist())
    return np.concatenate(all_imgs), np.asarray(all_labels)


def test_cifar10_parses_pickle_batches(tmp_path):
    raw, labels = _cifar_fixture(str(tmp_path), n_per_batch=8)
    ds = cifar10("train", data_dir=str(tmp_path))
    assert not ds.synthetic
    x, y = ds.arrays
    assert x.shape == (40, 32, 32, 3) and x.dtype == np.float32
    np.testing.assert_array_equal(y, labels.astype(np.int32))
    # CHW (3, 32, 32) rows -> NHWC: channel 0 of sample 0 is the row's
    # first 1024 bytes
    np.testing.assert_allclose(
        x[0, :, :, 0],
        raw[0, :1024].reshape(32, 32).astype(np.float32) / 255.0,
    )


def test_cifar10_extracts_tar(tmp_path):
    """The tar.gz path: archive is unpacked then parsed like the batch dir."""
    inner = str(tmp_path / "stage")
    _cifar_fixture(inner, n_per_batch=4)
    tar_path = str(tmp_path / "data" / "cifar-10-python.tar.gz")
    os.makedirs(os.path.dirname(tar_path))
    with tarfile.open(tar_path, "w:gz") as t:
        t.add(
            os.path.join(inner, "cifar-10-batches-py"),
            arcname="cifar-10-batches-py",
        )
    ds = cifar10("train", data_dir=str(tmp_path / "data"))
    assert not ds.synthetic
    assert ds.arrays[0].shape == (20, 32, 32, 3)


def test_cifar10_synthetic_fallback(tmp_path):
    ds = cifar10("test", data_dir=str(tmp_path / "none"))
    assert ds.synthetic
    assert ds.arrays[0].shape == (10000, 32, 32, 3)
