"""Native (C++) data-loader runtime: bit-exact parity with the pure-numpy paths.

The native library (``data/_native/loader.cc`` via ``data/native.py``) re-creates the C++
substrate the reference's input path leans on (torchvision cache reader + DataLoader worker
pool, reference ``src/train.py:26-31``, ``src/train_dist.py:43-45``). These tests assert that
every native entry point produces exactly what the numpy fallback produces, so the two paths
are interchangeable.
"""

import gzip
import os
import struct

import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    BatchLoader, load_mnist, mnist, native,
)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native loader library not built (no toolchain)")


@pytest.fixture(scope="module")
def imgs_u8():
    return np.random.default_rng(7).integers(0, 256, size=(64, 28, 28), dtype=np.uint8)


@pytest.fixture(scope="module")
def dataset():
    train, _ = load_mnist("/nonexistent-data-dir", synthetic_seed=99)
    return train


def _write_idx(path: str, arr: np.ndarray, gz: bool = False) -> str:
    header = struct.pack(">I", 0x0800 | arr.ndim) + struct.pack(
        f">{arr.ndim}I", *arr.shape)
    opener = gzip.open if gz else open
    with opener(path, "wb") as f:
        f.write(header + arr.tobytes())
    return path


class TestIdxParsing:
    def test_images_plain_and_gz(self, tmp_path, imgs_u8):
        plain = _write_idx(str(tmp_path / "imgs"), imgs_u8)
        gzed = _write_idx(str(tmp_path / "imgs.gz"), imgs_u8, gz=True)
        np.testing.assert_array_equal(native.load_idx(plain), imgs_u8)
        np.testing.assert_array_equal(native.load_idx(gzed), imgs_u8)
        np.testing.assert_array_equal(native.load_idx(plain), mnist._read_idx(plain))

    def test_labels_1d(self, tmp_path):
        labels = np.arange(100, dtype=np.uint8) % 10
        path = _write_idx(str(tmp_path / "labels"), labels)
        np.testing.assert_array_equal(native.load_idx(path), labels)

    def test_missing_file_raises(self, tmp_path):
        with pytest.raises(ValueError):
            native.load_idx(str(tmp_path / "nope"))

    def test_bad_magic_raises(self, tmp_path):
        path = tmp_path / "bad"
        path.write_bytes(b"\x00\x00\x07\x03" + b"\x00" * 32)
        with pytest.raises(ValueError):
            native.load_idx(str(path))


class TestNormalize:
    def test_bit_exact_vs_numpy(self, imgs_u8):
        got = native.normalize(imgs_u8, mnist.MNIST_MEAN, mnist.MNIST_STD)
        want = mnist._normalize(imgs_u8)
        assert got.shape == want.shape == (64, 28, 28, 1)
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got, want)

    def test_multithreaded_matches_single(self, imgs_u8):
        a = native.normalize(imgs_u8, mnist.MNIST_MEAN, mnist.MNIST_STD, num_threads=1)
        b = native.normalize(imgs_u8, mnist.MNIST_MEAN, mnist.MNIST_STD, num_threads=8)
        np.testing.assert_array_equal(a, b)


class TestGather:
    def test_matches_fancy_index(self, dataset):
        idx = np.random.default_rng(3).permutation(len(dataset))[:128].astype(np.int32)
        gi, gl = native.gather(dataset.images, dataset.labels, idx)
        np.testing.assert_array_equal(gi, dataset.images[idx])
        np.testing.assert_array_equal(gl, dataset.labels[idx])

    def test_out_of_range_raises(self, dataset):
        with pytest.raises(IndexError):
            native.gather(dataset.images, dataset.labels,
                          np.array([0, len(dataset)], dtype=np.int32))


class TestPrefetcher:
    def test_order_and_content(self, dataset):
        rng = np.random.default_rng(11)
        plan = rng.integers(0, len(dataset), size=(23, 32)).astype(np.int32)
        with native.Prefetcher(dataset.images, dataset.labels, plan,
                               num_workers=3, capacity=4) as pf:
            steps = 0
            for s, (bi, bl) in enumerate(pf):
                np.testing.assert_array_equal(bi, dataset.images[plan[s]])
                np.testing.assert_array_equal(bl, dataset.labels[plan[s]])
                steps += 1
        assert steps == 23

    def test_capacity_smaller_than_steps(self, dataset):
        plan = np.arange(40 * 8, dtype=np.int32).reshape(40, 8)
        with native.Prefetcher(dataset.images, dataset.labels, plan,
                               num_workers=2, capacity=2) as pf:
            got = [bl.copy() for _, bl in pf]
        assert len(got) == 40
        for s, bl in enumerate(got):
            np.testing.assert_array_equal(bl, dataset.labels[plan[s]])

    def test_early_close_does_not_hang(self, dataset):
        plan = np.arange(100 * 16, dtype=np.int32).reshape(100, 16) % len(dataset)
        pf = native.Prefetcher(dataset.images, dataset.labels, plan,
                               num_workers=4, capacity=2)
        it = iter(pf)
        next(it)
        pf.close()  # workers blocked on a full ring must exit cleanly
        with pytest.raises(ValueError, match="closed"):
            next(it)  # iterating a closed prefetcher must raise, not segfault

    def test_bad_plan_index_reported(self, dataset):
        plan = np.full((3, 4), len(dataset), dtype=np.int32)  # every index out of range
        with native.Prefetcher(dataset.images, dataset.labels, plan) as pf:
            with pytest.raises(IndexError):
                list(pf)


class TestNormalizeInProductPath:
    def test_load_mnist_routes_through_native_normalize(self, tmp_path, monkeypatch):
        """load_mnist must actually call native.normalize when the library is available,
        and its output must equal the pure-numpy pipeline bit-for-bit. Exercised end to end
        with real IDX files so both the native IDX read and normalize wiring run."""
        rng = np.random.default_rng(2)
        train_x = rng.integers(0, 256, (20, 28, 28), dtype=np.uint8)
        test_x = rng.integers(0, 256, (8, 28, 28), dtype=np.uint8)
        train_y = (np.arange(20) % 10).astype(np.uint8)
        test_y = (np.arange(8) % 10).astype(np.uint8)
        _write_idx(str(tmp_path / "train-images-idx3-ubyte"), train_x)
        _write_idx(str(tmp_path / "train-labels-idx1-ubyte"), train_y)
        _write_idx(str(tmp_path / "t10k-images-idx3-ubyte"), test_x)
        _write_idx(str(tmp_path / "t10k-labels-idx1-ubyte"), test_y)

        calls = []
        real_normalize = native.normalize

        def recording_normalize(*args, **kwargs):
            calls.append(args[0].shape)
            return real_normalize(*args, **kwargs)

        monkeypatch.setattr(native, "normalize", recording_normalize)
        train, test = load_mnist(str(tmp_path), allow_synthetic=False)

        assert train.source == "idx"
        assert calls == [(20, 28, 28), (8, 28, 28)]
        np.testing.assert_array_equal(train.images, mnist._normalize(train_x))
        np.testing.assert_array_equal(test.images, mnist._normalize(test_x))
        np.testing.assert_array_equal(train.labels, train_y.astype(np.int32))
        np.testing.assert_array_equal(test.labels, test_y.astype(np.int32))


class TestBatchLoaderIntegration:
    def test_iter_uses_native_and_matches_numpy(self, dataset):
        loader = BatchLoader(dataset, 64, shuffle=True, seed=5)
        loader.set_epoch(2)
        indices = loader.sampler.epoch_indices(2)
        for i, (bi, bl) in enumerate(loader):
            idx = indices[i * 64:(i + 1) * 64]
            np.testing.assert_array_equal(bi, dataset.images[idx])
            np.testing.assert_array_equal(bl, dataset.labels[idx])
            if i >= 3:
                break

    def test_prefetch_iter_matches_index_matrix(self, dataset):
        loader = BatchLoader(dataset, 128, shuffle=True, seed=6)
        loader.set_epoch(1)
        plan = loader.epoch_index_matrix(1)
        for s, (bi, bl) in enumerate(loader.prefetch_iter(1)):
            np.testing.assert_array_equal(bi, dataset.images[plan[s]])
            np.testing.assert_array_equal(bl, dataset.labels[plan[s]])
        assert s == plan.shape[0] - 1

    def test_iter_plan_batches_on_noncontiguous_column_slice(self, dataset):
        """The distributed host-local feed passes a column slice of the global plan
        (non-contiguous view) — native-path batches must equal a plain gather of the
        same rows (the numpy-fallback leg is covered unconditionally in
        test_data.py::test_iter_plan_batches_numpy_fallback)."""
        from csed_514_project_distributed_training_using_pytorch_tpu.data.loader import (
            iter_plan_batches,
        )
        rng = np.random.default_rng(13)
        full = rng.integers(0, len(dataset), size=(9, 32)).astype(np.int32)
        local = full[:, 8:24]            # a process's column block, as in _host_local_columns
        steps = 0
        for s, (bi, bl) in enumerate(iter_plan_batches(dataset, local)):
            np.testing.assert_array_equal(bi, dataset.images[local[s]])
            np.testing.assert_array_equal(bl, dataset.labels[local[s]])
            steps += 1
        assert steps == 9


class TestLoaderVisibility:
    """Which loader a process got is never silent (PR 21): ``status()`` names it, and
    a compiler that RAN and failed is told apart from a machine without one."""

    def test_status_names_the_loader_that_ran(self):
        assert native.available()          # this image has g++; the suite needs it
        assert native.status() == "native"

    def _fresh_build(self, monkeypatch, tmp_path, run):
        from csed_514_project_distributed_training_using_pytorch_tpu.data._native import (
            build,
        )
        monkeypatch.setattr(build, "LIBRARY", str(tmp_path / "libnativeloader.so"))
        monkeypatch.setattr(build.subprocess, "run", run)
        return build

    def test_failed_compile_is_typed_and_reported(self, monkeypatch, tmp_path):
        import types

        build = self._fresh_build(
            monkeypatch, tmp_path,
            lambda *a, **k: types.SimpleNamespace(returncode=1,
                                                  stderr="loader.cc:1: error: boom"))
        with pytest.raises(build.BuildFailed, match="boom"):
            build.build()
        lib, status = native._open_library()
        assert lib is None and status == "numpy (build failed: loader.cc:1: error: boom)"
        assert not list(tmp_path.iterdir())             # no half-written library

    def test_missing_toolchain_is_not_a_failure(self, monkeypatch, tmp_path):
        def no_gpp(*a, **k):
            raise FileNotFoundError("g++")

        build = self._fresh_build(monkeypatch, tmp_path, no_gpp)
        assert build.build() is None
        lib, status = native._open_library()
        assert lib is None and status.startswith("numpy (no g++")

    def test_disable_env_is_reported(self, monkeypatch):
        monkeypatch.setenv("CSED514_TPU_NO_NATIVE", "1")
        assert native._open_library() == (None, "numpy (CSED514_TPU_NO_NATIVE set)")
