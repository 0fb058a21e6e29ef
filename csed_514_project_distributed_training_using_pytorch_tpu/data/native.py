"""ctypes bindings to the native (C++) data-loader runtime in ``data/_native``.

The reference's host-side input path is C++ inside libtorch: torchvision's MNIST cache
reader (reference ``src/train.py:26-31``) and the DataLoader worker pool
(``num_workers=4, pin_memory=True``, reference ``src/train_dist.py:43-45``). This module is
that native substrate rebuilt first-party for the TPU framework — IDX parsing, pixel
normalization, batch gather, and a threaded prefetching batch queue — compiled on demand from
``_native/loader.cc`` and reached over a C ABI (ctypes; pybind11 intentionally not required).

If the toolchain or library is unavailable, ``available()`` is False and callers
(``data.mnist``, ``data.loader``) use their pure-numpy paths, which are bit-exact
equivalents (asserted by tests/test_native.py). Which path a process got is never silent:
the first load prints one ``data.native: ...`` line to stderr and ``status()`` returns the
same string — ``"native"``, or ``"numpy (<why>)"`` where a why starting ``build failed``
means a compiler ran and failed, which ``chip_smoke.py`` treats as an error.
"""

from __future__ import annotations

import ctypes
import os
import sys
from typing import Iterator

import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.data._native import build

_lib: ctypes.CDLL | None = None
_status: str | None = None      # None until the first _load()

_DISABLE_ENV = "CSED514_TPU_NO_NATIVE"


def _load() -> ctypes.CDLL | None:
    global _lib, _status
    if _status is None:
        _lib, _status = _open_library()
        print(f"data.native: {_status}", file=sys.stderr, flush=True)
    return _lib


def _open_library() -> tuple[ctypes.CDLL | None, str]:
    """``(library or None, status string)`` — see the module docstring."""
    if os.environ.get(_DISABLE_ENV):
        return None, f"numpy ({_DISABLE_ENV} set)"
    try:
        path = build.build()
    except build.BuildFailed as e:
        return None, f"numpy (build failed: {str(e).strip().splitlines()[-1]})"
    if path is None:
        return None, "numpy (no g++ to build the loader)"
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        return None, f"numpy (build failed: dlopen {e})"

    c_ll, c_int, c_float = ctypes.c_longlong, ctypes.c_int, ctypes.c_float
    p_u8 = ctypes.POINTER(ctypes.c_ubyte)
    p_f32 = ctypes.POINTER(c_float)
    p_i32 = ctypes.POINTER(c_int)
    p_ll = ctypes.POINTER(c_ll)

    lib.nl_idx_info.argtypes = [ctypes.c_char_p, ctypes.POINTER(c_int), p_ll]
    lib.nl_idx_info.restype = c_int
    lib.nl_idx_read.argtypes = [ctypes.c_char_p, p_u8, c_ll]
    lib.nl_idx_read.restype = c_int
    lib.nl_normalize.argtypes = [p_u8, p_f32, c_ll, c_float, c_float, c_int]
    lib.nl_normalize.restype = c_int
    lib.nl_gather_f32.argtypes = [p_f32, c_ll, c_ll, p_i32, c_ll, p_f32, c_int]
    lib.nl_gather_f32.restype = c_int
    lib.nl_gather_i32.argtypes = [p_i32, c_ll, p_i32, c_ll, p_i32]
    lib.nl_gather_i32.restype = c_int
    lib.nl_prefetcher_create.argtypes = [p_f32, p_i32, c_ll, c_ll, p_i32, c_ll, c_ll,
                                         c_int, c_int]
    lib.nl_prefetcher_create.restype = ctypes.c_void_p
    lib.nl_prefetcher_next.argtypes = [ctypes.c_void_p, p_f32, p_i32]
    lib.nl_prefetcher_next.restype = c_ll
    lib.nl_prefetcher_destroy.argtypes = [ctypes.c_void_p]
    lib.nl_prefetcher_destroy.restype = None
    lib.nl_abi_version.argtypes = []
    lib.nl_abi_version.restype = c_int

    if lib.nl_abi_version() != 1:
        return None, (f"numpy (build failed: ABI version "
                      f"{lib.nl_abi_version()} != 1 — stale library?)")
    return lib, "native"


def available() -> bool:
    """True when the native library is built and loadable."""
    return _load() is not None


def status() -> str:
    """Which loader this process uses and why (loads on first call)."""
    _load()
    return _status


def _as_ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def load_idx(path: str) -> np.ndarray:
    """Parse one IDX file (plain or .gz) into a uint8 array — native analog of
    ``data.mnist._read_idx``."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    ndim = ctypes.c_int(0)
    shape = (ctypes.c_longlong * 4)()
    rc = lib.nl_idx_info(path.encode(), ctypes.byref(ndim), shape)
    if rc != 0:
        raise ValueError(f"nl_idx_info({path!r}) failed with {rc}")
    dims = tuple(shape[i] for i in range(ndim.value))
    out = np.empty(int(np.prod(dims)), dtype=np.uint8)
    rc = lib.nl_idx_read(path.encode(), _as_ptr(out, ctypes.c_ubyte), out.size)
    if rc != 0:
        raise ValueError(f"nl_idx_read({path!r}) failed with {rc}")
    return out.reshape(dims)


def normalize(images_u8: np.ndarray, mean: float, std: float,
              num_threads: int = 4) -> np.ndarray:
    """uint8 [N,H,W] → normalized float32 [N,H,W,1] — native analog of
    ``data.mnist._normalize``."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    src = np.ascontiguousarray(images_u8, dtype=np.uint8)
    dst = np.empty(src.shape, dtype=np.float32)
    rc = lib.nl_normalize(_as_ptr(src, ctypes.c_ubyte), _as_ptr(dst, ctypes.c_float),
                          src.size, mean, std, num_threads)
    if rc != 0:
        raise ValueError(f"nl_normalize failed with {rc}")
    return dst[..., None]


def gather(images: np.ndarray, labels: np.ndarray, idx: np.ndarray,
           num_threads: int = 4) -> tuple[np.ndarray, np.ndarray]:
    """(images[idx], labels[idx]) via the threaded native gather — one DataLoader-worker
    batch assembly."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    images = np.ascontiguousarray(images, dtype=np.float32)
    labels = np.ascontiguousarray(labels, dtype=np.int32)
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    sample_elems = int(np.prod(images.shape[1:]))
    out_i = np.empty((len(idx),) + images.shape[1:], dtype=np.float32)
    out_l = np.empty(len(idx), dtype=np.int32)
    rc = lib.nl_gather_f32(_as_ptr(images, ctypes.c_float), images.shape[0],
                           sample_elems, _as_ptr(idx, ctypes.c_int), len(idx),
                           _as_ptr(out_i, ctypes.c_float), num_threads)
    if rc == 0:
        rc = lib.nl_gather_i32(_as_ptr(labels, ctypes.c_int), labels.shape[0],
                               _as_ptr(idx, ctypes.c_int), len(idx),
                               _as_ptr(out_l, ctypes.c_int))
    if rc != 0:
        raise IndexError("gather index out of range")
    return out_i, out_l


class Prefetcher:
    """Threaded batch queue over a ``[steps, batch]`` index plan — the ``num_workers``
    prefetch pool (reference ``src/train_dist.py:43-45``) as a first-party C++ component.

    Iterates ``(images[batch], labels[batch])`` in plan order while worker threads gather
    ahead into a bounded ring. Use as a context manager or iterate to exhaustion.
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray, plan: np.ndarray, *,
                 num_workers: int = 4, capacity: int = 8):
        lib = _load()
        if lib is None:
            raise RuntimeError("native loader unavailable")
        self._lib = lib
        # Keep references so the buffers outlive the C++ threads reading them.
        self._images = np.ascontiguousarray(images, dtype=np.float32)
        self._labels = np.ascontiguousarray(labels, dtype=np.int32)
        plan = np.ascontiguousarray(plan, dtype=np.int32)
        if plan.ndim != 2:
            raise ValueError(f"plan must be [steps, batch], got shape {plan.shape}")
        self.steps, self.batch = plan.shape
        self._sample_shape = self._images.shape[1:]
        sample_elems = int(np.prod(self._sample_shape))
        self._handle = lib.nl_prefetcher_create(
            _as_ptr(self._images, ctypes.c_float), _as_ptr(self._labels, ctypes.c_int),
            self._images.shape[0], sample_elems, _as_ptr(plan, ctypes.c_int),
            self.steps, self.batch, num_workers, capacity)
        if not self._handle:
            raise RuntimeError("nl_prefetcher_create failed")

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        while True:
            if self._handle is None:
                raise ValueError("Prefetcher is closed")
            out_i = np.empty((self.batch,) + self._sample_shape, dtype=np.float32)
            out_l = np.empty(self.batch, dtype=np.int32)
            step = self._lib.nl_prefetcher_next(
                self._handle, _as_ptr(out_i, ctypes.c_float),
                _as_ptr(out_l, ctypes.c_int))
            if step == -1:
                return
            if step == -2:
                raise IndexError("prefetcher: plan index out of range")
            yield out_i, out_l

    def close(self) -> None:
        if self._handle:
            self._lib.nl_prefetcher_destroy(self._handle)
            self._handle = None

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass
