"""Native runtime bindings: host arena + async CSV pipeline.

Reference parity: the flat C ABI mirrors NativeOps.h/JavaCPP (SURVEY.md §2.1
N8) — here compiled from ``csrc/dl4jtpu_native.cpp`` with the system g++ on
first use and bound via ctypes (no pybind11 in the image). Everything is
gated behind :func:`is_available`; pure-Python fallbacks exist throughout the
framework, so the native path is an accelerator, not a dependency.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csrc", "dl4jtpu_native.cpp")
_SRC_IMG = os.path.join(_HERE, "csrc", "dl4jtpu_image.cpp")


def _so_path() -> str:
    """The artefact is named by a digest of the sources it is built from,
    so a binary left behind by another revision, or copied in with the tree
    from another machine, is never loaded: only the committed sources
    decide what runs."""
    h = hashlib.sha256()
    for src in (_SRC, _SRC_IMG):
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"_dl4jtpu_native.{h.hexdigest()[:16]}.so")


_lib = None
_lock = threading.Lock()
_build_error: Optional[str] = None


def _build(so: str) -> Optional[str]:
    """Compile the native library to ``so`` unless this source digest's
    build is already there. → error message or None."""
    try:
        srcs = [_SRC, _SRC_IMG]
        if os.path.exists(so):
            return None
        for stale in glob.glob(os.path.join(_HERE, "_dl4jtpu_native*.so")):
            os.remove(stale)
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
               *srcs, "-o", so + ".tmp", "-ljpeg", "-lpng"]
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            # image decode libs may be absent: fall back to the CSV-only core
            cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
                   _SRC, "-o", so + ".tmp"]
            proc2 = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=300)
            if proc2.returncode != 0:
                return proc.stderr[-2000:]
        os.replace(so + ".tmp", so)
        return None
    except Exception as e:  # no compiler, read-only fs, ...
        return repr(e)


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        so = _so_path()
        err = _build(so)
        if err is not None:
            _build_error = err
            return None
        lib = ctypes.CDLL(so)
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_create.argtypes = [ctypes.c_size_t]
        lib.arena_alloc.restype = ctypes.c_void_p
        lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t]
        lib.arena_reset.argtypes = [ctypes.c_void_p]
        lib.arena_used.restype = ctypes.c_size_t
        lib.arena_used.argtypes = [ctypes.c_void_p]
        lib.arena_capacity.restype = ctypes.c_size_t
        lib.arena_capacity.argtypes = [ctypes.c_void_p]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.csv_count_rows.restype = ctypes.c_long
        lib.csv_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.csv_parse.restype = ctypes.c_long
        lib.csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char,
                                  ctypes.POINTER(ctypes.c_float), ctypes.c_long,
                                  ctypes.c_long]
        lib.pipe_create.restype = ctypes.c_void_p
        lib.pipe_create.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                                    ctypes.c_int, ctypes.c_char, ctypes.c_int,
                                    ctypes.c_int]
        lib.pipe_next.restype = ctypes.c_long
        lib.pipe_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
                                  ctypes.POINTER(ctypes.c_int)]
        lib.pipe_free_batch.argtypes = [ctypes.POINTER(ctypes.c_float)]
        lib.pipe_destroy.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "image_decode_file"):
            lib.image_decode_file.restype = ctypes.c_int
            lib.image_decode_file.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float)]
            lib.img_pipe_create.restype = ctypes.c_void_p
            lib.img_pipe_create.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int),
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int]
            lib.img_pipe_next_batch.restype = ctypes.c_long
            lib.img_pipe_next_batch.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.c_long, ctypes.POINTER(ctypes.c_int)]
            lib.img_pipe_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def is_available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


# ---------------------------------------------------------------------------
# Host arena (workspace parity)
# ---------------------------------------------------------------------------


class HostArena:
    """Page-aligned bump allocator for staging buffers (MemoryWorkspace
    parity — scoped use: allocate per step, reset after device_put)."""

    def __init__(self, capacity_bytes: int):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._ptr = lib.arena_create(capacity_bytes)
        if not self._ptr:
            raise MemoryError("arena_create failed")

    def alloc_array(self, shape, dtype=np.float32, align: int = 64) -> np.ndarray:
        """A numpy view over arena memory (no copy on reset — reuse)."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        p = self._lib.arena_alloc(self._ptr, nbytes, align)
        if not p:
            raise MemoryError("arena exhausted")
        buf = (ctypes.c_char * nbytes).from_address(p)
        return np.frombuffer(buf, dtype=dtype).reshape(shape)

    def used(self) -> int:
        return self._lib.arena_used(self._ptr)

    def capacity(self) -> int:
        return self._lib.arena_capacity(self._ptr)

    def reset(self):
        """Invalidates previously returned views — scope discipline is the
        caller's (the reference throws on workspace scope violations)."""
        self._lib.arena_reset(self._ptr)

    def close(self):
        if self._ptr:
            self._lib.arena_destroy(self._ptr)
            self._ptr = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# CSV
# ---------------------------------------------------------------------------


def parse_csv(text: bytes, cols: int, delimiter: str = ",") -> np.ndarray:
    """Parse CSV bytes → (rows, cols) float32. Non-numeric cells → NaN."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    if isinstance(text, str):
        text = text.encode()
    rows = lib.csv_count_rows(text, len(text))
    out = np.empty((rows, cols), np.float32)
    parsed = lib.csv_parse(
        text, len(text), delimiter.encode()[0:1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), rows, cols)
    if parsed < 0:
        raise ValueError("malformed CSV (inconsistent column count)")
    return out[:parsed]


class AsyncCSVPipeline:
    """Threaded read+parse of many CSV files, delivered in order
    (AsyncDataSetIterator parity: bounded prefetch off the training thread).

    Iterate → (file_index, float32 array (rows, cols))."""

    def __init__(self, paths: List[str], cols: int, delimiter: str = ",",
                 n_threads: int = 2, prefetch: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self.paths = [os.fspath(p) for p in paths]
        self.cols = cols
        arr = (ctypes.c_char_p * len(self.paths))(
            *[p.encode() for p in self.paths])
        self._keepalive = arr
        self._ptr = lib.pipe_create(arr, len(self.paths), cols,
                                    delimiter.encode()[0:1], n_threads, prefetch)

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[int, np.ndarray]:
        data = ctypes.POINTER(ctypes.c_float)()
        idx = ctypes.c_int()
        rows = self._lib.pipe_next(self._ptr, ctypes.byref(data),
                                   ctypes.byref(idx))
        if rows == -3:
            raise StopIteration
        if rows == -1:
            raise ValueError(f"malformed CSV: {self.paths[idx.value]}")
        if rows == -2:
            raise IOError(f"unreadable file: {self.paths[idx.value]}")
        try:
            arr = np.ctypeslib.as_array(data, shape=(rows, self.cols)).copy()
        finally:
            self._lib.pipe_free_batch(data)
        return idx.value, arr

    def close(self):
        if getattr(self, "_ptr", None):
            self._lib.pipe_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        self.close()


# ---------------------------------------------------------------------------
# Image pipeline (NativeImageLoader parity)
# ---------------------------------------------------------------------------


def image_available() -> bool:
    """True when the native image decode path (libjpeg/libpng) compiled in."""
    return _load() is not None and hasattr(_lib, "image_decode_file")


def decode_image_file(path: str, height: int, width: int,
                      channels: int = 3) -> np.ndarray:
    """Decode JPEG/PNG + bilinear resize → float32 (H, W, C) in [0, 255]."""
    lib = _load()
    if lib is None or not hasattr(lib, "image_decode_file"):
        raise RuntimeError(f"native image decode unavailable: {_build_error}")
    out = np.empty((height, width, channels), np.float32)
    rc = lib.image_decode_file(
        os.fspath(path).encode(), height, width, channels,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if rc == -2:
        raise IOError(f"unreadable image: {path}")
    if rc != 0:
        raise ValueError(f"undecodable image (JPEG/PNG only): {path}")
    return out


class AsyncImagePipeline:
    """Threaded decode+resize of many images into float32 NHWC batches
    (NativeImageLoader + AsyncDataSetIterator parity: the ETL hot path the
    reference keeps native so the accelerator is never input-bound).

    Iterate → (x (n, H, W, C) float32, labels (n,) int32, indices (n,) int32);
    undecodable files are skipped (counted in .failed)."""

    def __init__(self, paths, labels=None, height=224, width=224, channels=3,
                 batch=32, n_threads: int = 4, prefetch: int = 64):
        lib = _load()
        if lib is None or not hasattr(lib, "img_pipe_create"):
            raise RuntimeError(
                f"native image pipeline unavailable: {_build_error}")
        self._lib = lib
        self.paths = [os.fspath(p) for p in paths]
        self.height, self.width, self.channels = height, width, channels
        self.batch = batch
        self.failed = 0
        arr = (ctypes.c_char_p * len(self.paths))(
            *[p.encode() for p in self.paths])
        labs = (ctypes.c_int * len(self.paths))(
            *([int(l) for l in labels] if labels is not None
              else [-1] * len(self.paths)))
        self._keepalive = (arr, labs)
        self._ptr = lib.img_pipe_create(arr, labs, len(self.paths),
                                        height, width, channels,
                                        n_threads, prefetch)

    def __iter__(self):
        return self

    def __next__(self):
        x = np.empty((self.batch, self.height, self.width, self.channels),
                     np.float32)
        labels = np.empty((self.batch,), np.int32)
        indices = np.empty((self.batch,), np.int32)
        n_failed = ctypes.c_int()
        n = self._lib.img_pipe_next_batch(
            self._ptr, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
            indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
            self.batch, ctypes.byref(n_failed))
        self.failed += n_failed.value
        if n == 0:
            raise StopIteration
        return x[:n], labels[:n], indices[:n]

    def close(self):
        if getattr(self, "_ptr", None):
            self._lib.img_pipe_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        self.close()
