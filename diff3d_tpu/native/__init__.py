"""ctypes bindings for the native (C++) data-loader runtime.

``decoder.cpp`` is compiled on first use with the system ``g++`` into
``libd3dnative.so`` next to this file (rebuilt automatically when the
source is newer; the library is git-ignored, so a fresh checkout always
builds it).  If the toolchain or libpng is missing, :func:`available`
is False and callers (SRNDataset, InfiniteLoader) stay on the pure-PIL
path; either way the first probe says once, at INFO, which PNG decoder
the process took.

Public surface:
  * :func:`available` — native runtime usable?
  * :func:`decode_image` — one PNG -> ``[s, s, 3] float32`` in [-1, 1].
  * :class:`DecoderPool` — persistent C++ worker pool decoding whole
    batches GIL-free.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "decoder.cpp")
_LIB = os.path.join(_DIR, "libd3dnative.so")

log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None  # guarded-by: _lock
_tried = False  # guarded-by: _lock

_ERRORS = {1: "cannot open file", 2: "not a PNG", 3: "PNG decode error",
           4: "bad arguments"}


def _build() -> bool:
    # Compile to a per-pid temp path and os.rename into place: concurrent
    # processes (multi-process jax.distributed, pytest-xdist) may race on
    # a shared checkout, and rename is atomic while `g++ -o final` is not.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
           "-o", tmp, "-lpng", "-pthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.rename(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        _lib, how = _load_once()
        log.info("PNG decoder: %s", how)
        return _lib


def _load_once():
    """``(lib or None, one-line description of the decoder taken)``."""
    stale = (not os.path.exists(_LIB)
             or os.path.getmtime(_LIB) < os.path.getmtime(_SRC))
    if stale and not _build():
        return None, "PIL (building libd3dnative.so with g++ -lpng failed)"
    try:
        lib = ctypes.CDLL(_LIB)
    except OSError as e:
        return None, f"PIL (libd3dnative.so did not load: {e})"
    lib.d3d_version.restype = ctypes.c_int
    lib.d3d_decode.restype = ctypes.c_int
    lib.d3d_decode.argtypes = [ctypes.c_char_p, ctypes.c_int,
                               ctypes.POINTER(ctypes.c_float)]
    lib.d3d_pool_create.restype = ctypes.c_void_p
    lib.d3d_pool_create.argtypes = [ctypes.c_int]
    lib.d3d_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.d3d_pool_decode.restype = ctypes.c_int
    lib.d3d_pool_decode.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_float)]
    if lib.d3d_version() != 1:
        return None, "PIL (libd3dnative.so reports an unknown version)"
    return lib, ("native libd3dnative.so ("
                 + ("built now" if stale else "found built") + ")")


def available() -> bool:
    return _load() is not None


_shared_pool: Optional["DecoderPool"] = None  # guarded-by: _pool_lock


_pool_lock = threading.Lock()


def shared_pool() -> Optional["DecoderPool"]:
    """Process-wide decoder pool (lazy).  The data pipeline routes batch
    decodes through this; None when the native runtime is unavailable."""
    global _shared_pool
    if _load() is None:      # before _pool_lock: _load takes its own lock
        return None
    with _pool_lock:
        if _shared_pool is None:
            _shared_pool = DecoderPool()
        return _shared_pool


def decode_image(path: str, size: int) -> np.ndarray:
    """Decode + box-resize + normalize one PNG via the native runtime."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    out = np.empty((size, size, 3), np.float32)
    err = lib.d3d_decode(path.encode(), size,
                         out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if err:
        raise IOError(f"{_ERRORS.get(err, err)}: {path}")
    return out


class DecoderPool:
    """Persistent native worker pool: ``decode_batch(paths) -> [N,s,s,3]``.

    The pool's std::threads never touch the GIL while decoding, so a
    training host can assemble the next global batch entirely during
    device compute (the reference needs 16 DataLoader worker *processes*
    for the same overlap, ``train.py:217``)."""

    def __init__(self, num_threads: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native decoder unavailable")
        self._lib = lib
        self._pool = lib.d3d_pool_create(num_threads)
        if not self._pool:
            raise RuntimeError("pool creation failed")

    def decode_batch(self, paths: Sequence[str], size: int) -> np.ndarray:
        n = len(paths)
        out = np.empty((n, size, size, 3), np.float32)
        arr = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
        err = self._lib.d3d_pool_decode(
            self._pool, arr, n, size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if err:
            raise IOError(f"batch decode failed: {_ERRORS.get(err, err)}")
        return out

    def close(self) -> None:
        if getattr(self, "_pool", None):
            self._lib.d3d_pool_destroy(self._pool)
            self._pool = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
