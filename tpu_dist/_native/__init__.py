"""ctypes bindings for the native host-data-path library (csrc/).

Auto-builds with the in-tree Makefile on first import if g++ is available;
every entry point has a pure-numpy fallback, so the framework works without a
toolchain (the native path just makes the 1-core host loader faster and lets
batch assembly overlap compute by releasing the GIL during memcpy).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_LIB_PATH = os.path.join(os.path.dirname(__file__), "libtpudist.so")
_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _stale() -> bool:
    """True when the .so is missing or older than any csrc/ source — a
    stale binary would dlopen but lack newer entry points."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    for fn in os.listdir(_CSRC):
        if fn.endswith((".cpp", ".h")) or fn == "Makefile":
            if os.path.getmtime(os.path.join(_CSRC, fn)) > built:
                return True
    return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.path.isdir(_CSRC) and _stale():
        # cross-process build lock: spawned ranks / multi-host shared FS must
        # not run `make` concurrently onto the same .so (a reader could dlopen
        # a half-written ELF and silently pin itself to the numpy fallback)
        import fcntl
        lock_path = os.path.join(_CSRC, ".build.lock")
        try:
            with open(lock_path, "w") as lock:
                fcntl.flock(lock, fcntl.LOCK_EX)
                if _stale():  # re-check under the lock
                    subprocess.run(["make", "-C", _CSRC, "-B"], check=True,
                                   capture_output=True, timeout=120)
        except Exception:
            if not os.path.exists(_LIB_PATH):
                return None  # no binary at all; else try the stale one
    if os.path.exists(_LIB_PATH):
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            lib.gather_rows_u8.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64]
            lib.gather_i32.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64]
            try:
                # newer entry points bound separately: a stale .so (no
                # toolchain to rebuild) must keep its working gather path
                lib.decode_available.restype = ctypes.c_int
                lib.decode_jpeg_resize_crop.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                    ctypes.c_int, ctypes.c_void_p]
                lib.decode_jpeg_resize_crop.restype = ctypes.c_int
            except AttributeError:
                pass
            _lib = lib
        except (OSError, AttributeError):
            _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


from contextlib import contextmanager


@contextmanager
def numpy_fallback():
    """Force the pure-numpy path inside the block (debug hook: a test
    compares the two implementations with it), however the lazy-load
    cache is organized internally."""
    global _lib, _tried
    saved = (_lib, _tried)
    _lib, _tried = None, True
    try:
        yield
    finally:
        _lib, _tried = saved


def gather_batch(images: np.ndarray, labels: np.ndarray,
                 indices: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """out = (images[indices], labels[indices]) via native memcpy rows.

    Falls back to numpy fancy indexing when the library is unavailable.
    """
    lib = _load()
    idx_arr = np.asarray(indices)
    # the native path has no bounds checking (raw memcpy); route anything
    # numpy-special (negative indices, out-of-range -> IndexError) to numpy
    in_bounds = (idx_arr.size == 0 or
                 (idx_arr.min() >= 0 and idx_arr.max() < images.shape[0]))
    if lib is None or not images.flags.c_contiguous or not in_bounds:
        # int32 labels to match the native path's output dtype exactly
        return images[indices], labels[indices].astype(np.int32)
    idx = np.ascontiguousarray(idx_arr, np.int64)
    n = idx.shape[0]
    row_bytes = images.dtype.itemsize * int(np.prod(images.shape[1:]))
    out_imgs = np.empty((n,) + images.shape[1:], images.dtype)
    lib.gather_rows_u8(images.ctypes.data, idx.ctypes.data,
                       out_imgs.ctypes.data, n, row_bytes)
    lab = np.ascontiguousarray(labels, np.int32)
    out_lab = np.empty((n,), np.int32)
    lib.gather_i32(lab.ctypes.data, idx.ctypes.data, out_lab.ctypes.data, n)
    return out_imgs, out_lab


def decode_available() -> bool:
    """True when the library was built against libjpeg (csrc/decode.cpp).
    False for missing library, stale pre-decode .so, or no-libjpeg build."""
    lib = _load()
    fn = getattr(lib, "decode_available", None) if lib is not None else None
    return bool(fn and fn())


def decode_jpeg(data: bytes, size: int) -> Optional[np.ndarray]:
    """JPEG bytes -> (size, size, 3) RGB u8 via the native decoder, or None.

    Native path = libjpeg DCT-scaled decode + bilinear short-side resize to
    size*256//224 + center crop — the same framing as the PIL fallback in
    tpu_dist.data.imagefolder._decode (resampling kernels differ). Returns
    None (caller falls back to PIL) when the library/libjpeg is missing or
    the bytes fail to decode.
    """
    if not decode_available():
        return None
    lib = _load()
    out = np.empty((size, size, 3), np.uint8)
    pre_short = size * 256 // 224
    rc = lib.decode_jpeg_resize_crop(data, len(data), size, pre_short,
                                     out.ctypes.data)
    return out if rc == 0 else None
