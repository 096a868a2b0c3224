"""Native host runtime (reference L0's C++ half: csrc/
flatten_unflatten.cpp and friends, SURVEY.md §2.4 `apex_C`).

The .so is built lazily with the system g++ on first import (the
environment bans pip installs, not compilers) and cached next to the
source; every entry point has a NumPy fallback so the package works even
without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "apex_c.cpp")
_SO = os.path.join(_HERE, "libapex_c.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _src_hash() -> str:
    import hashlib
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _build() -> Optional[str]:
    # Rebuild keyed on a source-content hash, not mtimes: a checkout
    # refreshes every mtime, which made a stale (possibly other-arch)
    # committed .so look fresh forever (ADVICE r1).
    stamp = _SO + ".srchash"
    try:
        want = _src_hash()
    except OSError:      # source not shipped/readable: NumPy fallback
        return _SO if os.path.exists(_SO) else None
    if os.path.exists(_SO) and os.path.exists(stamp):
        try:
            with open(stamp) as f:
                if f.read().strip() == want:
                    return _SO
        except OSError:
            pass
    try:
        # compile to a private temp path and publish atomically: a
        # concurrent first-run process must never CDLL a torn ELF
        tmp = f"{_SO}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        tmp_stamp = f"{stamp}.{os.getpid()}.tmp"
        with open(tmp_stamp, "w") as f:
            f.write(want)
        os.replace(tmp_stamp, stamp)
        return _SO
    except Exception as e:
        # a fresh checkout ships no .so (.gitignore): the build is the
        # normal path, so a failed one must be said, not swallowed
        import warnings
        warnings.warn(
            f"apex_tpu._native: could not build libapex_c.so with g++ "
            f"({type(e).__name__}: {e}); the NumPy fallbacks engage",
            RuntimeWarning, stacklevel=2)
        return None


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (NumPy fallbacks engage)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            # double-checked locking: writes happen under _lock;
            # the unlocked fast-path READ above is a GIL-atomic
            # reference check whose worst case is blocking on
            # _lock like everyone else
            _tried = True   # apexlint: disable=APX1001
            so = _build()
            if so:
                try:
                    l = ctypes.CDLL(so)
                    i64p = ctypes.POINTER(ctypes.c_int64)
                    l.apex_c_flatten.restype = None
                    l.apex_c_flatten.argtypes = [
                        ctypes.POINTER(ctypes.c_void_p), i64p,
                        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
                    l.apex_c_unflatten.restype = None
                    l.apex_c_unflatten.argtypes = [
                        ctypes.c_void_p, i64p, ctypes.c_int64,
                        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64]
                    l.apex_c_l2norm_sq_f32.restype = ctypes.c_double
                    l.apex_c_l2norm_sq_f32.argtypes = [
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
                        ctypes.c_int64]
                    _lib = l   # apexlint: disable=APX1001
                except OSError:
                    _lib = None
    return _lib


def available() -> bool:
    return lib() is not None


def _n_threads() -> int:
    return min(8, os.cpu_count() or 1)


def host_flatten(arrays: List[np.ndarray]) -> np.ndarray:
    """Pack host arrays into one contiguous byte buffer (apex_C.flatten
    semantics on the host side; dtype-agnostic)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    sizes = np.asarray([a.nbytes for a in arrays], np.int64)
    out = np.empty(int(sizes.sum()), np.uint8)
    l = lib()
    if l is None or not arrays:
        off = 0
        for a, nb in zip(arrays, sizes):
            out[off:off + nb] = a.view(np.uint8).ravel()
            off += int(nb)
        return out
    Ptrs = ctypes.c_void_p * len(arrays)
    ptrs = Ptrs(*[a.ctypes.data_as(ctypes.c_void_p) for a in arrays])
    l.apex_c_flatten(ptrs, sizes.ctypes.data_as(
        ctypes.POINTER(ctypes.c_int64)), len(arrays),
        out.ctypes.data_as(ctypes.c_void_p), _n_threads())
    return out


def host_unflatten(flat: np.ndarray, like: List[np.ndarray]
                   ) -> List[np.ndarray]:
    """Inverse of host_flatten: split into arrays shaped/dtyped as `like`."""
    flat = np.ascontiguousarray(flat.view(np.uint8).ravel())
    outs = [np.empty(a.shape, a.dtype) for a in like]
    sizes = np.asarray([a.nbytes for a in outs], np.int64)
    l = lib()
    if l is None or not outs:
        off = 0
        for o, nb in zip(outs, sizes):
            o.view(np.uint8).ravel()[:] = flat[off:off + int(nb)]
            off += int(nb)
        return outs
    Ptrs = ctypes.c_void_p * len(outs)
    ptrs = Ptrs(*[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
    l.apex_c_unflatten(flat.ctypes.data_as(ctypes.c_void_p),
                       sizes.ctypes.data_as(
                           ctypes.POINTER(ctypes.c_int64)),
                       len(outs), ptrs, _n_threads())
    return outs


def host_l2norm(x: np.ndarray) -> float:
    """Threaded L2 norm of a host f32 buffer (checkpoint checksums)."""
    x = np.ascontiguousarray(x, np.float32).ravel()
    l = lib()
    if l is None:
        return float(np.linalg.norm(x.astype(np.float64)))
    return float(l.apex_c_l2norm_sq_f32(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        x.size, _n_threads())) ** 0.5
