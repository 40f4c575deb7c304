"""Native host kernels: build-on-demand C++ with ctypes bindings.

``lib()`` returns the loaded shared library, compiling
``src/ramses_native.cpp`` with g++ on first use; ``None`` when no
compiler is available (callers fall back to numpy — the build error
is kept in :data:`build_error` so a caller that needs the native
path, ``chip_smoke.py``, can fail loudly).  The built file carries a
hash of the source in its name, so only a binary built from the
committed source as it stands is ever loaded: a copied tree (mtimes
meaningless) or an edited source rebuilds, and a stale binary is
never picked up.  Set ``RAMSES_TPU_NATIVE=0`` to force the numpy
paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "ramses_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
build_error = ""

_i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
_u64p = np.ctypeslib.ndpointer(dtype=np.uint64, flags="C_CONTIGUOUS")


def so_path() -> str:
    """Path of the binary built from the source as it stands."""
    with open(_SRC, "rb") as f:
        h = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_ramses_native_{h}.so")


def _build(so: str) -> bool:
    global build_error
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)         # atomic: concurrent builders race
        return True
    except Exception as e:
        err = getattr(e, "stderr", None)
        build_error = repr(e) + (
            ": " + err.decode(errors="replace")[-2000:] if err else "")
        return False


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried, build_error
    if os.environ.get("RAMSES_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            L = ctypes.CDLL(so)
        except OSError as e:
            build_error = repr(e)
            return None
        L.morton_encode.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int,
                                    _i64p]
        L.hilbert_encode.argtypes = [_i64p, ctypes.c_int64, ctypes.c_int,
                                     ctypes.c_int, _u64p]
        L.searchsorted_i64.argtypes = [_i64p, ctypes.c_int64, _i64p,
                                       ctypes.c_int64, _i64p]
        L.lookup_i64.argtypes = [_i64p, ctypes.c_int64, _i64p,
                                 ctypes.c_int64, _i64p]
        L.neighbor_lookup.argtypes = [_i64p, _i64p, ctypes.c_int64,
                                      ctypes.c_int, ctypes.c_int64,
                                      _i64p, ctypes.c_int64, _i64p]
        _lib = L
        return _lib


def morton_encode(og: np.ndarray, ndim: int) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    og = np.ascontiguousarray(og, dtype=np.int64)
    out = np.empty(len(og), dtype=np.int64)
    L.morton_encode(og, len(og), ndim, out)
    return out


def hilbert_encode(og: np.ndarray, ndim: int,
                   nbits: int) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    og = np.ascontiguousarray(og, dtype=np.int64)
    out = np.empty(len(og), dtype=np.uint64)
    L.hilbert_encode(og, len(og), ndim, nbits, out)
    return out


def lookup_sorted(sorted_keys: np.ndarray,
                  queries: np.ndarray) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    s = np.ascontiguousarray(sorted_keys, dtype=np.int64)
    q = np.ascontiguousarray(queries, dtype=np.int64)
    out = np.empty(len(q), dtype=np.int64)
    L.lookup_i64(s, len(s), q, len(q), out)
    return out


def neighbor_lookup(sorted_keys: np.ndarray, og: np.ndarray, ndim: int,
                    level_size: int,
                    offsets: np.ndarray) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    s = np.ascontiguousarray(sorted_keys, dtype=np.int64)
    o = np.ascontiguousarray(og, dtype=np.int64)
    offs = np.ascontiguousarray(offsets, dtype=np.int64)
    out = np.empty(len(o) * len(offs), dtype=np.int64)
    L.neighbor_lookup(s, o, len(o), ndim, level_size, offs, len(offs), out)
    return out.reshape(len(o), len(offs))
