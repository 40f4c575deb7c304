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

Entry points (each returns ``None`` without the library):
``morton_encode``, ``hilbert_encode``, ``lookup_sorted``,
``neighbor_lookup`` and, since PR 37, :func:`tile_tables` — the blocked
tile tables of one partial level (``amr/maps.py::BlockMaps``) in ONE
pass over the level's Morton tiles.  C side: ``tile_plan(keys of l-1,
l, l+1 [sorted int64] with their lengths, ndim, shift, dims[ndim] (cells
of level l), bc[2*ndim] (low, high kind per dim: 0 periodic, 1
reflecting, 2 outflow), has_coarse, counts[4])`` returns an opaque plan
and the counts ``(ntile, ni, any reflection, missing fathers)``; the
caller buckets ``ntile`` and ``ni``; ``tile_emit(plan, keys_l, n_l,
ntile_pad, ni_pad, noct_pad, <the thirteen output arrays>)`` writes
every table, pads included; ``tile_free`` drops the plan.
:func:`tile_tables` wraps the three so a plan never outlives the call.
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
        L.tile_plan.restype = ctypes.c_void_p
        L.tile_plan.argtypes = [_i64p, ctypes.c_int64, _i64p,
                                ctypes.c_int64, _i64p, ctypes.c_int64,
                                ctypes.c_int, ctypes.c_int, _i64p, _i64p,
                                ctypes.c_int, _i64p]
        L.tile_emit.restype = None
        L.tile_emit.argtypes = ([ctypes.c_void_p, _i64p]
                                + [ctypes.c_int64] * 4
                                + [ctypes.c_void_p] * 13)
        L.tile_free.restype = None
        L.tile_free.argtypes = [ctypes.c_void_p]
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


def tile_tables(keys_m1: np.ndarray, keys_l: np.ndarray,
                keys_p1: np.ndarray, ndim: int, dims, bc_kinds,
                shift: int, has_coarse: bool, noct_pad: int,
                pads) -> Optional[dict]:
    """Blocked tile tables of one partial level in one native pass
    (``amr/maps.py::build_block_maps`` says what each table is).

    ``keys_*``: sorted oct keys of levels l-1, l, l+1 (empty where the
    level is absent); ``dims``: cells per dim at l; ``bc_kinds[d] = (low,
    high)``; ``has_coarse``: missed cells get interpolation rows (false at
    ``levelmin``); ``pads(ntile, ni) -> (ntile_pad, ni_pad)``: the
    caller's buckets, asked once the pass has counted.  Returns the
    counts and the padded arrays, or only ``missing_fathers`` (> 0) where
    2:1 gradedness is violated."""
    L = lib()
    if L is None:
        return None
    km, kl, kp = (np.ascontiguousarray(k, dtype=np.int64)
                  for k in (keys_m1, keys_l, keys_p1))
    dims = np.ascontiguousarray(dims, dtype=np.int64)
    bc = np.ascontiguousarray(bc_kinds, dtype=np.int64).reshape(-1)
    if (ndim not in (1, 2, 3) or shift < 0 or dims.shape != (ndim,)
            or bc.shape != (2 * ndim,) or km.ndim != 1 or kl.ndim != 1
            or kp.ndim != 1):
        raise ValueError(f"tile_tables: ndim={ndim} shift={shift} "
                         f"dims={dims.shape} bc={bc.shape}")
    counts = np.zeros(4, dtype=np.int64)
    plan = L.tile_plan(km, len(km), kl, len(kl), kp, len(kp), ndim, shift,
                       dims, bc, int(has_coarse), counts)
    try:
        ntile, ni, any_refl, nmiss = (int(c) for c in counts)
        if nmiss:
            return {"missing_fathers": nmiss}
        ntile_pad, ni_pad = pads(ntile, ni)
        nslot = ((1 << (shift + 1)) + 4) ** ndim
        ncell_pad = noct_pad << ndim
        out = {
            "tile_src": np.empty((ntile_pad, nslot), dtype=np.int32),
            "tile_ok": np.empty((ntile_pad, nslot), dtype=bool),
            "tile_vsgn": (np.empty((ntile_pad, nslot), dtype=np.uint8)
                          if any_refl else None),
            "slot_ckey": np.empty((ntile, nslot), dtype=np.int64),
            "slot_vbits": (np.empty((ntile, nslot), dtype=np.uint8)
                           if any_refl else None),
            "interp_cell": np.empty(ni_pad, dtype=np.int32),
            "interp_nb": np.empty((ni_pad, ndim, 2), dtype=np.int32),
            "interp_sgn": np.empty((ni_pad, ndim), dtype=np.int8),
            "cell_tile": np.empty(ncell_pad, dtype=np.int32),
            "cell_slot": np.empty(ncell_pad, dtype=np.int32),
            "oct_tile": np.empty(noct_pad, dtype=np.int32),
            "oct_slot": np.empty(noct_pad, dtype=np.int32),
            "tile_key": np.empty(ntile, dtype=np.int64),
        }
        L.tile_emit(plan, kl, len(kl), ntile_pad, ni_pad, noct_pad,
                    *(a.ctypes.data if a is not None else None
                      for a in out.values()))
        out.update(ntile=ntile, ntile_pad=ntile_pad, ni=ni, ni_pad=ni_pad,
                   missing_fathers=0)
        return out
    finally:
        L.tile_free(plan)
