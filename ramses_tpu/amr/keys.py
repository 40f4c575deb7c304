"""Integer Morton (Z-order) keys for oct coordinates.

Replaces the reference's Hilbert state-machine keys (``amr/hilbert.f90:5-196``)
for *topology bookkeeping*: the tree only needs a total order with fast
encode/decode and uniqueness, which bit-interleaved int64 Morton codes give
without the reference's ``real*16 QUADHILBERT`` workaround (its level cap —
19 in 3D — came from squeezing keys into floats; int64 Morton supports 21
bits/dim in 3D).  Hilbert ordering still matters for *domain decomposition*
locality and is provided separately (``parallel/``); within a single host the
sorted Morton array is the whole "tree": membership = ``searchsorted``.
"""

from __future__ import annotations

import functools

import numpy as np


def _spread2(x: np.ndarray) -> np.ndarray:
    """Spread bits of x (< 2^31) with 1 zero between (2D interleave)."""
    x = x.astype(np.uint64)
    x = (x | (x << np.uint64(16))) & np.uint64(0x0000FFFF0000FFFF)
    x = (x | (x << np.uint64(8))) & np.uint64(0x00FF00FF00FF00FF)
    x = (x | (x << np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    x = (x | (x << np.uint64(2))) & np.uint64(0x3333333333333333)
    x = (x | (x << np.uint64(1))) & np.uint64(0x5555555555555555)
    return x


def _spread3(x: np.ndarray) -> np.ndarray:
    """Spread bits of x (< 2^21) with 2 zeros between (3D interleave)."""
    x = x.astype(np.uint64) & np.uint64(0x1FFFFF)
    x = (x | (x << np.uint64(32))) & np.uint64(0x1F00000000FFFF)
    x = (x | (x << np.uint64(16))) & np.uint64(0x1F0000FF0000FF)
    x = (x | (x << np.uint64(8))) & np.uint64(0x100F00F00F00F00F)
    x = (x | (x << np.uint64(4))) & np.uint64(0x10C30C30C30C30C3)
    x = (x | (x << np.uint64(2))) & np.uint64(0x1249249249249249)
    return x


def encode(ig: np.ndarray, ndim: int) -> np.ndarray:
    """Morton keys (int64) from integer coords ``ig [n, ndim]``."""
    ig = np.asarray(ig)
    if ndim == 1:
        return ig[:, 0].astype(np.int64)
    if len(ig) >= 4096:      # amortize the ctypes call
        from ramses_tpu import native
        nat = native.morton_encode(ig, ndim)
        if nat is not None:
            return nat
    if ndim == 2:
        return (_spread2(ig[:, 0]) | (_spread2(ig[:, 1]) << np.uint64(1))
                ).astype(np.int64)
    return (_spread3(ig[:, 0]) | (_spread3(ig[:, 1]) << np.uint64(1))
            | (_spread3(ig[:, 2]) << np.uint64(2))).astype(np.int64)


def _compact2(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) & np.uint64(0x5555555555555555)
    x = (x | (x >> np.uint64(1))) & np.uint64(0x3333333333333333)
    x = (x | (x >> np.uint64(2))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    x = (x | (x >> np.uint64(4))) & np.uint64(0x00FF00FF00FF00FF)
    x = (x | (x >> np.uint64(8))) & np.uint64(0x0000FFFF0000FFFF)
    x = (x | (x >> np.uint64(16))) & np.uint64(0x00000000FFFFFFFF)
    return x


def _compact3(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) & np.uint64(0x1249249249249249)
    x = (x | (x >> np.uint64(2))) & np.uint64(0x10C30C30C30C30C3)
    x = (x | (x >> np.uint64(4))) & np.uint64(0x100F00F00F00F00F)
    x = (x | (x >> np.uint64(8))) & np.uint64(0x1F0000FF0000FF)
    x = (x | (x >> np.uint64(16))) & np.uint64(0x1F00000000FFFF)
    x = (x | (x >> np.uint64(32))) & np.uint64(0x1FFFFF)
    return x


def decode(keys: np.ndarray, ndim: int) -> np.ndarray:
    """Integer coords ``[n, ndim]`` from Morton keys."""
    k = np.asarray(keys).astype(np.uint64)
    if ndim == 1:
        return k.astype(np.int64)[:, None]
    if ndim == 2:
        return np.stack([_compact2(k), _compact2(k >> np.uint64(1))],
                        axis=1).astype(np.int64)
    return np.stack([_compact3(k), _compact3(k >> np.uint64(1)),
                     _compact3(k >> np.uint64(2))], axis=1).astype(np.int64)


@functools.lru_cache(maxsize=256)
def axis_bits(x: int, axis: int, ndim: int) -> np.int64:
    """Key bits of the single coordinate ``x`` on ``axis``."""
    row = np.zeros((1, ndim), dtype=np.int64)
    row[0, axis] = x
    return encode(row, ndim)[0]


def neighbor_keys(keys: np.ndarray, axis: int, ndim: int, n: int,
                  periodic: bool):
    """Keys of the -1 and +1 neighbours along ``axis`` of in-domain
    cells on an ``n``-wide axis, by dilated-integer arithmetic on the
    axis's interleaved bits (the other axes' bits pass through).  At a
    face a periodic axis wraps and any other saturates — what
    ``tree.map_coords`` gives a +-1 offset for reflecting and outflow
    alike.  ``n`` need not be a power of two (non-unit root)."""
    mask = axis_bits((1 << (63 // ndim)) - 1, axis, ndim)
    top = axis_bits(n - 1, axis, ndim)
    ax = keys & mask
    rest = keys ^ ax
    dn = ((ax - 1) & mask) | rest
    up = (((keys | ~mask) + 1) & mask) | rest
    dn = np.where(ax == 0, rest | top if periodic else keys, dn)
    up = np.where(ax == top, rest if periodic else keys, up)
    return dn, up
