"""Flat↔dense conversion for COMPLETE levels as a bit-permutation
reshape/transpose — no gather.

A complete level's flat row order is (sorted-Morton oct index) × (cell
offset): the sorted Morton keys of a full oct grid are simply
0..noct-1, so the flat cell index is a fixed *bit permutation* of the
dense C-order ravel index::

    flat bits (MSB→LSB):  [z_{l-1} y_{l-1} x_{l-1}] … [z_1 y_1 x_1] [x_0 y_0 z_0]
    dense bits (MSB→LSB): [x_{l-1} … x_0] [y_{l-1} … y_0] [z_{l-1} … z_0]

(x_k = bit k of the cell's x coordinate; the oct Morton triplets carry
coordinate bits 1..l-1 with z most significant per triplet —
``amr/keys.py`` ``encode`` — and the within-oct offset carries bit 0
with x slowest — ``amr/tree.py`` ``cell_offsets``.)

A gather by this permutation moves one ~nvar-float row per index: on
TPU that lowers to millions of latency-bound small copies.  Reshapes +
transposes express the same data movement with static regular strides
that XLA vectorizes.

The permutation is applied in STAGES, one Morton group (one bit of
every axis) at a time from the least significant up: each stage is a
single transpose of at most ``2*ndim + 2`` axes that moves whole
contiguous sub-boxes.  The one-shot form — reshape to ``(2,)*ndim*lvl``
axes and transpose once — is the same permutation, but the TPU compiler
takes 34–63 s for that 21-axis transpose at 128³ and, once the flag
criteria fuse into it, 394–478 s and 68 MB of code for the flags
program (sandbox compiles for a described v5e, PR 22); the staged form
compiles in ~1.5 s.  Pure data movement, so both are bitwise identical.

Only valid for cubic complete levels (2^lvl cells per dim); callers
fall back to the index-permutation gather otherwise (non-cubic roots).

Slab (shard-local) variant: fixing the top ``mbits`` flat index bits
selects one contiguous flat row chunk of ``ncell / 2^mbits`` rows — a
device's shard under the equal row-split ``P("oct")`` sharding.  The
remaining bits are a bit permutation of a DENSE SUB-BOX: the fixed top
bits are the most significant coordinate bits (z-major interleave), so
chunk ``D`` is the axis-aligned box whose per-axis origin is the
device-grid coordinate × the local extent.  Each shard can therefore
run the same reshape→transpose→reshape on only the rows it owns — no
cross-device data motion at all (:mod:`ramses_tpu.parallel.dense_slab`
builds the halo exchange separately).  ``mbits`` must not reach into
the within-oct bits (``mbits <= ndim*(lvl-1)``) so every chunk cut
lands on an oct boundary.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax.numpy as jnp


@lru_cache(maxsize=None)
def _bit_seq(lvl: int, ndim: int) -> tuple:
    """The flat index's bit slots MSB→LSB as (axis, coord_bit) pairs:
    oct Morton triplets (z most significant) then the within-oct
    offset (x slowest)."""
    seq = [(d, i) for i in range(lvl - 1, 0, -1)
           for d in range(ndim - 1, -1, -1)]
    seq += [(d, 0) for d in range(ndim)]
    return tuple(seq)


@lru_cache(maxsize=None)
def _stages(lvl: int, ndim: int, mbits: int = 0) -> tuple:
    """Per Morton group ``k = 1..lvl-1`` (least significant first): the
    axes whose coordinate bit ``k`` is still a flat index bit after the
    top ``mbits`` device bits are fixed, in flat MSB→LSB order, with
    the transpose that merges them into the local box.  The cut takes
    whole groups from the top and at most one group partly, so the
    list simply ends where the cut begins.

    Stage input ``[A, 2 per kept axis, s_0..s_{ndim-1}, W]`` (``s_d`` =
    local oct-grid extent of axis d assembled so far, ``W`` = the cells
    of one oct × trailing); the permutation puts each kept axis' new
    bit directly above that axis' extent."""
    kept = set(_bit_seq(lvl, ndim)[mbits:])
    out = []
    for k in range(1, lvl):
        axes = tuple(d for d in range(ndim - 1, -1, -1) if (d, k) in kept)
        if not axes:
            break
        na = len(axes)
        perm = [0]
        for d in range(ndim):
            if d in axes:
                perm.append(1 + axes.index(d))
            perm.append(1 + na + d)
        perm.append(1 + na + ndim)
        inv = [0] * len(perm)
        for i, p in enumerate(perm):
            inv[p] = i
        out.append((axes, tuple(perm), tuple(inv)))
    return tuple(out)


@lru_cache(maxsize=None)
def grid_bits(lvl: int, ndim: int, mbits: int) -> tuple:
    """Per-axis device-bit counts of an ``mbits``-bit chunk split: the
    top ``mbits`` flat bits in MSB→LSB order, tallied by axis.  The
    device grid is ``(2^b for b in grid_bits)`` and the local box is
    ``(2^(lvl-b))`` — z is cut first (it carries the most significant
    flat bits), then y, then x."""
    if mbits > ndim * (lvl - 1):
        raise ValueError(
            f"mbits={mbits} would cut inside octs at lvl={lvl}")
    md = [0] * ndim
    for d, _ in _bit_seq(lvl, ndim)[:mbits]:
        md[d] += 1
    return tuple(md)


@lru_cache(maxsize=None)
def slab_shape(lvl: int, ndim: int, mbits: int) -> tuple:
    """Local dense sub-box shape owned by one of ``2^mbits`` chunks."""
    return tuple(1 << (lvl - b) for b in grid_bits(lvl, ndim, mbits))


@lru_cache(maxsize=None)
def chunk_coords(lvl: int, ndim: int, mbits: int) -> tuple:
    """Device-grid coordinates of every chunk: ``coords[D][d]`` is
    chunk D's position along axis d (D = the top ``mbits`` flat bits
    verbatim; its axis-d bits are the coordinate's high bits in
    order)."""
    seq = _bit_seq(lvl, ndim)[:mbits]
    out = []
    for D in range(1 << mbits):
        g = [0] * ndim
        for j, (d, _) in enumerate(seq):
            g[d] = (g[d] << 1) | ((D >> (mbits - 1 - j)) & 1)
        out.append(tuple(g))
    return tuple(out)


@lru_cache(maxsize=None)
def _offset_perm(ndim: int) -> tuple:
    """``[s_0..s_{nd-1}, 2_0..2_{nd-1}, T]`` → ``[s_0, 2_0, s_1, 2_1,
    ..., T]``: the last step, putting every axis' within-oct bit below
    that axis' oct coordinate.  Returns (perm, inverse)."""
    perm = tuple(x for d in range(ndim) for x in (d, ndim + d)) \
        + (2 * ndim,)
    return perm, tuple(perm.index(i) for i in range(len(perm)))


def flat_to_dense_slab(rows, lvl: int, ndim: int, mbits: int):
    """One chunk's flat-order rows ``[ncell/2^mbits, *trailing]`` →
    its dense local sub-box ``slab_shape + trailing`` (staged
    reshape/transposes, shard-local).

    The ``2^ndim`` cells of an oct ride through the stages as part of
    the minor (trailing) axis and are interleaved last: every
    intermediate keeps a minor axis of at least ``2^ndim`` elements —
    a bare ``[n, n, n]`` mask staged with a minor axis of 1–2 elements
    costs the TPU compiler 30 s at 64³ where this form costs 0.3 s."""
    loc = slab_shape(lvl, ndim, mbits)
    trailing = rows.shape[1:]
    T = math.prod(trailing)
    W = T << ndim
    s = [1] * ndim                      # oct-grid extent assembled so far
    a = rows.reshape((-1,) + tuple(s) + (W,))
    for axes, perm, _ in _stages(lvl, ndim, mbits):
        A = a.shape[0] >> len(axes)
        a = a.reshape((A,) + (2,) * len(axes) + tuple(s) + (W,))
        a = jnp.transpose(a, perm)
        for d in axes:
            s[d] *= 2
        a = a.reshape((A,) + tuple(s) + (W,))
    a = a.reshape(tuple(s) + (2,) * ndim + (T,))
    return jnp.transpose(a, _offset_perm(ndim)[0]).reshape(loc + trailing)


def dense_to_flat_slab(dense, lvl: int, ndim: int, mbits: int):
    """Dense local sub-box → one chunk's flat-order rows (inverse of
    :func:`flat_to_dense_slab`)."""
    ncell = 1 << (ndim * lvl - mbits)
    trailing = dense.shape[ndim:]
    T = math.prod(trailing)
    W = T << ndim
    s = [n // 2 for n in slab_shape(lvl, ndim, mbits)]
    a = dense.reshape(tuple(x for n in s for x in (n, 2)) + (T,))
    a = jnp.transpose(a, _offset_perm(ndim)[1]).reshape(
        (1,) + tuple(s) + (W,))
    for axes, _, inv in reversed(_stages(lvl, ndim, mbits)):
        A = a.shape[0]
        split = []
        for d in range(ndim):
            if d in axes:
                s[d] //= 2
                split += [2, s[d]]
            else:
                split.append(s[d])
        a = jnp.transpose(a.reshape((A,) + tuple(split) + (W,)), inv)
        a = a.reshape((A << len(axes),) + tuple(s) + (W,))
    return a.reshape((ncell,) + trailing)


def flat_index_np(coords, lvl: int, ndim: int):
    """Host-side (numpy) flat row index of dense cell coordinates —
    the scalar form of the bit permutation above, for map builders that
    need Morton-interleaved scatter targets (``mhd/amr.py`` builds its
    slab-path EMF override indices with this instead of a C-order
    ``ravel_multi_index``).  ``coords``: int array ``[..., ndim]``
    (values in ``[0, 2^lvl)``); returns int64 flat indices of shape
    ``coords.shape[:-1]``."""
    import numpy as np
    coords = np.asarray(coords)
    seq = _bit_seq(lvl, ndim)
    nb = len(seq)
    flat = np.zeros(coords.shape[:-1], dtype=np.int64)
    for p, (d, i) in enumerate(seq):
        flat |= ((coords[..., d].astype(np.int64) >> i) & 1) << (nb - 1 - p)
    return flat


def flat_to_dense(rows, lvl: int, ndim: int):
    """[ncell(+pad), *trailing] flat-order rows → dense
    ``(2^lvl,)*ndim + trailing`` array (pure reshape/transpose)."""
    n = 1 << lvl
    ncell = n ** ndim
    return flat_to_dense_slab(rows[:ncell], lvl, ndim, 0)


def dense_to_flat(dense, lvl: int, ndim: int):
    """Dense ``(2^lvl,)*ndim + trailing`` array → [ncell, *trailing]
    flat-order rows (inverse of :func:`flat_to_dense`)."""
    return dense_to_flat_slab(dense, lvl, ndim, 0)
