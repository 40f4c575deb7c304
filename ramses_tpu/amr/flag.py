"""Refinement flag computation — ``flag_fine`` (``amr/flag_utils.f90:57-718``).

Per level: device gradient criteria (``hydro_refine``) + host geometric
criteria, ``nexpand``-fold dilation (``smooth_fine``, ``:555``), then a
top-down nesting sweep that is the constructive form of the reference's
2:1 ``ensure_ref_rules`` (``:213``): a cell at level l is flagged whenever
any flagged cell x at level l+1 has a father-neighbourhood cell
``(x+e)>>1`` equal to it — this guarantees every surviving oct's 3^ndim
father-cell stencil exists.

The device hands the criteria over as one byte per oct (bit ``j`` = the
oct's flat-offset cell ``j``); ``flagged_cells`` decodes only the bytes
that are non-zero into the ascending flat-cell indices of the flagged
cells, so no per-cell array of a whole level is formed on the host.

The tree build works on sorted Morton cell keys end to end
(``compute_new_tree``): a level's flagged cells are the keys of its
flagged flat-cell indices, a 3^ndim dilation is ``ndim`` separable
passes of +-1 key arithmetic (``keys.neighbor_keys``) with a
sort-and-dedupe after each, nesting is one more dilation and
``>> ndim``, and the cell keys of level l are the oct keys of level
l+1.  No coordinate array of a whole level is formed; coordinates are
decoded only for the new partial levels' ``og``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ramses_tpu.amr import keys as kmod
from ramses_tpu.amr.tree import Octree, cell_offsets
from ramses_tpu.config import Params


def _dilate_keys(ks: np.ndarray, dims, periodic, ndim: int) -> np.ndarray:
    """One smoothing pass: the 3^ndim dilation of a cell-key set, as one
    (keep, -1, +1) pass per axis; returns sorted unique keys."""
    for d in range(ndim):
        dn, up = kmod.neighbor_keys(ks, d, ndim, dims[d], periodic[d])
        ks = np.unique(np.concatenate([ks, dn, up]))
    return ks


def geometry_flags(centers: np.ndarray, lvl: int, p: Params) -> np.ndarray:
    """Geometric refinement region of this level
    (``amr/flag_utils.f90:494-553``): generalized-ellipsoid ball around
    (x_refine, y_refine, z_refine) with radius r_refine, semi-axis ratios
    a/b_refine and p-norm exp_refine.  r_refine < 0 → disabled."""
    r = p.refine
    i = lvl - 1                                        # 1-based level lists
    if i >= len(r.r_refine) or r.r_refine[i] <= 0.0:
        return np.zeros(len(centers), dtype=bool)
    cen = [r.x_refine[i], r.y_refine[i], r.z_refine[i]][:p.ndim]
    ax = [1.0, r.a_refine[i], r.b_refine[i]][:p.ndim]
    en = float(r.exp_refine[i])
    rr = np.zeros(len(centers))
    for d in range(p.ndim):
        t = np.abs(centers[:, d] - cen[d]) / ax[d]
        rr += t ** min(en, 10.0) if en < 10.0 else 0.0
    if en < 10.0:
        rr = rr ** (1.0 / en)
    else:
        rr = np.maximum.reduce(
            [np.abs(centers[:, d] - cen[d]) / ax[d] for d in range(p.ndim)])
    return rr < float(r.r_refine[i])


def flagged_cells(packed: np.ndarray, ndim: int, noct: int,
                  oct_row: Optional[np.ndarray] = None):
    """Sparse decode of one level's bitpacked criteria flags.

    ``packed``: uint8, one byte per oct ROW as fetched from the device
    (bit ``j`` = flat-offset cell ``j`` of the oct; rows past ``noct`` are
    padding).  ``oct_row``: tree oct -> row of a layout-permuted level,
    else the first ``noct`` rows are the octs in tree order.  Returns
    ``(cells, nocts)``: the ascending int64 flat-cell indices
    ``oct << ndim | j`` of the flagged cells (``np.flatnonzero`` of the
    level's per-cell mask) and how many octs held one.  Only the
    non-zero bytes are unpacked."""
    b = packed[oct_row] if oct_row is not None else packed[:noct]
    o = np.flatnonzero(b)
    ttd = 1 << ndim
    bits = np.unpackbits(b[o, None], axis=1, count=ttd,
                         bitorder="little").astype(bool)
    cells = ((o[:, None] << ndim) | np.arange(ttd))[bits]
    return cells, len(o)


def compute_new_tree(tree: Octree, crit_flags: Dict[int, np.ndarray],
                     bc_kinds, params: Params) -> Octree:
    """New octree from per-level flagged cells.

    ``crit_flags[l]``: the ascending flat-cell indices (int64) of the
    flagged cells of level l on the CURRENT tree.  Returns a
    tree whose level-(l+1) oct set is exactly the flagged cell set of level
    l after smoothing + nesting.  Its base level is ``tree``'s own
    ``OctLevel`` (complete and never mutated in place).
    """
    ndim = tree.ndim
    lmin, lmax = tree.levelmin, tree.levelmax
    nexpand = params.amr.nexpand
    periodic = [tuple(bc_kinds[d]) == (0, 0) for d in range(ndim)]
    # flat-cell offset (x slowest) -> Morton child bits (x is bit 0)
    child = kmod.encode(cell_offsets(ndim), ndim)

    # flagged cell keys per level (sorted), smoothed
    fkeys: Dict[int, np.ndarray] = {}
    for l in range(lmin, lmax + 1):
        i = crit_flags.get(l)
        if i is None or not tree.has(l):
            fkeys[l] = np.zeros(0, dtype=np.int64)
            continue
        if i.dtype == bool:    # a mask would index cells 0 and 1 silently
            raise TypeError("compute_new_tree takes flagged-cell indices "
                            "(np.flatnonzero of a mask), not the mask")
        ks = np.sort((tree.levels[l].keys[i >> ndim] << ndim)
                     | child[i & ((1 << ndim) - 1)])
        ne = nexpand[l - 1] if l - 1 < len(nexpand) else 1
        for _ in range(max(int(ne), 0)):
            ks = _dilate_keys(ks, tree.cell_dims(l), periodic, ndim)
        fkeys[l] = ks

    # top-down nesting: project fine flags into father-neighbourhood flags
    for l in range(lmax, lmin, -1):
        up = _dilate_keys(fkeys[l], tree.cell_dims(l), periodic, ndim)
        fkeys[l - 1] = np.unique(np.concatenate([fkeys[l - 1],
                                                 up >> ndim]))

    # flags only refine existing cells: intersect with current cell sets
    new = Octree(ndim, lmin, lmax, root=tree.root)
    new.levels[lmin] = tree.levels[lmin]               # base stays complete
    for l in range(lmin, lmax):
        ks = fkeys[l]
        # a flagged cell must exist on the (new) level l to spawn an oct
        ks = ks[new.lookup_keys(l, ks >> ndim) >= 0]
        if len(ks) == 0:
            break
        new.set_level_keys(l + 1, ks)                  # cell keys = oct keys
    return new
