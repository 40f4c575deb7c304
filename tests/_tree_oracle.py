"""The tree build as it stood before PR 28, kept verbatim as the oracle of
``tests/test_tree_build.py``: ``compute_new_tree`` on per-cell coordinate
arrays (``Octree.cell_coords`` of every level, 3^ndim offsets through
``map_coords``, Morton encode / ``np.unique`` / decode per pass).  The
package's ``ramses_tpu.amr.flag.compute_new_tree`` must return bitwise
this tree.  Not a test module (leading underscore)."""

from __future__ import annotations

import itertools
from typing import Dict

import numpy as np

from ramses_tpu.amr import keys as kmod
from ramses_tpu.amr.tree import Octree, map_coords
from ramses_tpu.config import Params


def _neighbor_offsets(ndim: int) -> np.ndarray:
    return np.array(list(itertools.product((-1, 0, 1), repeat=ndim)),
                    dtype=np.int64)


def dilate(flag_coords: np.ndarray, lvl: int, bc_kinds, ndim: int,
           dims=None) -> np.ndarray:
    """One smoothing pass: the 3^ndim dilation of the flagged cell set."""
    if len(flag_coords) == 0:
        return flag_coords
    offs = _neighbor_offsets(ndim)
    ex = (flag_coords[:, None, :] + offs[None, :, :]).reshape(-1, ndim)
    ex, _ = map_coords(ex, lvl, bc_kinds, ndim, dims=dims)
    ks = np.unique(kmod.encode(ex, ndim))
    return kmod.decode(ks, ndim)


def compute_new_tree(tree: Octree, crit_flags: Dict[int, np.ndarray],
                     bc_kinds, params: Params) -> Octree:
    """New octree from per-level per-cell criteria flags.

    ``crit_flags[l]``: bool [ncell_flat(l)] on the CURRENT tree.  Returns a
    tree whose level-(l+1) oct set is exactly the flagged cell set of level
    l after smoothing + nesting.
    """
    ndim = tree.ndim
    lmin, lmax = tree.levelmin, tree.levelmax
    nexpand = params.amr.nexpand

    # flagged cell coordinate sets per level, smoothed
    fcoords: Dict[int, np.ndarray] = {}
    for l in range(lmin, lmax + 1):
        if not tree.has(l):
            fcoords[l] = np.zeros((0, ndim), dtype=np.int64)
            continue
        cc = tree.cell_coords(l)
        f = crit_flags.get(l)
        coords = cc[f] if f is not None and f.any() else \
            np.zeros((0, ndim), dtype=np.int64)
        ne = nexpand[l - 1] if l - 1 < len(nexpand) else 1
        for _ in range(max(int(ne), 0)):
            coords = dilate(coords, l, bc_kinds, ndim,
                            dims=tree.cell_dims(l))
        fcoords[l] = coords

    # top-down nesting: project fine flags into father-neighbourhood flags
    offs = _neighbor_offsets(ndim)
    for l in range(lmax, lmin, -1):
        x = fcoords[l]
        if len(x) == 0:
            continue
        ex = (x[:, None, :] + offs[None, :, :]).reshape(-1, ndim)
        ex, _ = map_coords(ex, l, bc_kinds, ndim, dims=tree.cell_dims(l))
        up = ex >> 1
        ks = np.unique(kmod.encode(up, ndim))
        prev = kmod.encode(fcoords[l - 1], ndim) if len(fcoords[l - 1]) \
            else np.zeros(0, dtype=np.int64)
        allk = np.unique(np.concatenate([prev, ks]))
        fcoords[l - 1] = kmod.decode(allk, ndim)

    # flags only refine existing cells: intersect with current cell sets
    new = Octree(ndim, lmin, lmax, root=tree.root)
    new.set_level(lmin, tree.levels[lmin].og)          # base stays complete
    for l in range(lmin, lmax):
        coords = fcoords[l]
        if len(coords) == 0:
            break
        # a flagged cell must exist on the (new) level l to spawn an oct
        parent = new.lookup(l, coords >> 1)
        coords = coords[parent >= 0]
        if len(coords) == 0:
            break
        new.set_level(l + 1, coords)                   # cell coords = oct
    return new
