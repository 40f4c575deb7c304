"""``flag.compute_new_tree`` (sorted Morton keys end to end) against the
coordinate-array build it replaced (``tests/_tree_oracle.py``): the same
flags on the same tree must give bitwise the same tree, for every
``ndim``, boundary kind, root and ``nexpand``; and each result must keep
the guarantees the module states."""

import itertools

import numpy as np
import pytest

from ramses_tpu.amr import flag as flagmod
from ramses_tpu.amr import keys as kmod
from ramses_tpu.amr.tree import Octree, map_coords
from ramses_tpu.config import params_from_dict
from tests import _tree_oracle as oracle

PERIODIC, REFLECTING, OUTFLOW = (0, 0), (1, 1), (2, 2)
BCS = {"periodic": [PERIODIC] * 3, "reflecting": [REFLECTING] * 3,
       "outflow": [OUTFLOW] * 3,
       # walls of two kinds, and an axis periodic on one side only
       "mixed": [(1, 2), (0, 0), (2, 1)], "halfperiodic": [(0, 2), (1, 0), (0, 0)]}
LMIN = {1: 5, 2: 4, 3: 3}


def _case(ndim, bc, mode="random", root=None, nexpand=1, depth=2,
          levelmax=None, seed=0):
    root = tuple(root) if root is not None else (1,) * ndim
    ne = nexpand if isinstance(nexpand, list) else [nexpand]
    name = (f"{ndim}d-{bc}-{mode}-root{'x'.join(map(str, root))}"
            f"-nexpand{''.join(map(str, ne))}")
    if levelmax is not None:
        name += f"-lmax{levelmax}"
    return pytest.param(dict(ndim=ndim, bc=BCS[bc][:ndim], mode=mode,
                             root=root, nexpand=nexpand, depth=depth,
                             levelmax=levelmax, seed=seed), id=name)


CASES = (
    # every ndim under every boundary kind, random flags, three levels
    [_case(n, bc, seed=7 * n + i) for n in (1, 2, 3)
     for i, bc in enumerate(BCS)]
    # non-unit roots: one a power of two, one not (dims no power of two)
    + [_case(1, "periodic", root=(3,)), _case(1, "mixed", root=(2,)),
       _case(2, "periodic", root=(3, 1)), _case(2, "mixed", root=(1, 2)),
       _case(3, "periodic", root=(2, 1, 1)),
       _case(3, "reflecting", root=(1, 3, 2)),
       _case(3, "mixed", mode="faces", root=(2, 1, 3))]
    # nexpand 0 and 2, and a different count on every level
    + [_case(n, bc, nexpand=ne, seed=3)
       for n in (1, 2, 3) for bc, ne in (("periodic", 0), ("reflecting", 2))]
    + [_case(2, "outflow", nexpand=[2, 2, 2, 0, 1, 2]),
       _case(3, "periodic", nexpand=[1, 1, 1, 2, 0, 1])]
    # flags on every face and corner of the box, on every level
    + [_case(n, bc, mode="faces") for n in (1, 2, 3)
       for bc in ("periodic", "reflecting", "outflow", "halfperiodic")]
    # flags on level lmax only (they refine nothing, but nest downwards)
    + [_case(n, "periodic", mode="lmax_only") for n in (2, 3)]
    # a middle level without flags (no entry) and one with none set
    + [_case(n, "reflecting", mode="gap") for n in (1, 2, 3)]
    # nothing flagged at all: the tree falls back to its base level
    + [_case(3, "outflow", mode="none")]
    # the finest level is empty: two levels of a three- and four-level range
    + [_case(n, bc, depth=1, levelmax=LMIN[n] + dl)
       for n, bc, dl in ((2, "periodic", 2), (3, "mixed", 2),
                         (3, "reflecting", 3))]
)


def _params(c):
    lmin = LMIN[c["ndim"]]
    lmax = c["levelmax"] or lmin + c["depth"]
    p = params_from_dict({"amr_params": {"levelmin": lmin, "levelmax": lmax}},
                         ndim=c["ndim"])
    ne = c["nexpand"]
    p.amr.nexpand = list(ne) if isinstance(ne, list) else [ne] * lmax
    return p


def _on_faces(tree, l, rng):
    """Every corner cell the level holds, and a third of its cells on
    any face."""
    cc = tree.cell_coords(l)
    edge = (cc == 0) | (cc == np.array(tree.cell_dims(l)) - 1)
    return edge.all(axis=1) | (edge.any(axis=1) & (rng.random(len(cc)) < 0.3))


def _flags(tree, mode, rng):
    levels = [l for l in range(tree.levelmin, tree.levelmax + 1)
              if tree.has(l)]
    if mode == "none":
        return {l: np.zeros(tree.noct(l) << tree.ndim, bool) for l in levels}
    if mode == "faces":
        return {l: _on_faces(tree, l, rng) for l in levels}
    dens = {1: 0.2, 2: 0.08, 3: 0.04}[tree.ndim]
    fl = {l: rng.random(tree.noct(l) << tree.ndim) < dens for l in levels}
    if mode == "lmax_only":
        return {tree.levelmax: fl[tree.levelmax]}
    if mode == "gap":
        del fl[levels[1]]
        fl[levels[-1]][:] = False
    return fl


def _cells(flags):
    """The program's input form: per level the ascending flat-cell
    indices of the flagged cells (the oracle keeps taking the masks)."""
    return {l: np.flatnonzero(f) for l, f in flags.items()}


def _tree_and_flags(c):
    """A ``depth``-deep tree grown by the ORACLE from the base level
    (random flags, or flags on the faces), and the flags to compare."""
    rng = np.random.default_rng(c["seed"])
    p = _params(c)
    lmin = p.amr.levelmin
    tree = Octree.base(c["ndim"], lmin, p.amr.levelmax, root=c["root"])
    grow = "faces" if c["mode"] == "faces" else "random"
    for _ in range(c["depth"]):
        tree = oracle.compute_new_tree(tree, _flags(tree, grow, rng),
                                       c["bc"], p)
    assert tree.finest == lmin + c["depth"], "the case lost its depth"
    return tree, _flags(tree, c["mode"], rng), p


@pytest.mark.parametrize("c", CASES)
def test_tree_is_the_oracles(c):
    tree, flags, p = _tree_and_flags(c)
    want = oracle.compute_new_tree(tree, flags, c["bc"], p)
    got = flagmod.compute_new_tree(tree, _cells(flags), c["bc"], p)
    assert sorted(got.levels) == sorted(want.levels)
    assert (got.ndim, got.levelmin, got.levelmax, got.root) == \
        (want.ndim, want.levelmin, want.levelmax, want.root)
    for l, w in want.levels.items():
        g = got.levels[l]
        assert g.lvl == w.lvl
        assert g.keys.dtype == w.keys.dtype and g.og.dtype == w.og.dtype
        assert np.array_equal(g.keys, w.keys), l
        assert np.array_equal(g.og, w.og), l
    if c["mode"] == "none":
        assert sorted(got.levels) == [tree.levelmin]
    elif c["mode"] != "gap" and c["levelmax"] is None:
        assert got.has(tree.levelmax), "the case compares no fine level"


@pytest.mark.parametrize("c", CASES)
def test_tree_invariants(c):
    tree, flags, p = _tree_and_flags(c)
    new = flagmod.compute_new_tree(tree, _cells(flags), c["bc"], p)
    ndim, lmin = tree.ndim, tree.levelmin
    # the complete base level is shared, not rebuilt: whoever mutates a
    # level's arrays in place breaks the OLD tree too, and fails here
    assert new.levels[lmin] is tree.levels[lmin]
    offs = np.array(list(itertools.product((-1, 0, 1), repeat=ndim)))
    for l, lev in new.levels.items():
        assert lev.keys.dtype == np.int64 and lev.og.dtype == np.int64
        assert lev.keys.ndim == 1 and np.all(np.diff(lev.keys) > 0)
        assert np.array_equal(lev.og, kmod.decode(lev.keys, ndim))
        assert np.all(lev.og < np.array(new.oct_dims(l)))
        if l == lmin:
            continue
        # the module's guarantee: the 3^ndim father-cell neighbourhood of
        # every oct exists on the level below
        ex = (lev.og[:, None, :] + offs[None]).reshape(-1, ndim)
        ex, _ = map_coords(ex, l - 1, c["bc"], ndim,
                           dims=new.cell_dims(l - 1))
        assert np.all(new.lookup(l - 1, ex >> 1) >= 0), l


def test_neighbor_keys_are_map_coords():
    """``keys.neighbor_keys`` against ``map_coords`` on every cell of a
    small non-cubic box, wrapping and saturating."""
    for ndim, dims in ((1, (6,)), (2, (8, 12)), (3, (4, 6, 2))):
        cc = np.stack([g.ravel() for g in np.meshgrid(
            *[np.arange(n) for n in dims], indexing="ij")], axis=1)
        ks = kmod.encode(cc, ndim)
        for d, kind in itertools.product(range(ndim), (0, 1, 2)):
            bc = [(kind, kind)] * ndim
            got = kmod.neighbor_keys(ks, d, ndim, dims[d], kind == 0)
            for step, g in zip((-1, 1), got):
                sh = cc.copy()
                sh[:, d] += step
                sh, _ = map_coords(sh, 0, bc, ndim, dims=dims)
                assert g.dtype == np.int64
                assert np.array_equal(g, kmod.encode(sh, ndim)), (dims, d)
