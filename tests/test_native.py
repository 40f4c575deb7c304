"""Native C++ kernel tests: equivalence with the numpy paths and the
mathematical properties of the space-filling curves."""

import itertools

import numpy as np
import pytest

from ramses_tpu import native
from ramses_tpu.amr import keys as kmod
from ramses_tpu.amr import maps as mapmod
from ramses_tpu.amr.hilbert import _hilbert_numpy, hilbert_key


def _grid(nbits, ndim):
    n = 1 << nbits
    ax = np.arange(n, dtype=np.int64)
    g = np.meshgrid(*([ax] * ndim), indexing="ij")
    return np.stack([x.ravel() for x in g], axis=1)


@pytest.fixture(scope="module")
def has_native():
    return native.lib() is not None


def test_native_builds(has_native):
    assert has_native, "g++ present but native library failed to build"


@pytest.mark.parametrize("ndim", [2, 3])
def test_morton_native_matches_numpy(has_native, ndim):
    if not has_native:
        pytest.skip("no native lib")
    rng = np.random.default_rng(0)
    og = rng.integers(0, 1 << 20 if ndim == 2 else 1 << 15,
                      size=(5000, ndim))
    nat = native.morton_encode(og, ndim)
    ref = kmod.encode(og[:10], ndim)   # small → numpy path
    assert np.array_equal(nat[:10], ref)


@pytest.mark.parametrize("ndim,nbits", [(2, 5), (3, 3)])
def test_hilbert_native_matches_numpy(has_native, ndim, nbits):
    if not has_native:
        pytest.skip("no native lib")
    og = _grid(nbits, ndim)
    nat = native.hilbert_encode(og, ndim, nbits)
    ref = _hilbert_numpy(og, ndim, nbits)
    assert np.array_equal(nat, ref)


@pytest.mark.parametrize("ndim,nbits", [(2, 4), (3, 3)])
def test_hilbert_bijective_and_unit_stride(ndim, nbits):
    """Keys are a bijection onto [0, 2^(ndim·nbits)) and consecutive keys
    are grid neighbours (THE Hilbert property)."""
    og = _grid(nbits, ndim)
    keys = hilbert_key(og, ndim, nbits)
    nk = 1 << (ndim * nbits)
    assert len(np.unique(keys)) == len(keys) == nk
    assert keys.min() == 0 and keys.max() == nk - 1
    order = np.argsort(keys)
    path = og[order]
    steps = np.abs(np.diff(path, axis=0))
    assert np.all(steps.sum(axis=1) == 1), "curve is not unit-stride"


def test_hilbert_locality_beats_morton():
    """Mean |Δposition| between key-consecutive cells: Hilbert = 1 by
    construction, Morton jumps across the box."""
    og = _grid(4, 2)
    hk = hilbert_key(og, 2, 4)
    mk = kmod.encode(og, 2)
    jump_h = np.abs(np.diff(og[np.argsort(hk)], axis=0)).sum(1).mean()
    jump_m = np.abs(np.diff(og[np.argsort(mk)], axis=0)).sum(1).mean()
    assert jump_h == 1.0
    assert jump_m > 1.5


def test_lookup_native_matches_numpy(has_native):
    if not has_native:
        pytest.skip("no native lib")
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 1 << 40, size=8000))
    q = np.concatenate([rng.choice(keys, 3000),
                        rng.integers(0, 1 << 40, size=3000)])
    nat = native.lookup_sorted(keys, q)
    pos = np.searchsorted(keys, q)
    pos = np.clip(pos, 0, len(keys) - 1)
    ref = np.where(keys[pos] == q, pos, -1)
    assert np.array_equal(nat, ref)


def test_neighbor_lookup_periodic(has_native):
    if not has_native:
        pytest.skip("no native lib")
    from ramses_tpu.amr.tree import Octree
    t = Octree.base(2, 4, 4)          # full 8x8 oct grid at level 4
    lev = t.levels[4]
    offs = np.array(list(itertools.product((-1, 0, 1), repeat=2)),
                    dtype=np.int64)
    out = native.neighbor_lookup(lev.keys, lev.og, 2, 8, offs)
    # complete periodic level: every neighbour exists
    assert (out >= 0).all()
    # cross-check one oct against Octree.lookup
    i = 13
    for k, off in enumerate(offs):
        cc = np.mod(lev.og[i] + off, 8)[None, :]
        assert out[i, k] == t.lookup(4, cc)[0]


def test_fallback_env(monkeypatch):
    monkeypatch.setenv("RAMSES_TPU_NATIVE", "0")
    assert native.lib() is None
    og = _grid(3, 2)
    keys = hilbert_key(og, 2, 3)      # numpy fallback still works
    assert len(np.unique(keys)) == 64


# ----------------------------------------------------------------------
# blocked tile tables: the one native pass against the numpy passes
# ----------------------------------------------------------------------
# every BlockMaps field that is uploaded or read by
# ``balance.apply_layout_blocks`` (``maps.BLOCK_TABLES``), and what is
# kept for the next regrid
TABLES = mapmod.BLOCK_TABLES + ("tile_key", "slot_ckey")
COUNTS = ("lvl", "shift", "ntile", "ntile_pad", "ni", "ni_pad", "noct",
          "noct_pad", "blocks_rebuilt")
FACES = {"periodic": [(0, 0)] * 3, "reflecting": [(1, 1)] * 3,
         "outflow": [(2, 2)] * 3, "mixed": [(1, 2), (0, 0), (2, 1)]}
LMIN = {1: 6, 2: 5, 3: 4}
_TREES = {}


def _ball_tree(ndim, root=None, centre=0.12, radii=(0.3, 0.16, 0.08)):
    """Complete ``lmin`` and three nested partial levels: balls (nearest
    periodic image, so every face kind sees a graded tree) about a point
    near the low corner, where they cross the domain's faces."""
    from ramses_tpu.amr.tree import Octree
    key = (ndim, root, centre, radii)
    if key not in _TREES:
        lmin = LMIN[ndim]
        t = Octree.base(ndim, lmin, lmin + len(radii), root=root)
        ext = np.array(t.root, dtype=float)
        for i, r in enumerate(radii):
            lvl = lmin + 1 + i
            axes = [np.arange(n, dtype=np.int64) for n in t.oct_dims(lvl)]
            og = np.stack([g.ravel() for g in
                           np.meshgrid(*axes, indexing="ij")], axis=1)
            d = np.abs((og + 0.5) / (1 << (lvl - 1)) - centre)
            d = np.minimum(d, ext - d)
            t.set_level(lvl, og[np.sqrt((d * d).sum(axis=1)) < r])
        _TREES[key] = t
    return _TREES[key]


def _upto(tree, top, levelmin=None):
    """The same tree without its levels above ``top``."""
    from ramses_tpu.amr.tree import Octree
    t = Octree(tree.ndim, levelmin or tree.levelmin, tree.levelmax,
               root=tree.root)
    t.levels = {l: v for l, v in tree.levels.items() if l <= top}
    return t


def _build(monkeypatch, use_native, *args, **kw):
    monkeypatch.setenv("RAMSES_TPU_NATIVE", "1" if use_native else "0")
    return mapmod.build_block_maps(*args, **kw)


def _assert_same_tables(a, b):
    """``a`` by numpy, ``b`` by the native pass: equal to the element."""
    assert a.tiles_native == 0 and b.tiles_native == b.ntile > 0
    for f in COUNTS:
        assert getattr(a, f) == getattr(b, f), f
    for f in TABLES:
        x, y = getattr(a, f), getattr(b, f)
        if x is None or y is None:
            assert x is None and y is None, f
            continue
        assert x.dtype == y.dtype and x.shape == y.shape, \
            (f, x.dtype, y.dtype, x.shape, y.shape)
        assert np.array_equal(x, y), \
            (f, np.flatnonzero(x.ravel() != y.ravel())[:8])


@pytest.mark.parametrize("lm1", ["lm1-complete", "lm1-partial"])
@pytest.mark.parametrize("lp1", ["lp1-present", "lp1-absent"])
@pytest.mark.parametrize("prev", ["fresh", "prev"])
@pytest.mark.parametrize("faces", list(FACES))
@pytest.mark.parametrize("ndim", [1, 2, 3])
def test_tile_tables_native_equal_numpy(has_native, monkeypatch, ndim,
                                        faces, prev, lp1, lm1):
    """The tentpole's whole correctness argument: every table of a partial
    level from ONE native pass equals the numpy path's, same shapes,
    dtypes, pad values and row order."""
    if not has_native:
        pytest.skip("no native lib")
    bc = FACES[faces][:ndim]
    lvl = LMIN[ndim] + (1 if lm1 == "lm1-complete" else 2)
    tree = _upto(_ball_tree(ndim), lvl + (lp1 == "lp1-present"))
    pa = pb = None
    if prev == "prev":
        # the last regrid's tables: the same flow a tenth of the box
        # further along each axis, so some tile prefixes survive and
        # some are new
        old = _upto(_ball_tree(ndim, centre=0.22), lvl + 1)
        pa = _build(monkeypatch, False, old, lvl, bc)
        pb = _build(monkeypatch, True, old, lvl, bc)
    a = _build(monkeypatch, False, tree, lvl, bc, prev=pa)
    b = _build(monkeypatch, True, tree, lvl, bc, prev=pb)
    _assert_same_tables(a, b)
    assert a.ni > 0
    if not any(1 in k for k in bc):
        assert b.tile_vsgn is None and b.slot_vbits is None
    if prev == "prev":
        assert 0 < b.blocks_rebuilt < b.ntile
    if lp1 == "lp1-present":
        assert a.tile_ok.any()
    else:
        assert not a.tile_ok.any()


@pytest.mark.parametrize("ndim,root,faces", [
    (2, (3, 2), "mixed"), (3, (2, 1, 1), "periodic"),
    (3, (1, 3, 1), "reflecting")])
def test_tile_tables_native_non_cubic_root(has_native, monkeypatch, ndim,
                                           root, faces):
    """A non-cubic root: extents that are no power of two, and a complete
    level whose keys are not 0..n-1 (no direct index)."""
    if not has_native:
        pytest.skip("no native lib")
    bc = FACES[faces][:ndim]
    full = _ball_tree(ndim, root=root)
    for lvl in (LMIN[ndim] + 1, LMIN[ndim] + 2):
        tree = _upto(full, lvl + 1)
        _assert_same_tables(_build(monkeypatch, False, tree, lvl, bc),
                            _build(monkeypatch, True, tree, lvl, bc))


@pytest.mark.parametrize("shift", [0, 1, 3])
def test_tile_tables_native_other_tile_sizes(has_native, monkeypatch, shift):
    if not has_native:
        pytest.skip("no native lib")
    tree = _upto(_ball_tree(2), LMIN[2] + 3)
    for lvl in (LMIN[2] + 1, LMIN[2] + 2):
        _assert_same_tables(
            _build(monkeypatch, False, tree, lvl, FACES["mixed"][:2],
                   shift=shift),
            _build(monkeypatch, True, tree, lvl, FACES["mixed"][:2],
                   shift=shift))


@pytest.mark.parametrize("case", ["complete-level", "at-levelmin"])
def test_tile_tables_native_no_interpolation_rows(has_native, monkeypatch,
                                                  case):
    """``ni == 0``: a level with nothing missed, and a partial level with
    no coarser one (its missed slots read the trash row)."""
    if not has_native:
        pytest.skip("no native lib")
    full = _ball_tree(2)
    if case == "complete-level":
        tree, lvl = _upto(full, LMIN[2] + 1), LMIN[2]
    else:
        lvl = LMIN[2] + 1
        tree = _upto(full, lvl + 1, levelmin=lvl)
        del tree.levels[LMIN[2]]
    a = _build(monkeypatch, False, tree, lvl, FACES["outflow"][:2])
    b = _build(monkeypatch, True, tree, lvl, FACES["outflow"][:2])
    _assert_same_tables(a, b)
    assert b.ni == 0 and b.ni_pad == 8
    trash = b.ncell_pad + b.ni_pad
    assert ((b.tile_src[:b.ntile] == trash).any()
            == (case == "at-levelmin"))


def test_tile_tables_native_far_slots_read_trash(has_native, monkeypatch):
    """Missed slots beyond the influence radius of every oct get no
    interpolation row: an isolated oct in a tile asks for its 3^d
    neighbourhood only."""
    if not has_native:
        pytest.skip("no native lib")
    from ramses_tpu.amr.tree import Octree
    tree = Octree.base(3, 3, 4)
    tree.set_level(4, np.array([[1, 2, 5]]))
    a = _build(monkeypatch, False, tree, 4, FACES["periodic"])
    b = _build(monkeypatch, True, tree, 4, FACES["periodic"])
    _assert_same_tables(a, b)
    assert b.ntile == 1 and b.ni == 8 * (3 ** 3 - 1)
    assert (b.tile_src[0] == b.ncell_pad + b.ni_pad).sum() \
        == 12 ** 3 - 8 * 3 ** 3


def test_tile_tables_native_gradedness_error(has_native, monkeypatch):
    """A level whose halo asks for father octs the level below lacks:
    both paths raise the same error."""
    if not has_native:
        pytest.skip("no native lib")
    from ramses_tpu.amr.tree import Octree
    tree = Octree.base(2, 4, 6)
    tree.set_level(5, np.array([[3, 3], [3, 4]]))
    # level 6 fills its fathers to the edge: no 2:1 margin
    tree.set_level(6, np.array([[6, 6], [6, 7], [7, 6], [7, 7], [7, 9]]))
    msgs = []
    for use_native in (False, True):
        with pytest.raises(RuntimeError, match="2:1 gradedness") as e:
            _build(monkeypatch, use_native, tree, 6, FACES["periodic"][:2])
        msgs.append(str(e.value))
    assert msgs[0] == msgs[1], msgs
