"""The 6^d per-oct stencil tables exist only where something reads them.

``maps.build_level_maps`` builds ``stencil_src / vsgn / ok_ref /
interp_*`` for a partial level when asked; ``AmrSim._rebuild_maps``
asks iff the level's sweep and flags run the stencil formulation
(``oct_blocking=.false.``) or the RT transport
gathers through it.  A level on the Morton-tile path has none of them,
on the host or on the device, and steps bitwise as if it had.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from ramses_tpu.amr import flag as flagmod
from ramses_tpu.amr import maps as mapmod
from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.amr.tree import Octree
from ramses_tpu.config import params_from_dict
from ramses_tpu.parallel import balance
from tests.test_oct_blocking import _sedov

STENCIL_KEYS = ("stencil_src", "vsgn", "ok_ref", "interp_cell",
                "interp_nb", "interp_sgn")


def _partial(sim):
    return [l for l in sim.levels() if not sim.maps[l].complete]


def _cycle(sim, n, dt_max=np.inf):
    """``n`` coarse steps, a regrid after each; the octs of the partial
    levels whose maps each regrid rebuilt."""
    rebuilt = []
    for _ in range(n):
        sim.step_coarse(min(sim.coarse_dt(), dt_max))
        old = dict(sim.maps)
        sim.regrid()
        rebuilt.append(sum(sim.maps[l].noct for l in _partial(sim)
                           if sim.maps[l] is not old.get(l)))
    return rebuilt


def _assert_tables(sim, present):
    part = _partial(sim)
    assert part, "configuration must produce partial levels"
    for l in part:
        assert sim.maps[l].has_stencil == present, l
        for k in STENCIL_KEYS:
            assert (k in sim.dev[l]) == present, (l, k)


# ------------------------------------------------------------ tile path

@pytest.mark.parametrize("ndim,lmin,lmax", [(3, 3, 5), (2, 4, 6)],
                         ids=["3d", "2d"])
def test_tile_path_levels_have_no_stencil_tables(ndim, lmin, lmax):
    sim = _sedov(".true.", lmin=lmin, lmax=lmax, ndim=ndim)
    assert len(sim.levels()) == 3
    _assert_tables(sim, present=False)
    assert sim.block_stats["stencil_octs_built"] == 0
    rebuilt = _cycle(sim, 2)
    assert any(rebuilt), "no regrid changed the tree"
    _assert_tables(sim, present=False)
    assert sim.block_stats["stencil_octs_built"] == 0
    spec = sim._fused_spec()
    assert all(b or c for b, c in zip(spec.blocked, spec.complete))
    # the tile tables and what the stencil build shared with them stay
    for l in _partial(sim):
        for k in ("tile_src", "tile_ok", "b_interp_cell", "corr_idx",
                  "ref_cell", "son_oct", "valid_cell"):
            assert k in sim.dev[l], (l, k)


def test_tile_path_bitwise_with_tables_forced_on(monkeypatch):
    """The tables were never read on the tile path: a run that still
    builds and uploads them steps and regrids to the same bits."""
    lean = _sedov(".true.", lmin=3, lmax=5)
    _cycle(lean, 3)
    monkeypatch.setattr(AmrSim, "_reads_stencil", lambda self, l: True)
    full = _sedov(".true.", lmin=3, lmax=5)
    rebuilt = _cycle(full, 3)
    _assert_tables(full, present=True)
    assert full.blocks, "forcing the tables on must not leave the tile path"
    assert full.block_stats["stencil_octs_built"] == rebuilt[-1] > 0
    assert lean.t == full.t and lean.nstep == full.nstep
    assert lean.levels() == full.levels()
    for l in lean.levels():
        assert np.array_equal(lean.tree.levels[l].keys,
                              full.tree.levels[l].keys), l
        assert np.array_equal(np.asarray(lean.u[l]),
                              np.asarray(full.u[l])), l


# --------------------------------------------------------- stencil path

def _blocking_off():
    return _sedov(".false.", lmin=3, lmax=5)


def _rt_coupled():
    from tests.test_rt_amr import _rt_groups
    refine = {"r_refine": [0.15] * 8, "x_refine": [0.5] * 8,
              "y_refine": [0.5] * 8, "z_refine": [0.5] * 8}
    g = _rt_groups(3, 4, refine=refine, tend=0.001)
    sim = AmrSim(params_from_dict(g, ndim=3), dtype=jnp.float64)
    assert sim.rt_amr is not None and sim.blocks
    return sim


# the RT case refines by geometry, so its tree never changes and its
# regrids build nothing; its step is cut short (the RT subcycle count
# grows with dt) and is there to run the transport through the tables
@pytest.mark.parametrize("make,dt_max,tree_changes", [
    (_blocking_off, np.inf, True), (_rt_coupled, 1e-4, False)],
    ids=["oct_blocking_off", "rt"])
def test_stencil_readers_keep_their_tables(make, dt_max, tree_changes):
    sim = make()
    _assert_tables(sim, present=True)
    # the construction's last build made every level's tables
    assert sim.block_stats["stencil_octs_built"] == sum(
        sim.maps[l].noct for l in _partial(sim)) > 0
    rebuilt = _cycle(sim, 2, dt_max)
    assert any(rebuilt) == tree_changes
    _assert_tables(sim, present=True)
    assert sim.block_stats["stencil_octs_built"] == rebuilt[-1]


# -------------------------------------------------------- the map builder

def _graded_tree(ndim, bc_kinds, seed, lmin=3, depth=2):
    """Random 2:1-graded tree: ``depth`` rounds of random cell flags
    through the program's own smoothing and nesting."""
    rng = np.random.default_rng(seed)
    params = params_from_dict(
        {"amr_params": {"levelmin": lmin, "levelmax": lmin + depth}},
        ndim=ndim)
    tree = Octree.base(ndim, lmin, lmin + depth)
    for _ in range(depth):
        flags = {l: np.flatnonzero(rng.random(tree.noct(l) << ndim) < 0.04)
                 for l in range(lmin, lmin + depth) if tree.has(l)}
        tree = flagmod.compute_new_tree(tree, flags, bc_kinds, params)
    assert tree.has(lmin + depth)
    return tree


@pytest.mark.parametrize("kind", [0, 1], ids=["periodic", "reflecting"])
@pytest.mark.parametrize("ndim", [2, 3])
def test_build_level_maps_without_stencil(ndim, kind):
    bc = [(kind, kind)] * ndim
    tree = _graded_tree(ndim, bc, seed=10 * ndim + kind)
    for l in range(tree.levelmin + 1, tree.levelmax + 1):
        full = mapmod.build_level_maps(tree, l, bc)
        lean = mapmod.build_level_maps(tree, l, bc, stencil=False)
        assert full.has_stencil and not full.complete
        assert full.stencil_src.shape == (full.noct_pad, 6 ** ndim)
        assert not lean.has_stencil and lean.vsgn is None
        assert lean.ni == 0 and lean.ndim == ndim
        for f in ("corr_idx", "ref_cell", "son_oct", "valid_oct"):
            assert np.array_equal(getattr(lean, f), getattr(full, f)), f
        for f in ("lvl", "noct", "noct_pad", "ncell_pad", "nref",
                  "nref_pad", "complete"):
            assert getattr(lean, f) == getattr(full, f), f
    if kind == 1:
        assert full.vsgn is not None, "a wall must set sign bits"


@pytest.mark.parametrize("stencil", [False, True],
                         ids=["stencil_off", "stencil_on"])
def test_apply_layout_level_passes_absent_tables_through(stencil):
    ndim, bc = 2, [(0, 0)] * 2
    tree = _graded_tree(ndim, bc, seed=5)
    l = tree.levelmax - 1
    m = mapmod.build_level_maps(tree, l, bc, stencil=stencil)
    rng = np.random.default_rng(7)

    def lay(lv, ndev=2):
        pad = mapmod.bucket(tree.noct(lv))
        order = rng.permutation(tree.noct(lv)).astype(np.int64)
        counts = balance.balanced_cuts(np.ones(len(order)), ndev,
                                       pad // ndev)
        return balance.make_layout(order, counts, pad, ndev)

    lay_m1, lay_l, lay_p1 = lay(l - 1), lay(l), lay(l + 1)
    out = balance.apply_layout_level(m, lay_m1, lay_l, lay_p1)
    assert out.has_stencil == stencil
    if not stencil:
        for f in STENCIL_KEYS:
            a, b = getattr(out, f), getattr(m, f)
            assert (a is None and b is None) or np.array_equal(a, b), f
    # the maps a tile-path level keeps are laid out as the full build's
    ref = balance.apply_layout_level(
        mapmod.build_level_maps(tree, l, bc), lay_m1, lay_l, lay_p1)
    for f in ("corr_idx", "ref_cell", "son_oct", "valid_oct"):
        assert np.array_equal(getattr(out, f), getattr(ref, f)), f
    assert out.valid_oct[lay_l.oct_row].all()
    assert out.valid_oct.sum() == m.noct
