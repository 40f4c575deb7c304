"""The regrid's sparse flag decode (``flag.flagged_cells``: only the
non-zero fetched bytes are unpacked) against the dense unpack it replaced,
kept verbatim here: the same bytes must give the same flagged-cell
indices, and ``AmrSim._flag_and_tree`` the same tree, for every ``ndim``,
padded rows, load-balance layouts and every criterion that is merged
in (gradient, ``r_refine`` geometry, ``m_refine`` particle mass)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ramses_tpu.amr import flag as flagmod
from ramses_tpu.amr import hierarchy as hmod
from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.amr.tree import Octree
from ramses_tpu.config import (load_params, params_from_dict,
                               params_from_string)
from ramses_tpu.pm.particles import ParticleSet
from tests import _tree_oracle as oracle
from tests.test_oct_blocking import _sedov
from tests.test_telemetry import _amr_sim, _records


def _dense_mask(packed, ndim, noct, oct_row=None):
    """The parent's unpack (``hierarchy.py``, PR 29), verbatim: the
    per-cell bool mask of one level in flat-cell order."""
    ttd = 2 ** ndim
    fl = ((np.asarray(packed)[:, None] >> np.arange(ttd)) & 1) \
        .astype(bool)
    if oct_row is not None:        # rows → tree oct order first
        fl = fl[oct_row]
    else:
        fl = fl[:noct]
    return fl.reshape(-1)                              # flat-cell order


# ------------------------------------------------------------ the decode

NOCT = 1500


def _bytes(ndim, fill, n, rng):
    top = 1 << (1 << ndim)            # every valid bit set = top - 1
    if fill == "none":
        return np.zeros(n, np.uint8)
    if fill == "all":
        return np.full(n, top - 1, np.uint8)
    b = rng.integers(0, top, n).astype(np.uint8)
    if fill == "sparse":
        b[rng.random(n) > 0.02] = 0
    return b


@pytest.mark.parametrize("rows", ["plain", "padded", "permuted"])
@pytest.mark.parametrize("fill", ["none", "all", "sparse", "dense"])
@pytest.mark.parametrize("ndim", [1, 2, 3])
def test_decode_is_the_dense_unpack(ndim, fill, rows):
    rng = np.random.default_rng(100 * ndim + len(fill) + len(rows))
    npad = NOCT if rows == "plain" else 2048
    packed = _bytes(ndim, fill, npad, rng)
    if rows == "padded" and fill != "none":
        packed[NOCT:] = (1 << (1 << ndim)) - 1     # padding is not read
    oct_row = rng.permutation(npad)[:NOCT] if rows == "permuted" else None
    want = np.flatnonzero(_dense_mask(packed, ndim, NOCT, oct_row))
    got, nocts = flagmod.flagged_cells(packed, ndim, NOCT, oct_row)
    assert got.dtype == want.dtype == np.int64
    assert np.array_equal(got, want)
    assert nocts == len(np.unique(want >> ndim))
    if fill == "all":
        assert len(got) == NOCT << ndim
    if fill == "none":
        assert len(got) == 0 and got.ndim == 1


def test_tree_build_refuses_a_mask():
    """One input form: a bool mask would index cells 0 and 1 silently."""
    p = params_from_dict({"amr_params": {"levelmin": 3, "levelmax": 4}},
                         ndim=3)
    tree = Octree.base(3, 3, 4)
    mask = {3: np.ones(tree.noct(3) << 3, bool)}
    with pytest.raises(TypeError):
        flagmod.compute_new_tree(tree, mask, [(0, 0)] * 3, p)


# ------------------------------------------------- the whole flag phase

def _old_masks(sim):
    """The parent's ``AmrSim._flag_and_tree`` up to the tree build
    (PR 29, the in-core branch; ``self`` → ``sim``, its unpack lines are
    ``_dense_mask``): per-level bool masks, every criterion ORed in."""
    r = sim.params.refine
    spec = sim._fused_spec()
    ttd = 2 ** sim.tree_ndim
    flags = hmod._pack_flag_bits(sim._criteria_flags(spec), ttd)
    flags = jax.device_get(flags)
    crit = {}
    for fl, l in zip(flags, spec.levels):
        fl = _dense_mask(fl, sim.tree_ndim, sim.maps[l].noct,
                         sim.layouts[l].oct_row if l in sim.layouts
                         else None)
        i = l - 1                                  # 1-based level lists
        if i < len(r.r_refine) and r.r_refine[i] > 0.0:
            fl = fl | flagmod.geometry_flags(
                sim.tree.cell_centers(l, sim.boxlen), l, sim.params)
        if sim.pic and i < len(r.m_refine) and r.m_refine[i] >= 0.0:
            rho_dev = sim._rho_dev.get(l)
            if rho_dev is None or rho_dev.shape[0] < len(fl):
                if not sim._pm_dev:
                    sim._build_pm()
                if l in sim._pm_dev:
                    rho_dev = (sim.u[l][:, 0]
                               + sim._pm_rho(l).astype(
                                   sim.u[l].dtype))
            if rho_dev is not None and rho_dev.shape[0] >= len(fl):
                mp = float(jnp.sum(sim.p.m * sim.p.active)) \
                    / max(int(jnp.sum(sim.p.active)), 1)
                thr = r.m_refine[i] * mp \
                    / sim.dx(l) ** sim.tree_ndim
                rho_np = sim.tree_order_cells(rho_dev, l)[:len(fl)]
                fl = fl | (rho_np > thr)
        crit[l] = fl
    return flags, crit


def _sedov3d():
    sim = _sedov(".true.", lmin=3, lmax=5)
    for _ in range(2):
        sim.step_coarse(sim.coarse_dt())
    return sim


def _mhd_tube():
    from ramses_tpu.mhd.amr import MhdAmrSim
    p = load_params("namelists/tube_mhd.nml", ndim=1)
    p.amr.levelmin, p.amr.levelmax = 5, 7
    p.refine.err_grad_d = 0.02
    p.refine.err_grad_p = 0.05
    sim = MhdAmrSim(p, dtype=jnp.float64)
    for _ in range(2):
        sim.step_coarse(sim.coarse_dt())
    return sim


def _pm_params(refine, lb=False):
    return params_from_string("\n".join([
        "&RUN_PARAMS", "hydro=.true.", "poisson=.true.", "pic=.true.", "/",
        "&AMR_PARAMS", "levelmin=3", "levelmax=5", "boxlen=1.0",
        f"load_balance={'.true.' if lb else '.false.'}",
        "load_balance_threshold=1.05", "cost_weight_part=0.5", "/",
        "&INIT_PARAMS", "nregion=1", "region_type(1)='square'",
        "d_region=1.0", "p_region=1.0", "/",
        "&HYDRO_PARAMS", "riemann='hllc'", "courant_factor=0.5", "/",
        "&REFINE_PARAMS"] + refine + ["/"]), ndim=2)


BALL = ["x_refine=0,0,0.25,0.25", "y_refine=0,0,0.25,0.25",
        "r_refine=-1,-1,0.2,0.2"]


def _particles():
    rng = np.random.default_rng(7)
    x0 = np.concatenate([rng.uniform(0.55, 0.8, (48, 2)),
                         rng.uniform(0.0, 1.0, (16, 2))])
    return jax.device_put(ParticleSet.make(
        x0, rng.uniform(-0.05, 0.05, (64, 2)), np.full(64, 1.0 / 64)))


def _geometry():
    """``r_refine``: the geometry flags are the only ones set (uniform
    gas), merged as an index set."""
    sim = AmrSim(_pm_params(BALL), dtype=jnp.float64)
    assert flagmod.geometry_flags(
        sim.tree.cell_centers(4, sim.boxlen), 4, sim.params).any()
    return sim


def _particle_mass():
    """``m_refine``: cells holding more than two mean particle masses,
    beside the ball's geometry flags."""
    sim = AmrSim(_pm_params(BALL + ["m_refine=2,2,2,2,2"]),
                 dtype=jnp.float64, particles=_particles())
    assert sim.pic
    return sim


def _layout():
    """A load-balance layout: the fetched rows are a permutation of the
    tree's octs (``layouts[l].oct_row``)."""
    sim = AmrSim(_pm_params(BALL, lb=True), dtype=jnp.float64,
                 particles=_particles())
    sim.request_rebalance()
    sim.regrid()
    assert sim.layouts
    assert any(not np.array_equal(lay.oct_row, np.arange(lay.noct))
               for lay in sim.layouts.values())
    return sim


@pytest.mark.parametrize("build", [_sedov3d, _mhd_tube, _geometry,
                                   _particle_mass, _layout],
                         ids=lambda f: f.__name__.strip("_"))
def test_regrid_builds_the_old_paths_tree(build):
    sim = build()
    fetched, masks = _old_masks(sim)
    assert any(m.any() for m in masks.values())
    if build is _particle_mass:    # the branch adds cells of its own
        plain = AmrSim(_pm_params(BALL), dtype=jnp.float64)
        assert sum(int(m.sum()) for m in masks.values()) > \
            sum(int(m.sum()) for m in _old_masks(plain)[1].values())
    want = oracle.compute_new_tree(sim.tree, masks, sim.bc_kinds,
                                   sim.params)
    assert want.finest > sim.lmin, "the case refines nothing"
    sim.regrid()
    assert sorted(sim.tree.levels) == sorted(want.levels)
    for l, w in want.levels.items():
        assert np.array_equal(sim.tree.levels[l].keys, w.keys), l
        assert np.array_equal(sim.tree.levels[l].og, w.og), l
    # the counter: what the decode worked on at this regrid
    assert sim.flag_stats["octs_fetched"] == sum(len(f) for f in fetched)
    assert sim.flag_stats["octs_flagged"] <= sim.flag_stats["octs_fetched"]
    assert sim.flag_stats["cells_flagged"] <= sum(
        int(m.sum()) for m in masks.values())


def test_flag_stats_counts_the_decode_and_reaches_telemetry(tmp_path):
    sim = _amr_sim(tmp_path, nstep=2)
    sim.evolve(1e9, nstepmax=2)
    sim.telemetry.close(sim, print_timers=False)
    steps = [r for r in _records(tmp_path / "run.jsonl")
             if r["kind"] == "step"]
    assert len(steps) == 2
    for r in steps:
        fs = r["flag_stats"]
        assert set(fs) == {"octs_fetched", "octs_flagged", "cells_flagged"}
        assert 0 < fs["octs_flagged"] <= fs["octs_fetched"]
        assert fs["octs_flagged"] <= fs["cells_flagged"] \
            <= 4 * fs["octs_flagged"]
    assert steps[-1]["flag_stats"] == sim.flag_stats
    # against the dense path, on the state the run stopped in (gradient
    # criteria only: the decode's own counts)
    fetched, masks = _old_masks(sim)
    sim.regrid()
    assert sim.flag_stats == {
        "octs_fetched": sum(len(f) for f in fetched),
        "octs_flagged": sum(int(m.reshape(-1, 4).any(axis=1).sum())
                            for m in masks.values()),
        "cells_flagged": sum(int(m.sum()) for m in masks.values())}
