"""Gather-fused blocked oct sweep (amr/maps.py BlockMaps +
amr/kernels.py tile_sweep + the hierarchy wiring).

The oracle is the same invariance trick the rest of the AMR suite
uses: the blocked Morton-tile decomposition is a *layout* change, so
``oct_blocking=.true.`` must reproduce the per-oct stencil path
bitwise — same conserved state, same refinement flags, same trees —
on every configuration it is eligible for.  Map-level tests
cross-check the gathered tile values against the tree geometry
directly, and the incremental-rebuild contract (unchanged tiles are
never rebuilt) is pinned on real regrids.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from ramses_tpu.amr import maps as mapmod
from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.amr.tree import cell_offsets
from ramses_tpu.config import params_from_dict, params_from_string

SEDOV3D = """
&RUN_PARAMS
hydro=.true.
/
&AMR_PARAMS
levelmin={lmin}
levelmax={lmax}
boxlen=1.0
oct_blocking={blk}
/
&INIT_PARAMS
nregion=2
region_type(1)='square'
region_type(2)='point'
x_center=0.5,0.5
y_center=0.5,0.5
z_center=0.5,0.5
length_x=10.0,1.0
length_y=10.0,1.0
length_z=10.0,1.0
d_region=1.0,0.0
p_region=1e-5,0.1
/
&HYDRO_PARAMS
gamma=1.4
courant_factor=0.7
slope_type=1
riemann='{riemann}'
/
&REFINE_PARAMS
err_grad_p=0.1
/
"""


def _sedov(blk, lmin=4, lmax=5, ndim=3, dtype=None, riemann="llf"):
    p = params_from_string(
        SEDOV3D.format(lmin=lmin, lmax=lmax, blk=blk, riemann=riemann),
        ndim=ndim)
    return AmrSim(p, dtype=dtype or jnp.float64)


def _check_maps(sim):
    """Cross-check BlockMaps against the tree: every gathered slot must
    resolve to the cell its Morton key names, an interp row for its
    missing-father key, or the zero trash row."""
    from ramses_tpu.amr import keys as kmod
    nd = sim.tree.ndim
    for l, b in sim.blocks.items():
        lev = sim.tree.levels[l]
        # fabricate a cell field = its own BC-mapped Morton key; interp
        # rows get a distinct marker family, trash row a third
        u = np.full((b.ncell_pad, 1), -1.0)
        co = cell_offsets(nd)
        gc = (2 * lev.og[:, None, :] + co[None, :, :]).reshape(-1, nd)
        u[:len(gc), 0] = kmod.encode(gc, nd).astype(float)
        iv = np.full((b.ni_pad, 1), -2.0)
        iv[:b.ni, 0] = -1000.0 - np.arange(b.ni)
        src = np.concatenate([u, iv, [[-3.0]]], axis=0)
        got = src[np.asarray(b.tile_src), 0][:b.ntile]
        ck = b.slot_ckey
        exists = (sim.tree.lookup_keys(l, (ck >> nd).reshape(-1)) >= 0) \
            .reshape(ck.shape)
        assert np.array_equal(got[exists], ck[exists].astype(float)), \
            f"level {l}: existing-cell slots"
        missing = got[~exists]
        assert ((missing <= -1000.0) | (missing == -3.0)).all(), \
            f"level {l}: missing slots must be interp or trash"
        if b.ni:
            # an interp slot's row index must equal the rank of its key
            rows = (-(missing + 1000.0)).astype(int)
            onrow = missing <= -1000.0
            uniq = np.unique(ck[~exists][onrow])
            assert np.array_equal(
                rows[onrow], np.searchsorted(uniq, ck[~exists][onrow])), \
                f"level {l}: interp row ranks"
        # scatter maps invert the layout: flat cell order <-> tile slots
        nreal = lev.noct * (1 << nd)
        flat = np.arange(b.ntile_pad * (1 << (nd * (b.shift + 1)))) \
            .reshape(b.ntile_pad, -1)
        vals = flat[np.asarray(b.cell_tile)[:nreal],
                    np.asarray(b.cell_slot)[:nreal]]
        assert len(np.unique(vals)) == nreal, f"level {l}: cell scatter"


def test_block_maps_consistency():
    sim = _sedov(".true.")
    assert sim.blocks, "no blocked levels built"
    _check_maps(sim)


def test_unchanged_regrid_rebuilds_zero_blocks():
    """Steady-state regrid contract: tree untouched => every per-block
    map is reused, zero rebuilt."""
    sim = _sedov(".true.")
    assert sim.block_stats["blocks_total"] > 0
    sim.regrid()
    assert sim.block_stats["blocks_total"] > 0
    assert sim.block_stats["blocks_rebuilt"] == 0, sim.block_stats


def test_incremental_rebuild_matches_fresh():
    """After a real regrid, the prev-reusing build must equal a fresh
    build field-for-field."""
    sim = _sedov(".true.")
    for _ in range(2):
        sim.step_coarse(sim.coarse_dt())
    sim.regrid()
    shift = int(sim.params.amr.oct_block_shift)
    for l, b in sim.blocks.items():
        fresh = mapmod.build_block_maps(
            sim.tree, l, sim.bc_kinds, shift=shift,
            noct_pad=sim.maps[l].noct_pad)
        assert fresh.blocks_rebuilt == fresh.ntile
        for f in ("tile_src", "tile_ok", "interp_cell", "interp_nb",
                  "interp_sgn", "cell_tile", "cell_slot", "oct_tile",
                  "oct_slot", "tile_key", "slot_ckey"):
            a, c = getattr(b, f), getattr(fresh, f)
            assert np.array_equal(np.asarray(a), np.asarray(c)), (l, f)
        if b.tile_vsgn is not None:
            assert np.array_equal(b.tile_vsgn, fresh.tile_vsgn), l


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "RAMSES_TPU_NATIVE=0"])
def test_tiles_native_counts_the_native_pass(monkeypatch, use_native):
    """``block_stats["tiles_native"]``: every live tile where the native
    pass wrote the tables (a reused level's too), none under
    ``RAMSES_TPU_NATIVE=0`` — and the tables are the same either way."""
    from ramses_tpu import native
    monkeypatch.delenv("RAMSES_TPU_NATIVE", raising=False)
    if native.lib() is None:
        pytest.skip("no native lib")
    ref = _sedov(".true.")
    monkeypatch.setenv("RAMSES_TPU_NATIVE", "1" if use_native else "0")
    sim = _sedov(".true.")
    for s in (ref, sim):
        s.step_coarse(s.coarse_dt())
        s.regrid()
    want = sim.block_stats["blocks_total"] if use_native else 0
    assert sim.block_stats["tiles_native"] == want, sim.block_stats
    sim.regrid()                       # unchanged tree: tables reused
    assert sim.block_stats["tiles_native"] == want, sim.block_stats
    assert ref.blocks.keys() == sim.blocks.keys() and sim.blocks
    for l, b in sim.blocks.items():
        for f in mapmod.BLOCK_TABLES:
            x, y = getattr(ref.blocks[l], f), getattr(b, f)
            assert (x is None and y is None) or (
                x.dtype == y.dtype and np.array_equal(x, y)), (l, f)


def _parity(lmin, lmax, ndim, dtype=None, riemann="llf", nstep=2,
            with_regrid=True):
    sims = {}
    for blk in (".true.", ".false."):
        s = _sedov(blk, lmin=lmin, lmax=lmax, ndim=ndim, dtype=dtype,
                   riemann=riemann)
        if blk == ".true.":
            assert s.blocks, "no blocked levels built"
        else:
            assert not s.blocks
        for _ in range(nstep):
            s.step_coarse(s.coarse_dt())
        if with_regrid:
            s.regrid()
            s.step_coarse(s.coarse_dt())
        sims[blk] = s
    sa, sb = sims[".true."], sims[".false."]
    assert sorted(sa.levels()) == sorted(sb.levels())
    for l in sa.levels():
        # identical trees (flags parity, incl. tile_refine_flags)
        assert np.array_equal(np.asarray(sa.tree.levels[l].keys),
                              np.asarray(sb.tree.levels[l].keys)), l
        # FULL padded arrays: pad rows must stay bitwise too (the
        # sharded-vs-single suite compares them)
        ua, ub = np.asarray(sa.u[l]), np.asarray(sb.u[l])
        assert np.array_equal(ua, ub), \
            f"level {l}: maxdiff={np.abs(ua - ub).max()}"


def test_blocked_parity_3d_sedov():
    """Blocked vs per-oct stencil path: bitwise-identical state and
    trees through steps + a regrid (XLA tile fallback on CPU)."""
    _parity(4, 5, 3)


@pytest.mark.slow          # ~32s; nightly tier on the 1-core box
def test_blocked_parity_2d_sedov():
    _parity(4, 6, 2)


@pytest.mark.slow
def test_blocked_parity_3d_hllc_two_level_span():
    _parity(4, 6, 3, riemann="hllc")


@pytest.mark.slow
def test_blocked_parity_gravity():
    """Self-gravity run: want_flux path (phi mass-flux planes) must also
    be bitwise under blocking."""
    def blob(blk):
        groups = {
            "run_params": {"hydro": True, "poisson": True},
            "amr_params": {"levelmin": 4, "levelmax": 5, "boxlen": 1.0,
                           "oct_blocking": blk},
            "init_params": {"nregion": 2,
                            "region_type": ["square", "square"],
                            "x_center": [0.5, 0.5],
                            "y_center": [0.5, 0.5],
                            "z_center": [0.5, 0.5],
                            "length_x": [10.0, 0.25],
                            "length_y": [10.0, 0.25],
                            "length_z": [10.0, 0.25],
                            "exp_region": [10.0, 2.0],
                            "d_region": [1.0, 50.0],
                            "p_region": [10.0, 10.0]},
            "hydro_params": {"gamma": 1.4, "courant_factor": 0.5,
                             "riemann": "hllc"},
            "refine_params": {"err_grad_d": 0.2},
        }
        return AmrSim(params_from_dict(groups, ndim=3),
                      dtype=jnp.float64)

    sa, sb = blob(True), blob(False)
    assert sa.blocks and not sb.blocks
    for s in (sa, sb):
        for _ in range(2):
            s.step_coarse(s.coarse_dt())
    for l in sa.levels():
        nreal = sa.tree.levels[l].noct * 8
        assert np.array_equal(np.asarray(sa.u[l])[:nreal],
                              np.asarray(sb.u[l])[:nreal]), l


@pytest.mark.slow
def test_blocked_parity_pallas_interpret(monkeypatch):
    """The real Pallas tile kernel (interpret mode) vs the per-oct
    reference path: bitwise-identical f32 state.  Both sims run under
    FORCE_INTERPRET so the only difference is blocked vs stencil."""
    from ramses_tpu.hydro import pallas_oct
    monkeypatch.setattr(pallas_oct, "FORCE_INTERPRET", True)
    jax.clear_caches()                  # force a fresh branch choice
    try:
        sims = {}
        for blk in (".true.", ".false."):
            s = _sedov(blk, dtype=jnp.float32)
            if blk == ".true.":
                for l, b in s.blocks.items():
                    assert pallas_oct.tile_available(
                        s.cfg, b.ntile_pad, jnp.float32,
                        b.shift), (l, b.ntile_pad)
            for _ in range(2):
                s.step_coarse(s.coarse_dt())
            sims[blk] = s
        sa, sb = sims[".true."], sims[".false."]
        for l in sa.levels():
            nreal = sa.tree.levels[l].noct * 8
            assert np.array_equal(np.asarray(sa.u[l])[:nreal],
                                  np.asarray(sb.u[l])[:nreal]), l
    finally:
        jax.clear_caches()              # do not leak into other tests


# -------------------------------------------- universal eligibility

@pytest.mark.slow          # ~26s; nightly tier on the 1-core box
def test_blocked_parity_forced_layout():
    """Layout-composed tile tables: after a forced Hilbert relayout
    permutes the rows (balance.apply_layout_blocks), the blocked sweep
    must still reproduce the stencil path bitwise."""
    sims = {}
    for blk in (".true.", ".false."):
        p = params_from_string(
            SEDOV3D.format(lmin=4, lmax=6, blk=blk, riemann="llf"),
            ndim=2)
        p.amr.load_balance = True
        s = AmrSim(p, dtype=jnp.float64)
        for _ in range(2):
            s.step_coarse(s.coarse_dt())
        s.request_rebalance()
        s.regrid()
        assert s.layouts, "forced rebalance adopted no layout"
        for _ in range(2):
            s.step_coarse(s.coarse_dt())
        sims[blk] = s
    sa, sb = sims[".true."], sims[".false."]
    assert sa.blocks and not sb.blocks
    # the gate lift is doing work: a layout level IS blocked
    assert set(sa.blocks) & set(sa.layouts), (sa.blocks, sa.layouts)
    assert sorted(sa.layouts) == sorted(sb.layouts)
    for l, lay in sa.layouts.items():
        assert np.array_equal(lay.oct_row, sb.layouts[l].oct_row), l
    for l in sa.levels():
        assert np.array_equal(np.asarray(sa.tree.levels[l].keys),
                              np.asarray(sb.tree.levels[l].keys)), l
        ua, ub = np.asarray(sa.u[l]), np.asarray(sb.u[l])
        assert np.array_equal(ua, ub), \
            f"level {l}: maxdiff={np.abs(ua - ub).max()}"


@pytest.mark.slow          # ~33s; nightly tier on the 1-core box
def test_blocked_parity_sharded_mesh8():
    """mesh-of-8 == mesh-of-1 on the blocked path: row-sharded tile
    tables under GSPMD (FusedSpec.pallas_tiles=False pins the XLA tile
    formulation) reproduce the single-device run bitwise.  f32/3D is
    the regime the decomposition-invariance north star pins
    (test_determinism_f32.py); the partitioned tile program is NOT
    ulp-stable in other dtype/ndim corners."""
    from ramses_tpu.parallel.amr_sharded import ShardedAmrSim
    if len(jax.devices()) < 8:
        pytest.skip("needs an 8-device mesh")

    def mk(cls, **kw):
        p = params_from_string(
            SEDOV3D.format(lmin=4, lmax=5, blk=".true.", riemann="llf"),
            ndim=3)
        return cls(p, dtype=jnp.float32, **kw)

    s1 = mk(AmrSim)
    s8 = mk(ShardedAmrSim, devices=jax.devices()[:8])
    assert s1.blocks and s8.blocks, "blocked gate closed somewhere"
    assert s8._fused_spec().pallas_tiles is False
    for s in (s1, s8):
        for _ in range(2):
            s.step_coarse(s.coarse_dt())
        s.regrid()
        s.step_coarse(s.coarse_dt())
    for l in s1.levels():
        assert s8.tree.noct(l) == s1.tree.noct(l), l
        # noct_pad differs (mesh-multiple rounding): real rows only
        nreal = s1.tree.noct(l) * 8
        a = np.asarray(s1.u[l])[:nreal]
        b = np.asarray(s8.u[l])[:nreal]
        assert (a.view(np.uint32) == b.view(np.uint32)).all(), l


@pytest.mark.slow
def test_blocked_parity_sharded_blocked_vs_stencil():
    """3D f32 on the 8-device mesh: the row-sharded blocked tile sweep
    vs the row-sharded stencil sweep vs the mesh-of-1 stencil
    reference — one bitwise XLA family.  (The Pallas tile kernel's
    interpret-mode family is pinned single-device by
    test_blocked_parity_pallas_interpret: sharded meshes never take
    the Pallas kernel — FusedSpec.pallas_tiles=False by design.)"""
    from ramses_tpu.parallel.amr_sharded import ShardedAmrSim
    if len(jax.devices()) < 8:
        pytest.skip("needs an 8-device mesh")

    def mk(cls, blk, **kw):
        p = params_from_string(
            SEDOV3D.format(lmin=4, lmax=5, blk=blk, riemann="llf"),
            ndim=3)
        s = cls(p, dtype=jnp.float32, **kw)
        for _ in range(2):
            s.step_coarse(s.coarse_dt())
        return s

    s1 = mk(AmrSim, ".false.")
    s8b = mk(ShardedAmrSim, ".true.", devices=jax.devices()[:8])
    s8s = mk(ShardedAmrSim, ".false.", devices=jax.devices()[:8])
    assert s8b.blocks and not s8s.blocks
    for l in s1.levels():
        nreal = s1.tree.noct(l) * 8
        ref = np.asarray(s1.u[l])[:nreal]
        for tag, s in (("blocked8", s8b), ("stencil8", s8s)):
            got = np.asarray(s.u[l])[:nreal]
            assert (ref.view(np.uint32) == got.view(np.uint32)).all(), \
                (l, tag)


def _mhd_parity(lmin, lmax, ndim, nstep=2):
    """MHD CT blocked-vs-stencil parity: cells AND staggered faces."""
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.amr import MhdAmrSim
    sims = {}
    for blk in (True, False):
        p = load_params("namelists/tube_mhd.nml", ndim=ndim)
        p.amr.levelmin, p.amr.levelmax = lmin, lmax
        p.amr.oct_blocking = blk
        p.refine.err_grad_d = 0.02
        p.refine.err_grad_p = 0.05
        s = MhdAmrSim(p, dtype=jnp.float64)
        if blk:
            assert s.blocks, "no blocked MHD levels built"
        else:
            assert not s.blocks
        for _ in range(nstep):
            s.step_coarse(s.coarse_dt())
        s.regrid()
        s.step_coarse(s.coarse_dt())
        sims[blk] = s
    sa, sb = sims[True], sims[False]
    assert sorted(sa.levels()) == sorted(sb.levels())
    ttd = 1 << ndim
    for l in sa.levels():
        assert np.array_equal(np.asarray(sa.tree.levels[l].keys),
                              np.asarray(sb.tree.levels[l].keys)), l
        nreal = sa.tree.noct(l) * ttd
        # real rows only: the tile path zeroes the pad bf rows the
        # stencil path leaves as garbage (no consumer reads them)
        assert np.array_equal(np.asarray(sa.u[l])[:nreal],
                              np.asarray(sb.u[l])[:nreal]), l
        assert np.array_equal(np.asarray(sa.bfs[l])[:nreal],
                              np.asarray(sb.bfs[l])[:nreal]), l


@pytest.mark.slow          # ~145s; nightly tier on the 1-core box
def test_blocked_parity_mhd_ct_2d():
    """mhd_tile_sweep vs mhd_level_sweep through steps + a regrid:
    bitwise u and bf, including the z-EMF corner extraction."""
    _mhd_parity(4, 6, 2)


@pytest.mark.slow          # ~147s; nightly tier on the 1-core box
def test_blocked_parity_mhd_ct_3d():
    """3D exercises all three EMF pair planes and the non-pair-axis
    2-subcell mean."""
    _mhd_parity(3, 4, 3, nstep=1)


# --------------------------------------------- device-resident regrid

@pytest.mark.slow          # ~19s; nightly tier on the 1-core box
def test_device_regrid_matches_host(monkeypatch):
    """Changed-tree regrids on the device path must be bitwise-identical
    to the host build_prolong_maps reference — and must construct ZERO
    host prolongation tables while the reference builds many."""
    real = mapmod.build_prolong_maps
    counts, sims = {}, {}
    for dev_rg in (True, False):
        p = params_from_string(
            SEDOV3D.format(lmin=4, lmax=6, blk=".true.", riemann="llf"),
            ndim=2)
        p.amr.device_regrid = dev_rg
        s = AmrSim(p, dtype=jnp.float64)
        n = {"calls": 0}

        def spy(*a, _n=n, **k):
            _n["calls"] += 1
            return real(*a, **k)

        monkeypatch.setattr(mapmod, "build_prolong_maps", spy)
        try:
            for _ in range(3):
                for _ in range(2):
                    s.step_coarse(s.coarse_dt())
                s.regrid()
        finally:
            monkeypatch.setattr(mapmod, "build_prolong_maps", real)
        counts[dev_rg], sims[dev_rg] = n["calls"], s
    # the comparison is meaningful only if trees actually changed
    assert counts[False] > 0, "host run saw no changed-tree regrid"
    assert counts[True] == 0, "device path fell back to host tables"
    sa, sb = sims[True], sims[False]
    for l in sa.levels():
        assert np.array_equal(np.asarray(sa.tree.levels[l].keys),
                              np.asarray(sb.tree.levels[l].keys)), l
        ua, ub = np.asarray(sa.u[l]), np.asarray(sb.u[l])
        assert np.array_equal(ua, ub), \
            f"level {l}: maxdiff={np.abs(ua - ub).max()}"


def test_steady_regrid_builds_no_host_tables(monkeypatch):
    """Zero-host-allocation pin: a steady-state regrid (unchanged tree,
    unchanged layouts) must construct no host migration tables, upload
    no key arrays, and reuse every level array by identity."""
    from ramses_tpu.amr import device_regrid as dregrid
    sim = _sedov(".true.", lmin=4, lmax=5, ndim=2)
    for _ in range(2):
        sim.step_coarse(sim.coarse_dt())
    sim.regrid()                        # absorb any pending tree change
    before = {l: sim.u[l] for l in sim.levels()}

    def boom(*a, **k):
        raise AssertionError("host table built on a steady regrid")

    monkeypatch.setattr(mapmod, "build_prolong_maps", boom)
    monkeypatch.setattr(dregrid, "upload_keys", boom)
    sim.regrid()                        # guaranteed steady-state
    for l in sim.levels():
        assert sim.u[l] is before[l], l
