"""Parity suite for the Pallas async remote-copy (DMA) halo engine.

The DMA backend (:mod:`ramses_tpu.parallel.dma_halo`) is pure data
movement with ppermute ring semantics, so every consumer — the uniform
halo stepper, the slab-sharded dense sweep (including its comm/compute
overlap split), the flags/RT appliers, and the slab MHD CT advance —
must agree BITWISE with the ppermute backend and with the mesh-of-1
global-view path.  CI drives the real kernel through the Pallas
interpreter (:data:`dma_halo.FORCE_INTERPRET`); on a physical TPU the
same tests exercise the compiled ``make_async_remote_copy`` path.
"""

import warnings
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ramses_tpu.amr import bitperm
from ramses_tpu.amr import kernels as K
from ramses_tpu.grid.boundary import BoundarySpec
from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.parallel import dense_slab as DS
from ramses_tpu.parallel import dma_halo
from ramses_tpu.parallel.mesh import OCT_AXIS, oct_mesh

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs the 8-device mesh")


@pytest.fixture
def dma(monkeypatch):
    """Run the DMA kernels through the Pallas interpreter on the CPU
    test backend (the real kernel, serialized devices)."""
    monkeypatch.setattr(dma_halo, "FORCE_INTERPRET", True)


def _kinds(bc):
    return tuple((f[0].kind, f[1].kind) for f in bc.faces)


def _sedov_like(ncell, nvar, ndim, seed=0):
    rng = np.random.default_rng(seed)
    u = np.ones((ncell, nvar), np.float32)
    u[:, 0] = 1.0 + 0.1 * rng.random(ncell)
    u[:, 1:1 + ndim] = 0.05 * rng.standard_normal(
        (ncell, ndim)).astype(np.float32)
    u[:, nvar - 1] = 1.0 + 0.1 * rng.random(ncell)
    return jnp.asarray(u)


def _oct_mask(ncell, ndim, lvl, frac=0.3, seed=1):
    rng = np.random.default_rng(seed)
    noct = ncell // (1 << ndim)
    ok_flat = np.repeat(rng.random(noct) < frac, 1 << ndim)
    ok_dense = np.asarray(
        bitperm.flat_to_dense(jnp.asarray(ok_flat), lvl, ndim)
    ).reshape(-1)
    return jnp.asarray(ok_flat), jnp.asarray(ok_dense)


# ----------------------------------------------------------------------
# backend selection
# ----------------------------------------------------------------------
def test_resolve_backend_auto_cpu():
    """auto on the CPU test backend keeps the portable path — the
    tier-1 suite never changes behaviour."""
    assert not dma_halo.available()
    assert dma_halo.resolve_backend("auto") == "ppermute"
    assert dma_halo.resolve_backend(None) == "ppermute"
    assert dma_halo.resolve_backend("ppermute") == "ppermute"


def test_resolve_backend_dma_fallback(monkeypatch):
    """An explicit dma request without a TPU warns once and falls
    back (a namelist written for TPU still runs on a laptop)."""
    monkeypatch.setattr(dma_halo, "FORCE_INTERPRET", False)
    monkeypatch.setattr(dma_halo, "_warned", set())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert dma_halo.resolve_backend("dma") == "ppermute"
    assert any("falling back" in str(x.message) for x in w)


def test_resolve_backend_dma_interpret(dma):
    assert dma_halo.resolve_backend("dma") == "dma"


# ----------------------------------------------------------------------
# the exchange primitive: dma vs ppermute, bitwise
# ----------------------------------------------------------------------
@needs8
def test_exchange_slabs_bitwise(dma):
    """Fused multi-slab exchange under an arbitrary set of ring perms
    equals per-slab ppermute exactly."""
    mesh = oct_mesh(jax.devices())
    n = 8
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((n * 4, 3)))
    b = jnp.asarray(rng.standard_normal((n * 2, 5)).astype(np.float32))

    from jax.sharding import PartitionSpec as P
    results = {}
    for backend in ("ppermute", "dma"):
        def body(a_loc, b_loc):
            ga, gb = dma_halo.exchange_slabs(
                [a_loc, b_loc], [fwd, bwd], OCT_AXIS, backend=backend)
            return ga, gb

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(OCT_AXIS), P(OCT_AXIS)),
            out_specs=(P(OCT_AXIS), P(OCT_AXIS)),
            check_vma=(backend != "dma"))
        results[backend] = jax.jit(f)(a, b)
    for x, y in zip(results["ppermute"], results["dma"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------
# slab hydro sweep under DMA (overlap split included)
# ----------------------------------------------------------------------
# tier-1 keeps the full masked x ret_flux grid on the cheap 2D shape
# plus the strictest 3D overlap-active combo; the remaining 3D combos
# (split-inactive (3,3) and the weaker (3,4) masks) re-run in the
# nightly full suite — 8-device interpret compiles dominate their
# wall time, not the assertions
_slow = pytest.mark.slow
@needs8
@pytest.mark.parametrize("ndim,lvl,masked,ret_flux", [
    (2, 4, False, False),
    (2, 4, False, True),
    (2, 4, True, False),
    (2, 4, True, True),
    # loc (8,8,8): comm/compute overlap split ACTIVE
    pytest.param(3, 4, True, True, marks=_slow),
    pytest.param(3, 4, False, False, marks=_slow),
    pytest.param(3, 4, False, True, marks=_slow),
    pytest.param(3, 4, True, False, marks=_slow),
    # loc (4,4,4): split inactive (loc == 2*NGHOST)
    pytest.param(3, 3, False, False, marks=_slow),
    pytest.param(3, 3, False, True, marks=_slow),
    pytest.param(3, 3, True, False, marks=_slow),
    pytest.param(3, 3, True, True, marks=_slow),
])
def test_dense_sweep_slab_dma_bitwise(dma, ndim, lvl, masked, ret_flux):
    cfg = HydroStatic(ndim=ndim, gamma=1.4, riemann="hllc")
    bc = BoundarySpec.periodic(ndim)
    n = 1 << lvl
    shape = (n,) * ndim
    ncell = n ** ndim
    u = _sedov_like(ncell, cfg.nvar, ndim)
    ok_flat = ok_dense = None
    if masked:
        ok_flat, ok_dense = _oct_mask(ncell, ndim, lvl)
    dt = jnp.float32(1e-3)
    dx = 1.0 / n
    mesh = oct_mesh(jax.devices())
    spec = DS.build_slab_spec(mesh, lvl, ndim, shape, ncell,
                              _kinds(bc), halo_backend="dma")
    assert spec is not None and spec.backend == "dma"
    ref = K.dense_sweep(u, None, None, ok_dense, dt, dx, shape, bc,
                        cfg, ret_flux=ret_flux)
    got = jax.jit(partial(DS.dense_sweep_slab, spec=spec, cfg=cfg,
                          dx=dx, ret_flux=ret_flux))(u, ok_flat, dt)
    if ret_flux:
        np.testing.assert_array_equal(np.asarray(ref[0]),
                                      np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]),
                                      np.asarray(got[1]))
    else:
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@needs8
def test_overlap_split_engages(dma):
    """The split is declared (telemetry) exactly when the cut box is
    deep enough for a ghost-free interior."""
    from ramses_tpu.hydro.muscl import NGHOST
    mesh = oct_mesh(jax.devices())
    bc = _kinds(BoundarySpec.periodic(3))
    thin = DS.build_slab_spec(mesh, 3, 3, (8,) * 3, 512, bc,
                              halo_backend="dma")
    deep = DS.build_slab_spec(mesh, 4, 3, (16,) * 3, 4096, bc,
                              halo_backend="dma")
    assert DS._split_axis(thin, NGHOST) is None
    assert DS._split_axis(deep, NGHOST) is not None
    # ppermute never splits (no async copy to overlap with)
    deep_pp = DS.build_slab_spec(mesh, 4, 3, (16,) * 3, 4096, bc,
                                 halo_backend="ppermute")
    assert DS._split_axis(deep_pp, NGHOST) is None


# ----------------------------------------------------------------------
# refine flags + RT transport under DMA
# ----------------------------------------------------------------------
@needs8
def test_refine_flags_slab_dma_bitwise(dma):
    ndim, lvl = 2, 4
    cfg = HydroStatic(ndim=ndim, gamma=1.4)
    bc = BoundarySpec.periodic(ndim)
    n = 1 << lvl
    shape = (n,) * ndim
    ncell = n ** ndim
    u = _sedov_like(ncell, cfg.nvar, ndim, seed=2)
    mesh = oct_mesh(jax.devices())
    spec = DS.build_slab_spec(mesh, lvl, ndim, shape, ncell,
                              _kinds(bc), halo_backend="dma")
    eg = (0.05, 0.05, -1.0)
    fls = (1e-10, 1e-10, 1e-10)
    ref = K.dense_refine_flags(u, None, None, eg, fls, shape, bc, cfg,
                               dx=1.0 / n)
    fn = partial(K._flags_fn(cfg), err_grad=eg, floors=fls, spatial0=0,
                 cfg=cfg)
    got = jax.jit(partial(DS.dense_flags_slab, spec=spec, flags_fn=fn,
                          twotondim=2 ** ndim))(u)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@needs8
def test_rt_transport_slab_dma_bitwise(dma):
    from ramses_tpu.rt import m1

    ndim, lvl = 2, 4
    n = 1 << lvl
    shape = (n,) * ndim
    ncell = n ** ndim
    rng = np.random.default_rng(4)
    rad = jnp.asarray(rng.random((ncell, 1 + ndim)).astype(np.float64))
    dt, dx, c_red = 1e-3, 1.0 / n, 1.0

    def global_step(rows):
        dense = K.rows_to_dense(rows, None, shape)
        N, F = dense[..., 0], jnp.stack(
            [dense[..., 1 + c] for c in range(ndim)])
        N, F = m1.transport_step(N, F, dt, dx, c_red, ndim,
                                 periodic=True)
        cols = [N[..., None]] + [F[c][..., None] for c in range(ndim)]
        return K.dense_to_rows(jnp.concatenate(cols, axis=-1), None,
                               shape)

    def local_fn(ext):
        N, F = ext[..., 0], jnp.stack(
            [ext[..., 1 + c] for c in range(ndim)])
        N, F = m1.transport_step(N, F, dt, dx, c_red, ndim,
                                 periodic=True)
        cols = [N[..., None]] + [F[c][..., None] for c in range(ndim)]
        out = jnp.concatenate(cols, axis=-1)
        return out[tuple(slice(1, -1) for _ in range(ndim))]

    mesh = oct_mesh(jax.devices())
    spec = DS.build_slab_spec(mesh, lvl, ndim, shape, ncell,
                              ((0, 0),) * ndim, halo_backend="dma")
    ref = jax.jit(global_step)(rad)
    got = jax.jit(partial(DS.dense_apply_slab, spec=spec,
                          local_fn=local_fn, ng=1))(rad)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ----------------------------------------------------------------------
# uniform-grid halo stepper: dma vs ppermute vs global, split active
# ----------------------------------------------------------------------
@needs8
@pytest.mark.parametrize("ndim,lvl", [
    (2, 6), pytest.param(3, 5, marks=pytest.mark.slow)])
def test_run_steps_halo_dma_bitwise(dma, ndim, lvl):
    from ramses_tpu.config import params_from_string
    from ramses_tpu.driver import Simulation
    from ramses_tpu.grid.uniform import run_steps
    from ramses_tpu.parallel.halo import make_halo_mesh, run_steps_halo

    txt = "\n".join([
        "&RUN_PARAMS", "hydro=.true.", "/",
        "&AMR_PARAMS", f"levelmin={lvl}", f"levelmax={lvl}",
        "boxlen=1.0", "/",
        "&INIT_PARAMS", "nregion=2",
        "region_type(1)='square'", "region_type(2)='square'",
        "x_center=0.5,0.5", "y_center=0.5,0.5", "z_center=0.5,0.5",
        "length_x=10.0,0.12", "length_y=10.0,0.12",
        "length_z=10.0,0.12", "exp_region=10.0,2.0",
        "d_region=1.0,4.0", "p_region=1e-2,1.0", "/",
        "&HYDRO_PARAMS", "riemann='hllc'", "courant_factor=0.8", "/",
    ])
    sim = Simulation(params_from_string(txt, ndim=ndim),
                     dtype=jnp.float64)
    u0 = sim.state.u
    t0 = jnp.asarray(0.0, jnp.float64)
    tend = jnp.asarray(1e9, jnp.float64)
    u_ref, t_ref, n_ref = run_steps(sim.grid, u0, t0, tend, 4)
    mesh = make_halo_mesh()
    for backend in ("ppermute", "dma"):
        u_h, t_h, n_h = run_steps_halo(sim.grid, mesh, u0, t0, tend, 4,
                                       halo_backend=backend)
        assert int(n_h) == int(n_ref) == 4
        assert float(t_h) == float(t_ref)
        np.testing.assert_array_equal(np.asarray(u_h), np.asarray(u_ref))
    # the dma run at this size declares comm/compute overlap
    assert dma_halo.TRAFFIC["overlap_frac"] > 0.0


# ----------------------------------------------------------------------
# slab MHD CT: dma vs ppermute vs global (mask + EMF override), and
# the single-block Pallas CT kernel
# ----------------------------------------------------------------------
def _ct_state(ndim, lvl, seed=11):
    """Consistent CT state: random low faces, hi = periodic neighbour's
    lo, cell B = face mean, positive density/pressure."""
    from ramses_tpu.mhd import core as mcore
    from ramses_tpu.mhd.core import IBX, IP, NCOMP, MhdStatic

    cfg = MhdStatic(ndim=ndim, gamma=1.4)
    n = 1 << lvl
    shape = (n,) * ndim
    rng = np.random.default_rng(seed)
    blo = rng.standard_normal((NCOMP,) + shape) * 0.1 + 1.0
    bld = np.zeros(shape + (NCOMP, 2))
    for c in range(NCOMP):
        bld[..., c, 0] = blo[c]
        bld[..., c, 1] = (np.roll(blo[c], -1, axis=c) if c < ndim
                          else blo[c])
    q = np.zeros((cfg.nvar,) + shape)
    q[0] = 1.0 + 0.1 * rng.random(shape)
    q[1:1 + NCOMP] = 0.05 * rng.standard_normal((NCOMP,) + shape)
    q[IBX:IBX + NCOMP] = 0.5 * (bld[..., :, 0] + bld[..., :, 1]
                                ).transpose((ndim,) + tuple(range(ndim)))
    q[IP] = 1.0 + 0.1 * rng.random(shape)
    ud = jnp.asarray(mcore.prim_to_cons(jnp.asarray(q), cfg))
    return cfg, shape, ud, jnp.asarray(bld)


def _ct_global(cfg, shape, ud, bld, dt, dx, ok_dense=None, override=None):
    """Reference: the global-view CT branch (mu.step + _dense_hi) in
    the same (du_rows, b_rows) layout as mhd_ct_slab."""
    from ramses_tpu.mhd import uniform as mu
    from ramses_tpu.mhd.amr import _dense_hi
    from ramses_tpu.mhd.core import NCOMP

    ndim = cfg.ndim
    grid = mu.MhdGrid(cfg=cfg, shape=shape, dx=dx,
                      bc_kinds=((0, 0),) * ndim)
    bfd = jnp.stack([bld[..., c, 0] for c in range(NCOMP)])

    def fn(ud, bld):
        un_d, bfn_d = mu.step(grid, ud, bfd, dt, ok=ok_dense,
                              emf_override=override)
        du = K.dense_to_rows(jnp.moveaxis(un_d - ud, 0, -1), None, shape)
        comps = []
        for c in range(NCOMP):
            lo = bfn_d[c]
            hi = _dense_hi(lo, c, True) if c < ndim else lo
            comps.append(jnp.stack([lo, hi], axis=-1))
        b = K.dense_to_rows(jnp.stack(comps, axis=-2), None, shape)
        return du, b

    return jax.jit(fn)(ud, bld)


# slow: each combo costs a full 8-device interpret compile of the CT
# slab program (~20 s on CPU); the nightly full suite and the
# dedicated DMA-parity CI step run them
@needs8
@pytest.mark.slow
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("with_ovr", [False, True])
def test_mhd_ct_slab_dma_bitwise(dma, masked, with_ovr):
    ndim, lvl = 3, 3
    cfg, shape, ud, bld = _ct_state(ndim, lvl)
    n = 1 << lvl
    ncell = n ** ndim
    dt = jnp.asarray(2e-4, ud.dtype)
    dx = 1.0 / n
    u_rows = K.dense_to_rows(jnp.moveaxis(ud, 0, -1), None, shape)
    bf_rows = K.dense_to_rows(bld, None, shape)
    pairs = [(d1, d2) for d1 in range(ndim)
             for d2 in range(d1 + 1, ndim)]

    ok_flat = ok_dense = None
    if masked:
        ok_flat, okd = _oct_mask(ncell, ndim, lvl)
        ok_dense = okd.reshape(shape)
    override = ovr_flat = None
    if with_ovr:
        rng = np.random.default_rng(13)
        msk = rng.random((len(pairs),) + shape) < 0.2
        val = rng.standard_normal((len(pairs),) + shape) * 0.01
        override = {p: (jnp.asarray(msk[pi]), jnp.asarray(val[pi]))
                    for pi, p in enumerate(pairs)}
        om = jnp.stack([bitperm.dense_to_flat(
            jnp.asarray(msk[pi]).astype(u_rows.dtype), lvl, ndim)
            for pi in range(len(pairs))], axis=-1)
        ov = jnp.stack([bitperm.dense_to_flat(
            jnp.asarray(val[pi]).astype(u_rows.dtype), lvl, ndim)
            for pi in range(len(pairs))], axis=-1)
        ovr_flat = (om, ov)

    du_ref, b_ref = _ct_global(cfg, shape, ud, bld, dt, dx,
                               ok_dense, override)
    mesh = oct_mesh(jax.devices())
    for backend in ("ppermute", "dma"):
        spec = DS.build_slab_spec(mesh, lvl, ndim, shape, ncell,
                                  ((0, 0),) * ndim,
                                  halo_backend=backend)
        assert DS.mhd_slab_ok(spec)
        du, b = jax.jit(partial(DS.mhd_ct_slab, dx=dx, spec=spec,
                                cfg=cfg))(u_rows, bf_rows, dt,
                                          ok_flat=ok_flat,
                                          ovr_flat=ovr_flat)
        np.testing.assert_array_equal(np.asarray(du_ref),
                                      np.asarray(du))
        np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b))


@needs8
@pytest.mark.slow
def test_pallas_ct_kernel_bitwise(dma, monkeypatch):
    """The single-block Pallas CT kernel (interpret mode) equals the
    XLA step_padded spelling inside the same slab decomposition."""
    from ramses_tpu.mhd import pallas_ct

    ndim, lvl = 3, 3
    cfg, shape, ud, bld = _ct_state(ndim, lvl)
    n = 1 << lvl
    ncell = n ** ndim
    dt = jnp.asarray(2e-4, ud.dtype)
    dx = 1.0 / n
    u_rows = K.dense_to_rows(jnp.moveaxis(ud, 0, -1), None, shape)
    bf_rows = K.dense_to_rows(bld, None, shape)
    ok_flat, _ = _oct_mask(ncell, ndim, lvl)
    du_ref, b_ref = _ct_global(cfg, shape, ud, bld, dt, dx)

    mesh = oct_mesh(jax.devices())
    spec = DS.build_slab_spec(mesh, lvl, ndim, shape, ncell,
                              ((0, 0),) * ndim, halo_backend="dma")
    assert not pallas_ct.slab_available(cfg, spec.loc, u_rows.dtype)
    monkeypatch.setattr(pallas_ct, "FORCE_INTERPRET", True)
    assert pallas_ct.slab_available(cfg, spec.loc, u_rows.dtype)
    du, b = jax.jit(partial(DS.mhd_ct_slab, dx=dx, spec=spec,
                            cfg=cfg))(u_rows, bf_rows, dt)
    np.testing.assert_array_equal(np.asarray(du_ref), np.asarray(du))
    np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b))


def test_flat_index_np_matches_dense_to_flat():
    for ndim, lvl in [(1, 4), (2, 3), (3, 3)]:
        n = 1 << lvl
        rng = np.random.default_rng(5)
        coords = rng.integers(0, n, size=(64, ndim))
        X = jnp.asarray(rng.standard_normal((n,) * ndim))
        rows = np.asarray(bitperm.dense_to_flat(X, lvl, ndim))
        fi = bitperm.flat_index_np(coords, lvl, ndim)
        np.testing.assert_array_equal(
            rows[fi],
            np.asarray(X)[tuple(coords[:, d] for d in range(ndim))])


# ----------------------------------------------------------------------
# full sims: mesh-of-1 vs mesh-of-8 under the DMA backend
# ----------------------------------------------------------------------
@needs8
@pytest.mark.slow
def test_mhd_sim_shard_invariance_complete(dma):
    """Complete-level 3D MHD: MhdAmrSim vs ShardedMhdAmrSim on the
    DMA backend, bitwise (cells AND staggered faces)."""
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.amr import MhdAmrSim
    from ramses_tpu.parallel.amr_sharded import ShardedMhdAmrSim

    def mk(cls, **kw):
        p = load_params("namelists/tube_mhd.nml", ndim=3)
        p.amr.levelmin = p.amr.levelmax = 3
        p.boundary.nboundary = 0
        p.amr.halo_backend = "dma"
        return cls(p, dtype=jnp.float64, **kw)

    s1 = mk(MhdAmrSim)
    s8 = mk(ShardedMhdAmrSim, devices=jax.devices())
    assert s8._fused_spec().slab and s8._fused_spec().slab[0] is not None
    for _ in range(2):
        dt = min(s1.coarse_dt(), s8.coarse_dt())
        s1.step_coarse(dt)
        s8.step_coarse(dt)
    for l in s1.levels():
        np.testing.assert_array_equal(np.asarray(s1.u[l]),
                                      np.asarray(s8.u[l]))
        np.testing.assert_array_equal(np.asarray(s1.bfs[l]),
                                      np.asarray(s8.bfs[l]))


@needs8
@pytest.mark.slow
def test_mhd_sim_refined_dma_vs_ppermute(dma):
    """Refined 2D MHD (partial fine level, EMF override live): the two
    sharded backends are bitwise-identical — they run the same program
    modulo the exchange primitive.  The mesh-of-1 comparison is
    ulp-tight only: the partial level's correction scatter is GSPMD-
    partitioned, whose summation order is not the serial one."""
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.amr import MhdAmrSim
    from ramses_tpu.parallel.amr_sharded import ShardedMhdAmrSim

    def mk(cls, backend="dma", **kw):
        p = load_params("namelists/tube_mhd.nml", ndim=2)
        p.amr.levelmin, p.amr.levelmax = 4, 5
        p.boundary.nboundary = 0
        p.refine.err_grad_d = 0.02
        p.refine.err_grad_p = 0.05
        p.amr.halo_backend = backend
        return cls(p, dtype=jnp.float64, **kw)

    s1 = mk(MhdAmrSim)
    s8d = mk(ShardedMhdAmrSim, "dma", devices=jax.devices())
    s8p = mk(ShardedMhdAmrSim, "ppermute", devices=jax.devices())
    for _ in range(3):
        dt = min(s1.coarse_dt(), s8d.coarse_dt(), s8p.coarse_dt())
        s1.step_coarse(dt)
        s8d.step_coarse(dt)
        s8p.step_coarse(dt)
    assert s1.tree.noct(5) > 0
    for l in s1.levels():
        np.testing.assert_array_equal(np.asarray(s8d.u[l]),
                                      np.asarray(s8p.u[l]))
        np.testing.assert_array_equal(np.asarray(s8d.bfs[l]),
                                      np.asarray(s8p.bfs[l]))
        np.testing.assert_allclose(np.asarray(s1.u[l]),
                                   np.asarray(s8d.u[l]),
                                   rtol=1e-12, atol=1e-14)
        np.testing.assert_allclose(np.asarray(s1.bfs[l]),
                                   np.asarray(s8d.bfs[l]),
                                   rtol=1e-12, atol=1e-14)


@needs8
def test_hydro_sim_shard_invariance_dma(dma):
    """The hydro precedent (tests/test_dense_slab.py) on the DMA
    backend: complete-level sedov, two coarse steps, bitwise."""
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_string
    from ramses_tpu.parallel.amr_sharded import ShardedAmrSim

    nml = "\n".join([
        "&RUN_PARAMS", "hydro=.true.", "/",
        "&AMR_PARAMS", "levelmin=3", "levelmax=3", "boxlen=1.0",
        "halo_backend='dma'", "/",
        "&INIT_PARAMS", "nregion=1", "region_type(1)='square'",
        "d_region=1.0", "p_region=1.0", "/",
        "&HYDRO_PARAMS", "riemann='hllc'", "/",
        "&OUTPUT_PARAMS", "tend=0.01", "/",
    ])
    s1 = AmrSim(params_from_string(nml, ndim=3), dtype=jnp.float32)
    s8 = ShardedAmrSim(params_from_string(nml, ndim=3),
                       devices=jax.devices(), dtype=jnp.float32)
    spec8 = s8._fused_spec()
    assert spec8.slab and spec8.slab[0] is not None
    assert spec8.slab[0].backend == "dma"
    for _ in range(2):
        dt = min(s1.coarse_dt(), s8.coarse_dt())
        s1.step_coarse(dt)
        s8.step_coarse(dt)
    for l in s1.levels():
        np.testing.assert_array_equal(np.asarray(s1.u[l]),
                                      np.asarray(s8.u[l]))


@needs8
def test_dma_multi_step_donation_no_warnings(dma):
    """The donation pin of tests/test_dense_slab.py on the DMA
    backend: steady-state jits must keep donating cleanly."""
    import warnings as w

    from ramses_tpu.config import params_from_string
    from ramses_tpu.parallel.amr_sharded import ShardedAmrSim

    nml = "\n".join([
        "&RUN_PARAMS", "hydro=.true.", "/",
        "&AMR_PARAMS", "levelmin=3", "levelmax=3", "boxlen=1.0",
        "halo_backend='dma'", "/",
        "&INIT_PARAMS", "nregion=1", "region_type(1)='square'",
        "d_region=1.0", "p_region=1.0", "/",
        "&HYDRO_PARAMS", "riemann='hllc'", "/",
        "&OUTPUT_PARAMS", "tend=0.01", "/",
    ])
    sim = ShardedAmrSim(params_from_string(nml, ndim=3),
                        devices=jax.devices(), dtype=jnp.float32)
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        for _ in range(3):
            sim.step_coarse(sim.coarse_dt())
    bad = [x for x in rec if "donat" in str(x.message).lower()]
    assert not bad, [str(x.message) for x in bad]
