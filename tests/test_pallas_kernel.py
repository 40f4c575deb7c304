"""Parity of the fused Pallas MUSCL kernel vs the XLA reference path.

Runs the kernel in Pallas interpreter mode on the CPU test backend, so
the TPU code path's algorithm is covered by CI without TPU hardware
(``pallas_muscl.fused_step_padded(interpret=True)``).  The oracle is the
whole-grid XLA pipeline (``grid.uniform.step`` internals) that the TPU
kernel replaces — both implement ``hydro/umuscl.f90:22-171``.
"""

import contextlib
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

if __name__ == "__main__":      # the no-FMA child runs this file as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import no_fma_child  # noqa: E402  (tests/ is on the path: rootdir conftest)
from ramses_tpu.grid import boundary as bmod  # noqa: E402
from ramses_tpu.grid import uniform  # noqa: E402
from ramses_tpu.hydro import muscl, pallas_muscl as pk  # noqa: E402
from ramses_tpu.hydro.core import HydroStatic  # noqa: E402
from ramses_tpu.hydro.timestep import compute_dt  # noqa: E402
from ramses_tpu.config import Params  # noqa: E402

SHAPE = (16, 16, 128)
# a 512-cell lane axis: the widest the block rule admits
SHAPE512 = (8, 16, 512)
SHAPES = pytest.mark.parametrize("shape", [SHAPE, SHAPE512],
                                 ids=["lane128", "lane512"])


@contextlib.contextmanager
def _under_tile(tile):
    """The block rule overridden to ``tile``.  The pick is read at trace
    time and ``fused_step_padded`` caches its trace per signature, so its
    cache is dropped on the way in and out."""
    kernel = pk.fused_step_padded          # (a test may patch the name)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pk, "_pick_block", lambda shape, masked=False: tile)
        kernel.clear_cache()
        try:
            yield
        finally:
            kernel.clear_cache()


@pytest.fixture()
def tile_rule():
    """``tile_rule((bx, by))``: :func:`_under_tile` until the test ends."""
    with contextlib.ExitStack() as stack:
        yield lambda tile: stack.enter_context(_under_tile(tile))


def _cfg(riemann="llf", slope_type=1):
    p = Params(ndim=3)
    p.hydro.riemann = riemann
    p.hydro.slope_type = slope_type
    return HydroStatic.from_params(p)


def _state(cfg, seed=0, shape=SHAPE):
    rng = np.random.default_rng(seed)
    r = 1.0 + 0.3 * rng.random(shape)
    v = 0.2 * rng.standard_normal((3,) + shape)
    p_ = 0.5 + 0.2 * rng.random(shape)
    e = p_ / (cfg.gamma - 1.0) + 0.5 * r * (v ** 2).sum(axis=0)
    u = np.stack([r, r * v[0], r * v[1], r * v[2], e])
    return jnp.asarray(u, jnp.float32)


def _xla_step(u, dt, cfg, bc, dx):
    up = bmod.pad(u, bc, cfg, muscl.NGHOST)
    flux, _ = muscl.unsplit(up, None, dt, (dx,) * 3, cfg)
    un = muscl.apply_fluxes(up, flux, cfg)
    return bmod.unpad(un, 3, muscl.NGHOST)


@pytest.mark.smoke
@SHAPES
@pytest.mark.parametrize("riemann", ["llf", "hllc"])
def test_fused_step_matches_xla(riemann, shape):
    cfg = _cfg(riemann)
    bc = bmod.BoundarySpec.periodic(3)
    kinds = tuple((lo.kind, hi.kind) for lo, hi in bc.faces)
    assert pk.supports(cfg, shape, kinds, jnp.float32)
    u = _state(cfg, shape=shape)
    dx = 1.0 / shape[0]
    dt = jnp.asarray(1e-3, jnp.float32)
    ref = _xla_step(u, dt, cfg, bc, dx)
    up, _ = pk.pad_xy(u, bc, cfg)
    got = pk.fused_step_padded(up, dt, cfg, dx, shape, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    rec = [b for b in pk.block_stats()
           if b["shape"] == list(shape) and not b["masked"]]
    bx, by = {128: (16, 16), 512: (8, 16)}[shape[2]]
    assert len(rec) == 1 and (rec[0]["bx"], rec[0]["by"]) == (bx, by)
    assert rec[0]["window_cells"] == (bx + 4) * (by + 8) * shape[2]
    assert rec[0]["written_cells"] == bx * by * shape[2]


def test_fused_step_reflecting_xy():
    cfg = _cfg("llf")
    refl = bmod.FaceBC(kind=bmod.REFLECTING)
    per = bmod.FaceBC()
    bc = bmod.BoundarySpec(faces=((refl, refl), (refl, refl), (per, per)))
    kinds = tuple((lo.kind, hi.kind) for lo, hi in bc.faces)
    assert pk.supports(cfg, SHAPE, kinds, jnp.float32)
    u = _state(cfg, seed=3)
    dx = 1.0 / SHAPE[0]
    dt = jnp.asarray(5e-4, jnp.float32)
    ref = _xla_step(u, dt, cfg, bc, dx)
    up, _ = pk.pad_xy(u, bc, cfg)
    got = pk.fused_step_padded(up, dt, cfg, dx, SHAPE, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@SHAPES
@pytest.mark.parametrize("riemann", ["llf", "hllc"])
def test_fused_step_masked_matches_dense_sweep(riemann, shape):
    """Refined-face flux zeroing (the AMR dense path's mask input)."""
    cfg = _cfg(riemann)
    bc = bmod.BoundarySpec.periodic(3)
    u = _state(cfg, seed=7, shape=shape)
    dx = 1.0 / shape[0]
    dt = jnp.asarray(5e-4, jnp.float32)
    rng = np.random.default_rng(11)
    ok = jnp.asarray(rng.random(shape) < 0.1)

    # XLA oracle: the masked branch of dense_sweep
    up = bmod.pad(u, bc, cfg, muscl.NGHOST)
    flux, _ = muscl.unsplit(up, None, dt, (dx,) * 3, cfg)
    okp = ok
    for d in range(3):
        padw = [(muscl.NGHOST, muscl.NGHOST) if d2 == d else (0, 0)
                for d2 in range(3)]
        okp = jnp.pad(okp, padw, mode="wrap")
    masked = [flux[d] * (~(okp | jnp.roll(okp, 1, axis=d)))[None]
              .astype(flux.dtype) for d in range(3)]
    un = muscl.apply_fluxes(up, jnp.stack(masked), cfg)
    ref = bmod.unpad(un, 3, muscl.NGHOST)

    upad, okpad = pk.pad_xy(u, bc, cfg, ok=ok)
    got = pk.fused_step_padded(upad, dt, cfg, dx, shape, ok_pad=okpad,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@SHAPES
def test_fused_courant_matches_compute_dt(shape):
    cfg = _cfg("llf")
    bc = bmod.BoundarySpec.periodic(3)
    u = _state(cfg, seed=5, shape=shape)
    dx = 1.0 / shape[0]
    dt = jnp.asarray(1e-3, jnp.float32)
    up, _ = pk.pad_xy(u, bc, cfg)
    un, crt = pk.fused_step_padded(up, dt, cfg, dx, shape, courant=True,
                                   interpret=True)
    dtmax = cfg.courant_factor * dx / cfg.smallc
    want = float(compute_dt(un.astype(jnp.float32), None, dx, cfg))
    got = float(jnp.minimum(dtmax, crt[0, 0]))
    # (sqrt(1+2*cf*ratio)-1)/ratio cancels catastrophically in f32
    # (~1e-3 relative); cell_dt evaluates it per-cell in the array dtype
    # while the kernel folds it into one scalar — allow that spread
    assert got == pytest.approx(want, rel=3e-3)


# ---------------------------------------------------------------------------
# oct-batch kernel (pallas_oct): partial-level AMR sweeps
# ---------------------------------------------------------------------------

def _row_state(cfg, n, seed=0):
    """[n, nvar] physically-valid random conservative rows."""
    rng = np.random.default_rng(seed)
    r = 1.0 + 0.3 * rng.random(n)
    v = 0.2 * rng.standard_normal((3, n))
    p_ = 0.5 + 0.2 * rng.random(n)
    e = p_ / (cfg.gamma - 1.0) + 0.5 * r * (v ** 2).sum(axis=0)
    return jnp.asarray(np.stack([r, r * v[0], r * v[1], r * v[2], e],
                                axis=1), jnp.float32)


@pytest.mark.smoke
@pytest.mark.parametrize("riemann", ["llf", "hllc"])
def test_oct_sweep_matches_level_sweep(riemann, monkeypatch):
    """Drive kernels.level_sweep itself twice — pallas branch forced on
    (interpreter mode) vs forced off (XLA) — so the REAL production
    dispatch is what is pinned, not a replica of it."""
    from ramses_tpu.amr import kernels as K
    from ramses_tpu.hydro import pallas_oct

    cfg = _cfg(riemann)
    noct, ni_pad = 128, 256
    ncell_pad = noct * 8
    rng = np.random.default_rng(5)
    u_flat = _row_state(cfg, ncell_pad, seed=21)
    interp = _row_state(cfg, ni_pad, seed=22)
    nrows = ncell_pad + ni_pad + 1          # + trash row
    sten = jnp.asarray(rng.integers(0, nrows, (noct, 216)), jnp.int32)
    ok = jnp.asarray(rng.random((noct, 216)) < 0.15)
    dt = jnp.asarray(2e-4, jnp.float32)
    dx = 1.0 / 64

    def run():
        jax.clear_caches()                  # force a fresh branch choice
        du, corr, phi = K.level_sweep(u_flat, interp, sten, None, ok,
                                      None, dt, dx, cfg, ret_flux=True)
        return np.asarray(du), np.asarray(corr), np.asarray(phi)

    monkeypatch.setattr(pallas_oct, "FORCE_INTERPRET", True)
    assert pallas_oct.available(cfg, noct, jnp.float32)
    du_k, corr_k, phi_k = run()
    monkeypatch.setattr(pallas_oct, "FORCE_INTERPRET", False)
    monkeypatch.setattr(pallas_oct, "DISABLED", True)
    assert not pallas_oct.available(cfg, noct, jnp.float32)
    du_x, corr_x, phi_x = run()
    jax.clear_caches()                      # do not leak into other tests
    np.testing.assert_allclose(du_k, du_x, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(corr_k, corr_x, rtol=2e-5, atol=2e-6)
    # MC-tracer face-flux capture parity (want_flux kernel output)
    np.testing.assert_allclose(phi_k, phi_x, rtol=2e-5, atol=2e-6)


def test_fused_step_want_flux_matches_xla_dense_sweep():
    """The dense kernel's MC-tracer face-flux capture (want_flux)
    matches the XLA dense_sweep's ret_flux output."""
    import ramses_tpu.hydro.pallas_muscl as pk
    from ramses_tpu.amr import kernels as K
    from ramses_tpu.grid.boundary import BoundarySpec

    cfg = _cfg("hllc")
    shape = (16, 16, 128)
    bc = BoundarySpec.periodic(3)
    rng = np.random.default_rng(9)
    nvar = 5
    r = 1.0 + 0.3 * rng.random(shape)
    v = 0.2 * rng.standard_normal((3,) + shape)
    p_ = 0.5 + 0.2 * rng.random(shape)
    e = p_ / (cfg.gamma - 1.0) + 0.5 * r * (v ** 2).sum(axis=0)
    ud = jnp.asarray(np.stack([r, r * v[0], r * v[1], r * v[2], e]),
                     jnp.float32)
    ok = jnp.asarray(rng.random(shape) < 0.1)
    dt = jnp.asarray(1e-3, jnp.float32)
    dx = 1.0 / shape[0]
    # kernel path (interpreter mode)
    up, okp = pk.pad_xy(ud, bc, cfg, ok=ok)
    un_k, phi_k = pk.fused_step_padded(up, dt, cfg, dx, shape,
                                       ok_pad=okp, interpret=True,
                                       want_flux=True)
    # XLA path through dense_sweep itself (identity layout: feed a
    # flat array whose maps come from a tiny complete-level tree is
    # overkill — compare against level-free dense formulation):
    from ramses_tpu.grid import boundary as bmod
    from ramses_tpu.hydro import muscl
    up2 = bmod.pad(ud, bc, cfg, muscl.NGHOST, dx=dx)
    flux, _tmp = muscl.unsplit(up2, None, dt, (dx,) * 3, cfg)
    okp2 = ok
    for d in range(3):
        padw = [(muscl.NGHOST, muscl.NGHOST) if d2 == d else (0, 0)
                for d2 in range(3)]
        okp2 = jnp.pad(okp2, padw, mode="wrap")
    masked = []
    for d in range(3):
        keep = ~(okp2 | jnp.roll(okp2, 1, axis=d))
        masked.append(flux[d] * keep[None].astype(flux.dtype))
    g = muscl.NGHOST
    for d in range(3):
        f0 = masked[d][0]
        lo_ix = tuple(slice(g, g + shape[dd]) for dd in range(3))
        hi_ix = tuple(slice(g + 1, g + 1 + shape[dd]) if dd == d
                      else slice(g, g + shape[dd]) for dd in range(3))
        np.testing.assert_allclose(np.asarray(phi_k[d, 0]),
                                   np.asarray(f0[lo_ix]),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(phi_k[d, 1]),
                                   np.asarray(f0[hi_ix]),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("tile", [(16, 8), (8, 16)],
                         ids=lambda t: "bx%d-by%d" % t)
@pytest.mark.parametrize("want_flux", [False, True])
def test_fused_step_shard_relabel_parity(tile_rule, want_flux, tile):
    """Per-shard relabeled entry == unrelabeled interior kernel, under
    a y tile of 8 and of 16 (the junk rows ``fused_step_shard`` pads are
    the pick's ``Y_SLACK``, whatever ``by``).

    ``shard_axes`` gates to TPU, so drive ``fused_step_shard`` directly
    in interpreter mode: original axis 0 (extent 128) takes the lane
    role, axes 1/2 carry NG ghost slabs — the relabel the slab path
    produces when z was cut first.  Tolerance (not bitwise): the
    relabeled kernel sweeps directions in relabeled order.
    """
    from ramses_tpu.amr import kernels as K

    cfg = _cfg("hllc")
    loc = (128, 16, 16)
    axes = (1, 2, 0)
    rng = np.random.default_rng(7)
    r = 1.0 + 0.3 * rng.random(loc)
    v = 0.2 * rng.standard_normal((3,) + loc)
    p_ = 0.5 + 0.2 * rng.random(loc)
    e = p_ / (cfg.gamma - 1.0) + 0.5 * r * (v ** 2).sum(axis=0)
    u = jnp.asarray(np.stack([r, r * v[0], r * v[1], r * v[2], e]),
                    jnp.float32)
    okf = jnp.asarray(rng.random(loc) < 0.1, jnp.float32)
    dt = jnp.asarray(1e-3, jnp.float32)
    dx = 1.0 / loc[0]
    g = muscl.NGHOST
    # shard-path block: ghosts on axes[0]/axes[1] only, lane axis bare
    up, okp = u, okf
    for ax in axes[:2]:
        padw = [(g, g) if d == 1 + ax else (0, 0) for d in range(4)]
        up = jnp.pad(up, padw, mode="wrap")
        okp = jnp.pad(okp, [w for w in padw[1:]], mode="wrap")
    tile_rule(tile)
    out_k = pk.fused_step_shard(up, okp, dt, cfg, dx, loc, axes,
                                want_flux=want_flux, interpret=True)
    rec = [b for b in pk.block_stats() if b["shape"] == [16, 16, 128]
           and b["masked"]]
    assert [(b["bx"], b["by"]) for b in rec] == [tile]
    # reference: fully ghost-padded unrelabeled interior kernel
    upf, okpf = u, okf
    for ax in range(3):
        padw = [(g, g) if d == 1 + ax else (0, 0) for d in range(4)]
        upf = jnp.pad(upf, padw, mode="wrap")
        okpf = jnp.pad(okpf, [w for w in padw[1:]], mode="wrap")
    out_r = K.dense_interior_update(upf, okpf, dt, dx, loc, cfg,
                                    ret_flux=want_flux)
    du_k = out_k[0] if want_flux else out_k
    du_r = out_r[0] if want_flux else out_r
    np.testing.assert_allclose(np.asarray(du_k), np.asarray(du_r),
                               rtol=2e-5, atol=2e-6)
    if want_flux:
        np.testing.assert_allclose(np.asarray(out_k[1]),
                                   np.asarray(out_r[1]),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("loc,cut,want", [
    ((128, 64, 64), (False, True, True), (1, 2, 0)),    # the mesh cell's level 7
    ((128, 128, 64), (False, False, True), (0, 2, 1)),
    ((512, 256, 256), (False, True, True), (1, 2, 0)),  # a complete level 9
    ((640, 320, 320), (False, True, True), None),       # over the budget
    ((128, 64, 64), (True, True, True), None),          # no uncut axis
])
def test_shard_axes_takes_the_kernels_gate(monkeypatch, loc, cut, want):
    """``shard_axes`` gates to the TPU backend, so the CPU suite never
    reaches its body: name the backend here and hold its picks — the
    lane role goes to the last uncut axis whose relabelled box
    ``supports()`` admits (the same block budget as every other call)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _cfg("llf")
    assert pk.shard_axes(cfg, loc, cut, jnp.float32) == want
    assert pk.shard_axes(cfg, loc, cut, jnp.bfloat16) is None
    assert pk.shard_axes(_cfg("llf", slope_type=3), loc, cut,
                         jnp.float32) is None


# ---------------------------------------------------------------------------
# the fused uniform step program (grid/uniform._run_steps_pallas): the
# loop round the kernel.  The CPU suite never reaches it through
# ``run_steps`` (``kernel_available`` is false off the TPU), so it is
# driven directly with the kernel patched to interpreter mode, against
# the scan-with-masks form it replaced (kept here, nowhere else).
# ---------------------------------------------------------------------------

LOOP_SHAPE = (8, 16, 128)


@pytest.fixture()
def interpreted_kernel(monkeypatch, tile_rule):
    # held at the (8, 8) tile: two grid steps a box.  Under the rule's
    # pick for this small box (one grid step) XLA's CPU backend fuses the
    # interpreted body into the loop round it and contracts FMAs one way
    # in the while and another in the scan; without FMA
    # (``--xla_cpu_max_isa=SSE4_2``, tests/no_fma_child.py) every case
    # below holds under that pick too.
    tile_rule((8, 8))
    real = pk.fused_step_padded
    monkeypatch.setattr(
        pk, "fused_step_padded",
        lambda *a, **kw: real(*a, interpret=True, **kw))


def _loop_grid(riemann):
    return uniform.UniformGrid(
        cfg=_cfg(riemann), shape=LOOP_SHAPE, dx=1.0 / LOOP_SHAPE[0],
        bc=bmod.BoundarySpec.periodic(3))


@partial(jax.jit, static_argnames=("grid", "nsteps", "trace", "dt_scale"))
def _run_steps_scan(grid, u, t, tend, nsteps, trace=False, dt_scale=1.0):
    """The masked ``lax.scan`` form of the fused step program as it
    stood through PR 31: every step runs, an inactive one is selected
    away."""
    cfg = grid.cfg
    dtmax = cfg.courant_factor * grid.dx / cfg.smallc
    dt0 = compute_dt(u, None, grid.dx, cfg) * dt_scale

    def body(carry, _):
        u, t, ndone, dtc = carry
        dt = jnp.minimum(dtc, jnp.maximum(tend - t, 0.0))
        active = t < tend
        up, _ = pk.pad_xy(u, grid.bc, cfg)
        un, crt = pk.fused_step_padded(up, jnp.where(active, dt, 0.0),
                                       cfg, grid.dx, grid.shape,
                                       courant=True)
        dtn = jnp.minimum(dtmax, crt[0, 0] * dt_scale)
        u = jnp.where(active, un, u)
        t = jnp.where(active, t + dt, t)
        dtc = jnp.where(active, dtn, dtc)
        ndone = ndone + jnp.where(active, 1, 0)
        ys = (t, jnp.where(active, dt, 0.0)) if trace else None
        return (u, t, ndone, dtc), ys

    (u, t, ndone, _), hist = jax.lax.scan(
        body, (u, t, jnp.array(0), dt0), None, length=nsteps)
    if trace:
        return u, t, ndone, hist
    return u, t, ndone


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _first_dt(grid, u, dt_scale=1.0):
    return float(compute_dt(u, None, grid.dx, grid.cfg)) * dt_scale


# (id, nsteps, tend in units of the first step's dt or None = far away,
#  steps expected, dt_scale, dtype of the time axis)
LOOP_CASES = [
    ("all16", 16, None, 16, 1.0, jnp.float32),
    ("tend-after-3-of-8", 8, 2.5, 3, 1.0, jnp.float32),
    ("tend-not-after-t", 8, 0.0, 0, 1.0, jnp.float32),
    ("half-dt", 8, None, 8, 0.5, jnp.float32),
    ("f64-time-3-of-8", 8, 2.5, 3, 1.0, jnp.float64),
]


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "trace"])
@pytest.mark.parametrize("riemann", ["llf", "hllc"])
@pytest.mark.parametrize("case", LOOP_CASES, ids=[c[0] for c in LOOP_CASES])
def test_run_steps_pallas_matches_scan(interpreted_kernel, case, riemann,
                                       trace):
    """``_run_steps_pallas`` (a ``while_loop`` that stops when no step
    is owed) against the scan that masked such steps out: ``(u, t,
    ndone)`` and the ``(t_after, dt)`` history bit for bit."""
    _, nsteps, tend_dts, want_n, dt_scale, tdtype = case
    grid = _loop_grid(riemann)
    u = _state(grid.cfg, seed=13, shape=LOOP_SHAPE)
    t0 = 0.25
    tend = 1e9 if tend_dts is None else t0 + tend_dts * _first_dt(
        grid, u, dt_scale)
    t, tend = jnp.asarray(t0, tdtype), jnp.asarray(tend, tdtype)
    got = uniform._run_steps_pallas(grid, u, t, tend, nsteps, trace=trace,
                                    dt_scale=dt_scale)
    want = _run_steps_scan(grid, u, t, tend, nsteps, trace=trace,
                           dt_scale=dt_scale)
    _same_bits(got, want)
    assert int(got[2]) == want_n
    if tend_dts is not None:
        # the clip lands on tend exactly; no step: the input comes back
        assert got[1] == (tend if want_n else t)
    if want_n == 0:
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(u))
    if trace:
        t_hist, dt_hist = (np.asarray(h) for h in got[3])
        assert t_hist.shape == dt_hist.shape == (nsteps,)
        assert (dt_hist[:want_n] > 0).all() and (dt_hist[want_n:] == 0).all()
        assert (t_hist[want_n:] == np.asarray(got[1])).all()


@pytest.mark.parametrize("riemann", ["llf", "hllc"])
def test_run_steps_pallas_vmap_members_equal_solo(interpreted_kernel,
                                                  riemann):
    """``run_steps_batch``'s use: ``jax.vmap`` of the loop over members
    with different ``tend``s runs until the last member is done and
    leaves each member what its solo run gives (and what the vmapped
    scan gave)."""
    grid = _loop_grid(riemann)
    nsteps = 8
    us = jnp.stack([_state(grid.cfg, seed=s, shape=LOOP_SHAPE)
                    for s in (31, 32, 33)])
    ts = jnp.asarray([0.0, 0.5, 0.25], jnp.float32)
    # member 0 owes all 8 steps, member 1 none, member 2 three
    tends = jnp.asarray([1e9, 0.5, 0.25 + 2.5 * _first_dt(grid, us[2])],
                        jnp.float32)

    def batch(fn):
        return jax.vmap(lambda u, t, te: fn(grid, u, t, te, nsteps))(
            us, ts, tends)

    got = batch(uniform._run_steps_pallas)
    _same_bits(got, batch(_run_steps_scan))
    assert list(np.asarray(got[2])) == [8, 0, 3]
    for i in range(3):
        solo = uniform._run_steps_pallas(grid, us[i], ts[i], tends[i],
                                         nsteps)
        _same_bits(tuple(g[i] for g in got), solo)


# ---------------------------------------------------------------------------
# the tile: every (bx, by) the block rule chooses among gives the cells,
# face fluxes and Courant dt of (8, 8).  The tile changes which cells one
# grid step computes, not one cell's arithmetic, and ``min`` is exact: to
# the bit where the backend cannot contract FMAs differently per block
# shape (``tests/no_fma_child.py``), to the file's tolerance in this process.
# ---------------------------------------------------------------------------

TILE_SHAPE = (32, 32, 128)
TILES = [(bx, by) for by in (8, 16, 32) for bx in (4, 8, 16, 32)]
TILE_MODES = ("plain", "masked", "want_flux")
TILE_CASES = pytest.mark.parametrize(
    "mode,tile", [(m, t) for m in TILE_MODES for t in TILES],
    ids=lambda v: v if isinstance(v, str) else "bx%d-by%d" % v)


def _tile_call(mode, tile):
    """One interpreted call on ``TILE_SHAPE`` with the block rule
    overridden to ``tile``: ``plain`` → (cells, Courant dt), ``masked``
    → cells, ``want_flux`` → (cells, face fluxes) of the masked call."""
    cfg = _cfg("hllc")
    bc = bmod.BoundarySpec.periodic(3)
    u = _state(cfg, seed=17, shape=TILE_SHAPE)
    ok = jnp.asarray(np.random.default_rng(19).random(TILE_SHAPE) < 0.1)
    up, okp = pk.pad_xy(u, bc, cfg, ok=None if mode == "plain" else ok)
    with _under_tile(tile):
        out = pk.fused_step_padded(
            up, jnp.asarray(1e-3, jnp.float32), cfg, 1.0 / TILE_SHAPE[0],
            TILE_SHAPE, ok_pad=okp, courant=mode == "plain",
            want_flux=mode == "want_flux", interpret=True)
        rec = pk._BLOCKS[(TILE_SHAPE, mode != "plain")]
    return [np.asarray(a) for a in jax.tree.leaves(out)], rec


def tile_child_main(modes):
    """The bitwise cases, in a process without FMA: one JSON object
    ``{"mode/bxXby": "" when equal to the (8, 8) call to the bit, else
    why not}``."""
    out = {}
    for mode in modes:
        want, _ = _tile_call(mode, (8, 8))
        for tile in TILES:
            got = want if tile == (8, 8) else _tile_call(mode, tile)[0]
            bad = [i for i, (g, w) in enumerate(zip(got, want))
                   if g.dtype != w.dtype or g.shape != w.shape
                   or not np.array_equal(g, w)]
            out["%s/%dx%d" % ((mode,) + tile)] = (
                "" if not bad and len(got) == len(want) else
                "outputs %s differ, max %g" % (bad, max(
                    float(np.abs(got[i] - want[i]).max()) for i in bad)))
    print("RESULT " + json.dumps(out))


@pytest.fixture(scope="module")
def tile_children():
    """One child without FMA per mode, all started at once; a case's
    answer waits for its own mode's child only."""
    procs = {m: no_fma_child.start(__file__, m) for m in TILE_MODES}
    results = {}

    def answer(mode, tile):
        if mode in procs:
            results.update(no_fma_child.result(procs.pop(mode)))
        return results["%s/%dx%d" % ((mode,) + tile)]

    yield answer
    for proc in procs.values():
        proc.kill()


@pytest.fixture(scope="module")
def tile_88():
    return {m: _tile_call(m, (8, 8))[0] for m in TILE_MODES}


@TILE_CASES
def test_tile_changes_no_result(tile_88, mode, tile):
    """Cells, fluxes and the Courant dt under every candidate tile
    against the (8, 8) result of the same call, and the record the call
    leaves says the tile that ran."""
    got, rec = _tile_call(mode, tile)
    want = tile_88[mode]
    assert len(got) == len(want) == {"plain": 2, "masked": 1,
                                     "want_flux": 2}[mode]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
    bx, by = tile
    assert rec == {"shape": list(TILE_SHAPE), "masked": mode != "plain",
                   "bx": bx, "by": by,
                   "window_cells": (bx + 4) * (by + 8) * TILE_SHAPE[2],
                   "written_cells": bx * by * TILE_SHAPE[2]}


@TILE_CASES
def test_tile_changes_no_bit(tile_children, mode, tile):
    """The same comparison to the bit, where the backend has no FMA to
    contract differently per block shape."""
    assert tile_children(mode, tile) == ""


if __name__ == "__main__":
    tile_child_main(sys.argv[1].split(","))
