"""Fused MUSCL-Hancock TPU kernel for AMR oct-stencil batches (Pallas).

The partial-level sweep (``godfine1`` on an incomplete level,
``hydro/godunov_fine.f90:486-910``) runs on gathered ``[nvar, 6,6,6,
noct]`` stencil blocks (:func:`ramses_tpu.amr.kernels.level_sweep`).
The XLA formulation materializes ~60 block-sized intermediates in HBM;
at a few thousand octs that traffic — not the flops — is the whole cost,
and on the Sedov benchmark the fine-level sweeps end up costing as much
as the complete base level's fused kernel.  This kernel keeps every
intermediate in VMEM: HBM sees one read of the stencil block (+ mask)
and one write of (du, coarse-correction fluxes).

Layout: the oct axis is minor (lane dimension, 128-multiple — the
bucket padding guarantees this beyond tiny levels); the three 6-cell
stencil axes lead.  Neighbour access is ``jnp.roll`` along the leading
axes, wrap-around junk confined to stencil cells the 2³ interior never
consumes — exactly the XLA path's contract.

Scope (gated by :func:`available` / :func:`tile_available`, falls
back to the XLA formulation otherwise): ndim=3 hydro,
nener=npassive=0, no pressure_fix, scheme=muscl, slope_type∈{1,2,8},
riemann∈{llf, hllc}, f32, single device.  The gate only selects the
KERNEL, not the blocked decomposition: sharded meshes, f64, and MHD
still run the blocked Morton-tile sweep in its XLA formulation
(``FusedSpec.pallas_tiles=False``; ``mhd/amr.py mhd_tile_sweep``),
bitwise-identical to this kernel where both apply.  Self-gravity
needs NO kernel support: the hierarchy applies
it as a separate traced half-kick around the sweep
(``kick_flat`` — ``amr/hierarchy.py _advance_traced``), so gravity
production runs take this kernel too.  ``want_flux=True`` adds the MC
gas-tracer per-cell face mass-flux capture as a third output
(``godunov_fine.f90:685-715``), covering tracer runs as well.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.hydro.pallas_muscl import (DISABLED, _hllc_flux, _llf_flux,
                                           _slopes)


# Test hook: force the kernel branch on any backend, run it in Pallas
# interpreter mode — lets CI drive level_sweep's REAL pallas branch (not
# a replica) on the CPU test backend.  Module attribute so tests can
# monkeypatch; also settable via env for whole-suite sweeps.
FORCE_INTERPRET = bool(__import__("os").environ
                       .get("RAMSES_PALLAS_OCT_INTERPRET"))


def _in_scope(cfg: HydroStatic, dtype, ndev: int = 1) -> bool:
    """Platform + physics scope shared by both kernels' gates (see the
    module docstring).  ``ndev``: devices the caller's level rows span
    (not the host's device count); row-sharded levels stay off the
    kernels, interpreted or not, so GSPMD can partition the sweep."""
    if DISABLED or ndev != 1:
        return False
    if not FORCE_INTERPRET and jax.default_backend() != "tpu":
        return False
    if getattr(cfg, "physics", "hydro") != "hydro":
        return False
    if cfg.ndim != 3 or cfg.nener != 0 or cfg.npassive != 0:
        return False
    if cfg.pressure_fix or cfg.scheme != "muscl":
        return False
    if cfg.slope_type not in (1, 2, 8):
        return False
    if cfg.riemann not in ("llf", "hllc"):
        return False
    if dtype not in (jnp.float32, jnp.dtype("float32")):
        return False
    return True


def available(cfg: HydroStatic, noct_pad: int, dtype,
              ndev: int = 1) -> bool:
    """Availability gate for the oct-batch kernel (see module docstring;
    the one-device restriction mirrors ``pallas_muscl.kernel_available``
    — levels sharded over ``ndev`` > 1 devices keep the XLA formulation
    so GSPMD can partition; with blocking on they still get the compact
    tile batch)."""
    return _in_scope(cfg, dtype, ndev) and noct_pad % 128 == 0


def _tile(noct_pad: int) -> int:
    """Lane-tile size: ~45 live [6,6,6,NT] f32 arrays must fit VMEM."""
    for nt in (512, 256, 128):
        if noct_pad % nt == 0:
            return nt
    raise AssertionError("gated by available()")


# the device ops' names in a trace (``name=`` of the two pallas_calls)
OCT_KERNEL_NAME = "oct_sweep"
TILE_KERNEL_NAME = "tile_sweep"


def _make_kernel(cfg: HydroStatic, dx: float, want_flux: bool = False):
    """Kernel body; refs: u [5,6,6,6,NT], ok [6,6,6,NT] (state-dtype
    0/1 refined mask), dt [1,1] SMEM → du [5,2,2,2,NT] (interior
    update), corr [5,3,2,NT] (dt/dx-scaled boundary-face flux sums)
    [, phi [3,2,2,2,2,NT] (d, side, interior) dt/dx-scaled per-cell
    face MASS fluxes — the MC-tracer capture]."""
    st = cfg.slope_type
    theta = float(getattr(cfg, "slope_theta", 1.5))
    solver = _llf_flux if cfg.riemann == "llf" else _hllc_flux
    core = (slice(2, 4), slice(2, 4), slice(2, 4))

    def kernel(u_ref, ok_ref, dt_ref, du_ref, corr_ref, *phi_ref):
        dt = dt_ref[0, 0]
        # ---- ctoprim ----
        r = jnp.maximum(u_ref[0], cfg.smallr)
        ir = 1.0 / r
        v = [u_ref[1] * ir, u_ref[2] * ir, u_ref[3] * ir]
        ek = 0.5 * (v[0] * v[0] + v[1] * v[1] + v[2] * v[2])
        eint = jnp.maximum(u_ref[4] * ir - ek, cfg.smalle)
        p = (cfg.gamma - 1.0) * r * eint
        q = (r, v[0], v[1], v[2], p)
        # ---- uslope ----
        dq = []
        for d in range(3):
            qm1 = tuple(jnp.roll(c, 1, axis=d) for c in q)
            qp1 = tuple(jnp.roll(c, -1, axis=d) for c in q)
            dq.append(tuple(_slopes(a, b, c, st, theta)
                            for a, b, c in zip(qm1, q, qp1)))
        # ---- trace3d source terms ----
        divv = dq[0][1] + dq[1][2] + dq[2][3]
        adv = lambda comp: (v[0] * dq[0][comp] + v[1] * dq[1][comp]
                            + v[2] * dq[2][comp])
        sr0 = -adv(0) - divv * r
        sp0 = -adv(4) - divv * cfg.gamma * p
        sv0 = [-adv(1 + j) - dq[j][4] * ir for j in range(3)]
        dtdx2 = 0.5 * dt / dx
        okf = ok_ref[:]
        scale = dt / dx

        du = [None] * 5
        for d in range(3):
            def face_state(sgn):
                rho = r + sgn * 0.5 * dq[d][0] + sr0 * dtdx2
                rho = jnp.where(rho < cfg.smallr, r, rho)
                vs = [v[j] + sgn * 0.5 * dq[d][1 + j] + sv0[j] * dtdx2
                      for j in range(3)]
                pp = p + sgn * 0.5 * dq[d][4] + sp0 * dtdx2
                return (rho, vs[0], vs[1], vs[2], pp)
            qm = face_state(+1.0)
            qp = face_state(-1.0)
            ql5 = tuple(jnp.roll(c, 1, axis=d) for c in qm)
            qr5 = qp
            ql5 = (jnp.maximum(ql5[0], cfg.smallr), ql5[1], ql5[2], ql5[3],
                   jnp.maximum(ql5[4], ql5[0] * cfg.smallp))
            qr5 = (jnp.maximum(qr5[0], cfg.smallr), qr5[1], qr5[2], qr5[3],
                   jnp.maximum(qr5[4], qr5[0] * cfg.smallp))
            flux = solver(ql5, qr5, d, cfg)
            # refined-face zeroing (godunov_fine.f90:718-747): a face is
            # dropped when either adjacent cell is refined
            keepf = (1.0 - okf) * (1.0 - jnp.roll(okf, 1, axis=d))
            flux = tuple(f * keepf for f in flux)
            # coarse-correction sums: low face idx 2 / high face idx 4,
            # summed over the 2x2 transverse interior, ×dt/dx
            lo_ix = tuple(2 if dd == d else slice(2, 4) for dd in range(3))
            hi_ix = tuple(4 if dd == d else slice(2, 4) for dd in range(3))
            for c in range(5):
                corr_ref[c, d, 0] = flux[c][lo_ix].sum(axis=(0, 1)) * scale
                corr_ref[c, d, 1] = flux[c][hi_ix].sum(axis=(0, 1)) * scale
                contrib = (flux[c] - jnp.roll(flux[c], -1, axis=d)) * scale
                du[c] = contrib if du[c] is None else du[c] + contrib
            if want_flux:
                # per-cell (low, high) face mass flux: the cell's low
                # face sits at its own stencil slot, its high face at
                # the next slot along d
                phi_ref[0][d, 0] = (flux[0] * scale)[core]
                phi_ref[0][d, 1] = (jnp.roll(flux[0], -1, axis=d)
                                    * scale)[core]
        for c in range(5):
            du_ref[c] = du[c][core]

    return kernel


@partial(jax.jit, static_argnames=("cfg", "dx", "interpret",
                                   "want_flux"))
def oct_sweep(uloc, ok, dt, cfg: HydroStatic, dx: float,
              interpret: bool = False, want_flux: bool = False):
    """Fused partial-level sweep on a gathered stencil batch.

    uloc: [5, 6, 6, 6, N] (N = padded oct count, 128-multiple);
    ok: [6, 6, 6, N] refined-cell mask in the state dtype (0/1).
    Returns (du [5, 2, 2, 2, N], corr [5, 3, 2, N]) with corr already
    ×dt/dx — the :func:`~ramses_tpu.amr.kernels.level_sweep` convention
    — plus, with ``want_flux``, phi [3, 2, 2, 2, 2, N]: per-cell
    (d, side, interior) dt/dx-scaled face mass fluxes (the MC-tracer
    capture).
    """
    n = uloc.shape[-1]
    nt = _tile(n)
    dt2 = jnp.asarray(dt, uloc.dtype).reshape(1, 1)
    kern = _make_kernel(cfg, dx, want_flux)
    interpret = interpret or FORCE_INTERPRET
    out_specs = [
        pl.BlockSpec((5, 2, 2, 2, nt), lambda i: (0, 0, 0, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((5, 3, 2, nt), lambda i: (0, 0, 0, i),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((5, 2, 2, 2, n), uloc.dtype),
        jax.ShapeDtypeStruct((5, 3, 2, n), uloc.dtype),
    ]
    if want_flux:
        out_specs.append(
            pl.BlockSpec((3, 2, 2, 2, 2, nt),
                         lambda i: (0, 0, 0, 0, 0, i),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((3, 2, 2, 2, 2, n), uloc.dtype))
    return pl.pallas_call(
        kern,
        grid=(n // nt,),
        in_specs=[
            pl.BlockSpec((5, 6, 6, 6, nt), lambda i: (0, 0, 0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 6, 6, nt), lambda i: (0, 0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret, name=OCT_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(uloc, ok, dt2)


# ---------------------------------------------------------------------------
# Blocked Morton tile kernel (gather-fused oct path)
# ---------------------------------------------------------------------------

_NG = 2                                   # tile halo width (MUSCL stencil)


# tile sizes (``oct_block_shift``) whose kernel the chip's compiler is
# proven to accept (tests/test_chip_compile.py).  shift=1 (td=8) makes
# Mosaic ABORT the process (lower_to_llo.cc "d >> 32 == 0"), so the gate
# refuses it and those runs take the XLA tile formulation openly.
_PROVEN_SHIFTS = (2,)
_LANES = 128


def tile_shape_ok(ntile_pad: int, shift: int) -> bool:
    """Tile-batch shapes Mosaic accepts: the lane block must be the
    whole tile axis (counts below 128, sublane-aligned) or a multiple
    of 128.  Tile counts are power-of-2 bucketed (>= 8), so every
    bucket qualifies."""
    if shift not in _PROVEN_SHIFTS:
        return False
    if ntile_pad < _LANES:
        return ntile_pad % 8 == 0
    return ntile_pad % _LANES == 0


def tile_available(cfg: HydroStatic, ntile_pad: int, dtype,
                   shift: int) -> bool:
    """Availability gate for the blocked tile kernel — same physics scope
    as :func:`available`, plus the compile-proven tile shapes."""
    return _in_scope(cfg, dtype) and tile_shape_ok(ntile_pad, shift)


def _make_tile_kernel(cfg: HydroStatic, dx: float, c: int,
                      want_flux: bool = False):
    """Tile-kernel body; refs: u [5,td,td,td,NT], ok [td,td,td,NT],
    dt [1,1] SMEM → du [5,c,c,c,NT] (interior update), corrp
    [5,3,c//2+1,c,c,NT] (dt/dx-scaled per-oct-face flux planes,
    transverse interior, in increasing-dim order) [, phip
    [3,c+1,c,c,NT] (dt/dx-scaled per-cell-face mass-flux planes)].
    Physics body identical to :func:`_make_kernel`; only the geometry
    (interior core, plane outputs) differs."""
    st = cfg.slope_type
    theta = float(getattr(cfg, "slope_theta", 1.5))
    solver = _llf_flux if cfg.riemann == "llf" else _hllc_flux
    o = c // 2
    core = (slice(_NG, _NG + c),) * 3

    def kernel(u_ref, ok_ref, dt_ref, du_ref, corrp_ref, *phi_ref):
        dt = dt_ref[0, 0]
        # ---- ctoprim ----
        r = jnp.maximum(u_ref[0], cfg.smallr)
        ir = 1.0 / r
        v = [u_ref[1] * ir, u_ref[2] * ir, u_ref[3] * ir]
        ek = 0.5 * (v[0] * v[0] + v[1] * v[1] + v[2] * v[2])
        eint = jnp.maximum(u_ref[4] * ir - ek, cfg.smalle)
        p = (cfg.gamma - 1.0) * r * eint
        q = (r, v[0], v[1], v[2], p)
        # ---- uslope ----
        dq = []
        for d in range(3):
            qm1 = tuple(jnp.roll(cc, 1, axis=d) for cc in q)
            qp1 = tuple(jnp.roll(cc, -1, axis=d) for cc in q)
            dq.append(tuple(_slopes(a, b, cc, st, theta)
                            for a, b, cc in zip(qm1, q, qp1)))
        # ---- trace3d source terms ----
        divv = dq[0][1] + dq[1][2] + dq[2][3]
        adv = lambda comp: (v[0] * dq[0][comp] + v[1] * dq[1][comp]
                            + v[2] * dq[2][comp])
        sr0 = -adv(0) - divv * r
        sp0 = -adv(4) - divv * cfg.gamma * p
        sv0 = [-adv(1 + j) - dq[j][4] * ir for j in range(3)]
        dtdx2 = 0.5 * dt / dx
        okf = ok_ref[:]
        scale = dt / dx

        du = [None] * 5
        for d in range(3):
            def face_state(sgn):
                rho = r + sgn * 0.5 * dq[d][0] + sr0 * dtdx2
                rho = jnp.where(rho < cfg.smallr, r, rho)
                vs = [v[j] + sgn * 0.5 * dq[d][1 + j] + sv0[j] * dtdx2
                      for j in range(3)]
                pp = p + sgn * 0.5 * dq[d][4] + sp0 * dtdx2
                return (rho, vs[0], vs[1], vs[2], pp)
            qm = face_state(+1.0)
            qp = face_state(-1.0)
            ql5 = tuple(jnp.roll(cc, 1, axis=d) for cc in qm)
            qr5 = qp
            ql5 = (jnp.maximum(ql5[0], cfg.smallr), ql5[1], ql5[2], ql5[3],
                   jnp.maximum(ql5[4], ql5[0] * cfg.smallp))
            qr5 = (jnp.maximum(qr5[0], cfg.smallr), qr5[1], qr5[2], qr5[3],
                   jnp.maximum(qr5[4], qr5[0] * cfg.smallp))
            flux = solver(ql5, qr5, d, cfg)
            keepf = (1.0 - okf) * (1.0 - jnp.roll(okf, 1, axis=d))
            flux = tuple(f * keepf for f in flux)
            # per-oct-face flux planes at positions _NG + 2k, transverse
            # interior — the 2x2 per-oct sums happen outside the kernel
            for k in range(o + 1):
                ix = tuple(_NG + 2 * k if dd == d else slice(_NG, _NG + c)
                           for dd in range(3))
                for cv in range(5):
                    corrp_ref[cv, d, k] = (flux[cv] * scale)[ix]
            for cv in range(5):
                contrib = (flux[cv] - jnp.roll(flux[cv], -1, axis=d)) * scale
                du[cv] = contrib if du[cv] is None else du[cv] + contrib
            if want_flux:
                # all c+1 cell-face mass-flux planes along d
                for j in range(c + 1):
                    ix = tuple(_NG + j if dd == d else slice(_NG, _NG + c)
                               for dd in range(3))
                    phi_ref[0][d, j] = (flux[0] * scale)[ix]
        for cv in range(5):
            du_ref[cv] = du[cv][core]

    return kernel


@partial(jax.jit, static_argnames=("cfg", "dx", "shift", "interpret",
                                   "want_flux"))
def tile_sweep(ut, ok, dt, cfg: HydroStatic, dx: float, shift: int,
               interpret: bool = False, want_flux: bool = False):
    """Fused partial-level sweep on a compact blocked tile batch.

    ut: [5, td, td, td, N] (td = 2**(shift+1)+4, N = padded tile count);
    ok: [td, td, td, N] refined-cell mask in the state dtype (0/1).
    Returns (du [5, c, c, c, N], corrp [5, 3, c//2+1, c, c, N]) with
    fluxes already ×dt/dx, plus, with ``want_flux``, phip
    [3, c+1, c, c, N].  Per-oct/per-cell reordering happens in the
    caller (:func:`ramses_tpu.amr.kernels.tile_sweep`).
    """
    c = 1 << (shift + 1)
    td = c + 2 * _NG
    o = c // 2
    n = ut.shape[-1]
    nt = min(n, _LANES)   # whole tile axis below 128 tiles, else 128 lanes
    dt2 = jnp.asarray(dt, ut.dtype).reshape(1, 1)
    kern = _make_tile_kernel(cfg, dx, c, want_flux)
    interpret = interpret or FORCE_INTERPRET
    out_specs = [
        pl.BlockSpec((5, c, c, c, nt), lambda i: (0, 0, 0, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((5, 3, o + 1, c, c, nt),
                     lambda i: (0, 0, 0, 0, 0, i),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((5, c, c, c, n), ut.dtype),
        jax.ShapeDtypeStruct((5, 3, o + 1, c, c, n), ut.dtype),
    ]
    if want_flux:
        out_specs.append(
            pl.BlockSpec((3, c + 1, c, c, nt),
                         lambda i: (0, 0, 0, 0, i),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((3, c + 1, c, c, n), ut.dtype))
    return pl.pallas_call(
        kern,
        grid=(n // nt,),
        in_specs=[
            pl.BlockSpec((5, td, td, td, nt), lambda i: (0, 0, 0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((td, td, td, nt), lambda i: (0, 0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret, name=TILE_KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(ut, ok, dt2)
