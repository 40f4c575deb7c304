"""Fused MUSCL-Hancock TPU kernel (Pallas).

The whole unsplit update — ``ctoprim → uslope → trace3d → cmpflxm →
riemann → conservative update`` (``hydro/umuscl.f90:22-171``) — as ONE
Pallas kernel.  The XLA formulation in :mod:`ramses_tpu.hydro.muscl`
materializes ~60 grid-sized intermediates per step (~85 GB of HBM traffic
at 256³); here every intermediate lives in VMEM and HBM sees exactly one
read of the (haloed) state and one write of the update, the traffic the
algorithm actually requires.

Blocking: the grid is tiled over (x, y) in tiles of ``bx x by`` cells
that ONE rule picks per (shape, masked) (:func:`_pick_block`: what
Mosaic admits, ranked by a cost measured on the chip); each program
sees the FULL z extent (z is the TPU lane dimension — keeping it whole
makes the minor dims perfectly tiled and gives the z-direction stencil
for free via lane rotates).  x/y halos (2 cells) come from overlapping
`pl.Element` windows of ``(bx+4) x (by+8)`` cells into a pre-padded
array (y is the sublane dimension: the window is whole 8-row groups);
z wraps periodically inside the kernel with ``jnp.roll`` (non-periodic
z falls back to the XLA path).

Scope: ndim=3, nener=0, npassive=0, scheme=muscl, slope_type∈{1,2,8},
riemann∈{llf, hllc}.  Everything else falls back to
:func:`ramses_tpu.hydro.muscl.unsplit` (bit-identical physics, slower).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ramses_tpu.hydro.core import HydroStatic

NG = 2  # ghost cells per side (matches muscl.NGHOST)

# Read once at import: jit caches are keyed on static args, not the
# environment, so a post-import toggle would silently hit stale caches.
DISABLED = bool(__import__("os").environ.get("RAMSES_NO_PALLAS"))


def kernel_available(cfg: HydroStatic, shape, bc_faces, dtype,
                     ndev: int = 1) -> bool:
    """Full availability gate: env kill-switch, TPU backend, a state
    that lives on ONE device (the kernel has no GSPMD partitioning rule
    — a state sharded over ``ndev`` > 1 devices must keep the XLA solver
    so the SPMD partitioner can insert halo collectives), and
    configuration coverage.  ``ndev`` is how many devices the CALLER's
    arrays span, not how many the host has: a one-device simulation on
    a four-chip host keeps its kernel."""
    if DISABLED:
        return False
    if jax.default_backend() != "tpu" or ndev != 1:
        return False
    kinds = tuple((lo.kind, hi.kind) for lo, hi in bc_faces)
    return supports(cfg, shape, kinds, dtype)


def supports(cfg: HydroStatic, shape, bc_kinds, dtype) -> bool:
    """True when the fused kernel covers this configuration.

    ``bc_kinds``: per-dim (low, high) boundary kinds (grid.boundary codes).
    """
    if getattr(cfg, "physics", "hydro") != "hydro":
        return False
    if cfg.ndim != 3 or cfg.nener != 0 or cfg.npassive != 0:
        return False
    if cfg.scheme != "muscl" or cfg.slope_type not in (1, 2, 8):
        return False
    if cfg.pressure_fix:
        return False
    if cfg.riemann not in ("llf", "hllc"):
        return False
    if tuple(bc_kinds[2]) != (0, 0):  # z handled by in-kernel periodic roll
        return False
    for d in (0, 1):                  # x/y pad: periodic/reflect/outflow
        if any(k not in (0, 1, 2) for k in bc_kinds[d]):
            return False
    if dtype not in (jnp.float32, jnp.dtype("float32")):
        return False
    return _pick_block(shape, masked=True)[0] is not None


# The fixed y tile of mhd/pallas_ct (its own kernel, its own rule: by
# hand its fastest tile is the one it ships), imported from here.  This
# module's tile is _pick_block's.
WY = 16
BY = 8

# y window of a ``by``-row tile: 2 ghost rows each side, rounded up to
# the 8-sublane rule — 4 junk rows at the high end for EVERY by, so the
# padded state is (nx+4, ny+8, nz) whatever the pick.
Y_SLACK = 4


def _wy(by: int) -> int:
    return by + 2 * NG + Y_SLACK


# The tile rule, stated once.  A grid step reads a window of
# (bx+4) x (by+8) x nz cells of each input (five variables, six with
# the refined mask) to write bx x by x nz.  Both halves are measured on
# a v5e (PERF.md section 6, PR 35: 16-step slices of the loop form under
# every tile of TILES_X x TILES_Y at 256^3 and 512^3, the masked kernel
# at 128^3):
#
# Cost.  A slice's kernel time is K * (bx + X_HALO_COST)/bx * (by+8)/by:
#   y: the kernel computes whole 8-row sublane groups, and the written
#      rows 2..by+1 touch all (by+8)/8 of the window's — 2.0 x the
#      written groups at by 8, 1.5 at 16, 1.25 at 32 (measured 1 : 0.754
#      : 0.641 at 512^3, 1 : 0.753 : 0.623 at 256^3);
#   x: the leading dim is unrolled plane by plane, and most work on the
#      four halo planes is dead and dropped: they cost about ONE plane
#      (X_HALO_COST fitted 0.9-1.2 over both sizes and all three by).
# Among the admitted tiles this cost ranks every measured pair right.
#
# Budget.  The input windows of one grid step may hold WINDOW_BYTES.
# Every tile up to 960 KiB a variable ran at the cost above; every tile
# from 1152 KiB a variable up ran SLOWER than its half (512^3: bx 32 at
# by 8, (32, 16), (16, 32); 256^3: (32, 32)): 5 MiB over the five
# variables.  Mosaic itself admits more: by its own report the scoped
# VMEM of a call is the register allocator's spill slots (40.9 windows,
# 46.3 masked, at (32, 32) x 512 lanes) plus the pipeline's double
# buffers, under VMEM_LIMIT_BYTES (what every call asks for) up to
# ~1.7 MiB a variable — so every tile this budget admits compiles.
# The MASKED signature (a complete level inside a hierarchy) is held to
# MASKED_WINDOW_BYTES over its six windows, 256 KiB a variable: the
# kernel is unrolled over its window, its code (0.6 MB at 160 KiB a
# variable, 2.9 MB at 720) lives in HBM once in EACH whole-hierarchy
# program that sweeps the level (4 on one chip, 10 on the mesh), and at
# (32, 32) x 128 lanes that was +9.1 MB = +1.9 % of the AMR run's peak
# HBM for a sweep that is a few ms of a host-bound step.
# Each admitted (shape, masked, want_flux) has its compile case for the
# described chip in tests/test_chip_compile.py, which also pins every
# pick.  LANES are the lane extents that have those cases; the gate
# declines the rest.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
WINDOW_BYTES = 5 * 1024 * 1024
MASKED_WINDOW_BYTES = 6 * 256 * 1024
X_HALO_COST = 1.0
LANES = (128, 256, 384, 512)
TILES_X = (32, 16, 8, 4)
TILES_Y = (32, 16, 8)


def _window_fits(bx: int, by: int, nz: int, masked: bool = False) -> bool:
    window = (bx + 2 * NG) * _wy(by) * nz * 4
    if masked:
        return 6 * window <= MASKED_WINDOW_BYTES
    return 5 * window <= WINDOW_BYTES


def _tile_cost(bx: int, by: int) -> float:
    return (bx + X_HALO_COST) / bx * _wy(by) / by


def _pick_block(shape, masked: bool = False
                ) -> Tuple[Optional[int], Optional[int]]:
    """The (bx, by) tile of a call on ``shape`` (``masked``: with the
    refined-cell mask), or (None, None) where the kernel cannot tile
    the box: the admitted tile the rule above ranks first.

    Mosaic requires the last two block dims divisible by (8, 128): z is
    always the full extent (lane dim: one of ``LANES``); the y tile is
    whole 8-row groups read through a ``by+8``-row window (2 halo + 2
    junk rows per side); x is a free (untiled) dim, so its window is
    exactly bx+4.
    """
    nx, ny, nz = shape
    if nz not in LANES:
        return None, None
    fits = [(bx, by) for bx in TILES_X for by in TILES_Y
            if nx % bx == 0 and ny % by == 0
            and _window_fits(bx, by, nz, masked)]
    return min(fits, key=lambda t: _tile_cost(*t), default=(None, None))


# Trace-time record of what the block rule picked, one per call
# signature (jit caching: each signature traces once).  Read by
# telemetry (``run_header.sweep_block``), the ``[kernel]`` screen line
# and the benchmark's ``sweep_window_ratio``.
_BLOCKS: dict = {}


def _block_record(shape, masked: bool) -> dict:
    bx, by = _pick_block(shape, masked)
    return {"shape": list(shape), "masked": masked, "bx": bx, "by": by,
            "window_cells": (bx + 2 * NG) * _wy(by) * shape[2],
            "written_cells": bx * by * shape[2]}


def block_stats() -> list:
    """``[{shape, masked, bx, by, window_cells, written_cells}]`` for
    every (shape, masked) the kernel was traced for in this process:
    each grid step loads and computes ``window_cells`` to write
    ``written_cells``."""
    return [dict(b) for b in _BLOCKS.values()]


def _slopes(ql, q, qr, st: int, theta: float):
    """TVD slope of one variable given (left, centre, right) neighbours."""
    dl = q - ql
    dr = qr - q
    dcen = 0.5 * (dl + dr)
    if st in (1, 2):
        f = float(st)
        slop = f * jnp.minimum(jnp.abs(dl), jnp.abs(dr))
    else:                              # generalized minmod (theta)
        slop = theta * jnp.minimum(jnp.abs(dl), jnp.abs(dr))
    dlim = jnp.where(dl * dr <= 0.0, 0.0, slop)
    return jnp.sign(dcen) * jnp.minimum(dlim, jnp.abs(dcen))


def _roll(a, shift: int, axis: int):
    return jnp.roll(a, shift, axis=axis)


def _llf_flux(ql, qr, d: int, cfg: HydroStatic):
    """LLF flux of one face set; ql/qr are 5-tuples (r, vx, vy, vz, p) with
    density/pressure already floored.  Returns 5-tuple of state-layout
    fluxes (mass, mom_x, mom_y, mom_z, energy)."""
    g = cfg.gamma
    entho = 1.0 / (g - 1.0)
    rl, pl_ = ql[0], ql[4]
    rr, pr_ = qr[0], qr[4]
    ul, ur = ql[1 + d], qr[1 + d]
    cl = jnp.sqrt(jnp.maximum(g * pl_ / rl, cfg.smallc ** 2))
    cr = jnp.sqrt(jnp.maximum(g * pr_ / rr, cfg.smallc ** 2))
    cmax = jnp.maximum(jnp.abs(ul) + cl, jnp.abs(ur) + cr)

    def cons_flux(q5, un):
        r, p = q5[0], q5[4]
        ek = 0.5 * r * (q5[1] * q5[1] + q5[2] * q5[2] + q5[3] * q5[3])
        et = p * entho + ek
        ucons = (r, r * q5[1], r * q5[2], r * q5[3], et)
        f = [r * un * q5[1 + c] for c in range(3)]
        f[d] = f[d] + p
        return ucons, (r * un, f[0], f[1], f[2], un * (et + p))

    uL, fL = cons_flux(ql, ul)
    uR, fR = cons_flux(qr, ur)
    return tuple(0.5 * (fl + fr - cmax * (ur_ - ul_))
                 for fl, fr, ul_, ur_ in zip(fL, fR, uL, uR))


def _hllc_flux(ql, qr, d: int, cfg: HydroStatic):
    """HLLC with Toro sampling (``riemann_hllc``, godunov_utils.f90:988),
    specialized to nener=0/npassive=0, state-layout output."""
    g = cfg.gamma
    entho = 1.0 / (g - 1.0)
    rl, pl_ = ql[0], ql[4]
    rr, pr_ = qr[0], qr[4]
    ul, ur = ql[1 + d], qr[1 + d]
    ekl = 0.5 * rl * (ql[1] * ql[1] + ql[2] * ql[2] + ql[3] * ql[3])
    ekr = 0.5 * rr * (qr[1] * qr[1] + qr[2] * qr[2] + qr[3] * qr[3])
    etotl = pl_ * entho + ekl
    etotr = pr_ * entho + ekr
    cfastl = jnp.sqrt(jnp.maximum(g * pl_ / rl, cfg.smallc ** 2))
    cfastr = jnp.sqrt(jnp.maximum(g * pr_ / rr, cfg.smallc ** 2))
    SL = jnp.minimum(ul, ur) - jnp.maximum(cfastl, cfastr)
    SR = jnp.maximum(ul, ur) + jnp.maximum(cfastl, cfastr)
    rcl = rl * (ul - SL)
    rcr = rr * (SR - ur)
    ustar = (rcr * ur + rcl * ul + (pl_ - pr_)) / (rcr + rcl)
    pstar = (rcr * pl_ + rcl * pr_ + rcl * rcr * (ul - ur)) / (rcr + rcl)
    rstarl = rl * (SL - ul) / (SL - ustar)
    etotstarl = ((SL - ul) * etotl - pl_ * ul + pstar * ustar) / (SL - ustar)
    rstarr = rr * (SR - ur) / (SR - ustar)
    etotstarr = ((SR - ur) * etotr - pr_ * ur + pstar * ustar) / (SR - ustar)

    def sel(a_l, a_sl, a_sr, a_r):
        return jnp.where(SL > 0.0, a_l,
               jnp.where(ustar > 0.0, a_sl,
               jnp.where(SR > 0.0, a_sr, a_r)))

    ro = sel(rl, rstarl, rstarr, rr)
    uo = sel(ul, ustar, ustar, ur)
    po = sel(pl_, pstar, pstar, pr_)
    etoto = sel(etotl, etotstarl, etotstarr, etotr)
    left = ustar > 0.0
    fmass = ro * uo
    f = [None] * 5
    f[0] = fmass
    f[4] = (etoto + po) * uo
    for c in range(3):
        if c == d:
            f[1 + c] = fmass * uo + po
        else:
            f[1 + c] = fmass * jnp.where(left, ql[1 + c], qr[1 + c])
    return tuple(f)


def _make_kernel(cfg: HydroStatic, dx: float, bx: int, by: int,
                 masked: bool, courant: bool, want_flux: bool = False):
    """Kernel body closure; refs: u_pad [5, bx+4, by+8, nz] window,
    (ok [bx+4, by+8, nz] window,) dt [1,1] SMEM → out [5, bx, by, nz]
    (+ per-block courant dt min [1, 1] SMEM when ``courant``)
    (+ phi [3, 2, bx, by, nz] per-cell (low, high) dt/dx-scaled face
    MASS fluxes when ``want_flux`` — the MC-tracer capture)."""
    st = cfg.slope_type
    theta = float(getattr(cfg, "slope_theta", 1.5))
    solver = _llf_flux if cfg.riemann == "llf" else _hllc_flux
    sx = slice(NG, NG + bx)
    sy = slice(NG, NG + by)

    def kernel(*refs):
        i = 1
        u_ref = refs[0]
        ok_ref = refs[i] if masked else None
        i += int(masked)
        dt_ref = refs[i]
        out_ref = refs[i + 1]
        i += 2
        crt_ref = refs[i] if courant else None
        i += int(courant)
        phi_ref = refs[i] if want_flux else None
        dt = dt_ref[0, 0]
        # ---- ctoprim (umuscl.f90:861-967) ----
        r = jnp.maximum(u_ref[0], cfg.smallr)
        ir = 1.0 / r
        v = [u_ref[1] * ir, u_ref[2] * ir, u_ref[3] * ir]
        ek = 0.5 * (v[0] * v[0] + v[1] * v[1] + v[2] * v[2])
        eint = jnp.maximum(u_ref[4] * ir - ek, cfg.smalle)
        p = (cfg.gamma - 1.0) * r * eint
        q = (r, v[0], v[1], v[2], p)
        # ---- uslope: dq[d][comp] ----
        dq = []
        for d in range(3):
            qm1 = tuple(_roll(c, 1, d) for c in q)
            qp1 = tuple(_roll(c, -1, d) for c in q)
            dq.append(tuple(_slopes(a, b, c, st, theta)
                            for a, b, c in zip(qm1, q, qp1)))
        # ---- trace3d source terms (umuscl.f90:176-714) ----
        divv = dq[0][1] + dq[1][2] + dq[2][3]
        adv = lambda comp: (v[0] * dq[0][comp] + v[1] * dq[1][comp]
                            + v[2] * dq[2][comp])
        sr0 = -adv(0) - divv * r
        sp0 = -adv(4) - divv * cfg.gamma * p
        sv0 = [-adv(1 + j) - dq[j][4] * ir for j in range(3)]
        dtdx2 = 0.5 * dt / dx

        if masked:
            # 0/1 mask already in the state dtype (see pad_xy): Mosaic
            # supports neither i1 vector rolls nor u8->f32 casts here
            okf = ok_ref[:]

        # ---- per-direction face flux + conservative update ----
        du = [None] * 5
        for d in range(3):
            def face_state(sgn):
                rho = r + sgn * 0.5 * dq[d][0] + sr0 * dtdx2
                rho = jnp.where(rho < cfg.smallr, r, rho)
                vs = [v[j] + sgn * 0.5 * dq[d][1 + j] + sv0[j] * dtdx2
                      for j in range(3)]
                pp = p + sgn * 0.5 * dq[d][4] + sp0 * dtdx2
                return (rho, vs[0], vs[1], vs[2], pp)
            qm = face_state(+1.0)     # high-side face state
            qp = face_state(-1.0)     # low-side face state
            # face i between cells i-1, i: left = qm(i-1), right = qp(i)
            ql5 = tuple(_roll(c, 1, d) for c in qm)
            qr5 = qp
            # floors (riemann.py _prims)
            ql5 = (jnp.maximum(ql5[0], cfg.smallr), ql5[1], ql5[2], ql5[3],
                   jnp.maximum(ql5[4], ql5[0] * cfg.smallp))
            qr5 = (jnp.maximum(qr5[0], cfg.smallr), qr5[1], qr5[2], qr5[3],
                   jnp.maximum(qr5[4], qr5[0] * cfg.smallp))
            flux = solver(ql5, qr5, d, cfg)
            if masked:
                # face kept iff neither adjacent cell is refined:
                # (1-ok_i)(1-ok_{i-1}) — pure arithmetic, no i1 vectors
                keepf = (1.0 - okf) * (1.0 - _roll(okf, 1, d))
                flux = tuple(f * keepf for f in flux)
            scale = dt / dx
            if want_flux:
                phi_ref[d, 0] = (flux[0] * scale)[sx, sy, :]
                phi_ref[d, 1] = (_roll(flux[0], -1, d) * scale)[sx, sy, :]
            for c in range(5):
                contrib = (flux[c] - _roll(flux[c], -1, d)) * scale
                du[c] = contrib if du[c] is None else du[c] + contrib
        # write updated interior (x/y halo dropped; z has no halo)
        un = [(u_ref[c] + du[c])[sx, sy, :] for c in range(5)]
        for c in range(5):
            out_ref[c] = un[c]
        if courant:
            # per-block Courant min of the UPDATED state (``cmpdt``,
            # godunov_utils.f90:5-125 with gravity off) — the next step's
            # dt comes out of the same kernel launch for free.
            r2 = jnp.maximum(un[0], cfg.smallr)
            ir2 = 1.0 / r2
            v2 = [un[1] * ir2, un[2] * ir2, un[3] * ir2]
            ek2 = 0.5 * r2 * (v2[0] * v2[0] + v2[1] * v2[1]
                              + v2[2] * v2[2])
            p2 = jnp.maximum((cfg.gamma - 1.0) * (un[4] - ek2),
                             r2 * cfg.smallp)
            c2 = jnp.sqrt(cfg.gamma * p2 * ir2)
            ws = 3.0 * c2 + jnp.abs(v2[0]) + jnp.abs(v2[1]) + jnp.abs(v2[2])
            ratio = 1e-4                      # gravity-off strength ratio
            cf = cfg.courant_factor
            fac = (jnp.sqrt(1.0 + 2.0 * cf * ratio) - 1.0) / ratio
            local = jnp.min(dx / ws) * fac
            # TPU grid steps run sequentially on the core: accumulate the
            # global min into the single shared (1,1) SMEM output.
            first = jnp.logical_and(pl.program_id(0) == 0,
                                    pl.program_id(1) == 0)

            @pl.when(first)
            def _():
                crt_ref[0, 0] = local

            @pl.when(jnp.logical_not(first))
            def _():
                crt_ref[0, 0] = jnp.minimum(crt_ref[0, 0], local)

    return kernel


# device op names of the two callers' kernels: a trace tells the
# per-shard call of the slab path from the whole-level one by them
SHARD_KERNEL_NAME = "fused_step_shard"


@partial(jax.jit,
         static_argnames=("cfg", "dx", "shape", "courant", "interpret",
                          "want_flux", "name"))
def fused_step_padded(u_pad, dt, cfg: HydroStatic, dx: float,
                      shape: Tuple[int, int, int],
                      ok_pad: Optional[jnp.ndarray] = None,
                      courant: bool = False, interpret: bool = False,
                      want_flux: bool = False,
                      name: Optional[str] = None):
    """Run the fused kernel on an x/y-ghost-padded state.

    u_pad: [5, nx+4, ny+8, nz] from :func:`pad_xy` (x: 2-cell ghosts
    both sides; y: 2-cell ghosts + 4 junk rows at the high end so the
    last ``by+8``-row window stays in bounds, whatever ``by`` the block
    rule picks for ``(shape, masked)``); ok_pad: optional refined-cell
    mask, same spatial shape — faces touching a refined cell get zero
    flux (``godunov_fine.f90:718``).  Returns the UPDATED active grid
    [5, nx, ny, nz].
    """
    nx, ny, nz = shape
    masked = ok_pad is not None
    bx, by = _pick_block(shape, masked)
    wy = _wy(by)
    _BLOCKS[(shape, masked)] = _block_record(shape, masked)
    dt2 = jnp.asarray(dt, u_pad.dtype).reshape(1, 1)
    kern = _make_kernel(cfg, dx, bx, by, masked, courant, want_flux)
    in_specs = [
        pl.BlockSpec(
            (pl.Element(5), pl.Element(bx + 2 * NG), pl.Element(wy), pl.Element(nz)),
            lambda i, j: (0, i * bx, j * by, 0),
            memory_space=pltpu.VMEM),
    ]
    args = [u_pad]
    if ok_pad is not None:
        in_specs.append(pl.BlockSpec(
            (pl.Element(bx + 2 * NG), pl.Element(wy), pl.Element(nz)),
            lambda i, j: (i * bx, j * by, 0),
            memory_space=pltpu.VMEM))
        args.append(ok_pad)
    in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                                 memory_space=pltpu.SMEM))
    args.append(dt2)
    out_specs = [pl.BlockSpec((5, bx, by, nz), lambda i, j: (0, i, j, 0),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((5, nx, ny, nz), u_pad.dtype)]
    if courant:
        out_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                                      memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((1, 1), u_pad.dtype))
    if want_flux:
        out_specs.append(pl.BlockSpec(
            (3, 2, bx, by, nz), lambda i, j: (0, 0, i, j, 0),
            memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((3, 2, nx, ny, nz), u_pad.dtype))
    if len(out_specs) == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]
    else:
        out_specs, out_shape = tuple(out_specs), tuple(out_shape)
    return pl.pallas_call(
        kern,
        grid=(nx // bx, ny // by),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,           # CPU parity tests
        name=name,                     # None: the kernel body's own
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(*args)


def shard_axes(cfg: HydroStatic, loc, cut, dtype):
    """Axis relabel for a PER-SHARD fused-kernel call, or None.

    The kernel wants its lane ("z") axis whole, periodic, %128 and
    uncut by the slab decomposition (the in-kernel roll would otherwise
    wrap inside one shard).  A slab cut always takes z first
    (amr/bitperm.py), so the per-shard call picks any UNCUT axis whose
    local extent fits the lane rules and relabels it to the kernel's z,
    permuting the momentum components to match.  Returns ``(a0, a1,
    az)``: the original axes taking the kernel's (x, y, z) roles.
    Unlike :func:`kernel_available` this gate has no single-device
    requirement — inside ``shard_map`` the kernel runs on the local
    block, so no GSPMD partitioning rule is needed.
    """
    if DISABLED:
        return None
    if jax.default_backend() != "tpu":
        return None
    periodic = ((0, 0),) * 3        # the slab path brings its own halos
    for az in (2, 1, 0):
        if cut[az]:
            continue
        a0, a1 = (d for d in range(3) if d != az)
        if supports(cfg, (loc[a0], loc[a1], loc[az]), periodic, dtype):
            return (a0, a1, az)
    return None


def fused_step_shard(up, okp, dt, cfg: HydroStatic, dx: float,
                     loc: Tuple[int, int, int], axes: Tuple[int, int, int],
                     want_flux: bool = False, interpret: bool = False):
    """Per-shard fused kernel on a halo-extended local box.

    ``up``: [5, *ext] in ORIGINAL axis order with NG ghost slabs on
    ``axes[0]``/``axes[1]`` and the bare local extent on the lane axis
    ``axes[2]`` (handled by the in-kernel periodic roll — valid because
    the slab gate guarantees that axis is uncut).  ``okp``: optional
    refined mask in the state dtype over the same extended box.
    Returns ``du [5, *loc]`` (+ ``phi [*loc, 3, 2]`` when
    ``want_flux``), both in original axis/component order — the same
    contract as :func:`ramses_tpu.amr.kernels.dense_interior_update`.

    NOTE: the relabeled kernel applies the directional sweeps in
    relabeled order, so it is NOT bitwise against the unrelabeled
    global kernel (float accumulation order differs); shard-invariance
    bitwise pins hold on the XLA path (CPU tests), the pallas shard
    path is tolerance-pinned.
    """
    a0, a1, az = axes
    vp = (0, 1 + a0, 1 + a1, 1 + az, 4)
    ivp = (0, 1 + axes.index(0), 1 + axes.index(1), 1 + axes.index(2), 4)
    sp = (0, 1 + a0, 1 + a1, 1 + az)               # relabel transpose
    isp = (0, 1 + axes.index(0), 1 + axes.index(1), 1 + axes.index(2))
    ur = jnp.transpose(up, sp)[jnp.asarray(vp)]
    # y window slack: junk rows at the high end (values never used)
    ur = jnp.pad(ur, ((0, 0), (0, 0), (0, Y_SLACK), (0, 0)), mode="edge")
    okr = None
    if okp is not None:
        okr = jnp.transpose(okp, (a0, a1, az))
        okr = jnp.pad(okr, ((0, 0), (0, Y_SLACK), (0, 0)), mode="edge")
    shape_rel = (loc[a0], loc[a1], loc[az])
    out = fused_step_padded(ur, dt, cfg, dx, shape_rel, ok_pad=okr,
                            want_flux=want_flux, interpret=interpret,
                            name=SHARD_KERNEL_NAME)
    un = out[0] if want_flux else out
    du = un - ur[:, NG:-NG, NG:NG + shape_rel[1], :]
    du = jnp.transpose(du[jnp.asarray(ivp)], isp)
    if not want_flux:
        return du
    phis = []
    for d in range(3):
        f = out[1][axes.index(d)]                  # [2, *rel spatial]
        f = jnp.transpose(f, (0,) + tuple(1 + axes.index(dd)
                                          for dd in range(3)))
        phis.append(jnp.moveaxis(f, 0, -1))        # [*loc, 2]
    return du, jnp.stack(phis, axis=-2)            # [*loc, 3, 2]


def pad_xy(u, bc, cfg: HydroStatic, ok=None):
    """Ghost-pad x (2/2) and y (2 low / 6 high: 2 ghosts + ``Y_SLACK``
    junk rows, the same for every y tile) only; z periodic is handled
    in-kernel."""
    up = _pad_leading2(u, bc, cfg)
    if ok is None:
        return up, None
    # ship the mask in the STATE dtype: Mosaic supports neither i1
    # vector rolls nor u8->f32 casts inside the kernel
    okp = _pad_leading2(ok[None].astype(u.dtype), bc, cfg)[0]
    return up, okp


def _pad_leading2(u, bc, cfg: HydroStatic):
    """Pad spatial axes 1,2 of [C, nx, ny, nz] per the x/y BCs."""
    for d in range(2):
        ax = 1 + d
        lo_bc, hi_bc = bc.faces[d]
        n = u.shape[ax]

        def take(a, b, step=1):
            idx = [slice(None)] * u.ndim
            idx[ax] = slice(a, b, step)
            return u[tuple(idx)]

        def ghost(fbc, side, ng):
            if fbc.kind == 0:                          # periodic
                if side == 0:
                    return take(n - ng, n)
                g = take(0, NG)
                if ng == NG:
                    return g
                # junk rows beyond the true ghosts: repeat (finite values)
                reps = [1] * u.ndim
                reps[ax] = (ng + NG - 1) // NG
                return jnp.tile(g, reps)[tuple(
                    slice(0, ng) if a == ax else slice(None)
                    for a in range(u.ndim))]
            if fbc.kind == 1:                          # reflecting
                g = take(0, ng) if side == 0 else take(n - ng, n)
                g = jnp.flip(g, axis=ax)
                if u.shape[0] == cfg.nvar:             # state: flip mom_d
                    sgn = jnp.ones((u.shape[0],), u.dtype).at[1 + d].set(-1)
                    g = g * sgn.reshape(-1, 1, 1, 1)
                return g
            # outflow / inflow approximated by edge copy for the kernel
            edge = take(0, 1) if side == 0 else take(n - 1, n)
            reps = [1] * u.ndim
            reps[ax] = ng
            return jnp.tile(edge, reps)

        hi_ng = NG if d == 0 else NG + Y_SLACK         # y: +4 junk rows
        u = jnp.concatenate([ghost(lo_bc, 0, NG), u, ghost(hi_bc, 1, hi_ng)],
                            axis=ax)
    return u
