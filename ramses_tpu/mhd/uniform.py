"""Uniform-grid constrained-transport MHD stepper.

The ``mag_unsplit`` pipeline (``mhd/umuscl.f90``, 2,844 LoC of
nvector-batched stencils) re-designed as whole-grid fused XLA ops:

  ctoprim → TVD slopes → conservative Hancock half-step predictor →
  per-direction HLLD/HLL/LLF face fluxes → Gardiner-Stone arithmetic
  edge-EMF averaging → induction update of the staggered field
  (``mhd/godunov_fine.f90:960-973``'s B += curl(EMF)) → conservative update.

div(B) is zero to machine precision by construction (staggered curl), the
property the reference maintains with face-B pairs + EMF arrays
(``mhd/godunov_fine.f90:565``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace as dreplace
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ramses_tpu.grid import boundary as bmod
from ramses_tpu.hydro import muscl as hmuscl
from ramses_tpu.mhd import core, riemann as rsolve
from ramses_tpu.mhd.core import IBX, IP, MhdStatic, NCOMP

NGHOST = 2


@dataclass(frozen=True)
class MhdGrid:
    cfg: MhdStatic
    shape: Tuple[int, ...]
    dx: float
    bc_kinds: Tuple[Tuple[int, int], ...]   # per-dim (low, high) kinds

    @property
    def ncell(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _axis(ndim: int, d: int, a) -> int:
    return a.ndim - ndim + d


def _pad(a, ndim: int, bc_kinds, ng: int = NGHOST, flip_comp: int = -1):
    """Ghost-pad the trailing ndim axes.  Periodic wrap or outflow edge
    replication (the two kinds the MHD path supports; reflecting walls
    need face-field mirroring — not yet wired)."""
    for d in range(ndim):
        ax = a.ndim - ndim + d
        lo, hi = bc_kinds[d]
        n = a.shape[ax]

        def take(s0, s1):
            idx = [slice(None)] * a.ndim
            idx[ax] = slice(s0, s1)
            return a[tuple(idx)]

        def ghost(kind, side):
            if kind == bmod.PERIODIC:
                return take(n - ng, n) if side == 0 else take(0, ng)
            # outflow: replicate edge
            edge = take(0, 1) if side == 0 else take(n - 1, n)
            reps = [1] * a.ndim
            reps[ax] = ng
            return jnp.tile(edge, reps)

        a = jnp.concatenate([ghost(lo, 0), a, ghost(hi, 1)], axis=ax)
    return a


def _unpad(a, ndim: int, ng: int = NGHOST):
    idx = [slice(None)] * a.ndim
    for d in range(ndim):
        ax = a.ndim - ndim + d
        idx[ax] = slice(ng, a.shape[ax] - ng)
    return a[tuple(idx)]


def _slopes(q, cfg: MhdStatic):
    """The hydro TVD limiter bank applied to the MHD primitive stack —
    ``uslope`` only reads ndim/slope_type/slope_theta, which MhdStatic
    provides with identical semantics."""
    return list(hmuscl.uslope(q, cfg))


def _rot_perm(cfg: MhdStatic, d: int):
    t1, t2 = (d + 1) % 3, (d + 2) % 3
    perm = [0, 1 + d, 1 + t1, 1 + t2, IP, IBX + d, IBX + t1, IBX + t2]
    perm += list(range(8, cfg.nvar))
    return perm


def ct_core(up, bfp, dt, dx: Sequence[float], cfg: MhdStatic,
            bax: int = 0, bn_faces=None, flux_mask=None,
            emf_override=None):
    """The CT MUSCL-Hancock pipeline on already-assembled arrays.

    ``up`` [nvar, *sp(, batch…)] cell conservative with B slots ALREADY
    cell-centered; ``bfp`` list of NCOMP low-face arrays (same spatial
    shape).  ``bax`` = number of trailing batch axes (0 for the uniform
    grid, 1 for the AMR per-oct stencil batch).  ``bn_faces``: optional
    override of the low-face normal fields fed to the Riemann solver
    (the AMR path prefers stored fine values on shared coarse-fine
    faces).  ``flux_mask``: optional per-dim keep factors (0 at refined
    faces, ``godunov_fine.f90:718`` semantics) applied to the CELL
    update and the returned fluxes but NOT to the EMF corner average —
    the fine region's state is restriction-overwritten while its edge
    EMFs stay whole-level consistent.  Spatial shifts are ``jnp.roll``
    — callers guarantee enough ghost/stencil margin that
    wrap-contaminated entries are never read from the region they keep.

    Returns (un, bfn_list, fluxes, e_edges) where ``e_edges[(d1,d2)]``
    is the final corner EMF field of that staggered pair (the quantity
    the AMR coarse-fine matching averages, ``mhd/godunov_fine.f90:826``).
    """
    nd = cfg.ndim

    def ax_(d, a):
        return a.ndim - nd - bax + d

    q = core.ctoprim(up, cfg)
    # the slope bank infers spatial axes from cfg: flag the batch axis
    scfg = dreplace(cfg, trailing_batch=True) if bax else cfg
    dq = _slopes(q, scfg)

    # conservative Hancock half-step: the cell's own reconstructed faces
    du_half = jnp.zeros_like(up)
    face_q = []
    for d in range(nd):
        q_hi = q + 0.5 * dq[d]
        q_lo = q - 0.5 * dq[d]
        f_hi = core.flux_along(q_hi, d, cfg)
        f_lo = core.flux_along(q_lo, d, cfg)
        du_half = du_half - (0.5 * dt / dx[d]) * (f_hi - f_lo)
        face_q.append((q_lo, q_hi))

    # half-dt prediction of the staggered field (edge-averaged cell EMFs),
    # so the Riemann normal field is time-centred like its other inputs —
    # the role of the reference's induction terms in trace3d
    # (``mhd/umuscl.f90`` magnetic predictor)
    base_faces = bn_faces if bn_faces is not None else bfp
    bf_half = [base_faces[c] for c in range(NCOMP)]
    for d1 in range(nd):
        for d2 in range(d1 + 1, nd):
            ax1 = ax_(d1, bfp[d1])
            ax2 = ax_(d2, bfp[d1])
            sig = 1.0 if (d1, d2) in ((0, 1), (1, 2), (2, 0)) else -1.0
            v1, v2 = q[1 + d1], q[1 + d2]
            b1, b2 = q[IBX + d1], q[IBX + d2]
            e_c0 = sig * (v2 * b1 - v1 * b2)
            e_edge0 = 0.25 * (e_c0 + jnp.roll(e_c0, 1, axis=ax1)
                              + jnp.roll(e_c0, 1, axis=ax2)
                              + jnp.roll(jnp.roll(e_c0, 1, axis=ax1),
                                         1, axis=ax2))
            bf_half[d1] = bf_half[d1] - sig * (0.5 * dt / dx[d2]) * (
                jnp.roll(e_edge0, -1, axis=ax2) - e_edge0)
            bf_half[d2] = bf_half[d2] + sig * (0.5 * dt / dx[d1]) * (
                jnp.roll(e_edge0, -1, axis=ax1) - e_edge0)

    fluxes = []
    for d in range(nd):
        ax = ax_(d, q)
        q_lo, q_hi = face_q[d]
        ul_c = core.prim_to_cons(q_hi, cfg) + du_half    # this cell's hi face
        ur_c = core.prim_to_cons(q_lo, cfg) + du_half    # this cell's lo face
        ql = core.ctoprim(jnp.roll(ul_c, 1, axis=ax), cfg)
        qr = core.ctoprim(ur_c, cfg)
        # static per-row stack, not a gather with an index array: the
        # Pallas CT kernel traces this body and may not close over
        # constants, and XLA folds the stack to the same copies anyway
        perm = _rot_perm(cfg, d)
        ql_r = jnp.stack([ql[i] for i in perm])
        qr_r = jnp.stack([qr[i] for i in perm])
        bn = bf_half[d]                # staggered, half-dt predicted
        fg = rsolve.solve(ql_r, qr_r, bn, cfg)
        # scatter to state layout
        out = [None] * cfg.nvar
        t1, t2 = (d + 1) % 3, (d + 2) % 3
        out[0] = fg[0]
        out[1 + d], out[1 + t1], out[1 + t2] = fg[1], fg[2], fg[3]
        out[IP] = fg[4]
        out[IBX + d], out[IBX + t1], out[IBX + t2] = fg[5], fg[6], fg[7]
        for s in range(cfg.npassive):
            out[8 + s] = fg[8 + s]
        fluxes.append(jnp.stack(out))

    # conservative update of cell state (staggered B rows excluded)
    if flux_mask is not None:
        fl_cell = [fluxes[d] * flux_mask[d][None] for d in range(nd)]
    else:
        fl_cell = fluxes
    un = up
    for d in range(nd):
        ax = ax_(d, up)
        un = un + (dt / dx[d]) * (fl_cell[d]
                                  - jnp.roll(fl_cell[d], -1, axis=ax))
    # half-step primitives for the cell-centered EMF reference
    q_half = core.ctoprim(up + du_half, cfg)

    # CT induction on staggered components.  The base is the SAME
    # face-value selection the Riemann solver saw (bn_faces): on the AMR
    # stencil path this keeps every cell's own (lo, hi) pair evolving
    # from its own stored values, so per-cell divB is preserved exactly
    # even where duplicated faces disagree across a coarse-fine seam.
    bfn = [base_faces[c] for c in range(NCOMP)]
    e_edges = {}
    use2d = cfg.riemann2d != "average" and nd >= 2
    for d1 in range(nd):
        for d2 in range(d1 + 1, nd):
            # axes on the scalar (no component dim) EMF arrays
            ax1 = ax_(d1, bfp[d1])
            ax2 = ax_(d2, bfp[d1])
            # face EMFs: E_e on d1-faces and d2-faces
            sig = 1.0 if (d1, d2) in ((0, 1), (1, 2), (2, 0)) else -1.0
            if use2d:
                # 2D corner Riemann upwinding (cmp_mag_flx,
                # mhd/umuscl.f90:1453): half-dt-evolved corner states
                # of the four cells around each edge.  Reconstruction
                # happens in PRIMITIVE space around the half-evolved
                # cell state (the reference's trace does the same) — a
                # conservative round-trip would divide momentum by the
                # floored density when the diagonal slope sum overshoots
                # at a strong contact, exploding the corner velocities.
                from ramses_tpu.mhd import riemann2d as r2d
                pfloor = cfg.smallr * cfg.smallc ** 2
                qcorner = {}
                for s1 in (-1.0, 1.0):
                    for s2 in (-1.0, 1.0):
                        qc = q_half + 0.5 * (s1 * dq[d1] + s2 * dq[d2])
                        qc = qc.at[0].set(jnp.maximum(qc[0], cfg.smallr))
                        qc = qc.at[IP].set(jnp.maximum(qc[IP], pfloor))
                        qcorner[(s1, s2)] = qc
                dorth = 3 - d1 - d2

                def comp(qc, *rolls):
                    for ax in rolls:
                        qc = jnp.roll(qc, 1, axis=ax)
                    return (qc[0], qc[IP], qc[1 + d1], qc[1 + d2],
                            qc[1 + dorth], qc[IBX + dorth])

                qax1, qax2 = ax_(d1, q), ax_(d2, q)
                states = {
                    ("R", "T"): comp(qcorner[(-1.0, -1.0)]),
                    ("L", "T"): comp(qcorner[(1.0, -1.0)], qax1),
                    ("R", "B"): comp(qcorner[(-1.0, 1.0)], qax2),
                    ("L", "B"): comp(qcorner[(1.0, 1.0)], qax1, qax2),
                }
                A_T = bf_half[d1]
                A_B = jnp.roll(bf_half[d1], 1, axis=ax2)
                B_R = bf_half[d2]
                B_L = jnp.roll(bf_half[d2], 1, axis=ax1)
                eps = r2d.corner_emf(states, A_T, A_B, B_R, B_L, cfg)
                e_edge = -sig * eps
            else:
                # F_d1(B_d2) = -sig*E_e ; F_d2(B_d1) = +sig*E_e
                e_f1 = -sig * fluxes[d1][IBX + d2]       # (lo d1, ctr d2)
                e_f2 = sig * fluxes[d2][IBX + d1]        # (ctr d1, lo d2)
                # cell-centered reference EMF from half-step state
                v1, v2 = q_half[1 + d1], q_half[1 + d2]
                b1, b2 = q_half[IBX + d1], q_half[IBX + d2]
                e_c = sig * (v2 * b1 - v1 * b2)          # E_e = -(v×B)_e
                # Gardiner & Stone (2005) arithmetic corner average
                e_edge = (0.5 * (e_f1 + jnp.roll(e_f1, 1, axis=ax2)
                                 + e_f2 + jnp.roll(e_f2, 1, axis=ax1))
                          - 0.25 * (e_c + jnp.roll(e_c, 1, axis=ax1)
                                    + jnp.roll(e_c, 1, axis=ax2)
                                    + jnp.roll(jnp.roll(e_c, 1,
                                                        axis=ax1),
                                               1, axis=ax2)))
            if emf_override is not None and (d1, d2) in emf_override:
                # coarse-fine EMF matching (godunov_fine.f90:826-973):
                # edges covered by a refined oct take the time-averaged
                # fine EMF, so the coarse induction lands EXACTLY on the
                # restriction of the fine faces
                msk, vals = emf_override[(d1, d2)]
                e_edge = jnp.where(msk, vals.astype(e_edge.dtype), e_edge)
            e_edges[(d1, d2)] = e_edge
            # dB_d1/dt = -sig * dE_e/d_d2 ; dB_d2/dt = +sig * dE_e/d_d1
            bfn[d1] = bfn[d1] - sig * (dt / dx[d2]) * (
                jnp.roll(e_edge, -1, axis=ax2) - e_edge)
            bfn[d2] = bfn[d2] + sig * (dt / dx[d1]) * (
                jnp.roll(e_edge, -1, axis=ax1) - e_edge)

    # degenerate (cell-centered) components advance with the conservative
    # flux update; without this they would be frozen at their ICs
    for c in range(nd, NCOMP):
        bfn[c] = un[IBX + c]
    # refresh cell-centered staggered B components from the new faces
    bc_new = []
    for c in range(min(nd, NCOMP)):
        b = bfn[c]
        bc_new.append(0.5 * (b + jnp.roll(b, -1, axis=ax_(c, b))))
    for c in range(min(nd, NCOMP)):
        un = un.at[IBX + c].set(bc_new[c])
    return un, bfn, fl_cell, e_edges


def step_padded(cfg: MhdStatic, dx: Sequence[float], up, bfp_ext, dt,
                okp=None, ovr=None):
    """The CT step on ALREADY ghost-assembled arrays — the single
    pipeline behind :func:`step` (global pad), the slab-sharded advance
    (:func:`ramses_tpu.parallel.dense_slab.mhd_ct_slab`, halo-exchanged
    ghosts) and the single-block Pallas kernel
    (:mod:`ramses_tpu.mhd.pallas_ct`).

    ``up`` [nvar, \\*sp+2·ng] padded cell conservative with the RAW
    (uncentered) B slots — the face-average centering happens here;
    ``bfp_ext`` [NCOMP, \\*sp+2·(ng+1)] low faces padded one layer
    deeper (the centred average must be valid in every padded cell);
    ``okp`` optional padded bool refined mask [\\*sp+2·ng]; ``ovr``
    optional dict (d1,d2) → (padded mask, padded values) on the padded
    corner lattice.  Returns the PADDED (un, bfn_list) — callers
    unpad."""
    nd = cfg.ndim
    trim = tuple([slice(None)] + [slice(1, -1)] * nd)
    bfp = bfp_ext[trim]
    bc = []
    for c in range(NCOMP):
        b = bfp_ext[c]
        lo = b[tuple(slice(1, -1) for _ in range(nd))]
        if c < nd:
            hi_idx = [slice(1, -1)] * nd
            hi_idx[c] = slice(2, None)      # neighbour's low face = high face
            bc.append(0.5 * (lo + b[tuple(hi_idx)]))
        else:
            bc.append(lo)
    up = up.at[IBX:IBX + NCOMP].set(jnp.stack(bc))

    flux_mask = None
    if okp is not None:
        flux_mask = []
        for d in range(nd):
            ax = okp.ndim - nd + d
            keep = ~(okp | jnp.roll(okp, 1, axis=ax))
            flux_mask.append(keep.astype(up.dtype))
    un, bfn, _fluxes, _e = ct_core(up, [bfp[c] for c in range(NCOMP)],
                                   dt, dx, cfg, flux_mask=flux_mask,
                                   emf_override=ovr)
    return un, bfn


def step(grid: MhdGrid, u, bf, dt, ok=None, emf_override=None):
    """One CT MUSCL-Hancock step.  ``u`` [nvar, *sp] cell conservative
    (B slots cell-centered, derived), ``bf`` [3, *sp] staggered low-face
    field.  ``ok``: optional refined-cell mask — faces touching a
    refined cell get zero cell-state flux (AMR complete-level path).
    ``emf_override``: dict (d1,d2) → (mask, values) on the ACTIVE grid's
    cell-corner lattice — coarse-fine EMF matching.
    Returns (u', bf')."""
    cfg = grid.cfg
    nd = cfg.ndim
    dx = (grid.dx,) * nd
    ng = NGHOST
    # the time axis may run in f64 while the state is f32: keep the
    # sweep in the state dtype (as grid/uniform.step)
    dt = jnp.asarray(dt, u.dtype)

    up = _pad(u, nd, grid.bc_kinds)
    # faces get one extra ghost layer so the cell-centred average is valid
    # in EVERY padded cell (a rolled average would wrap garbage into the
    # outermost ghosts and contaminate boundary-face slopes)
    bfp_ext = _pad(bf, nd, grid.bc_kinds, ng + 1)
    okp = None
    if ok is not None:
        okp = _pad(ok[None], nd, grid.bc_kinds)[0]
    ovr = None
    if emf_override is not None:
        ovr = {}
        for pair, (msk, vals) in emf_override.items():
            ovr[pair] = (_pad(msk[None], nd, grid.bc_kinds)[0],
                         _pad(vals[None], nd, grid.bc_kinds)[0])
    un, bfn = step_padded(cfg, dx, up, bfp_ext, dt, okp=okp, ovr=ovr)
    u_out = _unpad(un, nd)
    bf_out = jnp.stack([_unpad(b, nd) for b in bfn])
    return u_out, bf_out


@partial(jax.jit, static_argnames=("grid",))
def cfl_dt(grid: MhdGrid, u, bf):
    cfg = grid.cfg
    nd = cfg.ndim
    bc = core.cell_center_b([bf[c] for c in range(NCOMP)], nd)
    uu = u.at[IBX:IBX + NCOMP].set(jnp.stack(bc))
    q = core.ctoprim(uu, cfg)
    rate = 0.0
    for d in range(nd):
        cf = core.fast_speed(q, d, cfg)
        rate = rate + (jnp.abs(q[1 + d]) + cf) / grid.dx
    return cfg.courant_factor / jnp.max(rate)


_jit_step = jax.jit(step, static_argnames=("grid",))


def kernel_ok(grid: MhdGrid, dtype) -> bool:
    """True when the tiled CT kernel (:mod:`ramses_tpu.mhd.pallas_ct`)
    covers this grid on this backend: derived from platform, shape,
    dtype and boundaries — there is no option."""
    from ramses_tpu.mhd import pallas_ct
    return pallas_ct.kernel_available(grid.cfg, grid.shape, grid.bc_kinds,
                                      dtype)


@partial(jax.jit, static_argnames=("grid", "nsteps", "dt_scale"))
def run_steps(grid: MhdGrid, u, bf, t, tend, nsteps: int,
              dt_scale: float = 1.0):
    """Advance up to nsteps entirely on device (cf. hydro run_steps).
    ``dt_scale < 1``: redo-step retry at reduced Courant dt.

    Where :func:`kernel_ok` admits the grid the steps run on the tiled
    CT kernel (:func:`_run_steps_kernel`: the same results, a step run
    only while one is owed); the masked XLA scan below is the off-chip
    path."""
    if kernel_ok(grid, u.dtype):
        return _run_steps_kernel(grid, u, bf, t, tend, nsteps,
                                 dt_scale=dt_scale)

    def body(carry, _):
        u, bf, t, ndone = carry
        dt = cfl_dt(grid, u, bf) * dt_scale
        dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
        active = t < tend
        un, bfn = step(grid, u, bf, jnp.where(active, dt, 0.0))
        u = jnp.where(active, un, u)
        bf = jnp.where(active, bfn, bf)
        t = jnp.where(active, t + dt, t)
        ndone = ndone + jnp.where(active, 1, 0)
        return (u, bf, t, ndone), None

    (u, bf, t, ndone), _ = jax.lax.scan(
        body, (u, bf, t, jnp.array(0)), None, length=nsteps)
    return u, bf, t, ndone


@partial(jax.jit, static_argnames=("grid", "nsteps", "dt_scale"))
def _run_steps_kernel(grid: MhdGrid, u, bf, t, tend, nsteps: int,
                      dt_scale: float = 1.0):
    """:func:`run_steps` on the tiled CT kernel: a ``lax.while_loop``
    that runs a step only while one is owed (``ndone < nsteps`` and
    ``t < tend``) — the loop body is the ghost pass (``pad_xy`` of the
    five hydro rows and of the three faces) and the kernel, nothing else
    of the state's size, and no select masks a step out.  The loop
    carries the hydro rows and the centred field as two arrays (the
    kernel writes them so), joined into ``u`` once at the end.  The next
    step's dt comes from the kernel's Courant output; the first from
    :func:`cfl_dt`.  Same results as the scan form."""
    from ramses_tpu.mhd import pallas_ct as pk

    cfg = grid.cfg
    dt0 = cfl_dt(grid, u, bf) * dt_scale

    def owed(carry):
        t, ndone = carry[3:5]
        return (ndone < nsteps) & (t < tend)

    def body(carry):
        uh, _, bf, t, ndone, dtc = carry
        dt = jnp.minimum(dtc, jnp.maximum(tend - t, 0.0))
        uh, bc, bf, rate = pk.ct_step_tiled(
            pk.pad_xy(uh), pk.pad_xy(bf), dt, cfg, grid.dx, grid.shape)
        dtn = cfg.courant_factor / rate[0, 0] * dt_scale
        return uh, bc, bf, t + dt, ndone + 1, dtn

    uh, bc, bf, t, ndone, _ = jax.lax.while_loop(
        owed, body, (u[:pk.NHYDRO], u[pk.NHYDRO:], bf, t, jnp.array(0),
                     dt0))
    return jnp.concatenate([uh, bc]), bf, t, ndone


@partial(jax.jit,
         static_argnames=("grid", "nsteps", "dt_scale", "summarize"))
def run_steps_batch(grid: MhdGrid, u, bf, t, tend, nsteps: int,
                    dt_scale: float = 1.0, summarize: bool = False):
    """:func:`run_steps` vmapped over a leading ensemble axis
    (``u[B, nvar, *sp]``, ``bf[B, 3, *sp]``, ``t/tend[B]``) — cf. the
    hydro ``grid/uniform.run_steps_batch``.  Per-member completion is
    the in-scan ``t < tend`` mask; returns per-member ``ndone``, plus
    the per-member guard summary ``[B, 3]`` when ``summarize``."""
    def solo(u_, bf_, t_, tend_):
        return run_steps(grid, u_, bf_, t_, tend_, nsteps,
                         dt_scale=dt_scale)
    u, bf, t, ndone = jax.vmap(solo)(u, bf, t, tend)
    if summarize:
        from ramses_tpu.grid.uniform import batch_summary
        return u, bf, t, ndone, batch_summary(
            u, grid.cfg.ndim, grid.dx, IP, bf=bf)
    return u, bf, t, ndone


def totals(u, cfg: MhdStatic, dx: float):
    vol = dx ** cfg.ndim
    return {"mass": jnp.sum(u[0]) * vol,
            "energy": jnp.sum(u[IP]) * vol,
            "momentum": [jnp.sum(u[1 + c]) * vol for c in range(NCOMP)]}
