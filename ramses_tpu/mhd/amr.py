"""MHD on the AMR hierarchy: constrained transport on per-level oct
batches with divergence-free (Balsara-style) prolongation/restriction.

Reference scope: ``mhd/godunov_fine.f90`` (per-level CT sweep + EMF
bookkeeping), ``mhd/interpol_hydro.f90`` (interpol_mag: div-free
interpolation of face fields).  TPU re-design decisions:

* **Face storage is duplicated per cell** — ``bf[l]`` holds
  ``[ncell_pad, 3, 2]`` = (low, high) face field per dim per cell,
  exactly the reference's cell variables 6:8 + nvar+1:nvar+3.  Both
  copies of a shared face are updated from the SAME edge EMFs (each
  oct's stencil sees identical neighbourhood values), so they stay
  bitwise equal and ``divB`` per cell is a machine-exact telescoping
  sum — no linked-list face identity needed.
* **Prolongation** (ghosts + regrid) is the linear-normal Balsara
  reconstruction: a child's outer face injects the coarse face, the
  mid-face takes the coarse (lo+hi)/2 mean — child divB equals father
  divB exactly (= 0), the invariant ``interpol_mag`` maintains.
* **Restriction** is the area mean of son faces onto the covered
  coarse cell's faces (``upload_fine`` for face variables).
* The level sweep batches every oct's 6^ndim stencil and runs the SAME
  ``ct_core`` pipeline as the uniform solver (``mhd/uniform.py``), with
  the batch as a trailing axis.  Interior (2:4) results are extracted;
  roll wrap-around only touches discarded stencil margins.

Coarse-fine EMF matching (``mhd/godunov_fine.f90:826-973``) replaces
coarse corner EMFs with time-averaged fine EMFs on DENSE parent
levels; a partial-level parent keeps its own EMFs there (first-order
coupling; each level's own divB stays machine-zero regardless, by the
duplicated-face construction above).  Self-gravity rides the hydro
hierarchy's per-level Poisson solve with MHD-layout kicks
(:func:`mhd_kick_flat`); particles ride the shared PM layer
(``pm/amr_pm.py`` deposits into the Poisson rhs, ``synchro_fine``/
``move_fine`` KDK via the base class's ``_grav_pm_pre``/``_pm_drift``).
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.amr import kernels as K
from ramses_tpu.amr.hierarchy import AmrSim, FusedSpec
from ramses_tpu.config import Params
from ramses_tpu.grid import boundary as bmod
from ramses_tpu.mhd import core, uniform as mu
from ramses_tpu.mhd.core import IBX, IP, MhdStatic, NCOMP


# ----------------------------------------------------------------------
# div-free face prolongation (interpol_mag, mhd/interpol_hydro.f90)
# ----------------------------------------------------------------------
def _balsara_system(nd: int):
    """Minimal-norm solve for the interior fine faces of a refined
    cell: children's divB=0 conditions are A·m = c where m are the
    mid-face corrections to the two-point means.  A is a fixed ±1
    pattern; its pseudoinverse is precomputed (the closed forms in
    Balsara 2001 are exactly this least-squares solution)."""
    children = np.indices((2,) * nd).reshape(nd, -1).T   # x slowest
    nsub = 2 ** (nd - 1)
    A = np.zeros((2 ** nd, nd * nsub))
    submap = np.zeros((2 ** nd, nd), dtype=np.int64)
    for ci, ch in enumerate(children):
        for d in range(nd):
            sub = 0
            for dd in range(nd):
                if dd != d:
                    sub = sub * 2 + ch[dd]
            submap[ci, d] = sub
            A[ci, d * nsub + sub] = 1.0 - 2.0 * ch[d]    # +1 low child
    return np.linalg.pinv(A), submap, children


_BALSARA = {nd: _balsara_system(nd) for nd in (1, 2, 3)}


@partial(jax.jit, static_argnames=("nd",))
def matched_child_faces(father_bf, outer, nd: int):
    """Child faces of newly-refined cells, matched to their fine
    neighbours' stored sub-faces.

    ``father_bf`` [n, NCOMP, 2] (degenerate components + fallback);
    ``outer`` [n, nd, 2, nsub]: the cell's outer fine sub-face values —
    a donor neighbour's stored face where one exists, the injected
    coarse face otherwise.  Interior faces solve the children's
    divB = 0 system (minimal-norm correction to the two-point means);
    with divergence-consistent outer faces (the EMF-matching
    invariant), every child is divergence-free to round-off.
    Returns [n * 2^nd, NCOMP, 2] rows in flat-cell order.
    """
    pinv, submap, children = _BALSARA[nd]
    nsub = 2 ** (nd - 1)
    n = father_bf.shape[0]
    D = outer[:, :, 1, :] - outer[:, :, 0, :]            # [n, nd, nsub]
    mean = 0.5 * (outer[:, :, 0, :] + outer[:, :, 1, :])
    # c_child = -(1/2) sum_d D[d, sub_d(child)]
    cs = []
    for ci in range(2 ** nd):
        acc = 0.0
        for d in range(nd):
            acc = acc + D[:, d, submap[ci, d]]
        cs.append(-0.5 * acc)
    c = jnp.stack(cs, axis=-1)                           # [n, 2^nd]
    m = c @ jnp.asarray(pinv.T, dtype=c.dtype)           # [n, nd*nsub]
    m = m.reshape(n, nd, nsub)
    mid = mean + m                                       # [n, nd, nsub]

    rows = []
    for ci, ch in enumerate(children):
        comps = []
        for comp in range(NCOMP):
            if comp < nd:
                sub = submap[ci, comp]
                lo_out = outer[:, comp, 0, sub]
                hi_out = outer[:, comp, 1, sub]
                mid_c = mid[:, comp, sub]
                if ch[comp] == 0:
                    lo, hi = lo_out, mid_c
                else:
                    lo, hi = mid_c, hi_out
            else:
                ctr = 0.5 * (father_bf[:, comp, 0] + father_bf[:, comp, 1])
                lo = hi = ctr
            comps.append(jnp.stack([lo, hi], axis=-1))
        rows.append(jnp.stack(comps, axis=1))            # [n, NCOMP, 2]
    out = jnp.stack(rows, axis=1)                        # [n, 2^nd, ...]
    return out.reshape(n * 2 ** nd, NCOMP, 2)


def balsara_child_faces(bff, sgn, nd: int):
    """Child (lo, hi) faces from the father's: outer face = injection,
    mid face = (lo+hi)/2.  ``bff`` [n, NCOMP, 2]; ``sgn`` [n, nd] ±1
    child offsets.  Child divB == father divB exactly."""
    out = []
    for c in range(NCOMP):
        lo, hi = bff[:, c, 0], bff[:, c, 1]
        if c < nd:
            mid = 0.5 * (lo + hi)
            low_child = sgn[:, c] < 0
            clo = jnp.where(low_child, lo, mid)
            chi = jnp.where(low_child, mid, hi)
        else:
            clo = chi = 0.5 * (lo + hi)
        out.append(jnp.stack([clo, chi], axis=-1))
    return jnp.stack(out, axis=1)                      # [n, NCOMP, 2]


def _gather_faces(bf_flat, interp_faces, stencil_src, nd: int):
    """[NCOMP, 2, 6…, noct] stencil face batch (cf. K._gather_uloc)."""
    trash = jnp.zeros((1, NCOMP, 2), bf_flat.dtype)
    src = jnp.concatenate([bf_flat, interp_faces, trash], axis=0)
    g = src[stencil_src]                               # [noct, 6^d, 3, 2]
    noct = g.shape[0]
    g = jnp.moveaxis(g, (2, 3), (0, 1))                # [3, 2, noct, 6^d]
    g = jnp.swapaxes(g, 2, 3)                          # [3, 2, 6^d, noct]
    return g.reshape((NCOMP, 2) + (6,) * nd + (noct,))


def _gather_ftile(bf_flat, interp_faces, tile_src, nd: int, td: int):
    """[NCOMP, 2, td…, ntile] blocked face batch (cf. K._gather_utile):
    each Morton tile's staggered faces once plus the 2-cell halo instead
    of the ~(3^d)x-duplicated per-oct stencil copy."""
    trash = jnp.zeros((1, NCOMP, 2), bf_flat.dtype)
    src = jnp.concatenate([bf_flat, interp_faces, trash], axis=0)
    g = src[tile_src]                                  # [ntile, td^d, 3, 2]
    ntile = g.shape[0]
    g = jnp.moveaxis(g, (2, 3), (0, 1))                # [3, 2, ntile, td^d]
    g = jnp.swapaxes(g, 2, 3)                          # [3, 2, td^d, ntile]
    return g.reshape((NCOMP, 2) + (td,) * nd + (ntile,))


# ----------------------------------------------------------------------
# per-level sweep on the oct-stencil batch
# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def mhd_level_sweep(u_flat, interp_u, bf_flat, interp_bf, stencil_src,
                    ok_ref, dt, dx: float, cfg: MhdStatic):
    """CT MUSCL-Hancock for one level's octs.

    Returns (du_flat [ncell, nvar], bf_new [ncell, NCOMP, 2],
    corr [noct, nd, 2, nvar], emf [noct, npairs, 2, 2] | None) over the
    interior (2:4) cells of every oct, in flat-cell order; ``corr`` is
    the hydro-style coarse flux-correction payload (already × dt/dx);
    ``emf`` holds the oct's father-cell edge EMFs (per staggered pair,
    corner-low/high × corner-low/high, averaged along the edge) — the
    payload of the coarse-fine EMF matching.
    """
    nd = cfg.ndim
    uloc = K._gather_uloc(u_flat, interp_u, stencil_src, None, cfg)
    floc = _gather_faces(bf_flat, interp_bf, stencil_src, nd)
    noct = uloc.shape[-1]
    # real-cell mask: rows below ncell_pad are this level's own cells
    real = (stencil_src < u_flat.shape[0])             # [noct, 6^d]
    real = real.T.reshape((6,) * nd + (noct,))
    okl = ok_ref.T.reshape((6,) * nd + (noct,))        # refined cells

    # cell-centred B from the duplicated faces (valid in EVERY stencil
    # cell — no roll needed, unlike the low-face-only dense layout)
    centers = 0.5 * (floc[:, 0] + floc[:, 1])          # [NCOMP, 6…, noct]
    uloc = uloc.at[IBX:IBX + NCOMP].set(centers)

    # Riemann normal faces: prefer stored values on faces adjacent to a
    # real cell (a ghost's injected coarse value must not override the
    # fine stored field on a shared coarse-fine face)
    bn_faces = []
    for c in range(NCOMP):
        lo_c = floc[c, 0]
        if c < nd:
            ax = c
            hi_m1 = jnp.roll(floc[c, 1], 1, axis=ax)
            real_m1 = jnp.roll(real, 1, axis=ax)
            bn_faces.append(jnp.where(real, lo_c,
                                      jnp.where(real_m1, hi_m1, lo_c)))
        else:
            bn_faces.append(lo_c)

    flux_mask = []
    for d in range(nd):
        keep = jnp.logical_not(jnp.logical_or(okl, jnp.roll(okl, 1,
                                                            axis=d)))
        flux_mask.append(keep.astype(uloc.dtype))
    un, bfn, fl_cell, e_edges = mu.ct_core(
        uloc, [floc[c, 0] for c in range(NCOMP)], dt, (dx,) * nd, cfg,
        bax=1, bn_faces=bn_faces, flux_mask=flux_mask)

    interior = tuple(slice(2, 4) for _ in range(nd))
    du = (un - uloc)[(slice(None),) + interior]        # [nvar, 2…, noct]
    du_flat = jnp.transpose(
        du, (nd + 1,) + tuple(range(1, nd + 1)) + (0,)
    ).reshape(noct * 2 ** nd, cfg.nvar)

    # coarse flux-correction payload (cf. K.level_sweep): summed
    # boundary fluxes of the oct, already scaled by dt/dx
    corr = []
    for d in range(nd):
        f = fl_cell[d] * (dt / dx)
        idx_lo = [slice(None)]
        idx_hi = [slice(None)]
        for d2 in range(nd):
            if d2 == d:
                idx_lo.append(2)
                idx_hi.append(4)
            else:
                idx_lo.append(slice(2, 4))
                idx_hi.append(slice(2, 4))
        red = tuple(range(1, 1 + nd - 1))
        lo = f[tuple(idx_lo)].sum(axis=red) if nd > 1 else f[tuple(idx_lo)]
        hi = f[tuple(idx_hi)].sum(axis=red) if nd > 1 else f[tuple(idx_hi)]
        corr.append(jnp.stack([lo, hi], axis=-1))      # [nvar, noct, 2]
    corr = jnp.stack(corr, axis=-2)                    # [nvar, noct, nd, 2]
    corr = jnp.moveaxis(corr, 0, -1)                   # [noct, nd, 2, nvar]

    # interior faces: child lo at its own position, hi one step up in d
    def _cells(a):
        """[2…, noct] → flat [noct*2^nd]."""
        return jnp.transpose(a, (nd,) + tuple(range(nd))).reshape(-1)

    comps = []
    for c in range(NCOMP):
        if c < nd:
            lo_sl = tuple(slice(2, 4) for _ in range(nd))
            hi_sl = tuple(slice(3, 5) if d == c else slice(2, 4)
                          for d in range(nd))
            lo = _cells(bfn[c][lo_sl])
            hi = _cells(bfn[c][hi_sl])
        else:
            ctr = _cells(un[IBX + c][interior])
            lo = hi = ctr
        comps.append(jnp.stack([lo, hi], axis=-1))
    bf_new = jnp.stack(comps, axis=1)                  # [ncell, NCOMP, 2]

    # father-cell edge EMFs: fine corner EMFs at the oct surface corners
    # (positions {2,4} in the pair plane), edge-averaged over the
    # remaining interior positions (2:4)
    pairs = [(d1, d2) for d1 in range(nd) for d2 in range(d1 + 1, nd)]
    emf = None
    if pairs:
        outp = []
        for (d1, d2) in pairs:
            e = e_edges[(d1, d2)]                      # [6…, noct]
            sl = [slice(2, 4)] * nd + [slice(None)]
            corners = []
            for o1 in (2, 4):
                row = []
                for o2 in (2, 4):
                    s = list(sl)
                    s[d1] = o1
                    s[d2] = o2
                    v = e[tuple(s)]                    # [(2,)*rest, noct]
                    red = tuple(range(v.ndim - 1))
                    row.append(v.mean(axis=red) if red else v)
                corners.append(jnp.stack(row, axis=-1))
            outp.append(jnp.stack(corners, axis=-2))   # [noct, 2, 2]
        emf = jnp.stack(outp, axis=1)                  # [noct, np, 2, 2]
    return du_flat, bf_new, corr, emf


@partial(jax.jit, static_argnames=("cfg", "shift"))
def mhd_tile_sweep(u_flat, interp_u, bf_flat, interp_bf, tile_src,
                   tile_ok, cell_tile, cell_slot, oct_tile, oct_slot,
                   dt, dx: float, cfg: MhdStatic, shift: int):
    """CT MUSCL-Hancock on the compact blocked tile batch — the
    gather-fused replacement for :func:`mhd_level_sweep` (same return
    convention: du_flat [ncell_pad, nvar], bf_new [ncell_pad, NCOMP, 2],
    corr [noct_pad, nd, 2, nvar], emf [noct_pad, npairs, 2, 2] | None).

    MHD never passes ``pallas_oct.tile_available`` (that kernel is
    hydro-only), so this is always the trailing-batch XLA tile
    formulation; what it removes is the 6^d-duplicated stencil gather
    of cells AND staggered faces.  Every interior cell/face/corner sees
    the same radius-2 neighbourhood values as the stencil batch (tile
    halo = NGHOST_TILE = 2, shared ``maps._interp_requests`` ghost
    semantics) and ``mu.ct_core`` is shift-invariant, so the extracted
    du/bf/corr/EMF rows are bitwise identical to
    :func:`mhd_level_sweep` (pinned by tests/test_oct_blocking.py)."""
    nd = cfg.ndim
    c = 1 << (shift + 1)
    td = c + 2 * K._NG
    ut = K._gather_utile(u_flat, interp_u, tile_src, None, cfg, td)
    floc = _gather_ftile(bf_flat, interp_bf, tile_src, nd, td)
    ntile = ut.shape[-1]
    real = (tile_src < u_flat.shape[0]).T.reshape((td,) * nd + (ntile,))
    okl = tile_ok.T.reshape((td,) * nd + (ntile,))

    # cell-centred B from the duplicated faces (valid in every tile
    # cell, halo included — cf. mhd_level_sweep)
    centers = 0.5 * (floc[:, 0] + floc[:, 1])          # [NCOMP, td…, ntile]
    ut = ut.at[IBX:IBX + NCOMP].set(centers)

    # Riemann normal faces: stored values win next to a real cell (a
    # ghost's injected coarse value must not override the fine stored
    # field on a shared coarse-fine face)
    bn_faces = []
    for comp in range(NCOMP):
        lo_c = floc[comp, 0]
        if comp < nd:
            hi_m1 = jnp.roll(floc[comp, 1], 1, axis=comp)
            real_m1 = jnp.roll(real, 1, axis=comp)
            bn_faces.append(jnp.where(real, lo_c,
                                      jnp.where(real_m1, hi_m1, lo_c)))
        else:
            bn_faces.append(lo_c)

    flux_mask = []
    for d in range(nd):
        keep = jnp.logical_not(jnp.logical_or(okl,
                                              jnp.roll(okl, 1, axis=d)))
        flux_mask.append(keep.astype(ut.dtype))
    un, bfn, fl_cell, e_edges = mu.ct_core(
        ut, [floc[comp, 0] for comp in range(NCOMP)], dt, (dx,) * nd,
        cfg, bax=1, bn_faces=bn_faces, flux_mask=flux_mask)

    # interior update → flat rows.  Pad cell rows carry slot c^d /
    # tile 0 (maps.py), which flattens one past the interior batch —
    # the appended zero column — so they come out exactly 0 (K.tile_sweep
    # does the same)
    interior = tuple(slice(K._NG, K._NG + c) for _ in range(nd))
    du = (un - ut)[(slice(None),) + interior]          # [nvar, c…, ntile]
    flat_idx = cell_slot * ntile + cell_tile
    du_flat = jnp.concatenate(
        [du.reshape((cfg.nvar, c ** nd * ntile)),
         jnp.zeros((cfg.nvar, 1), du.dtype)], axis=1)[:, flat_idx].T

    # coarse flux-correction payload: the kernels tile helpers' per-oct
    # boundary-plane sums, gathered back to tree oct rows
    corr = []
    for d in range(nd):
        planes = K._face_planes(fl_cell[d] * (dt / dx), d, nd, c)
        lo, hi = K._corr_from_planes(planes, d, nd, c)
        corr.append(jnp.stack([lo[:, oct_slot, oct_tile],
                               hi[:, oct_slot, oct_tile]], axis=-1))
    corr = jnp.stack(corr, axis=-2)                    # [nvar, noct, nd, 2]
    corr = jnp.moveaxis(corr, 0, -1)                   # [noct, nd, 2, nvar]

    def _flat_cells(a):
        """[c…, ntile] → flat cell rows [ncell_pad] (pad rows 0)."""
        af = jnp.concatenate([a.reshape(c ** nd * ntile),
                              jnp.zeros((1,), a.dtype)])
        return af[flat_idx]

    # interior faces: cell's lo at its own position, hi one step up in d
    comps = []
    for comp in range(NCOMP):
        if comp < nd:
            hi_sl = tuple(slice(K._NG + 1, K._NG + c + 1) if dd == comp
                          else slice(K._NG, K._NG + c) for dd in range(nd))
            lo = _flat_cells(bfn[comp][interior])
            hi = _flat_cells(bfn[comp][hi_sl])
        else:
            lo = hi = _flat_cells(un[IBX + comp][interior])
        comps.append(jnp.stack([lo, hi], axis=-1))
    bf_new = jnp.stack(comps, axis=1)                  # [ncell, NCOMP, 2]

    # father-cell edge EMFs: corner-lattice planes at even cell offsets
    # (the stencil path's positions {2, 4} generalised to every oct in
    # the tile), edge-averaged over the remaining interior positions
    pairs = [(d1, d2) for d1 in range(nd) for d2 in range(d1 + 1, nd)]
    emf = None
    if pairs:
        o = c // 2
        outp = []
        for (d1, d2) in pairs:
            idx = tuple(slice(K._NG, K._NG + c + 1, 2) if dd in (d1, d2)
                        else slice(K._NG, K._NG + c) for dd in range(nd))
            g = e_edges[(d1, d2)][idx]
            # collapse each non-pair dim c → (o, 2) and average the
            # 2-subaxis (the stencil slice(2,4).mean edge average)
            shp, red, ax = [], [], 0
            for dd in range(nd):
                if dd in (d1, d2):
                    shp.append(o + 1)
                    ax += 1
                else:
                    shp += [o, 2]
                    red.append(ax + 1)
                    ax += 2
            g = g.reshape(shp + [ntile])
            if red:
                g = g.mean(axis=tuple(red))
            corners = []
            for i1 in (0, 1):
                row = []
                for i2 in (0, 1):
                    sl = [slice(None)] * (nd + 1)
                    sl[d1] = slice(i1, i1 + o)
                    sl[d2] = slice(i2, i2 + o)
                    row.append(g[tuple(sl)].reshape(o ** nd, ntile))
                corners.append(jnp.stack(row, axis=-1))
            pv = jnp.stack(corners, axis=-2)           # [o^nd, ntile, 2, 2]
            outp.append(pv[oct_slot, oct_tile])        # [noct, 2, 2]
        emf = jnp.stack(outp, axis=1)                  # [noct, np, 2, 2]
    return du_flat, bf_new, corr, emf


@partial(jax.jit, static_argnames=("cfg",))
def mhd_level_courant(u_flat, bf_flat, valid_cell, dx: float,
                      cfg: MhdStatic, fg=None):
    """Fast-magnetosonic CFL dt over the level (mhd courant_fine).

    ``fg`` [ncell, ndim]: enables the gravity-strength dt correction of
    ``cmpdt`` (``hydro/godunov_utils.f90:100-110``) so self-gravity
    kicks cannot outrun the step in near-free-fall cells."""
    u = jnp.moveaxis(u_flat, -1, 0)                    # [nvar, ncell]
    ctr = 0.5 * (bf_flat[:, :, 0] + bf_flat[:, :, 1])  # [ncell, NCOMP]
    u = u.at[IBX:IBX + NCOMP].set(ctr.T)
    q = core.ctoprim(u, cfg)
    ws = jnp.zeros_like(q[0])
    for d in range(cfg.ndim):
        ws = ws + jnp.abs(q[1 + d]) + core.fast_speed(q, d, cfg)
    ws = jnp.maximum(ws, cfg.smallc)
    dtc = dx / ws
    if fg is not None:
        gnorm = sum(jnp.abs(fg[:, d]) for d in range(cfg.ndim))
        ratio = jnp.maximum(gnorm * dx / ws ** 2, 1e-4)
        cf = cfg.courant_factor
        dtc = dtc * (jnp.sqrt(1.0 + 2.0 * cf * ratio) - 1.0) \
            / (cf * ratio)
    dtc = jnp.where(valid_cell, dtc, jnp.inf)
    return cfg.courant_factor * jnp.min(dtc)


@partial(jax.jit, static_argnames=("cfg",))
def mhd_restrict_upload(u_level, bf_level, u_fine, bf_fine, ref_cell,
                        son_oct, cfg: MhdStatic):
    """upload_fine for MHD: covered cells take the son means; covered
    FACES take the area mean of the son faces on that side (staggered
    dims) — the div-free restriction."""
    nd = cfg.ndim
    ttd = 2 ** nd
    valid = ref_cell >= 0
    safe_cell = jnp.where(valid, ref_cell, 0)
    rows = son_oct[:, None] * ttd + jnp.arange(ttd)[None, :]  # [nref, 2^d]
    umean = u_fine[rows].mean(axis=1)                  # [nref, nvar]
    bsub = bf_fine[rows]                               # [nref, 2^d, 3, 2]
    # child offset bits in flat order: x slowest
    offs = np.indices((2,) * nd).reshape(nd, -1).T     # [2^d, nd]
    comps = []
    for c in range(NCOMP):
        if c < nd:
            lo_children = jnp.asarray(offs[:, c] == 0)
            wlo = lo_children.astype(bsub.dtype)
            lo = (bsub[:, :, c, 0] * wlo).sum(1) / wlo.sum()
            hi = (bsub[:, :, c, 1] * (1 - wlo)).sum(1) / (ttd - wlo.sum())
        else:
            lo = hi = bsub[:, :, c, 0].mean(axis=1)
        comps.append(jnp.stack([lo, hi], axis=-1))
    bmean = jnp.stack(comps, axis=1)                   # [nref, NCOMP, 2]
    # refresh the covered cells' centred B from the restricted faces
    ctr = 0.5 * (bmean[:, :nd, 0] + bmean[:, :nd, 1])
    umean = umean.at[:, IBX:IBX + nd].set(ctr)

    cur_u = u_level[safe_cell]
    cur_b = bf_level[safe_cell]
    u_out = u_level.at[safe_cell].set(
        jnp.where(valid[:, None], umean, cur_u).astype(u_level.dtype))
    b_out = bf_level.at[safe_cell].set(
        jnp.where(valid[:, None, None], bmean, cur_b).astype(
            bf_level.dtype))
    return u_out, b_out


# ----------------------------------------------------------------------
# refinement criteria (mhd hydro_refine: err_grad_d/p/b)
# ----------------------------------------------------------------------
def _mhd_grad_flags(uloc, eg, fls, spatial0: int, cfg: MhdStatic):
    nd = cfg.ndim
    r = jnp.maximum(uloc[0], cfg.smallr)
    inv_r = 1.0 / r
    v2 = sum((uloc[1 + c] * inv_r) ** 2 for c in range(NCOMP))
    b = [uloc[IBX + c] for c in range(NCOMP)]
    b2 = sum(bc * bc for bc in b)
    p = jnp.maximum((cfg.gamma - 1.0) * (uloc[IP] - 0.5 * r * v2
                                         - 0.5 * b2),
                    cfg.smallr * cfg.smallc ** 2)
    bmag = jnp.sqrt(b2)
    egd, egp, egb = eg
    fld, flp, flb = fls

    def two_sided(f, floor):
        err = jnp.zeros_like(f)
        for d in range(nd):
            ax = spatial0 + d
            flf = jnp.roll(f, 1, axis=ax)
            frt = jnp.roll(f, -1, axis=ax)
            e1 = jnp.abs(frt - f) / (jnp.abs(frt) + jnp.abs(f) + floor)
            e2 = jnp.abs(f - flf) / (jnp.abs(f) + jnp.abs(flf) + floor)
            err = jnp.maximum(err, 2.0 * jnp.maximum(e1, e2))
        return err

    ok = jnp.zeros_like(r, dtype=bool)
    if egd >= 0.0:
        ok = ok | (two_sided(r, fld) > egd)
    if egp >= 0.0:
        ok = ok | (two_sided(p, flp) > egp)
    if egb >= 0.0:
        ok = ok | (two_sided(bmag, flb) > egb)
    return ok


@partial(jax.jit, static_argnames=("spec", "eg", "fls", "itype"))
def _mhd_fused_flags(u, dev, spec: FusedSpec, eg, fls, itype: int):
    cfg = spec.cfg
    nd = cfg.ndim
    bc_kinds = tuple((f[0].kind, f[1].kind) for f in spec.bspec.faces)
    out = []
    for i, l in enumerate(spec.levels):
        d = dev[l]
        if spec.complete[i]:
            sl = spec.slab[i] if spec.slab else None
            if sl is not None:
                # explicit slab-sharded flags (parallel/dense_slab.py):
                # shard-local bitperm + depth-1 ring halos (DMA or
                # ppermute per the halo_backend knob) instead of the
                # global-view transpose
                from functools import partial as _partial

                from ramses_tpu.parallel import dense_slab
                fn = _partial(_mhd_grad_flags, eg=eg, fls=fls,
                              spatial0=0, cfg=cfg)
                fl = dense_slab.dense_flags_slab(u[l], sl, fn, 2 ** nd)
            else:
                shape = (1 << l,) * nd
                ncell = shape[0] ** nd
                ud = jnp.moveaxis(
                    K.rows_to_dense(u[l], d.get("inv_perm"), shape),
                    -1, 0)
                # ghost-pad per the physical BCs: a raw roll would wrap
                # the two domain edges together and flag phantom
                # gradients there
                up = mu._pad(ud, nd, bc_kinds, 1)
                ok = _mhd_grad_flags(up, eg, fls, 0, cfg)
                ok = ok[tuple(slice(1, -1) for _ in range(nd))]
                fl = K.dense_to_rows(ok, d.get("perm"), shape).reshape(
                    ncell // 2 ** nd, 2 ** nd)
        elif spec.blocked and spec.blocked[i]:
            # flags reuse the blocked shared gather (tile batch) —
            # cf. K.tile_refine_flags
            if l == spec.lmin:
                interp = jnp.zeros((d["b_interp_cell"].shape[0],
                                    cfg.nvar), u[l].dtype)
            else:
                interp = K.interp_cells(u[l - 1], d["b_interp_cell"],
                                        d["b_interp_nb"],
                                        d["b_interp_sgn"],
                                        cfg, itype=itype)
            c = 1 << (spec.block_shift + 1)
            td = c + 2 * K._NG
            ut = K._gather_utile(u[l], interp, d["tile_src"], None,
                                 cfg, td)
            ntile = ut.shape[-1]
            ok = _mhd_grad_flags(ut, eg, fls, 0, cfg)
            oki = ok[tuple(slice(K._NG, K._NG + c) for _ in range(nd))]
            okc = jnp.concatenate([oki.reshape(c ** nd * ntile),
                                   jnp.zeros((1,), ok.dtype)])
            rows = okc[d["cell_slot"] * ntile + d["cell_tile"]]
            fl = rows.reshape(rows.shape[0] // 2 ** nd, 2 ** nd)
        else:
            if l == spec.lmin:
                interp = jnp.zeros((d["interp_cell"].shape[0], cfg.nvar),
                                   u[l].dtype)
            else:
                interp = K.interp_cells(u[l - 1], d["interp_cell"],
                                        d["interp_nb"], d["interp_sgn"],
                                        cfg, itype=itype)
            uloc = K._gather_uloc(u[l], interp, d["stencil_src"], None,
                                  cfg)
            ok = _mhd_grad_flags(uloc, eg, fls, 0, cfg)
            okc = ok[tuple(slice(2, 4) for _ in range(nd))]
            okc = jnp.moveaxis(okc, -1, 0)
            fl = okc.reshape(okc.shape[0], 2 ** nd)
        out.append(fl)
    return tuple(out)


# ----------------------------------------------------------------------
# fused coarse step
# ----------------------------------------------------------------------
def _dense_hi(lo_dense, d: int, periodic: bool):
    """High faces from a dense low-face field: the next cell's low face;
    non-periodic top plane keeps its own low value (zero-gradient)."""
    hi = jnp.roll(lo_dense, -1, axis=d)
    if not periodic:
        idx = [slice(None)] * lo_dense.ndim
        idx[d] = slice(-1, None)
        hi = hi.at[tuple(idx)].set(lo_dense[tuple(idx)])
    return hi


def mhd_kick_flat(u_rows, fg_rows, dteff, ndim: int, smallr: float):
    """Gravity momentum kick at fixed internal+magnetic energy on flat
    MHD rows (the ``synchro_hydro_fine`` step with the MHD layout:
    momentum always 3 components at 1..3, total energy at IP)."""
    r = jnp.maximum(u_rows[:, 0], smallr)
    ek_old = sum(0.5 * u_rows[:, 1 + c] ** 2 for c in range(NCOMP)) / r
    mom = [u_rows[:, 1 + c]
           + (r * fg_rows[:, c] * dteff if c < ndim else 0.0)
           for c in range(NCOMP)]
    ek_new = sum(0.5 * m * m for m in mom) / r
    e = u_rows[:, IP] - ek_old + ek_new
    out = u_rows
    for c in range(ndim):
        out = out.at[:, 1 + c].set(mom[c])
    return out.at[:, IP].set(e)


def _mhd_advance_traced(u, bf, dev, fg, dt, spec: FusedSpec):
    """Recursive subcycled MHD coarse step (cf. hydro _advance_traced).

    Cell-state conservation at coarse-fine interfaces follows the hydro
    scheme exactly: refined-face fluxes are zeroed in the coarse sweep
    and the fine level scatters its summed boundary fluxes into the
    unrefined coarse neighbours.  B-center rows are excluded from the
    correction (they must remain the face mean; face-field interface
    accounting is the EMF-matching step)."""
    cfg = spec.cfg
    nd = cfg.ndim
    u = dict(u)
    unew = dict(u)
    bf = dict(bf)
    levels = spec.levels
    bc_kinds = tuple((f[0].kind, f[1].kind) for f in spec.bspec.faces)

    def dx(l):
        return spec.boxlen / (1 << l)

    pairs = [(d1, d2) for d1 in range(nd) for d2 in range(d1 + 1, nd)]

    def advance(i, dtl):
        l = levels[i]
        d = dev[l]
        if spec.gravity:
            u[l] = mhd_kick_flat(u[l], fg[l], 0.5 * dtl, nd, cfg.smallr)
        unew[l] = u[l]
        child_emf = None
        if i + 1 < len(levels):
            e1 = advance(i + 1, 0.5 * dtl)
            e2 = advance(i + 1, 0.5 * dtl)
            if e1 is not None:
                child_emf = 0.5 * (e1 + e2)   # time-averaged fine EMFs
        my_emf = None
        if spec.complete[i]:
            shape = (1 << l,) * nd
            ncell = shape[0] ** nd
            from ramses_tpu.parallel import dense_slab
            sl = spec.slab[i] if spec.slab else None
            use_slab = sl is not None and dense_slab.mhd_slab_ok(sl)
            if use_slab and child_emf is not None:
                cd = dev[levels[i + 1]]
                if (cd.get("emf_dense_idx") is not None
                        and cd.get("emf_flat_idx") is None):
                    use_slab = False      # no Morton scatter map built
            if use_slab:
                # explicit slab-sharded CT (parallel/dense_slab.py):
                # shard-local bitperm + ring halos; the coarse-fine EMF
                # override becomes a row-order scatter OUTSIDE the
                # shard_map (emf_flat_idx), so the partitioned program
                # never sees a global index scatter
                ovr_flat = None
                if child_emf is not None:
                    fidx = dev[levels[i + 1]].get("emf_flat_idx")
                    if fidx is not None:
                        npair = len(pairs)
                        om = jnp.zeros((ncell, npair), u[l].dtype)
                        ov = jnp.zeros((ncell, npair), u[l].dtype)
                        for pi in range(npair):
                            rows = fidx[:, pi].reshape(-1)
                            ov = ov.at[rows, pi].set(
                                child_emf[:, pi].reshape(-1).astype(
                                    u[l].dtype), mode="drop")
                            om = om.at[rows, pi].set(1.0, mode="drop")
                        ovr_flat = (om, ov)
                du_rows, b_rows = dense_slab.mhd_ct_slab(
                    u[l], bf[l], dtl, dx(l), sl, cfg,
                    ok_flat=d.get("ok_flat"), ovr_flat=ovr_flat)
                unew[l] = unew[l] + du_rows.astype(u[l].dtype)
                bf[l] = b_rows.astype(bf[l].dtype)
            else:
                grid = mu.MhdGrid(cfg=cfg, shape=shape, dx=dx(l),
                                  bc_kinds=bc_kinds)
                ud = jnp.moveaxis(
                    K.rows_to_dense(u[l], d.get("inv_perm"), shape),
                    -1, 0)
                bld = K.rows_to_dense(bf[l], d.get("inv_perm"),
                                      shape)           # [*shape, 3, 2]
                bfd = jnp.stack([bld[..., c, 0] for c in range(NCOMP)])
                ok_d = (d["ok_dense"].reshape(shape)
                        if d.get("ok_dense") is not None else None)
                override = None
                if child_emf is not None:
                    idx = dev[levels[i + 1]].get("emf_dense_idx")
                    if idx is not None:
                        override = {}
                        for pi, pair in enumerate(pairs):
                            rows = idx[:, pi].reshape(-1)
                            vals = jnp.zeros(
                                (ncell,), child_emf.dtype).at[rows].set(
                                    child_emf[:, pi].reshape(-1),
                                    mode="drop")
                            msk = jnp.zeros((ncell,), bool).at[rows].set(
                                True, mode="drop")
                            override[pair] = (msk.reshape(shape),
                                              vals.reshape(shape))
                un_d, bfn_d = mu.step(grid, ud, bfd, dtl, ok=ok_d,
                                      emf_override=override)
                du_rows = K.dense_to_rows(
                    jnp.moveaxis(un_d - ud, 0, -1), d.get("perm"), shape)
                if u[l].shape[0] > ncell:
                    du_rows = jnp.zeros_like(u[l]).at[:ncell].set(
                        du_rows.astype(u[l].dtype))
                unew[l] = unew[l] + du_rows
                comps = []
                for c in range(NCOMP):
                    lo_d = bfn_d[c]
                    if c < nd:
                        hi_d = _dense_hi(lo_d, c, bc_kinds[c][0] == 0)
                    else:
                        hi_d = lo_d
                    comps.append(jnp.stack([lo_d, hi_d], axis=-1))
                b_rows = K.dense_to_rows(jnp.stack(comps, axis=-2),
                                         d.get("perm"), shape)
                bf[l] = (bf[l].at[:ncell].set(b_rows.astype(bf[l].dtype))
                         if bf[l].shape[0] > ncell
                         else b_rows.astype(bf[l].dtype))
        else:
            # gather-fused blocked tile path: the compact Morton-tile
            # batch replaces the 6^d-duplicated stencil gather of cells
            # and staggered faces (see AmrSim._advance_traced)
            blocked = bool(spec.blocked and spec.blocked[i])
            ic = "b_interp_cell" if blocked else "interp_cell"
            if l == spec.lmin:
                interp_u = jnp.zeros((d[ic].shape[0], cfg.nvar),
                                     u[l].dtype)
                interp_bf = jnp.zeros(
                    (d[ic].shape[0], NCOMP, 2), bf[l].dtype)
            elif blocked:
                interp_u = K.interp_cells(u[l - 1], d["b_interp_cell"],
                                          d["b_interp_nb"],
                                          d["b_interp_sgn"],
                                          cfg, itype=spec.itype)
                interp_bf = balsara_child_faces(
                    bf[l - 1][d["b_interp_cell"]],
                    d["b_interp_sgn"].astype(bf[l - 1].dtype), nd)
            else:
                interp_u = K.interp_cells(u[l - 1], d["interp_cell"],
                                          d["interp_nb"], d["interp_sgn"],
                                          cfg, itype=spec.itype)
                interp_bf = balsara_child_faces(
                    bf[l - 1][d["interp_cell"]],
                    d["interp_sgn"].astype(bf[l - 1].dtype), nd)
            if blocked:
                du, bfn, corr, my_emf = mhd_tile_sweep(
                    u[l], interp_u, bf[l], interp_bf, d["tile_src"],
                    d["tile_ok"], d["cell_tile"], d["cell_slot"],
                    d["oct_tile"], d["oct_slot"], dtl, dx(l), cfg,
                    spec.block_shift)
            else:
                du, bfn, corr, my_emf = mhd_level_sweep(
                    u[l], interp_u, bf[l], interp_bf, d["stencil_src"],
                    d["ok_ref"], dtl, dx(l), cfg)
            unew[l] = unew[l] + du
            if l > spec.lmin:
                # staggered B centers are face means, not flux-updated
                # cell variables — exclude them; degenerate components
                # (c >= ndim) are genuinely conserved and keep theirs
                corr = corr.at[..., IBX:IBX + min(nd, NCOMP)].set(0.0)
                unew[l - 1] = K.scatter_corrections(
                    unew[l - 1], corr, d["corr_idx"], cfg)
            bf[l] = bfn
        u[l] = unew[l]
        if spec.gravity:
            u[l] = mhd_kick_flat(u[l], fg[l], 0.5 * dtl, nd, cfg.smallr)
        if i + 1 < len(levels):
            u[l], bf[l] = mhd_restrict_upload(
                u[l], bf[l], u[levels[i + 1]], bf[levels[i + 1]],
                d["ref_cell"], d["son_oct"], cfg)
            unew[l] = u[l]
        return my_emf

    advance(0, dt)
    # degenerate (cell-centred) components are DEFINED as the cell value:
    # re-sync their face slots after corrections/restriction so the next
    # sweep's face-derived centers see the corrected state
    if nd < NCOMP:
        for l in levels:
            ctr = u[l][:, IBX + nd:IBX + NCOMP]
            bf[l] = bf[l].at[:, nd:NCOMP, 0].set(ctr)
            bf[l] = bf[l].at[:, nd:NCOMP, 1].set(ctr)
    return u, bf


def _mhd_courant_traced(u, bf, dev, spec: FusedSpec, fg=None):
    dts = []
    for i, l in enumerate(spec.levels):
        dt_l = mhd_level_courant(u[l], bf[l], dev[l]["valid_cell"],
                                 spec.boxlen / (1 << l), spec.cfg,
                                 fg.get(l) if fg else None)
        dts.append(dt_l * (2.0 ** (l - spec.lmin)))
    return jnp.stack(dts)


@partial(jax.jit, static_argnames=("spec",), donate_argnums=(0, 1))
def _mhd_fused_coarse_step(u, bf, dev, dt, spec: FusedSpec, fg=None):
    u, bf = _mhd_advance_traced(u, bf, dev, fg, dt, spec)
    return u, bf, jnp.min(_mhd_courant_traced(
        u, bf, dev, spec, fg if spec.gravity else None))


@partial(jax.jit, static_argnames=("spec",))
def _mhd_fused_courant(u, bf, dev, spec: FusedSpec, fg=None):
    return _mhd_courant_traced(u, bf, dev, spec, fg)


@partial(jax.jit, static_argnames=("spec", "nsteps", "trace"))
def _mhd_fused_multi_step(u, bf, dev, t, tend, dt0, spec: FusedSpec,
                          nsteps: int, trace: bool = False):
    def body(carry, _):
        u, bf, t, dtc, ndone = carry
        dt = jnp.minimum(dtc, jnp.maximum(tend - t, 0.0))
        active = t < tend
        sdt = jnp.where(active, dt, 0.0).astype(u[spec.lmin].dtype)
        un, bfn, dtn = _mhd_fused_coarse_step(u, bf, dev, sdt, spec)
        # (gravity runs step-at-a-time; the multi-step chunk path is
        # hydro-only like the base class)
        u = {l: jnp.where(active, un[l], u[l]) for l in u}
        bf = {l: jnp.where(active, bfn[l], bf[l]) for l in bf}
        t = jnp.where(active, t + dt, t)
        dtc = jnp.where(active, dtn.astype(dtc.dtype), dtc)
        ndone = ndone + jnp.where(active, 1, 0)
        ys = (t, jnp.where(active, dt, 0.0)) if trace else None
        return (u, bf, t, dtc, ndone), ys

    (u, bf, t, dtc, ndone), hist = jax.lax.scan(
        body, (u, bf, t, dt0, jnp.array(0)), None, length=nsteps)
    if trace:
        return u, bf, t, dtc, ndone, hist
    return u, bf, t, dtc, ndone


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------
class MhdAmrSim(AmrSim):
    """Adaptive MHD simulation (CT + div-free AMR transfer operators).

    Reuses the hydro hierarchy's octree, index maps, regrid machinery,
    and evolve loop; overrides the state layout (adds ``self.bfs``),
    the fused step, the CFL, the refinement criteria, and the
    migration/restriction to carry the staggered field."""

    _needs_mig_log = True
    _pm_physics = False      # MHD state layout carries cell-centred B
    _noncubic_ok = False     # dense CT path assumes one root cube
    # out-of-core offload drives the base class's per-level segmented
    # step, which doesn't carry the staggered face state — MHD keeps
    # its own fused step chain and opts out (amr/offload.py)
    _offload_capable = False
    # partial levels take the gather-fused blocked tile sweep too:
    # mhd_tile_sweep runs ct_core on the compact Morton-tile batch (XLA
    # tile formulation — the Pallas oct kernel stays hydro-only), so
    # cells AND staggered faces stop paying the 6^d stencil gather
    _oct_blocked = True

    def __init__(self, params: Params, dtype=jnp.float32, **kw):
        from ramses_tpu import patch
        patch.maybe_install_from_params(params)
        if patch.hook("condinit") is not None:
            import warnings
            warnings.warn(
                "patch condinit hook is not applied to the MHD solver: "
                "MHD ICs need divergence-free STAGGERED face fields; "
                "using &INIT_PARAMS regions instead")
        self.mcfg = MhdStatic.from_params(params)
        spec = bmod.BoundarySpec.from_params(params)
        for lo, hi in ((f[0].kind, f[1].kind) for f in spec.faces):
            for k in (lo, hi):
                if k not in (bmod.PERIODIC, bmod.OUTFLOW):
                    raise NotImplementedError(
                        "MHD-AMR boundaries: periodic/outflow only")
        super().__init__(params, dtype=dtype, **kw)

    # ---- state allocation -------------------------------------------
    def _mhd_region_state(self, lvl: int):
        """(u rows, bf rows) from &INIT_PARAMS regions (driver.py
        ``mhd_condinit`` semantics per arbitrary cell list)."""
        from ramses_tpu.mhd.driver import _region_mask, region_periods
        init = self.params.init
        cfg = self.mcfg
        period = region_periods(self.params, [self.boxlen] * cfg.ndim)
        m = self.maps[lvl]
        centers = self.tree.cell_centers(lvl, self.boxlen)
        x = [centers[:, d] for d in range(cfg.ndim)]
        n = len(centers)
        q = np.zeros((cfg.nvar, n))
        q[0] = cfg.smallr
        q[IP] = cfg.smallr * cfg.smallc ** 2 / cfg.gamma
        bf = np.zeros((n, NCOMP, 2))
        vels = [init.u_region, init.v_region, init.w_region]
        bvals = [init.A_region, init.B_region, init.C_region]
        for k in range(init.nregion):
            if str(init.region_type[k]).strip() != "square":
                raise NotImplementedError("mhd ICs: square regions only")
            msk = _region_mask(x, k, init, cfg.ndim, period)
            q[0][msk] = init.d_region[k]
            for c in range(NCOMP):
                q[1 + c][msk] = vels[c][k]
                bf[msk, c, 0] = bvals[c][k]
                bf[msk, c, 1] = bvals[c][k]
            q[IP][msk] = init.p_region[k]
        for c in range(NCOMP):
            q[IBX + c] = 0.5 * (bf[:, c, 0] + bf[:, c, 1])
        u = np.asarray(core.prim_to_cons(jnp.asarray(q), cfg)).T
        u_pad = np.zeros((m.ncell_pad, cfg.nvar))
        u_pad[:n] = u
        u_pad[n:, 0] = cfg.smallr
        u_pad[n:, IP] = cfg.smallr * cfg.smallc ** 2 / cfg.gamma
        bf_pad = np.zeros((m.ncell_pad, NCOMP, 2))
        bf_pad[:n] = bf
        return (self._place(jnp.asarray(u_pad, self.dtype), "cells"),
                self._place(jnp.asarray(bf_pad, self.dtype), "cells"))

    def _alloc_from_ics(self):
        self.u = {}
        self.bfs: Dict[int, jnp.ndarray] = {}
        for l in self.levels():
            self.u[l], self.bfs[l] = self._mhd_region_state(l)
        self._restrict_all()
        self._dt_cache = None

    def _donor_maps(self, l: int, new_octs) -> np.ndarray:
        """Per new oct: flat cell index of the existing (OLD) fine
        neighbour owning each outer sub-face, -1 where none —
        [nnew, nd, 2, nsub].  The donor's stored face on the shared
        side is copied verbatim (``interpol_mag``'s use of fine
        neighbour faces) so duplicated faces stay single-valued."""
        from ramses_tpu.amr.tree import map_coords
        nd = self.tree_ndim
        tree = self.tree
        lev = tree.levels[l]
        og = lev.og[new_octs]                  # [nnew, nd]
        nnew = len(og)
        nsub = 2 ** (nd - 1)
        is_new = np.zeros(tree.noct(l), dtype=bool)
        is_new[new_octs] = True
        offs = np.indices((2,) * nd).reshape(nd, -1).T
        out = np.full((nnew, nd, 2, nsub), -1, dtype=np.int64)
        for d in range(nd):
            side_offs = {s: offs[offs[:, d] == s] for s in (0, 1)}
            for s in (0, 1):
                for k, off in enumerate(side_offs[s]):
                    q = 2 * og + off               # fine cell coords
                    nq = q.copy()
                    nq[:, d] += 2 * s - 1
                    nqm, _ = map_coords(nq, l, self.bc_kinds, nd)
                    valid = np.ones(nnew, dtype=bool)
                    nmax = 1 << l
                    for dd in range(nd):
                        if self.bc_kinds[dd] != (0, 0):
                            valid &= ((nq[:, dd] >= 0)
                                      & (nq[:, dd] < nmax))
                    doct = tree.lookup(l, nqm >> 1)
                    ok = (doct >= 0) & valid
                    okn = ok & ~is_new[np.clip(doct, 0, None)]
                    doff = np.zeros(nnew, dtype=np.int64)
                    for dd in range(nd):
                        doff = doff * 2 + (nqm[:, dd] & 1)
                    out[:, d, s, k] = np.where(okn,
                                               doct * 2 ** nd + doff, -1)
        return out

    def _rebuild_maps(self, *a, **k):
        super()._rebuild_maps(*a, **k)
        self._build_emf_maps()

    def _build_emf_maps(self):
        """Scatter targets of the coarse-fine EMF matching: for each
        PARTIAL level whose parent level is dense, map every fine oct's
        father-cell edges onto the parent's dense corner lattice
        (corner of cell (i,j,…) ↔ array position (i,j,…)).  Out-of-
        domain corners (non-periodic walls) get an out-of-range index
        so the device scatter drops them.

        Two index layouts per level: ``emf_dense_idx`` (C-order ravel
        of the parent's dense box — the global-view ``mu.step`` path)
        and ``emf_flat_idx`` (the parent's Morton FLAT row order,
        :func:`ramses_tpu.amr.bitperm.flat_index_np`) — the
        slab-sharded CT path scatters the override into row-sharded
        flat arrays OUTSIDE the shard_map, so no global index scatter
        ever enters the partitioned program."""
        from ramses_tpu.amr import bitperm
        nd = self.tree_ndim
        pairs = [(d1, d2) for d1 in range(nd)
                 for d2 in range(d1 + 1, nd)]
        for l in self.levels():
            d = self.dev.get(l)
            if d is None:
                continue
            if (not pairs or l == self.lmin or self.maps[l].complete
                    or not self.maps[l - 1].complete):
                d.pop("emf_dense_idx", None)
                d.pop("emf_flat_idx", None)
                continue
            og = self.tree.levels[l].og        # father cells at l-1
            noct = len(og)
            n1 = 1 << (l - 1)
            ncell1 = n1 ** nd
            m = self.maps[l]
            idx = np.full((m.noct_pad, len(pairs), 2, 2), ncell1,
                          dtype=np.int64)
            fidx = np.full_like(idx, ncell1)
            cubic = tuple(self.root or (1,) * nd) == (1,) * nd
            for pi, (d1, d2) in enumerate(pairs):
                for o1 in (0, 1):
                    for o2 in (0, 1):
                        cc = og.copy()
                        cc[:, d1] += o1
                        cc[:, d2] += o2
                        oob = np.zeros(noct, dtype=bool)
                        for dd in range(nd):
                            lo_k, hi_k = self.bc_kinds[dd]
                            if lo_k == 0 and hi_k == 0:
                                cc[:, dd] %= n1
                            else:
                                oob |= (cc[:, dd] < 0) | (cc[:, dd] >= n1)
                                cc[:, dd] = np.clip(cc[:, dd], 0, n1 - 1)
                        flat = np.ravel_multi_index(
                            tuple(cc[:, dd] for dd in range(nd)),
                            (n1,) * nd)
                        idx[:noct, pi, o1, o2] = np.where(oob, ncell1,
                                                          flat)
                        if cubic:
                            mflat = bitperm.flat_index_np(cc, l - 1, nd)
                            fidx[:noct, pi, o1, o2] = np.where(
                                oob, ncell1, mflat)
                # shared corners are written by up to 2^(nd-1) fine
                # octs; their values agree only to roundoff, so the
                # scatter winner would be resolution-order dependent.
                # Keep ONE canonical writer (first in oct enumeration)
                # and drop the rest — applied identically to both
                # layouts so dense and flat scatters stay bitwise equal.
                v = idx[:noct, pi].reshape(-1).copy()
                _, first = np.unique(v, return_index=True)
                dup = np.ones(v.size, dtype=bool)
                dup[first] = False
                oi, a1, a2 = np.unravel_index(np.flatnonzero(dup),
                                              (noct, 2, 2))
                idx[oi, pi, a1, a2] = ncell1
                fidx[oi, pi, a1, a2] = ncell1
            d["emf_dense_idx"] = self._place(jnp.asarray(idx), "octs")
            if cubic:
                d["emf_flat_idx"] = self._place(jnp.asarray(fidx), "octs")
            else:
                d.pop("emf_flat_idx", None)

    # ---- transfer operators ------------------------------------------
    def _restrict_all(self):
        # during super().regrid() u is migrated before bf: skip the base
        # class's restrict call and run it after the bf migration
        if not hasattr(self, "bfs") or getattr(self, "_regridding", False):
            return
        for l in sorted(self.levels(), reverse=True):
            if self.tree.has(l + 1):
                d = self.dev[l]
                self.u[l], self.bfs[l] = mhd_restrict_upload(
                    self.u[l], self.bfs[l], self.u[l + 1],
                    self.bfs[l + 1], d["ref_cell"], d["son_oct"],
                    self.mcfg)

    def regrid(self):
        old_bf = dict(getattr(self, "bfs", {}))
        self._mig_log = {}
        oldtree = self.tree
        self._regridding = True
        try:
            super().regrid()
        finally:
            self._regridding = False
        if self.tree is oldtree and not self._mig_log:
            return                                     # unchanged
        nd = self.mcfg.ndim
        ttd = 2 ** nd
        nsub = 2 ** (nd - 1)
        new_bf: Dict[int, jnp.ndarray] = {}
        for l in self.levels():
            info = self._mig_log.get(l)
            if info is None:
                new_bf[l] = old_bf[l]
                continue
            (rows_d, rows_s, cell_rep, sgn_rep, rows_new, ncell_pad,
             new_octs, f_cell, _nb_rep) = info
            old = old_bf.get(l)
            if old is None:
                old = jnp.zeros((1, NCOMP, 2), self.dtype)
            buf = jnp.zeros((ncell_pad, NCOMP, 2), self.dtype)
            buf = buf.at[rows_d].set(old[rows_s], mode="drop")
            nnew = len(new_octs)
            if nnew:
                from ramses_tpu.amr.maps import bucket
                npad = bucket(nnew, 256)
                donor = self._donor_maps(l, new_octs)
                donor_p = np.full((npad, nd, 2, nsub), -1, dtype=np.int64)
                donor_p[:nnew] = donor
                f_p = np.zeros(npad, dtype=np.int64)
                f_p[:nnew] = f_cell
                oct_p = np.full(npad, ncell_pad, dtype=np.int64)  # drop
                oct_p[:nnew] = new_octs
                father = new_bf[l - 1][jnp.asarray(f_p)]  # [npad, 3, 2]
                outer_ds = []
                for d in range(nd):
                    per_s = []
                    for s in (0, 1):
                        di = jnp.asarray(donor_p[:, d, s])   # [npad,nsub]
                        val = buf[jnp.clip(di, 0, None), d, 1 - s]
                        inj = father[:, d, s][:, None]
                        per_s.append(jnp.where(di >= 0, val, inj))
                    outer_ds.append(jnp.stack(per_s, axis=1))
                outer = jnp.stack(outer_ds, axis=1)  # [npad, nd, 2, nsub]
                vals = matched_child_faces(father, outer, nd)
                rows_cells = (oct_p[:, None] * ttd
                              + np.arange(ttd)).reshape(-1)
                buf = buf.at[jnp.asarray(rows_cells)].set(
                    vals.astype(buf.dtype), mode="drop")
            new_bf[l] = self._place(buf, "cells")
            # re-derive the stored cell-centred B from the div-free
            # migrated faces — the conservative-variable interpolation
            # of u's B slots is NOT the face mean, and the sweep's
            # center/face invariant must hold
            ctr = 0.5 * (new_bf[l][:, :, 0] + new_bf[l][:, :, 1])
            self.u[l] = self.u[l].at[:, IBX:IBX + NCOMP].set(
                ctr.astype(self.u[l].dtype))
        self.bfs = new_bf
        self._restrict_all()
        self._dt_cache = None

    # ---- refinement criteria -----------------------------------------
    def _criteria_flags(self, spec):
        r = self.params.refine
        eg = (float(r.err_grad_d), float(r.err_grad_p),
              float(r.err_grad_b))
        fls = (float(r.floor_d), float(r.floor_p), float(r.floor_b))
        return _mhd_fused_flags(self.u, self.dev, spec, eg, fls,
                                int(self.params.refine.interpol_type))

    # ---- stepping ------------------------------------------------------
    def _fused_spec(self) -> FusedSpec:
        if self._spec is None:
            lv = tuple(self.levels())
            self._spec = FusedSpec(
                cfg=self.mcfg, bspec=self.bspec, lmin=self.lmin,
                boxlen=self.boxlen, levels=lv,
                complete=tuple(self.maps[l].complete for l in lv),
                gravity=self.gravity,
                itype=int(self.params.refine.interpol_type))
            # slab-sharded complete levels: gradient flags AND the CT
            # advance (mhd_ct_slab — the EMF override scatters into
            # flat rows via emf_flat_idx, so no global index scatter
            # remains); levels whose local box is too thin for the
            # deeper face halos fall back at advance time (mhd_slab_ok)
            slab = tuple(self._slab_spec(l) if self.maps[l].complete
                         else None for l in lv)
            if any(s is not None for s in slab):
                self._spec = self._spec._replace(slab=slab)
            blocked = tuple(l in self.blocks for l in lv)
            if any(blocked):
                self._spec = self._spec._replace(
                    blocked=blocked,
                    block_shift=int(getattr(self.params.amr,
                                            "oct_block_shift", 2)))
        return self._spec

    def coarse_dt(self) -> float:
        with self.timers.section("courant"):
            if self._dt_cache is not None:
                dts = [float(self._dt_cache)]
            else:
                dts = [float(jnp.min(_mhd_fused_courant(
                    self.u, self.bfs, self.dev, self._fused_spec(),
                    self.fg if (self.gravity and self.fg) else None)))]
            dts.extend(self._aux_dts())
            return min(dts)

    def step_coarse(self, dt: float):
        self._grav_pm_pre(float(dt))
        with self.timers.section("hydro - godunov"):
            self.u, self.bfs, self._dt_cache = _mhd_fused_coarse_step(
                self.u, self.bfs, self.dev,
                jnp.asarray(float(dt), self.dtype), self._fused_spec(),
                self.fg if self.gravity else None)
        self._pm_drift(float(dt))
        self.t += float(dt)
        # coarse-cadence source passes (for MHD the patch 'source'
        # hook and gas tracers are live — SF/sinks stay
        # _pm_physics-gated)
        self._source_passes(float(dt))
        self.dt_old = float(dt)
        self.nstep += 1

    def step_chunk(self, nsteps: int, tend: float, trace: bool = False):
        assert not self.gravity and not self.pic  # chunks are solver-only
        spec = self._fused_spec()
        tdtype = jnp.result_type(float)
        if self._dt_cache is not None:
            dt0 = jnp.asarray(self._dt_cache, tdtype)
        else:
            dt0 = jnp.min(_mhd_fused_courant(
                self.u, self.bfs, self.dev, spec)).astype(tdtype)
        with self.timers.section("hydro - godunov"):
            out = _mhd_fused_multi_step(
                self.u, self.bfs, self.dev, jnp.asarray(self.t, tdtype),
                jnp.asarray(tend, tdtype), dt0, spec, nsteps,
                trace=trace)
            if trace:
                u, bf, t, dtn, ndone, hist = out
            else:
                u, bf, t, dtn, ndone = out
            self.u, self.bfs = u, bf
            self._dt_cache = dtn
        self.t = float(t)
        n = int(ndone)
        self.nstep += n
        self.dt_old = float(dtn)
        if trace:
            ts, dts = jax.device_get(hist)
            return n, (ts[:n], dts[:n])
        return n

    # ---- diagnostics ---------------------------------------------------
    def totals(self):
        """Conservation audit over leaf cells (nvar = MHD layout)."""
        tot = np.zeros(self.mcfg.nvar)
        for l in self.levels():
            m = self.maps[l]
            vol = self.dx(l) ** self.tree_ndim
            u = np.asarray(self.u[l])[:m.noct * 2 ** self.tree_ndim]
            leaf = ~self.tree.refined_mask(l)
            tot += u[leaf].sum(axis=0) * vol
        return tot

    def max_divb(self) -> float:
        """Max |divB| over LEAF cells of every level (duplicated-face
        staggered divergence — machine-zero under CT + div-free
        transfer)."""
        worst = 0.0
        for l in self.levels():
            m = self.maps[l]
            dxl = self.dx(l)
            bf = np.asarray(self.bfs[l])[:m.noct * 2 ** self.cfg.ndim]
            leaf = ~self.tree.refined_mask(l)
            if not leaf.any():
                continue
            div = sum((bf[:, d, 1] - bf[:, d, 0]) / dxl
                      for d in range(self.tree_ndim))
            bscale = np.abs(bf).max() / dxl + 1e-300
            worst = max(worst, float(np.abs(div[leaf]).max()) / bscale)
        return worst

    def dump(self, iout: int = 1, base_dir: str = ".",
             namelist_path=None, ncpu: int = 1) -> str:
        """Reference-format snapshot with the MHD column set (density,
        velocity, B_left/right faces, pressure —
        ``mhd/output_hydro.f90:82-150``); the duplicated staggered
        faces round-trip exactly."""
        from ramses_tpu.io import snapshot as snapmod
        snap = snapmod.snapshot_from_mhd_amr(self, iout)
        return snapmod.dump_all(snap, iout, base_dir,
                                namelist_path=namelist_path, ncpu=ncpu)

    @classmethod
    def from_snapshot(cls, params: Params, outdir: str,
                      dtype=jnp.float32) -> "MhdAmrSim":
        """Resume from an MHD snapshot (``mhd/init_hydro.f90`` restart
        read: the face fields come back verbatim, the cell-centred B is
        their mean)."""
        from ramses_tpu.amr.hierarchy import restore_amr_scaffold
        from ramses_tpu.io.snapshot import mhd_out_to_state
        mcfg = MhdStatic.from_params(params)
        ttd = 2 ** params.ndim

        def place(sim, l, q, og, order):
            m = sim.maps[l]
            u_rows, bf_rows = mhd_out_to_state(q, mcfg)
            u_out = np.array(sim.u[l])
            bf_out = np.array(sim.bfs[l])
            u_out[:m.noct * ttd] = u_rows.reshape(
                len(og), ttd, mcfg.nvar)[order].reshape(-1, mcfg.nvar)
            bf_out[:m.noct * ttd] = bf_rows.reshape(
                len(og), ttd, 3, 2)[order].reshape(-1, 3, 2)
            sim.u[l] = jnp.asarray(u_out, dtype=dtype)
            sim.bfs[l] = jnp.asarray(bf_out, dtype=dtype)

        sim, _parts = restore_amr_scaffold(
            cls, params, outdir, dtype, to_cons=lambda q: q,
            place_level=place)
        return sim
