"""Jitted device kernels for the AMR hydro sweep.

One level-step = interp (buffer prolongation) → stencil gather → unsplit
MUSCL-Hancock → refined-face flux zeroing → conservative update + coarse
flux-correction scatter, the whole of ``godfine1``
(``hydro/godunov_fine.f90:486-910``) as a single fused XLA program over the
level's oct batch instead of nvector chunks.
"""

from __future__ import annotations

from dataclasses import replace as dreplace
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ramses_tpu.amr import bitperm
from ramses_tpu.hydro import muscl
from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.hydro.timestep import cell_dt
from ramses_tpu.telemetry.hlo import phase


def pow2_cube(shape) -> bool:
    """True when every dim equals the same power of two — the complete
    cubic-level case where flat↔dense is a bit permutation
    (:mod:`ramses_tpu.amr.bitperm`) instead of an index gather."""
    s0 = shape[0]
    return (s0 & (s0 - 1)) == 0 and all(s == s0 for s in shape)


def rows_to_dense(rows, inv_perm, shape):
    """Flat-order rows ``[ncell(+pad), *trailing]`` → dense
    ``[*shape, *trailing]``.  Bit-permutation transpose on cubic
    power-of-two levels (no gather — the TPU fast path); index gather
    through ``inv_perm`` otherwise."""
    if pow2_cube(shape):
        return bitperm.flat_to_dense(rows, shape[0].bit_length() - 1,
                                     len(shape))
    if inv_perm is None:
        raise ValueError(f"non-cubic complete level {shape} needs an "
                         "inv_perm index map")
    return rows[inv_perm].reshape(shape + rows.shape[1:])


def dense_to_rows(dense, perm, shape):
    """Dense ``[*shape, *trailing]`` → flat-order rows (inverse of
    :func:`rows_to_dense`)."""
    nd = len(shape)
    if pow2_cube(shape):
        return bitperm.dense_to_flat(dense, shape[0].bit_length() - 1, nd)
    if perm is None:
        raise ValueError(f"non-cubic complete level {shape} needs a "
                         "perm index map")
    ncell = 1
    for s in shape:
        ncell *= s
    return dense.reshape((ncell,) + dense.shape[nd:])[perm]


def _unsplit_fn(cfg):
    """Physics dispatch: the cfg class selects the sweep kernel family
    (hydro default; ``physics="rhd"`` → the SRHD set with the same
    low-face dt/dx-scaled flux convention)."""
    if getattr(cfg, "physics", "hydro") == "rhd":
        from ramses_tpu.rhd import sweeps
        return sweeps.unsplit
    return muscl.unsplit


def _cell_dt_fn(cfg):
    if getattr(cfg, "physics", "hydro") == "rhd":
        from ramses_tpu.rhd import sweeps
        return sweeps.cell_dt
    return cell_dt


def _flags_fn(cfg):
    if getattr(cfg, "physics", "hydro") == "rhd":
        from ramses_tpu.rhd import sweeps
        return sweeps.grad_flags
    return _grad_flags


@partial(jax.jit, static_argnames=("cfg", "itype"))
def interp_cells(u_coarse, cell_idx, nb_idx, sgn, cfg: HydroStatic,
                 itype: int = 1):
    """Prolongation values for requested fine cells.

    ``interpol_hydro`` with interpol_var=0 (conservative variables,
    ``hydro/interpol_hydro.f90:268-391``): fine = a0 + Σ_d w_d·(±0.5) with
    w from the chosen limiter on the father's face-neighbour differences.

    u_coarse: [ncell, nvar]; cell_idx: [ni]; nb_idx: [ni, ndim, 2];
    sgn: [ni, ndim] ±1.  Returns [ni, nvar].
    """
    a0, ws = _interp_slopes(u_coarse, cell_idx, nb_idx, cfg, itype)
    out = a0
    for d, w in enumerate(ws):
        out = out + w * (0.5 * sgn[:, d:d + 1])
    return out


def interp_octs(u_coarse, cell_idx, nb_idx, sgn_tab, cfg: HydroStatic,
                itype: int = 1):
    """:func:`interp_cells` for ALL ``2^ndim`` children of each
    requested father cell at once: the father/neighbour rows are
    gathered once per oct and the children differ only in the sign
    table ``sgn_tab [2^ndim, ndim]``.  Returns ``[ni * 2^ndim, nvar]``
    in (oct, child) row order — bitwise what ``interp_cells`` gives on
    the ``2^ndim``-fold repeated indices, with ``2^ndim``× fewer
    gathered rows (on TPU the repeated form also compiled 20× slower:
    42 s vs 2 s for one 512-oct level, sandbox compile, PR 22)."""
    a0, ws = _interp_slopes(u_coarse, cell_idx, nb_idx, cfg, itype)
    out = jnp.broadcast_to(a0[:, None], (a0.shape[0], sgn_tab.shape[0],
                                         a0.shape[1]))
    for d, w in enumerate(ws):
        out = out + w[:, None] * (0.5 * sgn_tab[None, :, d:d + 1])
    return out.reshape(-1, a0.shape[1])


def _interp_slopes(u_coarse, cell_idx, nb_idx, cfg: HydroStatic,
                   itype: int):
    """Father values ``a0 [ni, nvar]`` and the per-dim limited slopes
    ``[w_d [ni, nvar]]`` (empty for ``itype == 0``) of
    :func:`interp_cells`."""
    a0 = u_coarse[cell_idx]                            # [ni, nvar]
    ws = []
    if itype == 0:
        return a0, ws
    for d in range(cfg.ndim):
        al = u_coarse[nb_idx[:, d, 0]]
        ar = u_coarse[nb_idx[:, d, 1]]
        dl = 0.5 * (a0 - al)                     # halved differences
        dr = 0.5 * (ar - a0)                     # (compute_limiter_minmod)
        if itype == 1:
            w = jnp.where(dl * dr <= 0.0, 0.0,
                          jnp.sign(dr) * jnp.minimum(jnp.abs(dl),
                                                     jnp.abs(dr)))
        elif itype == 3:
            w = 0.25 * (ar - al)                       # unlimited central
        else:  # itype 2: per-dim monotonized central (the reference's
            # corner-coupled limiter is approximated dimension-by-dimension)
            dc = 0.25 * (ar - al)
            lim = jnp.minimum(2.0 * jnp.abs(dl), 2.0 * jnp.abs(dr))
            w = jnp.where(dl * dr <= 0.0, 0.0,
                          jnp.sign(dc) * jnp.minimum(jnp.abs(dc), lim))
        ws.append(w)
    return a0, ws


def _gather_uloc(u_flat, interp_vals, stencil_src, vsgn, cfg: HydroStatic):
    """Build [nvar, 6^d..., noct] stencil batch from flat cells + interps.

    The oct axis is minor-most on purpose: TPU layouts tile the two
    minor dims to (8, 128), so a [..., 6, 6] minor layout would pad
    ~28x in HBM while [..., 6, noct] pads ~1.3x.
    """
    trash = jnp.zeros((1, cfg.nvar), u_flat.dtype)
    src = jnp.concatenate([u_flat, interp_vals, trash], axis=0)
    srcT = src.T                                       # [nvar, nrows]
    ul = srcT[:, stencil_src]                          # [nvar, noct, 6^d]
    if vsgn is not None:
        # reflecting boundaries: flip mirrored velocity components
        for d in range(cfg.ndim):
            flip = ((vsgn >> d) & 1).astype(u_flat.dtype)  # [noct, 6^d]
            s = 1.0 - 2.0 * flip
            ul = ul.at[1 + d].multiply(s)
    noct = ul.shape[1]
    ul = jnp.swapaxes(ul, 1, 2)                        # [nvar, 6^d, noct]
    return ul.reshape((cfg.nvar,) + (6,) * cfg.ndim + (noct,))


def _flat_cells(blk, ndim: int):
    """[2..., noct] per-cell block → flat [noct*2^d] row order."""
    noct = blk.shape[-1]
    return jnp.transpose(
        blk, (ndim,) + tuple(range(ndim))).reshape(noct * 2 ** ndim)


@partial(jax.jit, static_argnames=("cfg", "dx", "ret_flux", "ndev"))
def level_sweep(u_flat, interp_vals, stencil_src, vsgn, ok_ref, gloc,
                dt, dx: float, cfg: HydroStatic, ret_flux: bool = False,
                ndev: int = 1):
    """Full godfine1 for one level.

    ``ndev``: devices the level's rows span (the caller's simulation,
    not the host); more than one keeps the XLA formulation.

    Returns (du_flat [ncell, nvar], corr [noct, ndim, 2, nvar]) where
    corr[:, d, side] is the summed boundary flux (already ×dt/dx) to be
    scattered ∓/2^ndim into unrefined coarse neighbours.

    ``ret_flux``: additionally return the per-cell signed mass flux
    ``phi [ncell, ndim, 2]`` at each cell's (low, high) face — the MC
    gas-tracer capture of ``godunov_fine.f90:685-715`` (fluxes already
    ×dt/dx, refined faces zeroed) — served by BOTH branches (the
    Pallas kernel emits it as a third output).
    """
    ndim, nvar = cfg.ndim, cfg.nvar
    bcfg = dreplace(cfg, trailing_batch=True)
    with phase("gather"):
        uloc = _gather_uloc(u_flat, interp_vals, stencil_src, vsgn, cfg)
        noct = uloc.shape[-1]
        # [noct, 6^d] → [6..., noct]
        okl = ok_ref.T.reshape((6,) * ndim + (noct,))

    from ramses_tpu.hydro import pallas_oct
    if gloc is None and pallas_oct.available(cfg, noct, u_flat.dtype,
                                             ndev):
        # fused TPU oct-batch kernel (same physics, VMEM-resident);
        # self-gravity rides as the hierarchy's separate traced
        # half-kick, so gloc is None on every production path
        with phase("kernel"):
            out_k = pallas_oct.oct_sweep(
                uloc, okl.astype(uloc.dtype), dt, cfg, dx,
                want_flux=ret_flux)
        with phase("scatter"):
            du_k, corr_k = out_k[0], out_k[1]
            du_flat = jnp.transpose(
                du_k, (ndim + 1,) + tuple(range(1, ndim + 1)) + (0,)
            ).reshape(noct * 2 ** ndim, nvar)
            corr_out = jnp.transpose(corr_k, (3, 1, 2, 0))
            if not ret_flux:
                return du_flat, corr_out
            # phi [3, 2, 2,2,2, N] → flat [ncell, ndim, 2]
            phi_k = jnp.transpose(out_k[2], (5, 2, 3, 4, 0, 1)).reshape(
                noct * 2 ** ndim, ndim, 2)
            return du_flat, corr_out, phi_k

    with phase("kernel"):
        flux, tmp = _unsplit_fn(cfg)(uloc, gloc, dt, (dx,) * ndim, bcfg)
        # flux[d]: [nvar, 6..., noct], defined at the LOW face of each cell.

        # Reset flux along direction at refined interfaces
        # (hydro/godunov_fine.f90:718-747): a face is zeroed when either
        # adjacent cell is refined — its contribution comes from level+1;
        # the reference zeroes the tmp (divu/eint-flux) faces the same way.
        fluxes = []
        tmps = []
        for d in range(ndim):
            keep = ~(okl | jnp.roll(okl, 1, axis=d))       # [6..., noct]
            fluxes.append(flux[d] * keep[None].astype(flux.dtype))
            if tmp is not None:
                tmps.append(tmp[d] * keep[None].astype(flux.dtype))
        # conservative update over the whole block (outer cells hold
        # wrapped garbage the interior never consumes), then the optional
        # dual-energy fix, then the interior extraction
        un_blk = muscl.apply_fluxes(uloc, jnp.stack(fluxes), bcfg)
        if tmp is not None and (cfg.pressure_fix or cfg.nener):
            un_blk = muscl.dual_energy_fix(uloc, un_blk, jnp.stack(tmps),
                                           dt, (dx,) * ndim, bcfg)
    with phase("scatter"):
        interior = (slice(None),) + tuple(slice(2, 4) for _ in range(ndim))
        du = un_blk[interior] - uloc[interior]
        # [nvar, 2..., noct] → flat [noct*2^d, nvar]
        du_flat = jnp.transpose(
            du, (ndim + 1,) + tuple(range(1, ndim + 1)) + (0,)
        ).reshape(noct * 2 ** ndim, nvar)

        # boundary fluxes for the coarse correction: low face idx 2, high idx 4
        corr = []
        for d in range(ndim):
            f = fluxes[d]
            idx_lo = [slice(None)]
            idx_hi = [slice(None)]
            for d2 in range(ndim):
                if d2 == d:
                    idx_lo.append(2)
                    idx_hi.append(4)
                else:
                    idx_lo.append(slice(2, 4))
                    idx_hi.append(slice(2, 4))
            red = tuple(range(1, 1 + ndim - 1))
            lo, hi = f[tuple(idx_lo)], f[tuple(idx_hi)]
            if ndim > 1:
                lo, hi = lo.sum(axis=red), hi.sum(axis=red)
            corr.append(jnp.stack([lo, hi], axis=-1))  # [nvar, noct, 2]
        corr = jnp.stack(corr, axis=-2)            # [nvar, noct, ndim, 2]
        corr = jnp.moveaxis(corr, 0, -1)           # [noct, ndim, 2, nvar]
        if not ret_flux:
            return du_flat, corr
        # per-cell (low, high) face mass flux: cell at stencil position i
        # along d has its low face flux at index i, high face at i+1
        phis = []
        for d in range(ndim):
            f0 = fluxes[d][0]                              # [6..., noct] mass
            lo_ix = tuple(slice(2, 4) for _ in range(ndim))
            hi_ix = tuple(slice(3, 5) if dd == d else slice(2, 4)
                          for dd in range(ndim))
            phis.append(jnp.stack([_flat_cells(f0[lo_ix], ndim),
                                   _flat_cells(f0[hi_ix], ndim)], axis=-1))
        phi = jnp.stack(phis, axis=-2)                     # [ncell, ndim, 2]
        return du_flat, corr, phi


# ---------------------------------------------------------------------------
# Blocked Morton tile sweep (gather-fused oct path)
# ---------------------------------------------------------------------------

_NG = 2                                   # tile halo width (MUSCL stencil)


def _gather_utile(u_flat, interp_vals, tile_src, tile_vsgn,
                  cfg: HydroStatic, td: int):
    """Compact blocked gather: [nvar, td..., ntile] from flat cells +
    interps — the gather-fused replacement for :func:`_gather_uloc`'s
    ~(3^d)x-duplicated per-oct stencil batch.  Each Morton-aligned tile
    holds its interior cells once plus a 2-cell halo, so HBM gather
    traffic scales with tile volume instead of stencil volume."""
    trash = jnp.zeros((1, cfg.nvar), u_flat.dtype)
    src = jnp.concatenate([u_flat, interp_vals, trash], axis=0)
    srcT = src.T                                       # [nvar, nrows]
    ut = srcT[:, tile_src]                             # [nvar, ntile, td^d]
    if tile_vsgn is not None:
        for d in range(cfg.ndim):
            flip = ((tile_vsgn >> d) & 1).astype(u_flat.dtype)
            ut = ut.at[1 + d].multiply(1.0 - 2.0 * flip)
    ntile = ut.shape[1]
    ut = jnp.swapaxes(ut, 1, 2)                        # [nvar, td^d, ntile]
    return ut.reshape((cfg.nvar,) + (td,) * cfg.ndim + (ntile,))


def _face_planes(fl, d, ndim: int, c: int):
    """Per-oct-face flux planes of masked flux ``fl`` [nvar, td..., ntile]
    along d: [nvar, c//2+1, c...(transverse, increasing-dim order),
    ntile] — positions _NG + 2k, transverse interior."""
    idx = [slice(None)]
    for dd in range(ndim):
        idx.append(slice(_NG, _NG + c + 1, 2) if dd == d
                   else slice(_NG, _NG + c))
    return jnp.moveaxis(fl[tuple(idx)], 1 + d, 1)


def _mass_planes(f0, d, ndim: int, c: int):
    """All c+1 per-cell-face planes of the mass flux ``f0``
    [td..., ntile] along d: [c+1, c...(transverse), ntile]."""
    idx = []
    for dd in range(ndim):
        idx.append(slice(_NG, _NG + c + 1) if dd == d
                   else slice(_NG, _NG + c))
    return jnp.moveaxis(f0[tuple(idx)], d, 0)


def _corr_from_planes(planes, d, ndim: int, c: int):
    """Per-oct boundary-flux sums from face planes: (lo, hi), each
    [nvar, (c//2)^ndim, ntile] flattened in global dim order — the same
    [nvar, 2, 2, ...] transverse reduction as :func:`level_sweep`."""
    o = c // 2
    nvar, ntile = planes.shape[0], planes.shape[-1]
    shape = [nvar, o + 1] + [o, 2] * (ndim - 1) + [ntile]
    g = planes.reshape(shape)
    cell_axes = [3 + 2 * i for i in range(ndim - 1)]
    g = jnp.moveaxis(g, cell_axes, tuple(range(1, ndim)))
    red = tuple(range(1, 1 + ndim - 1))
    s = g.sum(axis=red) if ndim > 1 else g
    # s: [nvar, o+1 (planes along d), o transverse dims..., ntile];
    # restore global dim order before flattening to oct slots
    def _oct_rows(x):
        x = jnp.moveaxis(x, 1, 1 + d)
        return x.reshape(nvar, o ** ndim, ntile)
    lo = jax.lax.slice_in_dim(s, 0, o, axis=1)
    hi = jax.lax.slice_in_dim(s, 1, o + 1, axis=1)
    return _oct_rows(lo), _oct_rows(hi)


@partial(jax.jit, static_argnames=("cfg", "dx", "shift", "ret_flux",
                                   "pallas_ok"))
def tile_sweep(u_flat, interp_vals, tile_src, tile_vsgn, tile_ok,
               cell_tile, cell_slot, oct_tile, oct_slot,
               dt, dx: float, cfg: HydroStatic, shift: int,
               ret_flux: bool = False, pallas_ok: bool = True):
    """Full godfine1 for one blocked partial level — the gather-fused
    replacement for :func:`level_sweep` (same return convention:
    du_flat [ncell, nvar], corr [noct, ndim, 2, nvar] [, phi
    [ncell, ndim, 2]]).  The 6^d-duplicated stencil batch is never
    materialized: the sweep runs on the compact [nvar, td..., ntile]
    tile batch (Pallas kernel on TPU, trailing-batch XLA fallback
    elsewhere), and du/corr/phi are reordered back to flat rows with
    small per-cell/per-oct gathers.

    ``pallas_ok=False`` forces the XLA tile formulation regardless of
    :func:`~ramses_tpu.hydro.pallas_oct.tile_available` — row-sharded
    meshes use it so GSPMD can partition the sweep (the two
    formulations are pinned bitwise-identical by tests)."""
    ndim, nvar = cfg.ndim, cfg.nvar
    c = 1 << (shift + 1)
    td = c + 2 * _NG
    with phase("gather"):
        ut = _gather_utile(u_flat, interp_vals, tile_src, tile_vsgn, cfg, td)
        ntile = ut.shape[-1]
        okl = tile_ok.T.reshape((td,) * ndim + (ntile,))

    from ramses_tpu.hydro import pallas_oct
    if pallas_ok and pallas_oct.tile_available(cfg, ntile, u_flat.dtype,
                                                shift):
        with phase("kernel"):
            out_k = pallas_oct.tile_sweep(ut, okl.astype(ut.dtype), dt, cfg,
                                          dx, shift, want_flux=ret_flux)
        with phase("scatter"):
            du_t, corrp = out_k[0], out_k[1]
            planes = [corrp[:, d] for d in range(ndim)]
            mass = ([out_k[2][d] for d in range(ndim)] if ret_flux else None)
    else:
        with phase("kernel"):
            bcfg = dreplace(cfg, trailing_batch=True)
            flux, tmp = _unsplit_fn(cfg)(ut, None, dt, (dx,) * ndim, bcfg)
            fluxes = []
            tmps = []
            for d in range(ndim):
                keep = ~(okl | jnp.roll(okl, 1, axis=d))
                fluxes.append(flux[d] * keep[None].astype(flux.dtype))
                if tmp is not None:
                    tmps.append(tmp[d] * keep[None].astype(flux.dtype))
            un_blk = muscl.apply_fluxes(ut, jnp.stack(fluxes), bcfg)
            if tmp is not None and (cfg.pressure_fix or cfg.nener):
                un_blk = muscl.dual_energy_fix(ut, un_blk, jnp.stack(tmps),
                                               dt, (dx,) * ndim, bcfg)
        with phase("scatter"):
            interior = (slice(None),) + (slice(_NG, _NG + c),) * ndim
            du_t = un_blk[interior] - ut[interior]
            planes = [_face_planes(fluxes[d], d, ndim, c) for d in range(ndim)]
            mass = ([_mass_planes(fluxes[d][0], d, ndim, c)
                     for d in range(ndim)] if ret_flux else None)

    with phase("scatter"):
        # interior update → flat rows.  Pad cell rows carry slot c^d /
        # tile 0 (maps.py), which flattens one past the interior batch —
        # an appended zero column — so they come out exactly 0 with no
        # masking on the real-row dataflow.
        flat_idx = cell_slot * ntile + cell_tile
        du_src = jnp.concatenate(
            [du_t.reshape((nvar, c ** ndim * ntile)),
             jnp.zeros((nvar, 1), du_t.dtype)], axis=1)
        du_flat = du_src[:, flat_idx].T                    # [ncell_pad, nvar]

        # boundary fluxes → per-oct corr rows
        corr = []
        for d in range(ndim):
            lo, hi = _corr_from_planes(planes[d], d, ndim, c)
            lo_g = lo[:, oct_slot, oct_tile]
            hi_g = hi[:, oct_slot, oct_tile]
            corr.append(jnp.stack([lo_g, hi_g], axis=-1))  # [nvar, noct, 2]
        corr = jnp.stack(corr, axis=-2)            # [nvar, noct, nd, 2]
        corr = jnp.moveaxis(corr, 0, -1)           # [noct, nd, 2, nvar]
        if not ret_flux:
            return du_flat, corr

        # per-cell (low, high) face mass flux
        def _cell_rows(x, d):
            x = jnp.moveaxis(x, 0, d)                      # [c..., ntile]
            xf = jnp.concatenate([x.reshape(c ** ndim * ntile),
                                  jnp.zeros((1,), x.dtype)])
            return xf[flat_idx]
        phis = []
        for d in range(ndim):
            phis.append(jnp.stack([_cell_rows(mass[d][:c], d),
                                   _cell_rows(mass[d][1:c + 1], d)], axis=-1))
        phi = jnp.stack(phis, axis=-2)                     # [ncell, ndim, 2]
        return du_flat, corr, phi


@partial(jax.jit, static_argnames=("cfg", "err_grad", "floors", "shift"))
def tile_refine_flags(u_flat, interp_vals, tile_src, tile_vsgn,
                      cell_tile, cell_slot,
                      err_grad: Tuple[float, float, float],
                      floors: Tuple[float, float, float],
                      cfg: HydroStatic, shift: int):
    """Blocked-gather variant of :func:`refine_flags`: evaluates the same
    gradient criteria on the compact tile batch (the shared gather of
    the blocked sweep) and reorders to flat-cell rows [noct_pad, 2^d]."""
    nd = cfg.ndim
    c = 1 << (shift + 1)
    td = c + 2 * _NG
    with phase("gather"):
        ut = _gather_utile(u_flat, interp_vals, tile_src, tile_vsgn, cfg, td)
        ntile = ut.shape[-1]
    with phase("criteria"):
        ok = _flags_fn(cfg)(ut, err_grad, floors, spatial0=0, cfg=cfg)
    with phase("scatter"):
        interior = (slice(_NG, _NG + c),) * nd
        okc = jnp.concatenate([ok[interior].reshape(c ** nd * ntile),
                               jnp.zeros((1,), ok.dtype)])
        rows = okc[cell_slot * ntile + cell_tile]          # [ncell_pad]
        return rows.reshape(len(cell_slot) // 2 ** nd, 2 ** nd)


def dense_interior_update(up, okp, dt, dx: float, shape: Tuple[int, ...],
                          cfg: HydroStatic, ret_flux: bool = False):
    """Padded-halo interior update shared by the global-view dense sweep
    and the per-shard slab path (:mod:`ramses_tpu.parallel.dense_slab`).

    ``up``: ``[nvar, *(shape + 2*NGHOST)]`` ghost-padded state; ``okp``:
    optional refined-cell mask over the same padded box, ALREADY in the
    state dtype (1.0 = refined) — faces touching a refined cell get zero
    flux.  Returns ``du [nvar, *shape]`` (+ ``phi [*shape, ndim, 2]``
    per-cell (low, high) dt/dx-scaled face mass fluxes when
    ``ret_flux``).
    """
    from ramses_tpu.grid import boundary as bmod

    nd = cfg.ndim
    flux, tmp = _unsplit_fn(cfg)(up, None, dt, (dx,) * nd, cfg)
    if okp is not None:
        masked = []
        masked_tmp = []
        for d in range(nd):
            # arithmetic (1-ok)(1-ok_roll) instead of pred ~(ok|roll):
            # the pred→f32 convert of the bit-permuted mask is exactly
            # the op the SPMD partitioner could only reshard by full
            # rematerialization (MULTICHIP_r05 tail)
            keep = (1.0 - okp) * (1.0 - jnp.roll(okp, 1, axis=d))
            masked.append(flux[d] * keep[None])
            if tmp is not None:
                masked_tmp.append(tmp[d] * keep[None])
        flux = jnp.stack(masked)
        if tmp is not None:
            tmp = jnp.stack(masked_tmp)
    un = muscl.apply_fluxes(up, flux, cfg)
    if tmp is not None and (cfg.pressure_fix or cfg.nener):
        un = muscl.dual_energy_fix(up, un, tmp, dt, (dx,) * nd, cfg)
    du = bmod.unpad(un, nd, muscl.NGHOST) - bmod.unpad(up, nd,
                                                       muscl.NGHOST)
    if not ret_flux:
        return du
    g = muscl.NGHOST
    phis = []
    for d in range(nd):
        f0 = flux[d][0]                                # [*padded] mass
        lo_ix = tuple(slice(g, g + shape[dd]) for dd in range(nd))
        hi_ix = tuple(slice(g + 1, g + 1 + shape[dd]) if dd == d
                      else slice(g, g + shape[dd]) for dd in range(nd))
        phis.append(jnp.stack([f0[lo_ix], f0[hi_ix]], axis=-1))
    return du, jnp.stack(phis, axis=-2)                # [*shape, ndim, 2]


def pad_ok_dense(ok_dense, shape: Tuple[int, ...], bc, dtype, ng: int):
    """Dense-ravel refined mask → ghost-padded arithmetic mask in the
    state dtype (the convert happens BEFORE the pad/bit-permuted views,
    on the cleanly row-sharded array)."""
    okp = ok_dense.astype(dtype).reshape(shape)
    for d in range(len(shape)):
        mode = "wrap" if bc.faces[d][0].kind == 0 else "edge"
        padw = [(ng, ng) if d2 == d else (0, 0)
                for d2 in range(len(shape))]
        okp = jnp.pad(okp, padw, mode=mode)
    return okp


@partial(jax.jit, static_argnames=("cfg", "shape", "bc", "dx", "ret_flux",
                                   "ndev"))
def dense_sweep(u_flat, inv_perm, perm, ok_dense, dt, dx: float,
                shape: Tuple[int, ...], bc, cfg: HydroStatic,
                ret_flux: bool = False, ndev: int = 1):
    """Sweep for a COMPLETE level (covers the whole box) as a dense grid.

    The 6^d stencil gather duplicates each cell ~3^d times and its
    [..., 6, 6] minors tile terribly on TPU; a complete level needs
    neither ghost interpolation nor coarse corrections, so it runs the
    roll-based uniform kernel instead (``grid/uniform.py`` path) with
    refined-face flux zeroing.  Returns du over the flat level rows.

    ``ret_flux``: additionally return ``phi [ncell, ndim, 2]`` — the
    per-cell (low, high) face mass flux ×dt/dx in flat row order (MC
    gas-tracer capture) — served by BOTH branches (the fused kernel
    emits it as a second output).  ``ndev``: devices the level's rows
    span (the caller's simulation, not the host); more than one keeps
    the XLA formulation so GSPMD can partition it.
    """
    from ramses_tpu.grid import boundary as bmod
    from ramses_tpu.hydro import pallas_muscl as pk

    nd, nvar = cfg.ndim, cfg.nvar
    ncell = 1
    for s in shape:
        ncell *= s
    with phase("gather"):
        ud = rows_to_dense(u_flat, inv_perm, shape)        # [*shape, nvar]
        ud = jnp.moveaxis(ud, -1, 0)                       # [nvar, *shape]
    if pk.kernel_available(cfg, shape, bc.faces, ud.dtype, ndev):
        # fused TPU kernel path (same physics, VMEM-resident pipeline);
        # refined-face flux zeroing rides in as the mask input, the
        # MC-tracer face-flux capture as a second kernel output
        with phase("pad"):
            ok = ok_dense.reshape(shape) if ok_dense is not None else None
            up, okp = pk.pad_xy(ud, bc, cfg, ok=ok)
        with phase("kernel"):
            if ret_flux:
                un, phid = pk.fused_step_padded(up, dt, cfg, dx, shape,
                                                ok_pad=okp, want_flux=True)
            else:
                un = pk.fused_step_padded(up, dt, cfg, dx, shape,
                                          ok_pad=okp)
        du_dense = un - ud
        # phid [3, 2, *shape] → flat rows [ncell, ndim, 2]
        phi_dense = (jnp.moveaxis(phid, (0, 1), (-2, -1)) if ret_flux
                     else None)
    else:
        with phase("pad"):
            up = bmod.pad(ud, bc, cfg, muscl.NGHOST, dx=dx)
            okp = (pad_ok_dense(ok_dense, shape, bc, up.dtype, muscl.NGHOST)
                   if ok_dense is not None else None)
        with phase("kernel"):
            out = dense_interior_update(up, okp, dt, dx, shape, cfg,
                                        ret_flux=ret_flux)
        du_dense = out[0] if ret_flux else out         # [nvar, *shape]
        phi_dense = out[1] if ret_flux else None       # [*shape, ndim, 2]
    with phase("scatter"):
        du_rows = dense_to_rows(jnp.moveaxis(du_dense, 0, -1), perm, shape)
        if u_flat.shape[0] > ncell:
            du_rows = jnp.zeros_like(u_flat).at[:ncell].set(du_rows)
        if not ret_flux:
            return du_rows
        phi = dense_to_rows(phi_dense, perm, shape)    # [ncell, ndim, 2]
        if u_flat.shape[0] > ncell:
            phi = jnp.zeros((u_flat.shape[0], nd, 2),
                            phi.dtype).at[:ncell].set(phi)
        return du_rows, phi


@partial(jax.jit, static_argnames=("cfg", "shape", "bc", "err_grad",
                                   "floors", "dx"))
def dense_refine_flags(u_flat, inv_perm, perm,
                       err_grad: Tuple[float, float, float],
                       floors: Tuple[float, float, float],
                       shape: Tuple[int, ...], bc, cfg: HydroStatic,
                       dx: float = None):
    """Gradient refinement criteria for a complete level on the dense
    grid (same semantics as :func:`refine_flags`)."""
    from ramses_tpu.grid import boundary as bmod

    nd, nvar = cfg.ndim, cfg.nvar
    ncell = 1
    for s in shape:
        ncell *= s
    with phase("gather"):
        ud = jnp.moveaxis(rows_to_dense(u_flat, inv_perm, shape), -1, 0)
    with phase("pad"):
        up = bmod.pad(ud, bc, cfg, 1, dx=dx)
    with phase("criteria"):
        ok = _flags_fn(cfg)(up, err_grad, floors, spatial0=0, cfg=cfg)
    with phase("scatter"):
        ok = ok[tuple(slice(1, -1) for _ in range(nd))]    # interior
        flags_flat = dense_to_rows(ok, perm, shape)    # flat cell order
        return flags_flat.reshape(ncell // 2 ** nd, 2 ** nd)


@partial(jax.jit, static_argnames=("cfg",))
def scatter_corrections(unew_coarse, corr, corr_idx, cfg: HydroStatic):
    """Scatter ∓flux/2^ndim into unrefined coarse neighbour cells
    (``hydro/godunov_fine.f90:795-910``).  corr_idx == -1 → dropped."""
    ndim = cfg.ndim
    w = 1.0 / (2 ** ndim)
    idx = corr_idx.reshape(-1)                         # [noct*ndim*2]
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    # side 0 (low face of the fine oct = high face of the coarse cell): -F
    # side 1: +F   (u += F_low - F_high seen from the coarse cell)
    sign = jnp.tile(jnp.array([-1.0, 1.0], unew_coarse.dtype),
                    corr_idx.shape[0] * ndim)
    vals = corr.reshape(-1, cfg.nvar) * (w * sign * valid)[:, None]
    return unew_coarse.at[safe].add(vals.astype(unew_coarse.dtype))


@partial(jax.jit, static_argnames=("cfg",))
def scatter_corr_flux(phi_coarse, corr, corr_idx, cfg: HydroStatic):
    """Fold the fine level's boundary mass fluxes into the coarse
    neighbours' face slots of the MC-tracer capture ``phi``.

    A fine oct's faces coincide with its parent cell's faces, so the
    low-side corr value IS the mass flux through the unrefined coarse
    neighbour's HIGH face (and vice versa), scaled 1/2^ndim into coarse
    Δρ units exactly like :func:`scatter_corrections`.  The coarse
    sweep zeroed those faces (refined-adjacent), so this is the only
    writer."""
    ndim = cfg.ndim
    w = 1.0 / (2 ** ndim)
    for d in range(ndim):
        for side, slot in ((0, 1), (1, 0)):
            idx = corr_idx[:, d, side]
            valid = idx >= 0
            safe = jnp.where(valid, idx, 0)
            vals = corr[:, d, side, 0] * w * valid
            phi_coarse = phi_coarse.at[safe, d, slot].add(
                vals.astype(phi_coarse.dtype))
    return phi_coarse


@partial(jax.jit, static_argnames=("cfg",))
def restrict_upload(u_level, u_fine, ref_cell, son_oct, cfg: HydroStatic):
    """upload_fine: overwrite refined cells with the mean of their son
    oct's cells (``hydro/interpol_hydro.f90:5-100``)."""
    ndim = cfg.ndim
    twotondim = 2 ** ndim
    valid = ref_cell >= 0
    safe_cell = jnp.where(valid, ref_cell, 0)
    rows = (son_oct[:, None] * twotondim
            + jnp.arange(twotondim)[None, :])          # [nref, 2^d]
    mean = u_fine[rows].mean(axis=1)                   # [nref, nvar]
    cur = u_level[safe_cell]
    vals = jnp.where(valid[:, None], mean, cur)
    return u_level.at[safe_cell].set(vals.astype(u_level.dtype))


@partial(jax.jit, static_argnames=("cfg",))
def level_courant(u_flat, valid_cell, dx: float, cfg: HydroStatic,
                  fg=None):
    """Min CFL dt over the level's (valid) cells — ``courant_fine``.

    ``fg`` [ncell, ndim]: gravitational acceleration; enables the
    gravity-strength dt correction of ``cmpdt``
    (``hydro/godunov_utils.f90:100-110``) that keeps a collapsing
    self-gravitating cell from outrunning its own kick."""
    u = jnp.moveaxis(u_flat, -1, 0)                    # [nvar, ncell]
    grav = ([fg[:, d] for d in range(cfg.ndim)]
            if fg is not None else None)
    dtc = _cell_dt_fn(cfg)(u, grav, dx, cfg)
    dtc = jnp.where(valid_cell, dtc, jnp.inf)
    return jnp.minimum(cfg.courant_factor * dx / cfg.smallc, jnp.min(dtc))


@partial(jax.jit, static_argnames=("cfg", "err_grad", "floors"))
def refine_flags(u_flat, interp_vals, stencil_src, vsgn,
                 err_grad: Tuple[float, float, float],
                 floors: Tuple[float, float, float],
                 cfg: HydroStatic):
    """Per-cell gradient refinement criteria — ``hydro_refine``
    (``hydro/godunov_utils.f90:125-260``): relative two-sided differences
    of ρ, P, and Mach-normalized velocity over the 3^d neighbourhood.

    Returns bool flags [noct, 2^d] in flat-cell order.
    """
    with phase("gather"):
        uloc = _gather_uloc(u_flat, interp_vals, stencil_src, vsgn, cfg)
    nd = cfg.ndim
    # fields below are [6..., noct]: spatial axes 0..nd-1, oct axis last
    with phase("criteria"):
        ok = _flags_fn(cfg)(uloc, err_grad, floors, spatial0=0, cfg=cfg)
    with phase("scatter"):
        interior = tuple(slice(2, 4) for _ in range(nd))
        okc = ok[interior]                             # [2..., noct]
        okc = jnp.moveaxis(okc, -1, 0)                 # [noct, 2...]
        return okc.reshape(okc.shape[0], 2 ** nd)


def two_sided_rel_err(f, floor, nd: int, spatial0: int):
    """Max-over-directions relative two-sided difference — the error
    metric of ``hydro_refine`` (``hydro/godunov_utils.f90:152-210``),
    shared by the hydro and SRHD flag kernels."""
    err = jnp.zeros_like(f)
    for d in range(nd):
        ax = spatial0 + d
        fl = jnp.roll(f, 1, axis=ax)
        fr = jnp.roll(f, -1, axis=ax)
        e1 = jnp.abs(fr - f) / (jnp.abs(fr) + jnp.abs(f) + floor)
        e2 = jnp.abs(f - fl) / (jnp.abs(f) + jnp.abs(fl) + floor)
        err = jnp.maximum(err, 2.0 * jnp.maximum(e1, e2))
    return err


def _grad_flags(uloc, err_grad, floors, spatial0: int, cfg: HydroStatic):
    """Shared gradient-criteria evaluation; ``uloc`` is [nvar, ...] with
    spatial axes starting at ``spatial0`` of the per-field arrays."""
    nd = cfg.ndim
    r = jnp.maximum(uloc[0], cfg.smallr)
    vels = [uloc[1 + d] / r for d in range(nd)]
    ek = sum(0.5 * r * v * v for v in vels)
    p = (cfg.gamma - 1.0) * (uloc[nd + 1] - ek)
    ok = jnp.zeros_like(r, dtype=bool)
    egd, egu, egp = err_grad
    fld, flu, flp = floors

    def two_sided(f, floor):
        return two_sided_rel_err(f, floor, nd, spatial0)

    if egd >= 0.0:
        ok = ok | (two_sided(r, fld) > egd)
    if egp >= 0.0:
        ok = ok | (two_sided(p, flp) > egp)
    if egu >= 0.0:
        c = jnp.sqrt(jnp.maximum(cfg.gamma * p / r, flu ** 2))
        for d in range(nd):
            v = vels[d]
            err = jnp.zeros_like(v)
            for dd in range(nd):
                ax = spatial0 + dd
                vl, vr = jnp.roll(v, 1, axis=ax), jnp.roll(v, -1, axis=ax)
                cl, cr = jnp.roll(c, 1, axis=ax), jnp.roll(c, -1, axis=ax)
                e1 = jnp.abs(vr - v) / (cr + c + jnp.abs(vr) + jnp.abs(v)
                                        + flu)
                e2 = jnp.abs(v - vl) / (c + cl + jnp.abs(v) + jnp.abs(vl)
                                        + flu)
                err = jnp.maximum(err, 2.0 * jnp.maximum(e1, e2))
            ok = ok | (err > egu)
    return ok


# ----------------------------------------------------------------------
# how is level l swept: the one place that decides.  Plain Python, not
# jitted — the callers' traces (hierarchy._advance_traced /
# _fused_flags, offload._seg_sweep / _seg_flags) inline them, and the
# kernels they pick between keep their own jax.jit.  ``spec`` is a
# hierarchy.FusedSpec, ``i`` an index into ``spec.levels``, ``d`` the
# level's device maps (``sim.dev[l]``).
# ----------------------------------------------------------------------
def level_kind(spec, i: int) -> str:
    """The formulation level ``spec.levels[i]`` takes.  A COMPLETE
    level (it covers the box) runs as a dense grid: ``"slab"`` where
    the mesh cut it into slabs (``spec.slab[i]``,
    parallel/dense_slab.py), else ``"dense"``.  A PARTIAL level runs
    the Morton-tile batch, ``"tile"`` (``spec.blocked[i]``), or the
    per-oct 6^d stencil, ``"stencil"``."""
    if spec.complete[i]:
        return ("slab" if spec.slab and spec.slab[i] is not None
                else "dense")
    return "tile" if spec.blocked and spec.blocked[i] else "stencil"


def dense_shape(spec, l: int) -> Tuple[int, ...]:
    """Cells per dim of complete level ``l``: ``root[d]·2^l``."""
    nd = spec.cfg.ndim
    return tuple(r << l for r in (spec.root or (1,) * nd)[:nd])


def _ghost_cells(spec, i: int, u_l, u_lm1, d, itype: int):
    """Ghost values of a partial level's gather, prolonged from level
    l-1 through the tile batch's ``b_interp_*`` tables or the
    stencil's ``interp_*``; a base level has nothing coarser and reads
    zeros (a base level is complete wherever it is swept)."""
    pre = "b_" if level_kind(spec, i) == "tile" else ""
    if spec.levels[i] == spec.lmin:
        return jnp.zeros((d[pre + "interp_cell"].shape[0], spec.cfg.nvar),
                         u_l.dtype)
    return interp_cells(u_lm1, d[pre + "interp_cell"],
                        d[pre + "interp_nb"], d[pre + "interp_sgn"],
                        spec.cfg, itype=itype)


def sweep_level(spec, i: int, u_l, u_lm1, d, dtl):
    """One godunov sweep of level ``spec.levels[i]`` over ``dtl``:
    ``(du, corr, phi)``.  ``corr`` is the coarse flux correction
    (None on a complete level: nothing coarser borders it), ``phi``
    the MC-tracer face mass flux (None unless ``spec.want_flux``).
    ``u_lm1`` is read on partial levels only."""
    cfg, l = spec.cfg, spec.levels[i]
    dx = spec.boxlen / (1 << l)
    kind = level_kind(spec, i)
    if spec.complete[i]:
        if kind == "slab":
            # shard-local bitperm + ring halos with DMA overlap
            # (parallel/dense_slab.py, dma_halo.py): the GSPMD
            # partitioner never sees the bit-interleaved transpose,
            # so no involuntary full rematerialization
            from ramses_tpu.parallel import dense_slab
            out = dense_slab.dense_sweep_slab(
                u_l, d.get("ok_flat"), dtl, dx, spec.slab[i], cfg,
                ret_flux=spec.want_flux)
        else:
            out = dense_sweep(u_l, d.get("inv_perm"), d.get("perm"),
                              d["ok_dense"], dtl, dx, dense_shape(spec, l),
                              spec.bspec, cfg, ret_flux=spec.want_flux,
                              ndev=spec.ndev)
        return (out[0], None, out[1]) if spec.want_flux \
            else (out, None, None)
    with phase("ghost"):
        interp = _ghost_cells(spec, i, u_l, u_lm1, d, spec.itype)
    if kind == "tile":
        # the compact Morton-tile batch replaces the ~(3^d)x-duplicated
        # stencil gather.  Pad cell rows index the kernels' appended
        # zero column (maps.py), so du/phi pad rows are exactly 0
        out = tile_sweep(
            u_l, interp, d["tile_src"], d["tile_vsgn"], d["tile_ok"],
            d["cell_tile"], d["cell_slot"], d["oct_tile"], d["oct_slot"],
            dtl, dx, cfg, spec.block_shift, ret_flux=spec.want_flux,
            pallas_ok=spec.pallas_tiles)
    else:
        out = level_sweep(u_l, interp, d["stencil_src"], d["vsgn"],
                          d["ok_ref"], None, dtl, dx, cfg,
                          ret_flux=spec.want_flux, ndev=spec.ndev)
    return out[0], out[1], (out[2] if spec.want_flux else None)


def flags_level(spec, i: int, u_l, u_lm1, d, eg, fls, itype: int):
    """Gradient refinement flags ``[noct_pad, 2^d]`` of level
    ``spec.levels[i]`` (``hydro_refine``), on the gather its sweep
    takes (:func:`level_kind`)."""
    cfg, l = spec.cfg, spec.levels[i]
    kind = level_kind(spec, i)
    if kind == "slab":
        from ramses_tpu.parallel import dense_slab
        fn = partial(_flags_fn(cfg), err_grad=eg, floors=fls, spatial0=0,
                     cfg=cfg)
        return dense_slab.dense_flags_slab(u_l, spec.slab[i], fn,
                                           2 ** cfg.ndim)
    if kind == "dense":
        return dense_refine_flags(u_l, d.get("inv_perm"), d.get("perm"),
                                  eg, fls, dense_shape(spec, l),
                                  spec.bspec, cfg,
                                  dx=spec.boxlen / (1 << l))
    with phase("ghost"):
        interp = _ghost_cells(spec, i, u_l, u_lm1, d, itype)
    if kind == "tile":
        return tile_refine_flags(u_l, interp, d["tile_src"],
                                 d["tile_vsgn"], d["cell_tile"],
                                 d["cell_slot"], eg, fls, cfg,
                                 spec.block_shift)
    return refine_flags(u_l, interp, d["stencil_src"], d["vsgn"], eg, fls,
                        cfg)
