"""Explicit per-shard comm schedule for the sharded-AMR level sweep.

The ``build_comm`` analogue (``amr/virtual_boundaries.f90:1286``): after
every regrid the host walks each partial level's stencil/interp/corr
maps and materialises, per device, exactly which rows must move — the
reference's per-(cpu,level) emission/reception lists become per-ring-
offset ``lax.ppermute`` schedules:

* P2 (halo): each shard's 6^d stencil references rows of the SAME level
  owned by other shards, and its ghost-interpolation requests reference
  rows of the COARSER level — both become packed row buffers sent along
  the Hilbert ring (``make_virtual_fine_dp``, ``:373-533``).  The
  permutes ride the backend-dispatched exchange engine
  (:mod:`ramses_tpu.parallel.dma_halo`): async remote-copy DMA on TPU,
  ``lax.ppermute`` elsewhere, per the ``&AMR_PARAMS halo_backend``
  knob resolved into :class:`SweepCommSpec`.
* P3 (reverse): coarse flux-correction contributions are packed per
  owner, permuted back, and folded into the owner's block in a FIXED
  order — own entries first, then ring offsets ascending — the
  deterministic owner-fold of ``make_virtual_reverse_dp`` (``:693``).

Hilbert-ordered row sharding keeps the peer set small: almost all
traffic rides offsets ±1, so the schedule is a handful of
neighbour permutes instead of partitioner-inferred all-gathers.  The
sweep itself is the UNCHANGED :func:`ramses_tpu.amr.kernels.level_sweep`
run shard-locally on ``[own ++ halo]`` rows — identical physics, pinned
communication.

Static metadata (ring offsets) rides in :class:`SweepCommSpec` (part of
the jit key via ``FusedSpec``); the variable-size index buffers are
``[ndev, ...]`` device arrays sharded on their leading axis so every
shard reads its own rows under ``shard_map``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ramses_tpu.parallel import dma_halo

AXIS = "oct"


class SweepCommSpec(NamedTuple):
    """Hashable static part of one level's sweep schedule."""
    mesh: Mesh
    fine_offsets: Tuple[int, ...]     # ring offsets carrying u_l halo rows
    coarse_offsets: Tuple[int, ...]   # ring offsets carrying u_{l-1} rows
    corr_offsets: Tuple[int, ...]     # ring offsets carrying corr folds
    itype: int
    backend: str = "ppermute"         # resolved halo backend (dma_halo)


def _shard_map(fn, mesh, in_specs, out_specs, check_rep=True):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def _halo_schedule(need: Dict[int, Dict[int, np.ndarray]], ndev: int):
    """need[s][p] = sorted global rows shard s needs from owner p.
    Returns (offsets, send_idx {k: [ndev, B_k]} sender-LOCAL rows,
    ext_pos {s: {global_row: ext_index}} via per-shard dicts)."""
    offs = sorted({(s - p) % ndev
                   for s in need for p in need[s] if len(need[s][p])})
    send_idx = {}
    bks = {}
    for k in offs:
        bk = max(len(need[(p + k) % ndev].get(p, ()))
                 for p in range(ndev))
        bks[k] = bk
        arr = np.zeros((ndev, bk), dtype=np.int32)
        for p in range(ndev):
            rows = need[(p + k) % ndev].get(p, np.zeros(0, np.int64))
            arr[p, :len(rows)] = rows           # sender-local remap later
        send_idx[k] = arr
    return offs, send_idx, bks


def _build_need(rows_by_shard, owner_of, ndev):
    """rows_by_shard[s] = global row refs of shard s (any order).
    Returns need[s][p] = np.sort(unique rows of s owned by p != s)."""
    need = {s: {} for s in range(ndev)}
    for s in range(ndev):
        rows = np.unique(rows_by_shard[s])
        own = owner_of(rows)
        for p in np.unique(own):
            if p == s:
                continue
            need[s][int(p)] = rows[own == p]
    return need


def build_sweep_comm(m, mc, ndev: int, mesh: Mesh, itype: int,
                     halo_backend: str = "auto"):
    """Schedule for one partial level l (maps ``m``) over coarse level
    l-1 (maps ``mc``).  Returns (SweepCommSpec, dict of numpy arrays
    [ndev, ...]) or None when ndev == 1.  ``halo_backend``: the
    ``&AMR_PARAMS`` knob, resolved here so the sweep's permutes
    dispatch to the DMA engine on TPU."""
    if ndev == 1:
        return None
    nd = m.ndim
    ttd = 1 << nd
    ns = m.stencil_src.shape[1]
    noct_pad, ncell_pad, ni_pad = m.noct_pad, m.ncell_pad, m.ni_pad
    assert noct_pad % ndev == 0, "oct rows must divide the mesh"
    octs_loc = noct_pad // ndev
    cells_loc = ncell_pad // ndev
    ncell_c = mc.ncell_pad
    assert ncell_c % ndev == 0
    coarse_loc = ncell_c // ndev
    trash = ncell_pad + ni_pad

    sten = m.stencil_src.reshape(ndev, octs_loc, ns).astype(np.int64)

    # ---- fine halo: same-level cell refs crossing shard boundaries
    fine_refs = [sten[s][(sten[s] < ncell_pad)] for s in range(ndev)]
    fneed = _build_need(fine_refs, lambda r: r // cells_loc, ndev)
    foffs, fsend, fbk = _halo_schedule(fneed, ndev)
    # sender-local remap of the send rows
    for k in foffs:
        fsend[k] = (fsend[k]
                    - (np.arange(ndev, dtype=np.int32)[:, None]
                       * cells_loc)).astype(np.int32)
        fsend[k] = np.maximum(fsend[k], 0)
    fbase = {}
    off_acc = cells_loc
    for k in foffs:
        fbase[k] = off_acc
        off_acc += fbk[k]
    halo_total = off_acc - cells_loc

    # ---- interp rows each shard must compute locally
    ineed = []
    for s in range(ndev):
        r = sten[s]
        sel = (r >= ncell_pad) & (r < trash)
        ineed.append(np.unique(r[sel] - ncell_pad))
    ipad_loc = max(8, max((len(x) for x in ineed), default=0))

    # ---- coarse halo: rows referenced by the local interp requests
    coarse_refs = []
    for s in range(ndev):
        rows = np.concatenate([
            m.interp_cell[ineed[s]].astype(np.int64),
            m.interp_nb[ineed[s]].reshape(-1).astype(np.int64)]) \
            if len(ineed[s]) else np.zeros(0, np.int64)
        coarse_refs.append(rows)
    cneed = _build_need(coarse_refs, lambda r: r // coarse_loc, ndev)
    coffs, csend, cbk = _halo_schedule(cneed, ndev)
    for k in coffs:
        csend[k] = (csend[k]
                    - (np.arange(ndev, dtype=np.int32)[:, None]
                       * coarse_loc)).astype(np.int32)
        csend[k] = np.maximum(csend[k], 0)
    cbase = {}
    off_acc = coarse_loc
    for k in coffs:
        cbase[k] = off_acc
        off_acc += cbk[k]

    # per-shard remap helpers ------------------------------------------
    def fine_ext_index(s, rows):
        """global fine-level row -> shard-s extended-array index."""
        out = np.empty(len(rows), dtype=np.int32)
        own = rows // cells_loc
        sel = own == s
        out[sel] = rows[sel] - s * cells_loc
        for p in np.unique(own[~sel]):
            k = (s - p) % ndev
            hrows = fneed[s][int(p)]
            pos = np.searchsorted(hrows, rows[own == p])
            out[own == p] = fbase[k] + pos
        return out

    def coarse_ext_index(s, rows):
        out = np.empty(len(rows), dtype=np.int32)
        own = rows // coarse_loc
        sel = own == s
        out[sel] = rows[sel] - s * coarse_loc
        for p in np.unique(own[~sel]):
            k = (s - p) % ndev
            hrows = cneed[s][int(p)]
            pos = np.searchsorted(hrows, rows[own == p])
            out[own == p] = cbase[k] + pos
        return out

    # ---- local stencil (into [own ++ halo ++ interp_loc ++ trash])
    interp_base = cells_loc + halo_total
    trash_loc = interp_base + ipad_loc
    lsten = np.full((ndev, octs_loc, ns), trash_loc, dtype=np.int32)
    licell = np.zeros((ndev, ipad_loc), dtype=np.int32)
    linb = np.zeros((ndev, ipad_loc, nd, 2), dtype=np.int32)
    lisgn = np.ones((ndev, ipad_loc, nd), dtype=np.int8)
    for s in range(ndev):
        r = sten[s].reshape(-1)
        cell = r < ncell_pad
        isel = (r >= ncell_pad) & (r < trash)
        out = np.full(len(r), trash_loc, dtype=np.int32)
        if cell.any():
            out[cell] = fine_ext_index(s, r[cell])
        if isel.any():
            ipos = np.searchsorted(ineed[s], r[isel] - ncell_pad)
            out[isel] = interp_base + ipos
        lsten[s] = out.reshape(octs_loc, ns)
        ii = ineed[s]
        if len(ii):
            licell[s, :len(ii)] = coarse_ext_index(s, m.interp_cell[ii]
                                                   .astype(np.int64))
            linb[s, :len(ii)] = coarse_ext_index(
                s, m.interp_nb[ii].reshape(-1).astype(np.int64)
            ).reshape(len(ii), nd, 2)
            lisgn[s, :len(ii)] = m.interp_sgn[ii]

    # ---- reverse (corr) schedule -------------------------------------
    corr = m.corr_idx.reshape(ndev, octs_loc * nd * 2).astype(np.int64)
    w = 1.0 / ttd
    sgn = np.tile(np.array([-1.0, 1.0]), octs_loc * nd)
    own_src, own_tgt, own_w = [], [], []
    rem = {}                               # k -> (src, w, rcv_tgt) lists
    for s in range(ndev):
        c = corr[s]
        valid = c >= 0
        coef = sgn * w * valid
        owner = np.where(valid, c // coarse_loc, s)
        sel_own = valid & (owner == s)
        own_src.append(np.nonzero(sel_own)[0].astype(np.int32))
        own_tgt.append((c[sel_own] - s * coarse_loc).astype(np.int32))
        own_w.append(coef[sel_own])
        for p in np.unique(owner[valid & (owner != s)]):
            k = int((int(p) - s) % ndev)
            src = np.nonzero(valid & (owner == p))[0].astype(np.int32)
            rem.setdefault(k, {})[s] = (
                src, coef[src],
                (c[src] - int(p) * coarse_loc).astype(np.int32))
    o_pad = max(8, max((len(x) for x in own_src), default=0))
    own_src_a = np.zeros((ndev, o_pad), dtype=np.int32)
    own_tgt_a = np.zeros((ndev, o_pad), dtype=np.int32)
    own_w_a = np.zeros((ndev, o_pad))
    for s in range(ndev):
        n = len(own_src[s])
        own_src_a[s, :n] = own_src[s]
        own_tgt_a[s, :n] = own_tgt[s]
        own_w_a[s, :n] = own_w[s]
    koffs = sorted(rem)
    corr_send, corr_w, corr_tgt = {}, {}, {}
    for k in koffs:
        pk = max(8, max(len(v[0]) for v in rem[k].values()))
        src_a = np.zeros((ndev, pk), dtype=np.int32)
        w_a = np.zeros((ndev, pk))
        tgt_a = np.zeros((ndev, pk), dtype=np.int32)
        for s, (src, cw, tgt) in rem[k].items():
            src_a[s, :len(src)] = src
            w_a[s, :len(src)] = cw
            # receiver (s+k)%ndev applies these targets in the SAME
            # packed order the sender used
            tgt_a[(s + k) % ndev, :len(tgt)] = tgt
        corr_send[k] = src_a
        corr_w[k] = w_a
        corr_tgt[k] = tgt_a

    spec = SweepCommSpec(mesh=mesh, fine_offsets=tuple(foffs),
                         coarse_offsets=tuple(coffs),
                         corr_offsets=tuple(koffs), itype=itype,
                         backend=dma_halo.resolve_backend(halo_backend))
    arrays = dict(
        lsten=lsten, licell=licell, linb=linb, lisgn=lisgn,
        own_src=own_src_a, own_tgt=own_tgt_a, own_w=own_w_a,
    )
    for k in foffs:
        arrays[f"fsend_{k}"] = fsend[k]
    for k in coffs:
        arrays[f"csend_{k}"] = csend[k]
    for k in koffs:
        arrays[f"corr_send_{k}"] = corr_send[k]
        arrays[f"corr_w_{k}"] = corr_w[k]
        arrays[f"corr_tgt_{k}"] = corr_tgt[k]
    return spec, arrays


def _perm(ndev: int, k: int):
    return [(p, (p + k) % ndev) for p in range(ndev)]


def sweep_correct_explicit(u_l, u_lm1, unew_lm1, d: dict, dt, dx: float,
                           cfg, spec: SweepCommSpec):
    """One partial-level sweep + coarse correction fold with the
    explicit schedule; drop-in for the global-view

        interp = K.interp_cells(...); du, corr = K.level_sweep(...)
        unew_lm1 = K.scatter_corrections(unew_lm1, corr, corr_idx, ...)

    Returns (du_flat rows of level l, updated unew_{l-1})."""
    from ramses_tpu.amr import kernels as K

    mesh = spec.mesh
    ndev = mesh.shape[AXIS]
    cm = d["comm"]

    def body(u_loc, uc_loc, unew_loc, dt_r, vsgn_loc, ok_loc, *sched):
        it = iter(sched)
        lsten = next(it)[0]
        licell, linb, lisgn = next(it)[0], next(it)[0], next(it)[0]
        own_src, own_tgt, own_w = (next(it)[0], next(it)[0],
                                   next(it)[0])
        fsend = {k: next(it)[0] for k in spec.fine_offsets}
        csend = {k: next(it)[0] for k in spec.coarse_offsets}
        corr_send = {k: next(it)[0] for k in spec.corr_offsets}
        corr_w = {k: next(it)[0] for k in spec.corr_offsets}
        corr_tgt = {k: next(it)[0] for k in spec.corr_offsets}

        # P2: fine + coarse halos — pack own rows, move them along the
        # ring in ONE fused backend exchange (every offset's buffer is
        # a separate slab of the same DMA kernel on TPU)
        halo = dma_halo.exchange_slabs(
            [u_loc[fsend[k]] for k in spec.fine_offsets]
            + [uc_loc[csend[k]] for k in spec.coarse_offsets],
            [_perm(ndev, k) for k in spec.fine_offsets]
            + [_perm(ndev, k) for k in spec.coarse_offsets],
            AXIS, backend=spec.backend)
        nf = len(spec.fine_offsets)
        u_ext = jnp.concatenate([u_loc] + halo[:nf], axis=0)
        uc_ext = jnp.concatenate([uc_loc] + halo[nf:], axis=0)

        interp = K.interp_cells(uc_ext, licell, linb,
                                lisgn.astype(u_loc.dtype), cfg,
                                itype=spec.itype)
        du, corr = K.level_sweep(u_ext, interp, lsten,
                                 vsgn_loc if has_vsgn else None, ok_loc,
                                 None, dt_r, dx, cfg, ndev=ndev)

        # P3: deterministic owner-fold — own first, then offsets
        # ascending (sorted segment order is fixed by the schedule)
        cflat = corr.reshape(-1, corr.shape[-1])
        unew_loc = unew_loc.at[own_tgt].add(
            (cflat[own_src] * own_w[:, None]).astype(unew_loc.dtype))
        if spec.corr_offsets:
            gots = dma_halo.exchange_slabs(
                [cflat[corr_send[k]] * corr_w[k][:, None]
                 for k in spec.corr_offsets],
                [_perm(ndev, k) for k in spec.corr_offsets],
                AXIS, backend=spec.backend)
            for k, got in zip(spec.corr_offsets, gots):
                unew_loc = unew_loc.at[corr_tgt[k]].add(
                    got.astype(unew_loc.dtype))
        return du, unew_loc

    sched_names = (["lsten", "licell", "linb", "lisgn", "own_src",
                    "own_tgt", "own_w"]
                   + [f"fsend_{k}" for k in spec.fine_offsets]
                   + [f"csend_{k}" for k in spec.coarse_offsets]
                   + [f"corr_send_{k}" for k in spec.corr_offsets]
                   + [f"corr_w_{k}" for k in spec.corr_offsets]
                   + [f"corr_tgt_{k}" for k in spec.corr_offsets])
    sched = [cm[n] for n in sched_names]
    has_vsgn = d["vsgn"] is not None
    vsgn = (d["vsgn"] if has_vsgn
            else jnp.zeros_like(d["ok_ref"], dtype=jnp.uint8))
    fn = _shard_map(
        body, mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(AXIS), P(AXIS))
        + (P(AXIS),) * len(sched),
        out_specs=(P(AXIS), P(AXIS)),
        check_rep=(spec.backend != "dma"))
    return fn(u_l, u_lm1, unew_lm1, jnp.asarray(dt), vsgn, d["ok_ref"],
              *sched)


def fold_corrections_explicit(corr, unew_lm1, d: dict,
                              spec: SweepCommSpec):
    """Deterministic owner-fold of precomputed partial-level corrections
    — the P3 leg of :func:`sweep_correct_explicit` alone.

    For solvers whose partial-level sweep cannot run inside the
    shard_map (the MHD CT sweep carries staggered faces and child-EMF
    overrides the hydro schedule knows nothing about), the sweep stays
    global-view but the coarse fold still must not be a GSPMD scatter-
    add: the partitioner turns ``unew.at[idx].add`` over shard-crossing
    indices into an all-gathered scatter whose fold order is
    unspecified.  This reuses the same reverse schedule — own entries
    first, then ring offsets ascending — so the fold is bitwise
    reproducible and identical across halo backends.

    ``corr`` is the level-l ``[noct_pad, ndim, 2, nvar]`` correction
    block (row-sharded like u_l); the schedule's weights already carry
    ``±1/2^ndim`` and the validity mask, making this a drop-in for
    ``K.scatter_corrections(unew_lm1, corr, corr_idx, cfg)``."""
    mesh = spec.mesh
    ndev = mesh.shape[AXIS]
    cm = d["comm"]

    def body(c_loc, unew_loc, *sched):
        it = iter(sched)
        own_src, own_tgt, own_w = (next(it)[0], next(it)[0],
                                   next(it)[0])
        corr_send = {k: next(it)[0] for k in spec.corr_offsets}
        corr_w = {k: next(it)[0] for k in spec.corr_offsets}
        corr_tgt = {k: next(it)[0] for k in spec.corr_offsets}
        cflat = c_loc.reshape(-1, c_loc.shape[-1])
        unew_loc = unew_loc.at[own_tgt].add(
            (cflat[own_src] * own_w[:, None]).astype(unew_loc.dtype))
        if spec.corr_offsets:
            gots = dma_halo.exchange_slabs(
                [cflat[corr_send[k]] * corr_w[k][:, None]
                 for k in spec.corr_offsets],
                [_perm(ndev, k) for k in spec.corr_offsets],
                AXIS, backend=spec.backend)
            for k, got in zip(spec.corr_offsets, gots):
                unew_loc = unew_loc.at[corr_tgt[k]].add(
                    got.astype(unew_loc.dtype))
        return unew_loc

    sched_names = (["own_src", "own_tgt", "own_w"]
                   + [f"corr_send_{k}" for k in spec.corr_offsets]
                   + [f"corr_w_{k}" for k in spec.corr_offsets]
                   + [f"corr_tgt_{k}" for k in spec.corr_offsets])
    sched = [cm[n] for n in sched_names]
    fn = _shard_map(
        body, mesh,
        in_specs=(P(AXIS), P(AXIS)) + (P(AXIS),) * len(sched),
        out_specs=P(AXIS),
        check_rep=(spec.backend != "dma"))
    return fn(corr, unew_lm1, *sched)
