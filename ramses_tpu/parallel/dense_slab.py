"""Explicit slab-sharded dense sweep for COMPLETE levels.

The global-view :func:`ramses_tpu.amr.kernels.dense_sweep` hands the
flat↔dense bit-permutation transpose to XLA's SPMD partitioner; on a
multi-chip mesh the partitioner cannot follow the bit-interleaved
reshape and falls back to "involuntary full rematerialization" — the
whole base grid is gathered to every chip and re-split each coarse
step (MULTICHIP_r05 tail).  This module is the EXPLICIT formulation:
the complete level's row batch stays sharded ``P("oct")`` exactly as
it already is, and a ``shard_map`` body does per device

1. a SHARD-LOCAL bit-permutation (:func:`ramses_tpu.amr.bitperm.
   flat_to_dense_slab`): a contiguous flat row chunk IS an axis-aligned
   dense sub-box (the top ``log2(ndev)`` flat bits are the most
   significant coordinate bits, z-major), so each chip converts only
   the rows it owns — no cross-chip gather exists;
2. a ring halo exchange per cut axis through the backend-dispatched
   engine (:mod:`ramses_tpu.parallel.dma_halo`): Pallas async
   remote-copy DMA on TPU, ``lax.ppermute`` elsewhere — sequenced
   axis-by-axis over the progressively extended block so corner ghosts
   fill with their true global values; uncut axes wrap locally;
3. the unchanged padded-interior kernel
   (:func:`ramses_tpu.amr.kernels.dense_interior_update`) on the local
   box — per-cell arithmetic identical to the global path, so mesh-of-1
   and mesh-of-N agree BITWISE (asserted in tests/test_dense_slab.py).
   On the DMA backend the update is split into an interior region that
   consumes NO ghost data (computed while the DMA is in flight) and
   ``NGHOST``-thin boundary strips finished after the receive
   semaphores — per-cell purity makes the split bitwise-invisible;
4. the inverse shard-local bit-permutation back to flat rows.

The MHD constrained-transport advance gets the same treatment
(:func:`mhd_ct_slab`): shard-local bitperm of cells AND staggered
faces, depth-2/3 halos, the shared padded CT pipeline
(:func:`ramses_tpu.mhd.uniform.step_padded` or its Pallas kernel,
:mod:`ramses_tpu.mhd.pallas_ct`) on the local box, and a depth-1
exchange of the new low faces to rebuild the high-face slots — the
coarse-fine EMF override arrives as flat-row scatters built OUTSIDE
the shard_map (``mhd/amr.py`` ``emf_flat_idx``), so no global index
scatter survives on the multi-chip path.

Geometry: the cut degenerates to z-slabs for 2 devices, (z, y) pencils
for 4, and octants for 8 — always aligned with oct boundaries.  Scope:
fully periodic cubic power-of-two levels with unpadded row batches and
a power-of-two device count; everything else falls back to the
global-view sweep (kept bitwise-pinned as the single-device reference).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ramses_tpu.amr import bitperm
from ramses_tpu.hydro import muscl
from ramses_tpu.parallel import dma_halo
from ramses_tpu.parallel.mesh import OCT_AXIS
from ramses_tpu.telemetry.hlo import phase


class SlabSpec(NamedTuple):
    """Static (hashable) description of one complete level's slab
    decomposition — rides inside ``FusedSpec`` as part of the jit key."""
    lvl: int
    ndim: int
    mbits: int             # log2(ndev): top flat bits = device index
    mesh: Mesh             # the 1-D "oct" mesh the rows shard over
    grid: Tuple[int, ...]  # device grid extent per axis (prod = ndev)
    loc: Tuple[int, ...]   # local dense sub-box shape per device
    # per-axis ring schedules ((fwd, bwd) pairs of (src, dst) tuples)
    # for cut axes; None = uncut (local periodic wrap)
    perms: tuple
    # resolved halo backend ("dma" | "ppermute") — dma_halo dispatch
    backend: str = "ppermute"


def max_slab_devices(lvl: int, ndim: int) -> int:
    """Largest power-of-two device count a complete level at ``lvl``
    can shard over under the eligibility rules of
    :func:`build_slab_spec` (``mbits <= ndim*(lvl-1)``, which also
    keeps every local extent >= the MUSCL stencil halo).  The job-level
    scheduler (ensemble/meshplan) uses this as the ``max_shards`` stamp
    for mesh-wide AMR namelists."""
    return 1 << max(0, ndim * (lvl - 1))


def build_slab_spec(mesh: Mesh, lvl: int, ndim: int,
                    shape: Tuple[int, ...], ncell_pad: int,
                    bc_kinds, halo_backend: str = "auto"
                    ) -> Optional[SlabSpec]:
    """SlabSpec for a complete level, or None when the level must keep
    the global-view path (non-periodic, non-cubic, padded rows, or a
    non-power-of-two / single-device mesh).  ``halo_backend``: the
    ``&AMR_PARAMS`` knob, resolved here via
    :func:`ramses_tpu.parallel.dma_halo.resolve_backend`."""
    if tuple(mesh.axis_names) != (OCT_AXIS,):
        return None
    ndev = int(mesh.devices.size)
    if ndev <= 1 or ndev & (ndev - 1):
        return None
    if tuple(shape) != (1 << lvl,) * ndim:
        return None
    ncell = (1 << lvl) ** ndim
    if ncell_pad != ncell:
        return None
    mbits = ndev.bit_length() - 1
    if mbits > ndim * (lvl - 1):
        return None
    if any(k != 0 for lohi in bc_kinds for k in lohi):
        return None                                   # periodic only
    gb = bitperm.grid_bits(lvl, ndim, mbits)
    grid = tuple(1 << b for b in gb)
    loc = bitperm.slab_shape(lvl, ndim, mbits)
    if any(loc[d] < muscl.NGHOST for d in range(ndim)):
        return None                                   # shard < stencil
    coords = bitperm.chunk_coords(lvl, ndim, mbits)
    dev_of = {g: D for D, g in enumerate(coords)}
    perms = []
    for d in range(ndim):
        if grid[d] == 1:
            perms.append(None)
            continue
        fwd = []
        bwd = []
        for D, g in enumerate(coords):
            up = list(g)
            dn = list(g)
            up[d] = (g[d] + 1) % grid[d]
            dn[d] = (g[d] - 1) % grid[d]
            fwd.append((D, dev_of[tuple(up)]))
            bwd.append((D, dev_of[tuple(dn)]))
        perms.append((tuple(fwd), tuple(bwd)))
    return SlabSpec(lvl=lvl, ndim=ndim, mbits=mbits, mesh=mesh,
                    grid=grid, loc=loc, perms=tuple(perms),
                    backend=dma_halo.resolve_backend(halo_backend))


def _take(a, ax: int, sl: slice):
    idx = [slice(None)] * a.ndim
    idx[ax] = sl
    return a[tuple(idx)]


def _sm(spec: SlabSpec, body, in_specs, out_specs, use_pallas=False):
    """shard_map with replication checking off whenever the body holds
    a pallas_call (DMA halos or the CT kernel)."""
    return jax.shard_map(
        body, mesh=spec.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=(spec.backend != "dma" and not use_pallas))


def halo_extend(a, spec: SlabSpec, ng: int, spatial0: int,
                axes=None):
    """Extend the local dense block by ``ng`` ghost cells on every
    spatial axis (axes ``spatial0 .. spatial0+ndim-1``): ring exchange
    (DMA or ppermute per ``spec.backend``) on cut axes, local periodic
    wrap on uncut ones.  Later axes exchange the already-extended
    block, so corner ghosts carry their exact global-periodic values.
    ``axes``: optional subset of the original spatial axes to extend
    (the pallas shard path leaves its lane axis bare for the in-kernel
    periodic roll; the DMA overlap split defers its cut axis)."""
    for d in range(spec.ndim):
        if axes is not None and d not in axes:
            continue
        ax = spatial0 + d
        if spec.perms[d] is None:
            pads = [(0, 0)] * a.ndim
            pads[ax] = (ng, ng)
            a = jnp.pad(a, pads, mode="wrap")
        else:
            fwd, bwd = spec.perms[d]
            lo, hi = dma_halo.exchange_pair(
                _take(a, ax, slice(-ng, None)), _take(a, ax, slice(0, ng)),
                OCT_AXIS, list(fwd), list(bwd), backend=spec.backend)
            a = jnp.concatenate([lo, a, hi], axis=ax)
    return a


def dense_apply_slab(rows, spec: SlabSpec, local_fn, ng: int,
                     out_ndim: Optional[int] = None):
    """Generic slab engine: flat rows → per-shard dense sub-box →
    ``ng``-deep halo extension → ``local_fn(extended) -> [*loc,
    *trailing_out]`` → flat rows.  ``local_fn`` sees the block with the
    spatial axes LEADING (trailing feature axes untouched) and must
    return the un-extended local box.  ``out_ndim``: rank of the
    returned rows array (defaults to the input rank)."""
    nd = spec.ndim

    def body(r_loc):
        with phase("gather"):
            dense = bitperm.flat_to_dense_slab(r_loc, spec.lvl, nd,
                                               spec.mbits)
        with phase("pad"):
            ext = halo_extend(dense, spec, ng, 0)
        out = local_fn(ext)
        with phase("scatter"):
            return bitperm.dense_to_flat_slab(out, spec.lvl, nd,
                                              spec.mbits)

    in_spec = P(OCT_AXIS, *([None] * (rows.ndim - 1)))
    out_rank = out_ndim if out_ndim is not None else rows.ndim
    out_spec = P(OCT_AXIS, *([None] * (out_rank - 1)))
    return _sm(spec, body, (in_spec,), out_spec)(rows)


def _split_axis(spec: SlabSpec, ng: int) -> Optional[int]:
    """Cut axis for the DMA comm/compute overlap split, or None when
    the split does not apply.  The LAST cut axis is chosen because its
    exchange comes last in :func:`halo_extend`'s sequencing — deferring
    it (while the other axes extend first) reproduces the exact corner
    values of the unsplit pipeline."""
    if spec.backend != "dma":
        return None
    cut = [d for d in range(spec.ndim) if spec.perms[d] is not None]
    if not cut:
        return None
    d = cut[-1]
    return d if spec.loc[d] > 2 * ng else None


def dense_sweep_slab(u_flat, ok_flat, dt, dx: float, spec: SlabSpec,
                     cfg, ret_flux: bool = False):
    """Slab-sharded complete-level hydro sweep — the shard_map
    formulation of :func:`ramses_tpu.amr.kernels.dense_sweep` (same
    physics, bitwise-identical du/phi).  ``ok_flat``: flat-row refined
    mask or None; ``dt`` traced scalar.  Returns du rows (+ phi rows
    when ``ret_flux``), sharded like the input.

    On the DMA backend the update is region-split for comm/compute
    overlap: the boundary slabs of the deferred cut axis start their
    async remote copy, the interior band (which reads no ghost data of
    that axis) is computed while the transfer is in flight, and two
    ``NGHOST``-thin strips are finished from the received ghosts.
    :func:`ramses_tpu.amr.kernels.dense_interior_update` is pure
    per-cell arithmetic, so the split output is bitwise identical to
    the unsplit (and to the ppermute) formulation."""
    from ramses_tpu.amr import kernels as K
    from ramses_tpu.hydro import pallas_muscl as pk

    nd = spec.ndim
    ng = muscl.NGHOST
    masked = ok_flat is not None
    # per-shard fused TPU kernel: relabel an uncut %128 axis to the
    # kernel lane role; None (e.g. every CPU run, or all axes cut)
    # takes the shared XLA interior update
    cut = tuple(p is not None for p in spec.perms)
    kaxes = (pk.shard_axes(cfg, spec.loc, cut, u_flat.dtype)
             if nd == 3 else None)
    dsp = _split_axis(spec, ng) if kaxes is None else None
    if dsp is not None:
        dma_halo.TRAFFIC["overlap_frac"] = (
            (spec.loc[dsp] - 2 * ng) / spec.loc[dsp])

    def _update(up, okp, dt_, shape):
        with phase("kernel"):
            return K.dense_interior_update(up, okp, dt_, dx, shape, cfg,
                                           ret_flux=ret_flux)

    def body(u_loc, ok_loc, dt_):
        ext = None if kaxes is None else kaxes[:2]
        if dsp is not None:
            ext = tuple(d for d in range(nd) if d != dsp)
        with phase("gather"):
            ud = jnp.moveaxis(bitperm.flat_to_dense_slab(
                u_loc, spec.lvl, nd, spec.mbits), -1, 0)
            if masked:
                # convert on the flat rows (clean shard-local op), halo
                # the arithmetic mask exactly like the state
                okd = bitperm.flat_to_dense_slab(
                    ok_loc.astype(u_loc.dtype), spec.lvl, nd, spec.mbits)
        with phase("pad"):
            up = halo_extend(ud, spec, ng, 1, axes=ext)
            okp = (halo_extend(okd, spec, ng, 0, axes=ext) if masked
                   else None)
        if kaxes is not None:
            with phase("kernel"):
                out = pk.fused_step_shard(up, okp, dt_, cfg, dx, spec.loc,
                                          kaxes, want_flux=ret_flux)
        elif dsp is not None:
            # overlap split: start the DMA of the deferred axis' slabs,
            # compute the ghost-free interior band meanwhile, finish
            # the two boundary strips from the received ghosts
            fwd, bwd = spec.perms[dsp]
            ax = 1 + dsp
            sends = [_take(up, ax, slice(-ng, None)),
                     _take(up, ax, slice(0, ng))]
            perms = [list(fwd), list(bwd)]
            if masked:
                sends += [_take(okp, dsp, slice(-ng, None)),
                          _take(okp, dsp, slice(0, ng))]
                perms += [list(fwd), list(bwd)]
            with phase("pad"):
                ghosts = dma_halo.exchange_slabs(sends, perms, OCT_AXIS,
                                                 backend=spec.backend)
            shape_int = tuple(spec.loc[d] - (2 * ng if d == dsp else 0)
                              for d in range(nd))
            shape_strip = tuple(ng if d == dsp else spec.loc[d]
                                for d in range(nd))
            out_int = _update(up, okp, dt_, shape_int)
            lo_u = jnp.concatenate(
                [ghosts[0], _take(up, ax, slice(0, 2 * ng))], axis=ax)
            hi_u = jnp.concatenate(
                [_take(up, ax, slice(-2 * ng, None)), ghosts[1]], axis=ax)
            lo_ok = hi_ok = None
            if masked:
                lo_ok = jnp.concatenate(
                    [ghosts[2], _take(okp, dsp, slice(0, 2 * ng))],
                    axis=dsp)
                hi_ok = jnp.concatenate(
                    [_take(okp, dsp, slice(-2 * ng, None)), ghosts[3]],
                    axis=dsp)
            out_lo = _update(lo_u, lo_ok, dt_, shape_strip)
            out_hi = _update(hi_u, hi_ok, dt_, shape_strip)
            if ret_flux:
                out = (jnp.concatenate(
                           [out_lo[0], out_int[0], out_hi[0]], axis=ax),
                       jnp.concatenate(
                           [out_lo[1], out_int[1], out_hi[1]], axis=dsp))
            else:
                out = jnp.concatenate([out_lo, out_int, out_hi], axis=ax)
        else:
            out = _update(up, okp, dt_, spec.loc)
        du = out[0] if ret_flux else out
        with phase("scatter"):
            du_rows = bitperm.dense_to_flat_slab(
                jnp.moveaxis(du, 0, -1), spec.lvl, nd, spec.mbits)
            if not ret_flux:
                return du_rows
            phi_rows = bitperm.dense_to_flat_slab(out[1], spec.lvl, nd,
                                                  spec.mbits)
            return du_rows, phi_rows

    ok_in = P(OCT_AXIS) if masked else P()
    out_specs = ((P(OCT_AXIS, None), P(OCT_AXIS, None, None))
                 if ret_flux else P(OCT_AXIS, None))
    if not masked:
        # shard_map needs a concrete operand for every spec slot
        ok_flat = jnp.zeros((), u_flat.dtype)
    return _sm(spec, body, (P(OCT_AXIS, None), ok_in, P()),
               out_specs)(u_flat, ok_flat, dt)


def dense_flags_slab(u_flat, spec: SlabSpec, flags_fn, twotondim: int):
    """Slab-sharded complete-level refinement flags: ``flags_fn`` maps
    the 1-ghost-extended local block ``[nvar, *loc+2]`` to a bool grid
    of the same spatial shape (the shared ``_grad_flags`` family); the
    interior is sliced here.  Returns ``[noct, 2^ndim]`` flags rows."""
    nd = spec.ndim

    def local_fn(dense_ext):
        with phase("criteria"):
            ok = flags_fn(jnp.moveaxis(dense_ext, -1, 0))
        with phase("scatter"):
            return ok[tuple(slice(1, -1) for _ in range(nd))]

    flags = dense_apply_slab(u_flat, spec, local_fn, ng=1, out_ndim=1)
    return flags.reshape(flags.shape[0] // twotondim, twotondim)


# ----------------------------------------------------------------------
# slab-sharded MHD constrained transport
# ----------------------------------------------------------------------
def mhd_slab_ok(spec: Optional[SlabSpec]) -> bool:
    """The CT advance needs face halos one deeper than the hydro
    stencil (``ng+1 = 3``), so every local extent must cover them."""
    from ramses_tpu.mhd import uniform as mu
    return (spec is not None
            and min(spec.loc) >= mu.NGHOST + 1)


def mhd_ct_slab(u_flat, bf_flat, dt, dx: float, spec: SlabSpec, cfg,
                ok_flat=None, ovr_flat=None):
    """Slab-sharded complete-level CT advance — the explicit
    formulation of the ``mu.step`` global-view branch of
    ``mhd/amr.py`` ``_mhd_advance_traced`` (same per-cell pipeline,
    bitwise-identical du / faces).

    ``u_flat`` [ncell, nvar] cell conservative rows; ``bf_flat``
    [ncell, NCOMP, 2] staggered (lo, hi) face rows; ``ok_flat``
    optional flat-row refined mask; ``ovr_flat`` optional coarse-fine
    EMF override as ``(msk_rows, val_rows)`` — BOTH ``[ncell, npairs]``
    flat-row arrays (mask in the state dtype), scattered OUTSIDE this
    call from the Morton-interleaved ``emf_flat_idx`` map so the
    shard_map body sees only row-sharded operands.  Returns
    ``(du_rows [ncell, nvar], b_rows [ncell, NCOMP, 2])``.

    High faces are rebuilt from the new low faces with a depth-1 ring
    exchange (the slab analogue of the global path's periodic
    ``jnp.roll`` in ``_dense_hi``)."""
    from ramses_tpu.mhd import pallas_ct
    from ramses_tpu.mhd import uniform as mu
    from ramses_tpu.mhd.core import NCOMP

    nd = spec.ndim
    ng = mu.NGHOST
    pairs = [(d1, d2) for d1 in range(nd) for d2 in range(d1 + 1, nd)]
    masked = ok_flat is not None
    has_ovr = ovr_flat is not None
    use_kernel = pallas_ct.slab_available(cfg, spec.loc, u_flat.dtype)

    def ftds(rows):
        return bitperm.flat_to_dense_slab(rows, spec.lvl, nd, spec.mbits)

    def dtfs(dense):
        return bitperm.dense_to_flat_slab(dense, spec.lvl, nd, spec.mbits)

    def body(u_loc, bf_loc, ok_loc, om_loc, ov_loc, dt_):
        up0 = jnp.moveaxis(ftds(u_loc), -1, 0)           # [nvar, *loc]
        bld = ftds(bf_loc)                               # [*loc, NCOMP, 2]
        bfd = jnp.stack([bld[..., c, 0] for c in range(NCOMP)])
        up = halo_extend(up0, spec, ng, 1)
        # faces get one extra ghost layer (the cell-centred average
        # must be valid in every padded cell — mu.step's contract)
        bf_ext = halo_extend(bfd, spec, ng + 1, 1)
        okp = None
        if masked:
            okd = ftds(ok_loc.astype(u_loc.dtype))
            okp = halo_extend(okd, spec, ng, 0)
        ovr = None
        if has_ovr:
            omp = halo_extend(jnp.moveaxis(ftds(om_loc), -1, 0),
                              spec, ng, 1)               # [npairs, *loc+2ng]
            ovp = halo_extend(jnp.moveaxis(ftds(ov_loc), -1, 0),
                              spec, ng, 1)
            ovr = {pair: (omp[pi] > 0.5, ovp[pi])
                   for pi, pair in enumerate(pairs)}
        if use_kernel:
            un_p, bfn_p = pallas_ct.ct_step_slab(
                up, bf_ext, dt_, (dx,) * nd, cfg,
                okp=okp, ovr=ovr,
                interpret=pallas_ct.interpret_mode())
        else:
            un_p, bfn_p = mu.step_padded(
                cfg, (dx,) * nd, up, bf_ext, dt_,
                okp=None if okp is None else okp > 0.5, ovr=ovr)
        du = mu._unpad(un_p, nd) - up0
        bfn_lo = [mu._unpad(b, nd) for b in bfn_p]       # each [*loc]
        # high faces: the next cell's low face.  Within the block a
        # shift; the top plane comes from the +1 neighbour via a
        # depth-1 exchange (global path: periodic jnp.roll in
        # _dense_hi) — uncut axes wrap locally, identical by
        # periodicity.
        hi = [None] * NCOMP
        if nd:
            ext1 = halo_extend(jnp.stack(bfn_lo[:nd]), spec, 1, 1)
            for c in range(nd):
                idx = [slice(None)] * nd
                for d in range(nd):
                    idx[d] = slice(2, None) if d == c else slice(1, -1)
                hi[c] = ext1[c][tuple(idx)]
        for c in range(nd, NCOMP):
            hi[c] = bfn_lo[c]                # degenerate: hi == lo
        comps = jnp.stack([jnp.stack([bfn_lo[c], hi[c]], axis=-1)
                           for c in range(NCOMP)], axis=-2)
        return (dtfs(jnp.moveaxis(du, 0, -1)), dtfs(comps))

    ok_in = P(OCT_AXIS) if masked else P()
    ov_in = P(OCT_AXIS, None) if has_ovr else P()
    if not masked:
        ok_flat = jnp.zeros((), u_flat.dtype)
    if has_ovr:
        om_rows, ov_rows = ovr_flat
    else:
        om_rows = ov_rows = jnp.zeros((), u_flat.dtype)
    return _sm(spec, body,
               (P(OCT_AXIS, None), P(OCT_AXIS, None, None), ok_in,
                ov_in, ov_in, P()),
               (P(OCT_AXIS, None), P(OCT_AXIS, None, None)),
               use_pallas=use_kernel)(
        u_flat, bf_flat, ok_flat, om_rows, ov_rows, dt)
