"""AMR simulation driver: recursive subcycled level stepping.

The host-side recursion of ``amr_step`` (``amr/amr_step.f90:1-586``) with
the hydro-only operation order preserved:

    set_unew(l) → recurse(l+1) ×2 → godunov(l) [+ coarse corrections]
    → set_uold(l) → upload_fine(l)

Timestep policy: one CFL evaluation per coarse step,
``dt = min_l courant(l) · 2^(l-levelmin)``, then exact factor-2 subcycling
(the reference's per-level adaptive ``dtnew``/``dtold`` bookkeeping,
``amr/update_time.f90``, is replaced by this stricter-but-simpler global
choice — fine dts are exact halves, so the flux-correction weights of
``godfine1`` are exact).  Refinement runs at coarse-step boundaries
(the reference refines every level substep; coarse-step granularity is the
standard regrid-interval relaxation).
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from functools import lru_cache, partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.amr import flag as flagmod
from ramses_tpu.amr import kernels as K
from ramses_tpu.amr import maps as mapmod
from ramses_tpu.amr.tree import Octree, cell_offsets
from ramses_tpu.config import Params
from ramses_tpu.grid import boundary as bmod
from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.init import regions
from ramses_tpu.telemetry import make_telemetry, sim_run_info
from ramses_tpu.telemetry import hlo as _hlo
from ramses_tpu.telemetry import screen as telemetry_screen
from ramses_tpu.telemetry.hlo import phase
from ramses_tpu.utils.timers import NullTimers, Timers


class _Cfg1:
    """Minimal cfg shim for interp_cells on a single-column array."""

    def __init__(self, ndim: int):
        self.ndim = ndim


def _sample_dense_periodic(dense: np.ndarray, x01: np.ndarray) -> np.ndarray:
    """Periodic multilinear interpolation of a cell-centred dense field
    ``[nvar, n, n, …]`` at unit-box positions ``x01 [npts, ndim]`` —
    used to seed refined levels from base-resolution IC grids."""
    nvar = dense.shape[0]
    nd = x01.shape[1]
    n = dense.shape[1]
    g = x01 * n - 0.5
    i0 = np.floor(g).astype(np.int64)
    w1 = g - i0
    out = np.zeros((nvar, len(x01)))
    for corner in range(1 << nd):
        idx = []
        w = np.ones(len(x01))
        for d in range(nd):
            bit = (corner >> d) & 1
            idx.append(np.mod(i0[:, d] + bit, n))
            w = w * (w1[:, d] if bit else 1.0 - w1[:, d])
        out += dense[(slice(None),) + tuple(idx)] * w
    return out


class FusedSpec(NamedTuple):
    """Static description of one coarse step's level structure — the jit
    cache key for :func:`_fused_coarse_step` (hashable; re-derived per
    regrid, identical across steady-state steps)."""
    cfg: HydroStatic
    bspec: bmod.BoundarySpec
    lmin: int
    boxlen: float
    levels: tuple          # populated levels, ascending
    complete: tuple        # per-level bool
    gravity: bool
    itype: int
    # coarse root-cell counts per dim (nx, ny, nz); level-l dense
    # shape is root[d]·2^l (all-ones = the single-cube default)
    root: tuple = ()
    # static cooling config; None disables the in-step cooling source
    # (``cooling_fine`` after ``godunov_fine``, amr/amr_step.f90:448-474)
    cool: Optional[object] = None
    # capture per-cell face mass fluxes for the MC gas tracers
    # (godunov_fine.f90:685-715); hydro single-device path only
    want_flux: bool = False
    # per-level slab decomposition (parallel/dense_slab.SlabSpec or
    # None) for COMPLETE levels on a multi-chip mesh; empty tuple =
    # global-view dense sweep everywhere (the single-device default)
    slab: tuple = ()
    # per-level bool: partial level runs the gather-fused blocked tile
    # sweep (Morton-aligned oct tiles, amr/maps.build_block_maps)
    # instead of the 6^d stencil gather; empty tuple = never
    blocked: tuple = ()
    # octs per tile side = 2**block_shift for the blocked levels
    block_shift: int = 2
    # devices the level rows span (the SIMULATION's mesh, not the
    # host's device count): the Pallas gates take it
    ndev: int = 1

    @property
    def pallas_tiles(self) -> bool:
        """Allow the Pallas tile kernel inside K.tile_sweep: a tree on
        one device; row-sharded trees force the XLA tile formulation so
        GSPMD can partition the sweep."""
        return self.ndev == 1


def _advance_traced(u, dev, fg, dt, spec: FusedSpec, cool_tables=None):
    """One ENTIRE coarse step (recursive subcycled ``amr_step``) traced
    as straight-line XLA.

    The host recursion of the round-1 driver dispatched ~15 device calls
    per step; each call costs host dispatch latency, which dominated
    the AMR profile.  Tracing the recursion turns a
    coarse step into ONE program; recompiles happen only when the
    bucketed level structure changes (the jit key is ``spec`` + shapes).
    """
    cfg = spec.cfg
    u = dict(u)
    unew = dict(u)
    levels = spec.levels
    # MC-tracer flux capture: per-level [ncell, ndim, 2] signed face
    # mass fluxes, accumulated over every substep of the coarse step
    phi = ({l: jnp.zeros((u[l].shape[0], cfg.ndim, 2), u[l].dtype)
            for l in levels} if spec.want_flux else None)

    def advance(i, dtl):
        from ramses_tpu.poisson.amr_solve import kick_flat

        l = levels[i]
        d = dev[l]
        if spec.gravity:
            with phase("source", l):
                u[l] = kick_flat(u[l], fg[l], 0.5 * dtl, cfg.ndim,
                                 cfg.smallr)
        unew[l] = u[l]
        if i + 1 < len(levels):
            advance(i + 1, 0.5 * dtl)
            advance(i + 1, 0.5 * dtl)
        with phase("sweep", l):
            du, corr, dphi = K.sweep_level(spec, i, u[l], u.get(l - 1), d,
                                           dtl)
            if spec.want_flux:
                phi[l] = phi[l] + dphi
            unew[l] = unew[l] + du
        if corr is not None and l > spec.lmin:
            with phase("fluxcorr", l):
                unew[l - 1] = K.scatter_corrections(unew[l - 1], corr,
                                                    d["corr_idx"], cfg)
                if spec.want_flux:
                    phi[l - 1] = K.scatter_corr_flux(phi[l - 1], corr,
                                                     d["corr_idx"], cfg)
        u[l] = unew[l]
        if spec.gravity:
            with phase("source", l):
                u[l] = kick_flat(u[l], fg[l], 0.5 * dtl, cfg.ndim,
                                 cfg.smallr)
        if spec.cool is not None:
            # cooling_fine follows godunov_fine at every level substep
            # (amr/amr_step.f90:448-474); pointwise, so the flat cell
            # batch transposes straight into the dense-grid kernel.
            # cool_tables = (tables, [scale_T2, scale_nH, scale_t]) —
            # the scales ride as traced values so cosmological epochs
            # don't recompile the fused program
            from ramses_tpu.hydro.cooling import cooling_step
            tabs, scl = cool_tables
            with phase("source", l):
                u[l] = cooling_step(u[l].T, tabs, spec.cool, dtl, cfg,
                                    scales=scl).T
        if i + 1 < len(levels):
            with phase("restrict", l):
                u[l] = K.restrict_upload(u[l], u[levels[i + 1]],
                                         d["ref_cell"], d["son_oct"], cfg)

    advance(0, dt)
    return (u, phi) if spec.want_flux else (u, None)


def _courant_traced(u, dev, spec: FusedSpec, fg=None):
    """All levels' CFL dts, [nlevel] coarse-step equivalents (already
    scaled by the exact factor-2 subcycle count).  ``fg`` enables the
    gravity-strength correction (one solve stale, like the reference's
    ``courant_fine`` reading the last force)."""
    cfg = spec.cfg
    dts = []
    for i, l in enumerate(spec.levels):
        dt_l = K.level_courant(u[l], dev[l]["valid_cell"],
                               spec.boxlen / (1 << l), cfg,
                               fg.get(l) if fg else None)
        dts.append(dt_l * (2.0 ** (l - spec.lmin)))
    return jnp.stack(dts)


@partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _fused_coarse_step(u, dev, fg, dt, spec: FusedSpec, cool_tables=None):
    """One coarse step + the NEXT step's Courant dt, one dispatch.

    The state dict ``u`` is DONATED: the output state aliases the input
    buffers, so the dense base level exists once in HBM instead of
    twice.  Callers must rebind their reference to the returned state
    (``sim.u = out[0]``) — the argument arrays die with the call.

    Returning dt(u^{n+1}) from the same program is the reference's
    ``dtnew`` bookkeeping (``amr/update_time.f90``): the next coarse
    step starts without a host round-trip to evaluate CFL.

    With ``spec.want_flux`` the result carries a third element: the
    per-level MC-tracer flux capture dict.
    """
    u, phi = _advance_traced(u, dev, fg, dt, spec, cool_tables)
    with phase("courant"):
        dtn = jnp.min(_courant_traced(u, dev, spec,
                                      fg if spec.gravity else None))
    return (u, dtn, phi) if spec.want_flux else (u, dtn)


@partial(jax.jit, static_argnames=("spec",))
def _fused_courant(u, dev, spec: FusedSpec, fg=None):
    with phase("courant"):
        return _courant_traced(u, dev, spec, fg)


@partial(jax.jit, static_argnames=("ncell_pad", "cfg", "itype"))
def _migrate_level(old_u, u_coarse, rows_d, rows_s, cell_rep, nb_rep,
                   sgn_rep, rows_new, ncell_pad: int, cfg, itype: int):
    """Device-side regrid migration of one level: copy surviving cells
    from the old batch, interpolate brand-new octs from the (already
    migrated) coarser level (``make_grid_fine``,
    ``amr/refine_utils.f90:590``).  All index arrays are bucket-padded
    with out-of-range targets so jit shapes stay stable; the scatter
    drops them."""
    with phase("migrate: copy"):
        buf = jnp.zeros((ncell_pad, old_u.shape[1]), old_u.dtype)
        buf = buf.at[rows_d].set(old_u[rows_s], mode="drop")
    with phase("migrate: interp"):
        vals = K.interp_cells(u_coarse, cell_rep, nb_rep, sgn_rep, cfg,
                              itype=itype)
        return buf.at[rows_new].set(vals.astype(buf.dtype), mode="drop")


@lru_cache(maxsize=None)
def _mig_consts(ndim: int):
    """Per-ndim constant migrate tables (child offsets, ±1 prolongation
    signs, intra-oct arange) — built once instead of on every regrid."""
    offs = cell_offsets(ndim)
    return offs, (offs * 2 - 1).astype(np.float64), np.arange(1 << ndim)


@partial(jax.jit, static_argnames=("ttd",))
def _pack_flag_bits(flags, ttd: int):
    """Bitpack per-oct refinement flags ([n, 2^d] bool each) into one
    uint8 per oct (bit j = cell j), so the regrid flag fetch moves 2^d×
    fewer bytes over the device-to-host link and the host decodes only
    the non-zero ones (``flag.flagged_cells``)."""
    shifts = jnp.arange(ttd, dtype=jnp.uint32)
    return tuple((fl.astype(jnp.uint32) << shifts[None, :])
                 .sum(axis=1).astype(jnp.uint8) for fl in flags)


@partial(jax.jit, static_argnames=("spec", "eg", "fls", "itype"))
def _fused_flags(u, dev, spec: FusedSpec, eg, fls, itype: int):
    """Every level's gradient refinement criteria in ONE dispatch (the
    per-level ``hydro_refine`` kernels of ``flag_fine``); the host
    fetches the whole tuple with a single device round-trip."""
    flags = []
    for i, l in enumerate(spec.levels):
        with phase("flags", l):
            flags.append(K.flags_level(spec, i, u[l], u.get(l - 1), dev[l],
                                       eg, fls, itype))
    return tuple(flags)


@partial(jax.jit, static_argnames=("spec", "nsteps", "trace"),
         donate_argnums=(0,))
def _fused_multi_step(u, dev, t, tend, dt0, spec: FusedSpec, nsteps: int,
                      cool_tables=None, trace: bool = False):
    """``nsteps`` hydro-only coarse steps as ONE device program
    (``lax.scan``), zero host round-trips between steps.

    ``u`` is donated (the scan carry aliases the input buffers — one
    copy of the dense base level in HBM); callers rebind to the
    returned state.

    Steps past ``tend`` become no-ops (the ``run_steps`` active-flag
    pattern).  Only valid while the tree is frozen — callers chunk by
    the regrid interval.  Returns (u, t, dt_next, n_done); with
    ``trace=True`` (telemetry-instrumented runs) the scan also stacks
    per-step ``(t_after, dt)`` so one summary fetch yields exact
    per-coarse-step records without leaving the fused fast path.
    """
    def body(carry, _):
        u, t, dtc, ndone = carry
        dt = jnp.minimum(dtc, jnp.maximum(tend - t, 0.0))
        active = t < tend
        # state dtype for the step (t/dt may carry f64 on x64 hosts)
        sdt = jnp.where(active, dt, 0.0).astype(u[spec.lmin].dtype)
        un, dtn = _fused_coarse_step(u, dev, {}, sdt, spec, cool_tables)
        u = {l: jnp.where(active, un[l], u[l]) for l in u}
        t = jnp.where(active, t + dt, t)
        dtc = jnp.where(active, dtn.astype(dtc.dtype), dtc)
        ndone = ndone + jnp.where(active, 1, 0)
        ys = (t, jnp.where(active, dt, 0.0)) if trace else None
        return (u, t, dtc, ndone), ys

    (u, t, dtc, ndone), hist = jax.lax.scan(
        body, (u, t, dt0, jnp.array(0)), None, length=nsteps)
    if trace:
        return u, t, dtc, ndone, hist
    return u, t, dtc, ndone


def restore_amr_scaffold(cls, params: Params, outdir: str, dtype,
                         to_cons, place_level, **ctor_kw):
    """Shared restart scaffold (the ``nrestart`` path) used by the
    hydro, MHD, and SRHD AMR sims: rebuild the octree from the file
    oct coords, construct the sim on it, place each level's restored
    rows (re-mapped defensively through the rebuilt tree's key order),
    then restrict.  ``to_cons(q_rows)`` converts file output columns
    to the solver's stored rows; ``place_level(sim, l, rows, og,
    order)`` writes them into the sim state; ``ctor_kw`` goes to the
    constructor (the sharded class's ``devices``).  Returns (sim, parts)."""
    from ramses_tpu.io.restart import restore_particles, restore_tree_state
    tree_og, rows_lv, meta, parts = restore_tree_state(
        outdir, None, params.amr.levelmin, to_cons=to_cons)
    root = [params.amr.nx, params.amr.ny, params.amr.nz][:params.ndim]
    tree = Octree(params.ndim, params.amr.levelmin, params.amr.levelmax,
                  root=root)
    for l, og in tree_og.items():
        tree.set_level(l, og)
    ps = None
    tracer_x = None
    tracer_id = None
    if parts:
        from ramses_tpu.pm.particles import (FAM_GAS_TRACER,
                                             lane_headroom)
        from ramses_tpu.pm.sinks import SinkSpec
        from ramses_tpu.pm.star_formation import SfSpec
        # gas tracers ride the part files as massless family-0 rows:
        # split them back out (they are host positions, not lanes)
        fam = parts.get("family")
        if fam is not None and (fam == FAM_GAS_TRACER).any():
            sel = fam == FAM_GAS_TRACER
            dims = "xyz"[:params.ndim]
            tracer_x = np.stack(
                [parts[f"position_{d}"][sel] for d in dims], axis=1)
            tracer_id = (parts["identity"][sel].astype(np.int64)
                         if "identity" in parts else None)
            npart = len(fam)
            parts = {k: (v[~sel] if isinstance(v, np.ndarray)
                         and len(v) == npart else v)
                     for k, v in parts.items()}
        # runs that keep creating particles need free lanes after the
        # restart too (the fresh-start path's npartmax headroom) — but
        # only for solver families whose __init__ keeps SF/sinks live
        grows = (cls._pm_family(cls._make_cfg(params))
                 and (SfSpec.from_params(params).enabled
                      or SinkSpec.from_params(params).enabled))
        if len(parts.get("mass", ())):
            ps = restore_particles(parts, params.ndim,
                                   nmax=lane_headroom(params, grows))
    # restarts never re-seed tracers: the restored population is the
    # truth, INCLUDING the empty one (e.g. every tracer escaped an
    # open box) — resurrecting a fresh population would fabricate
    # trajectories
    sim = cls(params, dtype=dtype, init_tree=tree, particles=ps,
              seed_tracers=False, **ctor_kw)
    if tracer_x is not None:
        sim.tracer_x = tracer_x
        sim.tracer_id = tracer_id
        sim._spec = None               # enable the MC flux capture
    elif bool(getattr(params.run, "tracer", False)) \
            and cls._tracer_physics:
        sim.tracer_x = np.zeros((0, params.ndim))
        sim.tracer_id = np.zeros(0, dtype=np.int64)
        sim._spec = None
    for l, rows in rows_lv.items():
        og = tree_og[l]
        pos = tree.lookup(l, og)
        place_level(sim, l, rows, og, np.argsort(pos))
    sim._restrict_all()
    sim._dt_cache = None
    sim.t = float(meta["t"])
    sim.nstep = int(meta["nstep"])
    if bool(params.run.lightcone) and sim.cosmo is not None:
        # seed the shell chain from the restored epoch so the first
        # post-restart coarse step emits its shell instead of silently
        # dropping it (the lazily-initialized prev-aexp would skip it)
        sim._cone_aexp_prev = sim.aexp_now()
    # the pending closing half-kick of the pre-dump step needs the old
    # coarse dt (KDK: the first post-restart kick is 0.5*(dtold + dt)),
    # and the stored dtnew makes the restart take the SAME next step a
    # continuous run would (its cached CFL dt included the gravity
    # term the fresh sim's empty force field cannot reproduce)
    lm = params.amr.levelmin
    dtold = np.atleast_1d(np.asarray(meta.get("dtold", 0.0)))
    if len(dtold) >= lm:
        sim.dt_old = float(dtold[lm - 1])
    dtnew = np.atleast_1d(np.asarray(meta.get("dtnew", 0.0)))
    if len(dtnew) >= lm and dtnew[lm - 1] > 0.0:
        sim._dt_cache = float(dtnew[lm - 1])
    if ps is not None:
        # new star ids must not collide with restored particles'
        sim._next_star_id = int(np.asarray(ps.idp).max()) + 1
    if sim.gravity:
        # prime the force field and the deposited-density maximum so
        # the first post-restart coarse_dt carries the same free-fall
        # cap (and the PCG the same warm start) a continuous run would
        if sim.pic:
            sim._build_pm()
        sim.solve_gravity()
    return sim, parts


def _place_u_rows(sim, l: int, rows: np.ndarray, og: np.ndarray,
                  order: np.ndarray):
    """Default row placement: cell-state array only (hydro/SRHD)."""
    nvar = sim.cfg.nvar
    ttd = 2 ** sim.cfg.ndim
    out = np.array(sim.u[l])
    out[sim.cell_rows(l)] = rows.reshape(
        len(og), ttd, nvar)[order].reshape(-1, nvar)
    sim.u[l] = sim._place(jnp.asarray(out, dtype=sim.dtype), "cells")


class AmrSim:
    """Adaptive simulation: host octree + per-level device states.

    ``_needs_mig_log``: subclasses carrying extra per-cell state set
    this to retain the regrid migration maps (see ``regrid``).

    ``particles`` (a :class:`~ramses_tpu.pm.particles.ParticleSet`)
    enables the particle-mesh layer on the hierarchy: per-coarse-step
    host-built CIC maps (``pm/amr_pm.py``), deposits into every level's
    Poisson rhs, force gather at each particle's finest covering level,
    and a split-kick KDK matching the uniform stepper's order
    (``amr/amr_step.f90:219-236,268-273,479-486``).
    """

    _needs_mig_log = False
    ndev = 1          # device count of the row sharding (sharded subclass)
    # gather-fused blocked tile sweep on partial levels: the universal
    # default — hydro, MHD (XLA tile formulation), load-balance layouts
    # (tables layout-composed at emission time), and row-sharded meshes
    # all take it.  Attr so a solver family can still opt out wholesale.
    _oct_blocked = True
    # solver families whose state layout differs from the hydro
    # [rho, mom, E, ...] convention opt out of the shared SF/sink passes
    _pm_physics = True
    # families whose kernels handle non-cubic root grids (the MHD/SRHD
    # dense paths still assume one root cube and opt out)
    _noncubic_ok = True
    # velocity tracers only need momentum/density at the hydro column
    # positions — true for hydro AND MHD layouts; SRHD's (D, S) are
    # not coordinate velocities, so RhdAmrSim opts out
    _tracer_physics = True
    # out-of-core residency (amr/offload.py): families whose coarse
    # step is the shared fused hydro window may run it as per-level
    # segments with host-parked inactive levels; MHD drives its own
    # step chain (CT staggered fields) and opts out
    _offload_capable = True

    @staticmethod
    def _make_cfg(params: Params):
        """Static solver cfg — the physics of the hierarchy (subclass
        hook; ``RhdAmrSim`` swaps in :class:`rhd.core.RhdStatic`)."""
        return HydroStatic.from_params(params)

    @classmethod
    def _pm_family(cls, cfg) -> bool:
        """True when SF/sinks/cooling/movie are live for this
        solver family: the Newtonian hydro state layout only (MHD
        carries cell-B, SRHD stores (D,S,tau))."""
        return (getattr(cfg, "physics", "hydro") == "hydro"
                and cls._pm_physics)

    def __init__(self, params: Params, dtype=jnp.float32,
                 init_tree: Optional[Octree] = None,
                 particles=None, init_dense_u=None,
                 seed_tracers: bool = True):
        from ramses_tpu import patch
        patch.maybe_install_from_params(params)
        self.params = params
        self.cfg = self._make_cfg(params)
        self.dtype = dtype
        self.boxlen = float(params.amr.boxlen)
        spec = bmod.BoundarySpec.from_params(params)
        self.bspec = spec
        self.bc_kinds = [(f[0].kind, f[1].kind) for f in spec.faces]
        self.root = tuple(
            int(b) for b in
            [params.amr.nx, params.amr.ny, params.amr.nz][:params.ndim])
        if any(b != 1 for b in self.root):
            # non-cubic coarse grids run the hydro solver family only
            # for now: the PM/RT/physics layers still wrap positions at
            # a scalar boxlen, and the non-hydro state layouts have
            # their own dense paths
            blocked = []
            if getattr(self.cfg, "physics", "hydro") != "hydro" \
                    or not self._noncubic_ok:
                blocked.append(f"{type(self).__name__} solver family")
            for flagname in ("pic", "rt", "tracer", "cosmo",
                             "clumpfind", "mhd"):
                if bool(getattr(params.run, flagname, False)):
                    blocked.append(flagname)
            if (params.raw or {}).get("sf_params"):
                blocked.append("star formation")
            if (params.raw or {}).get("sink_params"):
                blocked.append("sinks")
            if blocked:
                raise NotImplementedError(
                    f"non-cubic coarse grid {self.root} currently "
                    f"supports the plain hydro hierarchy only (got: "
                    f"{', '.join(blocked)})")
        self.lmin = params.amr.levelmin
        self.lmax = params.amr.levelmax
        self.t = 0.0
        self.nstep = 0
        # regrid cadence: the reference re-flags every level substep but
        # amortizes the expensive rebuild (load_balance) every ``nremap``
        # coarse steps (amr/amr_step.f90:100-123); our regrid is the
        # rebuild, so nremap maps onto its interval (>=1).
        self.regrid_interval = max(1, int(getattr(params.run, "nremap", 0)))
        # telemetry recorder (&OUTPUT_PARAMS telemetry=): the shared
        # no-op NULL when off.  Timers follow the same contract — an
        # un-instrumented run makes zero label switches (instrumented
        # passes, e.g. bench.py, install a real Timers explicitly).
        self.telemetry = make_telemetry(params)
        self.timers = Timers() if self.telemetry.enabled else NullTimers()
        # in-run fault recovery (&RUN_PARAMS max_step_retries): None
        # when off — evolve then captures nothing and fetches nothing
        from ramses_tpu.resilience.faultinject import FaultInjector
        from ramses_tpu.resilience.stepguard import StepGuard
        self._sguard = StepGuard.from_params(params,
                                             telemetry=self.telemetry)
        self._fault = FaultInjector.from_params(params)
        # out-of-core residency engine (&AMR_PARAMS offload): None when
        # off — the monolithic fused window then runs bit-for-bit
        # untouched with zero added device fetches
        from ramses_tpu.amr.offload import OffloadEngine
        self._offload = OffloadEngine.from_params(params)
        from ramses_tpu.resilience.watchdog import Watchdog
        self._wd = Watchdog.from_params(params, telemetry=self.telemetry)
        self._guard_snap = None
        # cosmology: supercomoving conformal-time integration
        # (``amr/update_time.f90``; aexp/hexp from the Friedmann tables)
        self.cosmo = None
        if bool(params.run.cosmo):
            from ramses_tpu.pm.cosmology import Cosmology
            self.cosmo = Cosmology.from_params(params)
            self.t = float(self.cosmo.tau_ini)
            if bool(params.run.lightcone):
                # seed the lightcone shell chain at the run's start so
                # the FIRST coarse step emits its shell (restarts
                # re-seed from the restored epoch in
                # restore_amr_scaffold)
                self._cone_aexp_prev = self.cosmo.aexp_ini
        # dense base-grid gas ICs (grafic baryons) sampled per level
        self._init_dense = (np.asarray(init_dense_u)
                            if init_dense_u is not None else None)
        # cooling microphysics inside the fused step (&COOLING_PARAMS)
        self.cool_spec = None
        self.cool_tables = None
        self._cool_aexp = 1.0
        if getattr(params.cooling, "cooling", False) \
                and self._pm_family(self.cfg):
            from ramses_tpu.hydro.cooling import CoolingSpec, build_tables
            from ramses_tpu.units import units as units_fn
            cosmo0 = None
            if bool(params.run.cosmo):
                from ramses_tpu.pm.cosmology import Cosmology
                cosmo0 = Cosmology.from_params(params)
            aexp0 = cosmo0.aexp_ini if cosmo0 is not None else 1.0
            un = units_fn(params, cosmo=cosmo0, aexp=aexp0)
            self.cool_spec = CoolingSpec.from_params(params, un)
            c = params.cooling
            self._cool_aexp = aexp0
            self._cool_scales = jnp.asarray(
                [un.scale_T2, un.scale_nH, un.scale_t])
            self.cool_tables = build_tables(
                aexp=aexp0, J21=float(c.J21), a_spec=float(c.a_spec),
                z_reion=float(c.z_reion),
                haardt_madau=bool(c.haardt_madau))
        # self-gravity (per-level Poisson, SURVEY.md §3.3): periodic
        # boxes solve the zero-mean problem; any non-periodic face
        # switches the base solve to the isolated multipole-Dirichlet
        # path (poisson/isolated.py; boundary_potential.f90)
        self.gravity = bool(params.run.poisson)
        self.grav_periodic = all(k == 0 for pair in self.bc_kinds
                                 for k in pair)
        if self.gravity:
            if not self.grav_periodic and bool(params.run.cosmo):
                raise NotImplementedError(
                    "cosmology requires a periodic box")
            if any(k == 1 for pair in self.bc_kinds for k in pair):
                # mirror walls need image masses, which the isolated
                # multipole solve does not provide — refuse rather than
                # silently drop the image attraction
                raise NotImplementedError(
                    "self-gravity with reflecting walls is unsupported "
                    "(isolated solve covers outflow/inflow boxes)")
            self.fourpi = 4.0 * np.pi
        self.phi: Dict[int, jnp.ndarray] = {}
        self.fg: Dict[int, jnp.ndarray] = {}
        self.poisson_iters: Dict[int, jnp.ndarray] = {}
        self._rho_dev: Dict[int, jnp.ndarray] = {}
        # particle-mesh layer
        self.p = particles
        self.pic = bool(params.run.pic) and particles is not None
        # star formation / feedback / sinks / tracers on the hierarchy
        # (pm/amr_physics.py; coarse-step cadence like the reference's
        # per-level calls folded through the subcycle)
        from ramses_tpu.pm.particles import ParticleSet
        from ramses_tpu.pm.sinks import SinkSet, SinkSpec
        from ramses_tpu.pm.star_formation import SfSpec
        from ramses_tpu.units import units as units_fn
        self.sf_spec = SfSpec.from_params(params)
        self.sink_spec = SinkSpec.from_params(params)
        self.sinks = (SinkSet.empty(params.ndim)
                      if self.sink_spec.enabled else None)
        # stellar objects from sinks (&STELLAR_PARAMS,
        # pm/stellar_particle.f90 + sink_sn_feedback.f90)
        from ramses_tpu.pm.stellar import StellarSet, StellarSpec
        self.stellar_spec = StellarSpec.from_params(params)
        self.stellar = (StellarSet.empty(params.ndim)
                        if (self.stellar_spec.enabled
                            and self.sinks is not None) else None)
        self.tracer_x = None          # optional [ntr, ndim] host array
        self.tracer_id = None         # stable per-tracer ids [ntr]
        # &MOVIE_PARAMS on-the-fly frames (amr/movie.f90); the frame
        # field extraction uses Newtonian hydro relations, so non-hydro
        # state layouts (MHD cell-B, SRHD (D,S,τ)) refuse loudly rather
        # than render physically wrong maps
        self.movie, self.movie_imov = None, 0
        if self._pm_family(self.cfg):
            from ramses_tpu.io.movie import MovieWriter
            self.movie, self.movie_imov = MovieWriter.from_params(params)
        elif (params.raw or {}).get("movie_params", {}).get("movie"):
            import warnings
            warnings.warn("&MOVIE_PARAMS is only wired for the hydro "
                          "solver family; no frames will be written")
        self._sf_rng = np.random.default_rng(1234)
        self._tracer_rng = np.random.default_rng(20481)
        self._tracer_phi = None        # MC flux capture of the last step
        self._next_star_id = 1
        if not self._pm_family(self.cfg):
            self.sf_spec = SfSpec(enabled=False)
            self.sinks = None
        self.units = None
        if (self.sf_spec.enabled or self.sinks is not None
                or getattr(params.cooling, "cooling", False)):
            cosmo0 = None
            if bool(params.run.cosmo):
                from ramses_tpu.pm.cosmology import Cosmology
                cosmo0 = Cosmology.from_params(params)
            self.units = units_fn(
                params, cosmo=cosmo0,
                aexp=(cosmo0.aexp_ini if cosmo0 is not None else 1.0))
        if self.sf_spec.enabled and self.p is None:
            from ramses_tpu.pm.particles import lane_headroom
            self.p = ParticleSet.make(
                jnp.zeros((0, params.ndim)), jnp.zeros((0, params.ndim)),
                jnp.zeros((0,)), nmax=lane_headroom(params, True))
        if self.sf_spec.enabled:
            self.pic = True           # stars deposit/drift like DM
        self.dt_old = 0.0
        self._pm_dev: Dict[int, dict] = {}
        self._rho_max: Optional[float] = None
        # next-step CFL dt (device scalar) emitted by the previous fused
        # step; None whenever u changed outside step_coarse (regrid, ICs,
        # restart) and a fresh synchronous evaluation is needed
        self._dt_cache = None
        self._pad_hist: Dict[int, int] = {}
        # per-regrid migration maps, logged for subclasses that carry
        # extra per-cell state (the MHD staggered field); gated so the
        # plain hydro driver doesn't pin ncell-sized index buffers
        self._mig_log: Dict[int, tuple] = {}
        # cost-weighted Hilbert load balancing (parallel/balance.py):
        # per-level row layouts of partial levels (absent == identity,
        # the seed's tree-order rows).  ``_built_lay`` records the
        # (l-1, l, l+1) layout signatures each cached map was built
        # under so map reuse stays layout-aware.
        self.layouts: Dict[int, "object"] = {}
        self._built_lay: Dict[int, tuple] = {}
        self._rebalance_count = 0
        self.balance_stats = None
        self._force_rebalance = False

        if init_tree is not None:
            self.tree = init_tree
            self._rebuild_maps()
            self._alloc_from_ics()
        else:
            self._init_refine()

        # &RUN_PARAMS tracer: seed velocity tracers on the leaf cells
        # (``pm/tracer_utils.f90`` initial seeding): Poisson-sampled
        # per cell at mean ``tracer_per_cell`` (fractional thinning and
        # oversampling both work) and jittered inside the cell so
        # coincident tracers don't ride identical trajectories
        if bool(getattr(params.run, "tracer", False)) and seed_tracers:
            from ramses_tpu.pm.particles import TRACER_ID0
            if not self._tracer_physics:
                import warnings
                warnings.warn("tracer=.true. needs coordinate "
                              "velocities (hydro/MHD layouts); no "
                              "tracers seeded for this solver family")
            else:
                rng = np.random.default_rng(20480)
                tpc = float(params.run.tracer_per_cell)
                # mass-proportional seeding (``tracer_utils.f90`` init:
                # tracers sample the GAS MASS distribution, not the
                # leaf-cell count — a refined region must not be
                # over-weighted 2^d-fold per level)
                cen, mass, dxs = [], [], []
                for l in self.levels():
                    m = self.maps[l]
                    leaf = ~self.tree.refined_mask(l)
                    c = self.tree.cell_centers(l, self.boxlen)[leaf]
                    rho = np.asarray(
                        self.u[l])[:m.noct * 2 ** self.cfg.ndim, 0][leaf]
                    cen.append(c)
                    mass.append(rho * self.dx(l) ** self.cfg.ndim)
                    dxs.append(np.full(len(c), self.dx(l)))
                cen = np.concatenate(cen)
                mass = np.concatenate(mass)
                dxs = np.concatenate(dxs)
                lam = tpc * len(cen) * mass / max(mass.sum(), 1e-300)
                nper = rng.poisson(lam)
                rep = np.repeat(cen, nper, axis=0)
                jit = rng.uniform(-0.5, 0.5, rep.shape) \
                    * np.repeat(dxs, nper)[:, None]
                self.tracer_x = rep + jit if len(rep) else None
                # ids are assigned ONCE at seeding and ride through
                # dump/restore — cross-snapshot trajectory tracking by
                # id must survive star formation changing the live
                # particle population.  Base 2^30 keeps them clear of
                # the incremental star/DM id space.
                if self.tracer_x is not None:
                    self.tracer_id = (TRACER_ID0 + np.arange(
                        len(self.tracer_x), dtype=np.int64))
                    self._spec = None    # enable the MC flux capture

        # radiative transfer on the hierarchy (rt=.true.; gray or
        # multigroup/He via &RT_PARAMS rt_ngroups/rt_y_he,
        # rt/amr.py) — built after the tree/maps exist
        self.rt_amr = None
        if bool(params.run.rt):
            if self._pm_family(self.cfg):
                from ramses_tpu.rt.amr import RtAmrCoupled
                from ramses_tpu.units import units as units_fn
                un = self.units if self.units is not None else units_fn(
                    params, cosmo=self.cosmo,
                    aexp=(self.cosmo.aexp_ini if self.cosmo else 1.0))
                self.rt_amr = RtAmrCoupled(self, params, un)
                self._needs_mig_log = True  # rad/xion migrate on regrid
            else:
                import warnings
                warnings.warn("rt=.true. is only wired for the hydro "
                              "solver family on the AMR hierarchy; no "
                              "radiative transfer will run")

    # ------------------------------------------------------------------
    # setup
    # ------------------------------------------------------------------
    def dx(self, lvl: int) -> float:
        return self.boxlen / (1 << lvl)

    def _noct_pad(self, lvl: int, noct: int) -> Optional[int]:
        """Padded oct count with hysteresis: keep the previous bucket
        while the level still fits in it and fills >1/4 — the growing
        blast then changes jit shapes (→ recompiles) only on 4x growth,
        the ``ngridmax`` headroom idea of the reference's static
        allocation.  Subclasses align the result to the device mesh."""
        pad = mapmod.bucket(noct)
        prev = self._pad_hist.get(lvl)
        if prev is not None and pad <= prev and noct * 4 > prev:
            pad = prev
        self._pad_hist[lvl] = pad
        return pad

    def _place(self, arr, kind: str):
        """Placement hook: ``kind`` ∈ {octs, cells, rep} row semantics.
        Single-device base class keeps arrays as-is; the sharded subclass
        device_puts octs/cells-row arrays across the mesh."""
        return arr

    def _keys_same(self, other: Optional[Octree], l: int) -> bool:
        """True when level ``l`` has identical oct sets in self.tree and
        ``other`` (both absent counts as same)."""
        if other is None:
            return False
        ha, hb = self.tree.has(l), other.has(l)
        if ha != hb:
            return False
        if not ha:
            return True
        a, b = self.tree.levels[l].keys, other.levels[l].keys
        return len(a) == len(b) and np.array_equal(a, b)

    # ---------------------------------------------------------- layouts
    def oct_rows(self, l: int) -> np.ndarray:
        """Row slot of each tree oct of level ``l`` (identity when the
        level has no layout)."""
        lay = self.layouts.get(l)
        if lay is None:
            return np.arange(self.tree.noct(l), dtype=np.int64)
        return lay.oct_row

    def cell_rows(self, l: int) -> np.ndarray:
        """Flat row of each tree cell of level ``l`` in tree order."""
        ttd = 1 << self.tree.ndim
        return (self.oct_rows(l)[:, None] * ttd
                + np.arange(ttd, dtype=np.int64)).reshape(-1)

    def tree_order_cells(self, arr, l: int) -> np.ndarray:
        """Host copy of a cells-row array's REAL rows in tree order —
        under a layout real rows are scattered between pads, so
        ``[:ncell]`` slicing is only valid on identity levels."""
        a = np.asarray(arr)
        if l in self.layouts:
            return a[self.cell_rows(l)]
        ttd = 1 << self.tree.ndim
        return a[:self.tree.noct(l) * ttd]

    def _lay_triple(self, l: int) -> tuple:
        from ramses_tpu.parallel import balance
        return tuple(balance.layout_sig(self.layouts.get(j))
                     for j in (l - 1, l, l + 1))

    def request_rebalance(self):
        """Force a layout recompute at the next regrid regardless of the
        imbalance threshold."""
        self._force_rebalance = True

    def _maybe_rebalance(self, old_tree: Optional[Octree]):
        """Regrid-time balance pass: drop layouts stale against the new
        tree, measure imbalance under the surviving ones, and adopt
        cost-weighted Hilbert cuts when over threshold (the
        ``load_balance`` analog of the reference)."""
        from ramses_tpu.parallel import balance
        for l in list(self.layouts):
            if (not self.tree.has(l)
                    or not self._keys_same(old_tree, l)
                    or self.tree.noct(l) == int(
                        np.prod(self.tree.oct_dims(l)))):
                del self.layouts[l]
        if not balance.enabled(self):
            self.layouts = {}
            self.balance_stats = None
            self._force_rebalance = False
            return
        stats = balance.measure(self)
        thr = float(getattr(self.params.amr, "load_balance_threshold", 1.1))
        if stats.imbalance > thr or self._force_rebalance:
            cand = balance.compute_layouts(self)
            cstats = balance.measure(self, cand)
            # adopt only a meaningful improvement (or on request):
            # re-cutting for noise would churn jit inputs every regrid
            if self._force_rebalance or \
                    cstats.imbalance < stats.imbalance * 0.95:
                self.layouts = cand
                self._rebalance_count += 1
                stats = cstats
        self._force_rebalance = False
        self.balance_stats = stats

    def _block_level_ok(self, l: int) -> bool:
        """Gate: is a PARTIAL level eligible for the gather-fused blocked
        tile sweep?  Universal since the layouts/sharded/MHD lift: tiles
        are always built in tree/Morton order and composed with
        row-permutation layouts at table-emission time
        (``balance.apply_layout_blocks``), and row-sharded meshes run the
        XLA tile formulation GSPMD can partition
        (``FusedSpec.pallas_tiles``).  Off only for a family that opts
        out (``_oct_blocked``) or ``&AMR_PARAMS oct_blocking=.false.``."""
        return self._oct_blocked and bool(
            getattr(self.params.amr, "oct_blocking", True))

    def _reads_stencil(self, l: int) -> bool:
        """Will anything that runs read PARTIAL level ``l``'s 6^d
        per-oct tables (``LevelMaps.stencil_src`` and company)?  The
        stencil sweep and flags do wherever the tile path is not
        taken; the RT transport gathers its partial-level rows through
        them (``rt/amr.py`` — asked of the namelist, since ``rt_amr``
        is attached once the first maps exist)."""
        return (not self._block_level_ok(l)
                or (bool(self.params.run.rt)
                    and self._pm_family(self.cfg)))

    def _rebuild_maps(self, old_tree: Optional[Octree] = None,
                      old_maps: Optional[dict] = None,
                      old_dev: Optional[dict] = None):
        """(Re)build per-level index maps, reusing cached maps for levels
        whose (l-1, l, l+1) oct sets are unchanged — the ``build_comm``
        amortization: steady-state steps do no host map construction.

        A partial level gets the 6^d per-oct stencil tables
        (``stencil_src``, ``vsgn``, ``ok_ref``, ``interp_*`` in
        ``maps[l]`` and ``dev[l]``) only where :meth:`_reads_stencil`
        says something reads them; a level on the tile path has the
        ``tile_*`` / ``b_interp_*`` tables instead, and its ``dev[l]``
        has no stencil keys.  ``block_stats["stencil_octs_built"]``
        counts the octs whose 6^d tables this call built."""
        from ramses_tpu.parallel import balance
        prev_maps = old_maps or {}
        prev_dev = old_dev or {}
        prev_blocks = getattr(self, "blocks", {})
        prev_lay = getattr(self, "_built_lay", {})
        self._spec = None
        self.maps: Dict[int, mapmod.LevelMaps] = {}
        self.dev: Dict[int, dict] = {}
        self.blocks: Dict[int, mapmod.BlockMaps] = {}
        self.block_stats = {"blocks_total": 0, "blocks_rebuilt": 0,
                            "stencil_octs_built": 0, "tiles_native": 0}
        self._built_lay = {}
        for l in range(self.lmin, self.lmax + 1):
            if not self.tree.has(l):
                break
            self._built_lay[l] = self._lay_triple(l)
            if (l in prev_maps
                    and self._keys_same(old_tree, l - 1)
                    and self._keys_same(old_tree, l)
                    and self._keys_same(old_tree, l + 1)
                    and prev_lay.get(l) == self._built_lay[l]):
                self.maps[l] = prev_maps[l]
                self.dev[l] = prev_dev[l]
                if l in prev_blocks:
                    # unchanged (l-1, l, l+1) oct sets: every per-block
                    # map is still valid — zero blocks rebuilt
                    b = self.blocks[l] = prev_blocks[l]
                    self.block_stats["blocks_total"] += b.ntile
                    self.block_stats["tiles_native"] += b.tiles_native
                continue
            if (l in prev_maps and prev_maps[l].complete
                    and self._keys_same(old_tree, l)):
                # COMPLETE level with unchanged oct set: the dense
                # permutation depends only on this level's keys — only
                # the restriction/ok_dense maps (which read l+1) need a
                # rebuild.  This skips the dominant host cost of the
                # regrid (the base level's 2^(3·lmin)-cell perm).
                m = mapmod.refresh_restriction(prev_maps[l], self.tree)
                lay_p1 = self.layouts.get(l + 1)
                if lay_p1 is not None:
                    m = balance.remap_son_oct(m, lay_p1)
                self.maps[l] = m
                with self.timers.section("regrid: maps upload"):
                    self.dev[l] = dict(
                        prev_dev[l],
                        ok_dense=(self._place(jnp.asarray(m.ok_dense), "cells")
                                  if m.ok_dense is not None else None),
                        ok_flat=(self._place(jnp.asarray(m.ok_flat), "cells")
                                 if m.ok_flat is not None else None),
                        ref_cell=self._place(jnp.asarray(m.ref_cell), "rep"),
                        son_oct=self._place(jnp.asarray(m.son_oct), "rep"),
                    )
                continue
            m = mapmod.build_level_maps(
                self.tree, l, self.bc_kinds,
                noct_pad=self._noct_pad(l, self.tree.noct(l)),
                stencil=self._reads_stencil(l))
            lay_m1, lay_l, lay_p1 = (self.layouts.get(l - 1),
                                     self.layouts.get(l),
                                     self.layouts.get(l + 1))
            if lay_m1 is not None or lay_l is not None or lay_p1 is not None:
                m = balance.apply_layout_level(m, lay_m1, lay_l, lay_p1)
            self.maps[l] = m
            valid_cell = np.repeat(m.valid_oct, 2 ** self.tree.ndim)
            if m.complete:
                # dense path: restriction (+ refined mask) only.  The
                # flat↔dense permutation is a bit-permutation transpose
                # on cubic levels (amr/bitperm.py) — no device index
                # arrays needed; NON-cubic roots keep the index-gather
                # conversion and ship the perm maps.
                with self.timers.section("regrid: maps upload"):
                    self.dev[l] = dict(
                        ok_dense=(self._place(jnp.asarray(m.ok_dense), "cells")
                                  if m.ok_dense is not None else None),
                        ok_flat=(self._place(jnp.asarray(m.ok_flat), "cells")
                                 if m.ok_flat is not None else None),
                        ref_cell=self._place(jnp.asarray(m.ref_cell), "rep"),
                        son_oct=self._place(jnp.asarray(m.son_oct), "rep"),
                        valid_cell=self._place(jnp.asarray(valid_cell),
                                               "cells"),
                    )
                    if not K.pow2_cube(self.tree.cell_dims(l)):
                        self.dev[l].update(
                            perm=self._place(jnp.asarray(m.perm), "cells"),
                            inv_perm=self._place(jnp.asarray(m.inv_perm),
                                                 "cells"))
                continue
            with self.timers.section("regrid: maps upload"):
                self.dev[l] = dict(
                    corr_idx=self._place(jnp.asarray(m.corr_idx), "rep"),
                    ref_cell=self._place(jnp.asarray(m.ref_cell), "rep"),
                    son_oct=self._place(jnp.asarray(m.son_oct), "rep"),
                    valid_cell=self._place(jnp.asarray(valid_cell), "cells"),
                )
                if m.has_stencil:
                    self.block_stats["stencil_octs_built"] += m.noct
                    self.dev[l].update(
                        stencil_src=self._place(jnp.asarray(m.stencil_src),
                                                "octs"),
                        vsgn=(self._place(jnp.asarray(m.vsgn), "octs")
                              if m.vsgn is not None else None),
                        ok_ref=self._place(jnp.asarray(m.ok_ref), "octs"),
                        interp_cell=self._place(jnp.asarray(m.interp_cell),
                                                "rep"),
                        interp_nb=self._place(jnp.asarray(m.interp_nb),
                                              "rep"),
                        interp_sgn=self._place(
                            jnp.asarray(m.interp_sgn, dtype=self.dtype),
                            "rep"),
                    )
            if self._block_level_ok(l):
                with self.timers.section("regrid: maps tiles"):
                    b = mapmod.build_block_maps(
                        self.tree, l, self.bc_kinds,
                        shift=int(getattr(self.params.amr,
                                          "oct_block_shift", 2)),
                        noct_pad=m.noct_pad, prev=prev_blocks.get(l))
                # TREE order here; the layout-composed copy ships to the device
                self.blocks[l] = b
                self.block_stats["blocks_total"] += b.ntile
                self.block_stats["blocks_rebuilt"] += b.blocks_rebuilt
                self.block_stats["tiles_native"] += b.tiles_native
                bt = (balance.apply_layout_blocks(b, lay_m1, lay_l)
                      if (lay_m1 is not None or lay_l is not None) else b)
                with self.timers.section("regrid: maps upload"):
                    self.dev[l].update(
                        tile_src=self._place(jnp.asarray(bt.tile_src),
                                             "octs"),
                        tile_vsgn=(self._place(jnp.asarray(bt.tile_vsgn),
                                               "octs")
                                   if bt.tile_vsgn is not None else None),
                        tile_ok=self._place(jnp.asarray(bt.tile_ok), "octs"),
                        cell_tile=self._place(jnp.asarray(bt.cell_tile),
                                              "cells"),
                        cell_slot=self._place(jnp.asarray(bt.cell_slot),
                                              "cells"),
                        oct_tile=self._place(jnp.asarray(bt.oct_tile), "octs"),
                        oct_slot=self._place(jnp.asarray(bt.oct_slot), "octs"),
                        b_interp_cell=self._place(
                            jnp.asarray(bt.interp_cell), "rep"),
                        b_interp_nb=self._place(jnp.asarray(bt.interp_nb),
                                                "rep"),
                        b_interp_sgn=self._place(
                            jnp.asarray(bt.interp_sgn, dtype=self.dtype),
                            "rep"),
                    )
            if self.gravity:
                g = mapmod.build_gravity_maps(self.tree, l, self.bc_kinds,
                                              noct_pad=m.noct_pad)
                if lay_m1 is not None or lay_l is not None:
                    g = balance.apply_layout_gravity(g, lay_m1, lay_l)
                with self.timers.section("regrid: maps upload"):
                    self.dev[l].update(
                        g_nb=self._place(jnp.asarray(g.nb), "cells"),
                        g_cell=self._place(jnp.asarray(g.g_cell), "rep"),
                        g_gnb=self._place(jnp.asarray(g.g_nb), "rep"),
                        g_sgn=self._place(jnp.asarray(g.g_sgn), "rep"),
                        g_octnb=self._place(jnp.asarray(g.oct_nb), "octs"),
                        g_valid=self._place(jnp.asarray(g.valid_cell),
                                            "cells"),
                        # masked-multigrid ladder: the depth-0 parent map
                        # is oct-row-sized (shards with the octs); deeper
                        # lattices are genuinely small and replicate
                        g_mg=tuple((self._place(jnp.asarray(nb_j), "rep"),
                                    self._place(jnp.asarray(par_j),
                                                "octs" if j == 0 else "rep"))
                                   for j, (nb_j, par_j, _n)
                                   in enumerate(g.mg)))
        # coverage telemetry: fraction of partial-level octs swept via
        # the blocked tile path (1.0 when every partial level is blocked
        # or there is none to block)
        part = [l for l, lm in self.maps.items() if not lm.complete]
        tot = sum(self.tree.noct(l) for l in part)
        blk = sum(self.tree.noct(l) for l in part if l in self.blocks)
        self.block_stats["blocked_frac"] = (blk / tot) if tot else 1.0

    # ------------------------------------------------------------------
    # cosmology helpers (host interpolation of the Friedmann tables)
    # ------------------------------------------------------------------
    def aexp_now(self) -> float:
        if self.cosmo is None:
            return 1.0
        return float(np.interp(self.t, self.cosmo.tau_frw,
                               self.cosmo.axp_frw))

    def hexp_now(self) -> float:
        if self.cosmo is None:
            return 0.0
        return float(np.interp(self.t, self.cosmo.tau_frw,
                               self.cosmo.hexp_frw))

    def grav_coeff(self) -> float:
        """Poisson source coefficient: 4π, or the supercomoving
        ``1.5·Ωm·aexp`` (``poisson/multigrid_fine_commons.f90`` rhs)."""
        if self.cosmo is None:
            return self.fourpi
        return 1.5 * self.cosmo.omega_m * self.aexp_now()

    def _ic_state(self, lvl: int) -> jnp.ndarray:
        """Analytic conservative ICs on this level's (padded) cells, or
        periodic-trilinear samples of a dense IC grid (grafic baryons)."""
        m = self.maps[lvl]
        if self._init_dense is not None:
            centers = self.tree.cell_centers(lvl, self.boxlen)
            u = _sample_dense_periodic(
                self._init_dense, centers / self.boxlen)  # [nvar, ncell]
        else:
            centers = self.tree.cell_centers(lvl, self.boxlen)
            x = [centers[:, d] for d in range(self.cfg.ndim)]
            q = regions.region_condinit(x, self.dx(lvl), self.params,
                                        self.cfg)
            u = regions.prim_to_cons(q, self.cfg)      # [nvar, ncell]
        out = np.zeros((m.ncell_pad, self.cfg.nvar))
        out[:, 0] = self.cfg.smallr
        out[:, self.cfg.ndim + 1] = self.cfg.smalle * self.cfg.smallr
        out[self.cell_rows(lvl)] = u.T
        return self._place(jnp.asarray(out, dtype=self.dtype), "cells")

    def _alloc_from_ics(self):
        self.u: Dict[int, jnp.ndarray] = {}
        for l in self.levels():
            self.u[l] = self._ic_state(l)
        self._restrict_all()
        self._dt_cache = None

    def _init_refine(self):
        """Iterative initial mesh build (``amr/init_refine.f90:5-154``):
        apply analytic ICs, flag, rebuild, repeat until stable."""
        self.tree = Octree.base(self.tree_ndim, self.lmin,
                                self.lmax, root=self.root)
        self._rebuild_maps()
        self._alloc_from_ics()
        for _ in range(self.lmax - self.lmin + 2):
            newtree = self._flag_and_tree()
            same = True
            for l in range(self.lmin, self.lmax + 1):
                if newtree.has(l) != self.tree.has(l):
                    same = False
                elif newtree.has(l) and not np.array_equal(
                        newtree.levels[l].keys, self.tree.levels[l].keys):
                    same = False
            if same:
                break
            self.tree = newtree
            self._rebuild_maps()
            self._alloc_from_ics()

    @property
    def tree_ndim(self) -> int:
        return self.params.ndim

    def levels(self):
        return [l for l in range(self.lmin, self.lmax + 1)
                if self.tree.has(l)]

    # ------------------------------------------------------------------
    # refinement
    # ------------------------------------------------------------------
    def _criteria_flags(self, spec: FusedSpec):
        """Device tuple of per-level gradient criteria flags — the
        solver-specific half of ``flag_fine`` (subclass hook)."""
        r = self.params.refine
        eg = (float(r.err_grad_d), float(r.err_grad_u),
              float(r.err_grad_p))
        fls = (float(r.floor_d), float(r.floor_u), float(r.floor_p))
        args = (self.u, self.dev, spec, eg, fls,
                int(self.params.refine.interpol_type))
        _hlo.note_dispatch(_fused_flags, *args)
        return _fused_flags(*args)

    def _flag_and_tree(self) -> Octree:
        r = self.params.refine
        spec = self._fused_spec()
        ttd = 2 ** self.tree_ndim
        # flags bitpacked on device (one uint8 per oct) so the single
        # flag fetch — the only device→host copy of a steady regrid —
        # moves 2^d× fewer bytes; only the non-zero bytes are decoded
        # below (``flagmod.flagged_cells``)
        if self._offload is not None and self._offload.engaged(self):
            # out-of-core: per-level flag segments so parked levels are
            # fetched one (plus interp source) at a time
            rr = self.params.refine
            eg = (float(rr.err_grad_d), float(rr.err_grad_u),
                  float(rr.err_grad_p))
            fls = (float(rr.floor_d), float(rr.floor_u),
                   float(rr.floor_p))
            flags = self._offload.criteria_flags_packed(
                self, spec, eg, fls,
                int(self.params.refine.interpol_type), ttd)
        else:
            flags = _pack_flag_bits(self._criteria_flags(spec), ttd)
        # the blocking fetch also waits out whatever the device still
        # owes (the previous coarse step only dispatched): its own span
        with self.timers.section("regrid: flag fetch"):
            flags = jax.device_get(flags)               # ONE trip
        # flagged cells per level: ascending flat-cell indices
        crit: Dict[int, np.ndarray] = {}
        stats = {"octs_fetched": 0, "octs_flagged": 0, "cells_flagged": 0}
        for packed, l in zip(flags, spec.levels):
            m = self.maps[l]
            ncell = m.noct * ttd
            lay = self.layouts.get(l)      # rows → tree oct order first
            fl, nocts = flagmod.flagged_cells(
                packed, self.tree_ndim, m.noct,
                lay.oct_row if lay is not None else None)
            stats["octs_fetched"] += len(packed)
            stats["octs_flagged"] += nocts
            stats["cells_flagged"] += len(fl)
            i = l - 1                                  # 1-based level lists
            if i < len(r.r_refine) and r.r_refine[i] > 0.0:
                fl = np.union1d(fl, np.flatnonzero(flagmod.geometry_flags(
                    self.tree.cell_centers(l, self.boxlen), l,
                    self.params)))
            if self.pic and i < len(r.m_refine) and r.m_refine[i] >= 0.0:
                # quasi-Lagrangian refinement (``flag_utils.f90``
                # m_refine): flag cells holding more than m_refine mean
                # particle masses.  Use the gravity solve's cached total
                # density when available; deposit on demand otherwise
                # (m_refine must not silently require poisson=.true.)
                rho_dev = self._rho_dev.get(l)
                if rho_dev is None or rho_dev.shape[0] < ncell:
                    if not self._pm_dev:
                        self._build_pm()
                    if l in self._pm_dev:
                        rho_dev = (self.u[l][:, 0]
                                   + self._pm_rho(l).astype(
                                       self.u[l].dtype))
                if rho_dev is not None and rho_dev.shape[0] >= ncell:
                    mp = float(jnp.sum(self.p.m * self.p.active)) \
                        / max(int(jnp.sum(self.p.active)), 1)
                    thr = r.m_refine[i] * mp \
                        / self.dx(l) ** self.tree_ndim
                    rho_np = self.tree_order_cells(rho_dev, l)[:ncell]
                    fl = np.union1d(fl, np.flatnonzero(rho_np > thr))
            crit[l] = fl
        self.flag_stats = stats
        with self.timers.section("regrid: tree build"):
            return flagmod.compute_new_tree(self.tree, crit, self.bc_kinds,
                                            self.params)

    def _bc_sig(self) -> tuple:
        """Hashable (lo, hi) bc-kind tuple per dim — jit static key."""
        return tuple(tuple(int(k) for k in f) for f in self.bc_kinds)

    def _device_regrid_ok(self) -> bool:
        """Gate for the jitted device-resident migrate
        (``amr/device_regrid.py``).  Families that replay migration into
        side-channel state (MHD face fields, RT) need the host prolong
        maps (``_mig_log``), and layout-permuted levels keep the host
        path (the row-remap tables are host objects) — both fall back to
        the bitwise-identical host reference, as does a key range too
        deep for the device integer width."""
        if not bool(getattr(self.params.amr, "device_regrid", True)):
            return False
        if self._needs_mig_log:
            return False
        from ramses_tpu.amr import device_regrid as dregrid
        return dregrid.keys_fit(self.tree_ndim, max(self.levels()),
                                self.root)

    def regrid(self):
        """Flag, rebuild the tree, and migrate device state
        (``flag_fine`` + ``refine_fine``/``kill_grid``,
        ``amr/refine_utils.f90:332,953``)."""
        with self.timers.section("regrid"):
            if self.lmax == self.lmin:
                return
            with self.timers.section("regrid: flag"):
                newtree = self._flag_and_tree()
            old_u = self.u
            oldtree = self.tree
            old_maps, old_dev = self.maps, self.dev
            old_layouts = dict(self.layouts)
            self.tree = newtree
            with self.timers.section("regrid: balance"):
                self._maybe_rebalance(oldtree)
            from ramses_tpu.parallel import balance
            lay_range = range(self.lmin, self.lmax + 2)
            unchanged = (all(self._keys_same(oldtree, l) for l in lay_range)
                         and balance.layouts_same(old_layouts, self.layouts,
                                                  lay_range))
            if unchanged:
                self.tree = oldtree
                # steady-state regrid: tree untouched, every table
                # stays live — nothing rebuilt
                self.block_stats = dict(self.block_stats, blocks_rebuilt=0,
                                        stencil_octs_built=0)
                return
            with self.timers.section("regrid: maps"):
                self._rebuild_maps(oldtree, old_maps, old_dev)
            with self.timers.section("regrid: migrate"):
                self._migrate(oldtree, old_u, old_layouts)
            with self.timers.section("regrid: restrict"):
                self._restrict_all()
            self._dt_cache = None          # u changed: stale CFL dt

    def _migrate(self, oldtree, old_u, old_layouts):
        """Move the level state onto the new tree (``self.tree`` /
        ``self.maps``): survivors copied, new octs prolonged from the
        level below, stale gravity state pruned."""
        from ramses_tpu.parallel import balance
        twotondim = 2 ** self.cfg.ndim
        offs, sgn_tab, oct_ar = _mig_consts(self.cfg.ndim)
        self._mig_log = {}
        dregrid = None
        if self._device_regrid_ok():
            from ramses_tpu.amr import device_regrid as dregrid
        dev_keys: Dict[tuple, jnp.ndarray] = {}

        def _keys_dev(tree_, l_, pad_):
            kk = (id(tree_), l_, pad_)
            if kk not in dev_keys:
                kn = (tree_.levels[l_].keys if tree_.has(l_)
                      else np.zeros(0, np.int64))
                dev_keys[kk] = dregrid.upload_keys(kn, pad_)
            return dev_keys[kk]

        new_u: Dict[int, jnp.ndarray] = {}
        from ramses_tpu.amr import offload as offmod

        def _coarse_dev(l_):
            # a parked (HostBuffer) coarse level must be device-resident
            # to serve as the prolongation source; fetch once and write
            # the device copy back so every finer level reuses it
            if offmod.is_parked(new_u[l_]):
                new_u[l_] = offmod.as_device(new_u[l_])
            return new_u[l_]

        for l in self.levels():
            m = self.maps[l]
            lay_new = self.layouts.get(l)
            lay_old = old_layouts.get(l)
            lay_m1 = self.layouts.get(l - 1)
            same_lay = (balance.layout_sig(lay_new)
                        == balance.layout_sig(lay_old))
            if (l == self.lmin or self._keys_same(oldtree, l)) \
                    and same_lay and old_u[l].shape[0] == m.ncell_pad:
                # identical oct set and identical padded layout: reuse
                new_u[l] = old_u[l]
                continue
            if dregrid is not None and lay_new is None \
                    and lay_old is None and lay_m1 is None:
                # device-resident migrate: survivor copy + new-oct
                # prolongation maps derived on device from the sorted
                # level key arrays (amr/device_regrid.py) — no per-level
                # host table construction, bitwise-identical to the
                # host reference path below
                old = offmod.as_device(old_u.get(l))
                if old is None:
                    old = jnp.zeros((1, new_u[l - 1].shape[1]),
                                    self.dtype)
                onoct = oldtree.noct(l) if oldtree.has(l) else 0
                new_u[l] = self._place(dregrid.migrate_level(
                    old, _coarse_dev(l - 1),
                    _keys_dev(self.tree, l, m.noct_pad),
                    _keys_dev(oldtree, l,
                              mapmod.bucket(max(onoct, 1), 8)),
                    _keys_dev(self.tree, l - 1,
                              self.maps[l - 1].noct_pad),
                    m.ncell_pad, self.cfg.ndim, self._bc_sig(),
                    tuple(int(n) for n in self.tree.cell_dims(l - 1)),
                    self.cfg,
                    int(self.params.refine.interpol_type)), "cells")
                continue
            cd, cs, new_octs, f_cell, nb = mapmod.build_prolong_maps(
                self.tree, oldtree, l, self.bc_kinds)
            # convert tree-order oct/cell indices to row slots: dst via
            # the NEW layouts, src via the OLD ones (both identity when
            # absent); f_cell/nb point at l-1 cells already migrated to
            # the new layout
            if lay_new is not None:
                cd_r = lay_new.oct_row[cd]
                new_r = lay_new.oct_row[new_octs] if len(new_octs) \
                    else new_octs
            else:
                cd_r, new_r = cd, new_octs
            cs_r = lay_old.oct_row[cs] if lay_old is not None else cs
            if lay_m1 is not None:
                f_cell = balance.remap_cells(f_cell, lay_m1, twotondim)
                nb = balance.remap_cells(nb, lay_m1, twotondim)
            # Device-side migration with bucket-padded index maps: no
            # whole-level host round-trips, and jit shapes only change
            # when a bucket boundary is crossed.
            ncopy = len(cd) * twotondim
            nnew = len(new_octs) * twotondim
            cpad = mapmod.bucket(max(ncopy, 1), 1024)
            npad = mapmod.bucket(max(nnew, 1), 1024)
            rows_d = np.full(cpad, m.ncell_pad, dtype=np.int64)   # drop
            rows_s = np.zeros(cpad, dtype=np.int64)
            if ncopy:
                rows_d[:ncopy] = (cd_r[:, None] * twotondim
                                  + oct_ar).reshape(-1)
                rows_s[:ncopy] = (cs_r[:, None] * twotondim
                                  + oct_ar).reshape(-1)
            cell_rep = np.zeros(npad, dtype=np.int64)
            nb_rep = np.zeros((npad, self.cfg.ndim, 2), dtype=np.int64)
            sgn_rep = np.ones((npad, self.cfg.ndim))
            rows_new = np.full(npad, m.ncell_pad, dtype=np.int64)  # drop
            if nnew:
                cell_rep[:nnew] = np.repeat(f_cell, twotondim)
                nb_rep[:nnew] = np.repeat(nb, twotondim, axis=0)
                sgn_rep[:nnew] = np.tile(sgn_tab, (len(new_octs), 1))
                rows_new[:nnew] = (new_r[:, None] * twotondim
                                   + oct_ar).reshape(-1)
            old = offmod.as_device(old_u.get(l))
            if old is None:
                old = jnp.zeros((1, new_u[l - 1].shape[1]), self.dtype)
            rows_d = jnp.asarray(rows_d)
            rows_s = jnp.asarray(rows_s)
            cell_rep = jnp.asarray(cell_rep)
            sgn_dev = jnp.asarray(sgn_rep, dtype=self.dtype)
            rows_new = jnp.asarray(rows_new)
            if self._needs_mig_log:
                self._mig_log[l] = (rows_d, rows_s, cell_rep, sgn_dev,
                                    rows_new, m.ncell_pad, new_octs,
                                    f_cell, jnp.asarray(nb_rep))
            new_u[l] = self._place(_migrate_level(
                old, _coarse_dev(l - 1), rows_d, rows_s, cell_rep,
                jnp.asarray(nb_rep), sgn_dev, rows_new, m.ncell_pad,
                self.cfg,
                int(self.params.refine.interpol_type)), "cells")
        self.u = new_u
        if getattr(self, "rt_amr", None) is not None:
            self.rt_amr.apply_migration(self)
        # prune stale gravity state: a level whose bucketed size changed,
        # vanished, or moved to a different row layout must not seed the
        # next solve's warm start
        for l in list(self.phi):
            if (l not in self.maps
                    or self.phi[l].shape[0] != self.maps[l].ncell_pad
                    or not balance.layouts_same(old_layouts, self.layouts,
                                                (l,))):
                self.phi.pop(l, None)
                self.fg.pop(l, None)
                self.poisson_iters.pop(l, None)
                self._rho_dev.pop(l, None)

    def _restrict_all(self):
        """Restriction sweep fine→coarse so non-leaf cells hold son means."""
        if self._offload is not None and self._offload.engaged(self):
            # out-of-core: sweep with at most two levels resident,
            # re-parking each fine source as soon as it is consumed
            self._offload.restrict_all_segmented(self, self._fused_spec())
            return
        for l in sorted(self.levels(), reverse=True):
            if self.tree.has(l + 1):
                d = self.dev[l]
                self.u[l] = K.restrict_upload(self.u[l], self.u[l + 1],
                                              d["ref_cell"], d["son_oct"],
                                              self.cfg)

    # ------------------------------------------------------------------
    # time stepping
    # ------------------------------------------------------------------
    def _fused_spec(self) -> FusedSpec:
        if self._spec is None:
            lv = tuple(self.levels())
            self._spec = FusedSpec(
                cfg=self.cfg, bspec=self.bspec, lmin=self.lmin,
                boxlen=self.boxlen, levels=lv,
                complete=tuple(self.maps[l].complete for l in lv),
                gravity=self.gravity,
                itype=int(self.params.refine.interpol_type),
                root=self.root, cool=self.cool_spec,
                want_flux=(self.tracer_x is not None
                           and len(self.tracer_x) > 0
                           and getattr(self.cfg, "physics",
                                       "hydro") == "hydro"),
                ndev=int(self.ndev))
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

    def level_formulations(self) -> list:
        """[(level, name, on its Pallas kernel)] for the CURRENT fused
        spec: the formulation the traced step takes
        (``K.level_kind``, as ``K.sweep_level`` asks it) and its
        kernel gate, asked with the same arguments."""
        from ramses_tpu.hydro import pallas_muscl as pk
        from ramses_tpu.hydro import pallas_oct as po
        spec = self._fused_spec()
        cfg, dtype = spec.cfg, self.dtype
        out = []
        for i, l in enumerate(spec.levels):
            kind = K.level_kind(spec, i)
            if kind == "slab":
                sl = spec.slab[i]
                cut = tuple(p is not None for p in sl.perms)
                kax = pk.shard_axes(cfg, sl.loc, cut, dtype)
                out.append((l, f"dense slab-sharded sweep (grid "
                            f"{sl.grid}, halo {sl.backend}, per-shard "
                            + (f"fused kernel axes {kax}" if kax
                               else "XLA update") + ")",
                            kax is not None))
            elif kind == "dense":
                k = pk.kernel_available(cfg, K.dense_shape(spec, l),
                                        spec.bspec.faces, dtype, spec.ndev)
                out.append((l, "dense fused kernel (pallas_muscl)" if k
                            else "dense XLA sweep", bool(k)))
            elif kind == "tile":
                nt = self.blocks[l].ntile_pad
                k = spec.pallas_tiles and po.tile_available(
                    cfg, nt, dtype, spec.block_shift)
                out.append((l, f"tile_sweep kernel (pallas_oct, {nt} tiles)"
                            if k else f"XLA tiles ({nt} tiles)", bool(k)))
            else:
                no = self.maps[l].noct_pad
                k = po.available(cfg, no, dtype, spec.ndev)
                out.append((l, f"oct_sweep kernel (pallas_oct, {no} octs)"
                            if k else f"XLA oct stencils ({no} octs)",
                            bool(k)))
        return out

    def _slab_spec(self, l: int):
        """SlabSpec for a complete level's explicit slab-sharded dense
        path, or None for the global-view sweep.  The single-device sim
        has no mesh — :class:`ramses_tpu.parallel.amr_sharded.
        ShardedAmrSim` overrides this with the real gate."""
        return None

    def _cool_bundle(self):
        """(tables, traced [scale_T2, scale_nH, scale_t]) for the fused
        step, or None when cooling is off."""
        if self.cool_tables is None:
            return None
        return (self.cool_tables, self._cool_scales)

    def coarse_dt(self) -> float:
        with self.timers.section("courant"):
            if self._dt_cache is not None:
                # emitted by the previous fused step (dtnew bookkeeping):
                # u is unchanged since, so this IS the current CFL dt
                dts = [float(self._dt_cache)]
            elif self._offload is not None and self._offload.engaged(self):
                # out-of-core: per-level Courant segments so parked
                # levels are fetched one at a time (same stack-then-min
                # reduction order — bitwise equal to the fused program)
                dts = [self._offload.coarse_dt_min(self,
                                                   self._fused_spec())]
            else:
                dtmin = jnp.min(_fused_courant(
                    self.u, self.dev, self._fused_spec(),
                    self.fg if (self.gravity and self.fg) else None))
                # only dispatched so far: the fetch blocks until the
                # device has run it (and what it still owed before)
                with self.timers.section("courant: fetch"):
                    dts = [float(dtmin)]
            dts.extend(self._aux_dts())
            return min(dts)

    def _aux_dts(self) -> list:
        """Non-solver dt caps shared by every solver family: particle
        Courant + lagged free-fall, cosmological expansion."""
        dts = []
        if self.pic:
            from ramses_tpu.pm import particles as pmod
            cf = float(self.cfg.courant_factor)
            # particle Courant: a level-l particle moves cf*dx(l) per
            # level substep, i.e. cf*dx(lmin) per coarse step
            # (pm/newdt_fine.f90:186-233 folded through the exact
            # factor-2 subcycling)
            dts.append(float(pmod.particle_dt(
                self.p, self.dx(self.lmin), cf)))
            if self.gravity and self._rho_max:
                # free-fall cap from the previous step's deposited
                # density (one step lagged; pm/newdt_fine.f90:51-60)
                dts.append(float(pmod.freefall_dt(
                    jnp.asarray(self._rho_max), cf,
                    self.grav_coeff())))
        if self.cosmo is not None:
            # expansion cap (amr/update_time.f90 cosmo branch)
            dts.append(0.1 / abs(self.hexp_now()))
        return dts

    # ------------------------------------------------------------------
    # particle-mesh on the hierarchy (pm/amr_pm.py)
    # ------------------------------------------------------------------
    def _build_pm(self):
        """Host CIC metadata pass, once per coarse step
        (``make_tree_fine`` + the index part of ``cic_amr``)."""
        from ramses_tpu.pm import amr_pm
        x_host = np.asarray(self.p.x, dtype=np.float64)
        ncp = {l: self.maps[l].ncell_pad for l in self.levels()}
        from ramses_tpu.pm.coupling import deposit_scheme_from_params
        pm_maps = amr_pm.build_pm_maps(
            self.tree, x_host, self.boxlen, self.bc_kinds, ncp,
            scheme=deposit_scheme_from_params(self.params))
        if self.layouts:
            from ramses_tpu.parallel import balance
            ttd = 1 << self.tree.ndim
            for l, mp in pm_maps.items():
                lay = self.layouts.get(l)
                if lay is not None:   # ncell_pad drop-sentinel unchanged
                    mp.idx = balance.remap_cells(mp.idx, lay, ttd)
        wdtype = self.dtype if self.p.x.dtype != jnp.float64 \
            else jnp.float64
        self._pm_dev = {
            l: dict(idx=self._place(jnp.asarray(mp.idx), "rep"),
                    w=self._place(jnp.asarray(mp.w, dtype=wdtype), "rep"),
                    mask=self._place(jnp.asarray(mp.assigned), "rep"))
            for l, mp in pm_maps.items()}

    def _pm_rho(self, l: int):
        """Particle density on level ``l``'s flat cells (``rho_fine``)."""
        from ramses_tpu.pm import amr_pm
        pd = self._pm_dev[l]
        return amr_pm.deposit_flat(
            pd["idx"], pd["w"], self.p.m.astype(pd["w"].dtype),
            self.p.active, self.maps[l].ncell_pad,
            self.dx(l) ** self.cfg.ndim)

    def _pm_force(self):
        """Force at particle positions, gathered at each particle's
        finest covering level (``move1``, ``pm/move_fine.f90:193``)."""
        from ramses_tpu.pm import amr_pm
        f = None
        for l in self.levels():
            pd = self._pm_dev[l]
            fl = amr_pm.gather_flat(self.fg[l].astype(pd["w"].dtype),
                                    pd["idx"], pd["w"], pd["mask"])
            f = fl if f is None else f + fl
        return f

    def solve_gravity(self):
        """Per-level Poisson solve, coarse→fine one-way interface
        (``multigrid_fine``): exact periodic FFT on any COMPLETE level
        (the base always; fully-refined levels above too),
        Dirichlet-ghost CG on partial levels; then the gradient force."""
        from ramses_tpu.poisson import amr_solve as gs
        from ramses_tpu.poisson.solver import fft_solve

        nd = self.cfg.ndim
        coeff = self.grav_coeff()
        if self.grav_periodic:
            # mean density over leaves + particles (periodic solvability)
            mtot = float(self.totals()[0])
            if self.pic:
                mtot += float(jnp.sum(self.p.m * self.p.active))
            vol_box = self.boxlen ** nd
            for r in self.root:
                vol_box *= r
            rho_mean = mtot / vol_box
        else:
            rho_mean = 0.0       # isolated problem is well-posed as-is
        rho_max = None
        for l in self.levels():
            m = self.maps[l]
            d = self.dev[l]
            dx = self.dx(l)
            rho = self.u[l][:, 0]
            if self.pic:
                rho = rho + self._pm_rho(l).astype(rho.dtype)
                self._rho_dev[l] = rho     # m_refine criterion input
                mx = jnp.max(rho)
                rho_max = mx if rho_max is None else jnp.maximum(rho_max,
                                                                 mx)
            rhs = coeff * (rho - rho_mean)
            if m.complete:
                # whole-box level: exact periodic FFT solve on the dense
                # grid (or the isolated multipole-Dirichlet CG when the
                # box is open), force by central differences
                ncell = m.noct * (1 << nd)
                shp = self.tree.cell_dims(l)
                dense = K.rows_to_dense(rhs, d.get("inv_perm"), shp)
                if self.grav_periodic:
                    phi_dense = fft_solve(dense, dx)
                    fg_rows = K.dense_to_rows(
                        gs.grad_dense(phi_dense,
                                      jnp.asarray(dx, rhs.dtype), nd),
                        d.get("perm"), shp)
                else:
                    from ramses_tpu.poisson.isolated import (
                        grad_isolated, isolated_solve)
                    # dense already includes coeff: pass rho = dense/coeff
                    phi_dense, gh = isolated_solve(
                        dense / coeff, dx, jnp.asarray(coeff, rhs.dtype),
                        iters=300, tol=float(self.params.poisson.epsilon))
                    fg_rows = K.dense_to_rows(jnp.moveaxis(
                        grad_isolated(phi_dense, gh, dx), 0, -1),
                        d.get("perm"), shp)
                phi = jnp.zeros((m.ncell_pad,), rhs.dtype)
                phi = phi.at[:ncell].set(
                    K.dense_to_rows(phi_dense, d.get("perm"), shp))
                if m.ncell_pad > ncell:
                    fg_rows = jnp.zeros(
                        (m.ncell_pad, nd), fg_rows.dtype
                    ).at[:ncell].set(fg_rows)
                self.phi[l] = phi
                self.fg[l] = fg_rows.astype(self.dtype)
                continue
            else:
                ghosts = K.interp_cells(
                    self.phi[l - 1][:, None], d["g_cell"], d["g_gnb"],
                    d["g_sgn"].astype(self.phi[l - 1].dtype),
                    _Cfg1(nd), itype=1)[:, 0]
                phi, nit = gs.pcg_level(
                    rhs, ghosts, d["g_nb"], d["g_octnb"],
                    jnp.asarray(dx, rhs.dtype), d["g_valid"], nd,
                    tol=float(self.params.poisson.epsilon), iters=200,
                    phi0=self.phi.get(l), mg=d.get("g_mg", ()))
                self.poisson_iters[l] = nit
            self.phi[l] = phi
            self.fg[l] = gs.grad_phi(phi, ghosts, d["g_nb"],
                                     jnp.asarray(dx, phi.dtype),
                                     d["g_valid"], nd).astype(self.dtype)
        if self.pic and rho_max is not None:
            self._rho_max = float(rho_max)   # one host sync per solve

    def _grav_pm_pre(self, dt: float):
        """Pre-sweep gravity/PM sequence shared by the solver families:
        rebuild particle maps, solve the per-level Poisson problem, and
        complete the previous half-kick + this step's opening half-kick
        with the new force at x^n (``synchro_fine``)."""
        from ramses_tpu.pm import particles as pmod
        if self.pic:
            with self.timers.section("particles: maps"):
                self._build_pm()
        if self.gravity:
            with self.timers.section("poisson"):
                self.solve_gravity()
        if self.pic and self.gravity:
            with self.timers.section("particles: kick"):
                f_at_p = self._pm_force()
                self.p = pmod.kick(self.p, f_at_p,
                                   0.5 * (self.dt_old + dt))

    def _pm_drift(self, dt: float):
        """``move_fine``: drift with the coarse dt (fine levels would
        split it into exact halves with the same frozen force)."""
        from ramses_tpu.pm import particles as pmod
        if self.pic:
            with self.timers.section("particles: drift"):
                self.p = pmod.drift(self.p, dt, self.boxlen,
                                    periodic=self.grav_periodic)

    def step_coarse(self, dt: float):
        if self.cosmo is not None and (self.cool_tables is not None
                                       or self.units is not None):
            # supercomoving unit scales are aexp-dependent
            # (``amr/units.f90``): refresh the host Units (SF/sinks) and
            # the traced cooling scales EVERY coarse step, and
            # re-tabulate the UV/cooling tables at 2% aexp granularity
            # (``set_table(aexp)`` per coarse step)
            from ramses_tpu.units import units as units_fn
            a = self.aexp_now()
            un = units_fn(self.params, cosmo=self.cosmo, aexp=a)
            if self.units is not None:
                self.units = un
            if self.cool_tables is not None:
                self._cool_scales = jnp.asarray(
                    [un.scale_T2, un.scale_nH, un.scale_t])
                if abs(a - self._cool_aexp) > 0.02 * self._cool_aexp:
                    from ramses_tpu.hydro.cooling import build_tables
                    c = self.params.cooling
                    self.cool_tables = build_tables(
                        aexp=a, J21=float(c.J21), a_spec=float(c.a_spec),
                        z_reion=float(c.z_reion),
                        haardt_madau=bool(c.haardt_madau))
                    self._cool_aexp = a
        self._grav_pm_pre(float(dt))
        spec = self._fused_spec()
        if spec.want_flux:
            # density BEFORE the step: the tracer jump probability
            # denominator (move_tracer.f90 uses the pre-step cell mass)
            self._tracer_rho0 = {l: self.u[l][:, 0] for l in self.levels()}
        with self.timers.section("hydro - godunov"):
            if self._offload is not None and self._offload.engaged(self):
                # out-of-core: the same step as per-level segments with
                # host-park/prefetch swap points (amr/offload.py) —
                # bitwise identical to the monolithic window
                self.u, self._dt_cache = self._offload.run_step(
                    self, float(dt), spec)
            else:
                args = (self.u, self.dev, self.fg if self.gravity else {},
                        jnp.asarray(float(dt), self.dtype), spec,
                        self._cool_bundle())
                _hlo.note_dispatch(_fused_coarse_step, *args)
                out = _fused_coarse_step(*args)
                if spec.want_flux:
                    self.u, self._dt_cache, self._tracer_phi = out
                else:
                    self.u, self._dt_cache = out
        self._pm_drift(float(dt))
        self.t += float(dt)
        self._source_passes(float(dt))
        self.dt_old = float(dt)
        self.nstep += 1

    def _source_passes(self, dt: float):
        """Coarse-cadence source physics on the hierarchy: star
        formation, SN feedback, sink passes, tracer advection
        (``amr_step`` order ``:369-380,493,549-567``)."""
        from ramses_tpu.pm import amr_physics as ap

        if self.sf_spec.enabled:
            with self.timers.section("star formation"):
                ap.star_formation_amr(self, dt)
                # f_w > 0 selects the mass-loaded kinetic wind scheme
                if self.sf_spec.f_w > 0:
                    ap.kinetic_feedback_amr(self)
                else:
                    ap.thermal_feedback_amr(self)
        if self.sinks is not None:
            with self.timers.section("sinks"):
                ap.sink_passes_amr(self, dt)
        if self.stellar is not None:
            from ramses_tpu.pm import stellar as stmod
            with self.timers.section("stellar"):
                self.stellar = stmod.make_stellar_from_sinks(
                    self.sinks, self.stellar, self.stellar_spec,
                    self._sf_rng, self.t)
                self.stellar = stmod.sn_from_stellar(
                    self, self.stellar, self.stellar_spec)
        if self.tracer_x is not None:
            with self.timers.section("tracers"):
                if getattr(self, "_tracer_phi", None) is not None:
                    # MC flux-probability jumps (pm/move_tracer.f90) —
                    # the fused step captured this step's face fluxes
                    ap.mc_tracer_amr(self)
                else:
                    # no flux capture on this path (MHD hierarchy):
                    # velocity tracers
                    ap.tracer_drift_amr(self, dt)
        if self.movie is not None and self.nstep % self.movie_imov == 0:
            with self.timers.section("movie"):
                self.movie.emit_amr(self)
        if bool(self.params.run.lightcone) and self.cosmo is not None \
                and self.p is not None:
            # output_cone every coarse step (amr_step.f90:177-178)
            from ramses_tpu.pm import lightcone as lcmod
            with self.timers.section("lightcone"):
                lcmod.emit_coarse_step(
                    self, outdir=str(self.params.output.output_dir))
        if self.rt_amr is not None:
            with self.timers.section("rt"):
                self.rt_amr.advance(self, dt)
        from ramses_tpu import patch
        user_source = patch.hook("source")
        if user_source is not None:
            with self.timers.section("patch source"):
                user_source(self, dt)
        if (self.sf_spec.enabled or self.sinks is not None
                or user_source is not None):
            # the passes changed u AFTER the fused step emitted the next
            # CFL dt — an SN dump makes that cached dt ~1000x too large
            # (the reference re-evaluates courant_fine after the source
            # sweep for the same reason); force a fresh evaluation
            self._dt_cache = None

    def step_chunk(self, nsteps: int, tend: float, trace: bool = False):
        """Run up to ``nsteps`` hydro-only coarse steps in ONE device
        dispatch (``_fused_multi_step``); returns steps done.  Callers
        guarantee no regrid is due inside the chunk.

        ``trace=True`` (telemetry-instrumented runs only): also return
        per-step ``(t, dt)`` host arrays from the scan's stacked
        outputs — one extra summary fetch, the fused program itself is
        unchanged in structure."""
        assert not self.gravity and not self.pic
        if self._offload is not None:
            # the multi-step window keeps the whole hierarchy in one
            # donated scan carry — callers gate chunking on engagement,
            # this is the defensive unpark for direct calls
            self._offload.unpark_all(self)
        spec = self._fused_spec()
        tdtype = jnp.result_type(float)
        if self._dt_cache is not None:
            dt0 = jnp.asarray(self._dt_cache, tdtype)
        else:
            dt0 = jnp.min(_fused_courant(self.u, self.dev, spec)) \
                .astype(tdtype)
        with self.timers.section("hydro - godunov"):
            out = _fused_multi_step(
                self.u, self.dev, jnp.asarray(self.t, tdtype),
                jnp.asarray(tend, tdtype), dt0, spec, nsteps,
                self._cool_bundle(), trace=trace)
            if trace:
                u, t, dtn, ndone, hist = out
            else:
                u, t, dtn, ndone = out
            self.u = u
            self._dt_cache = dtn
        # the program above was only dispatched: these fetches block
        # until the device has run it
        with self.timers.section("evolve: wait"):
            self.t = float(t)
            n = int(ndone)
            self.dt_old = float(dtn)
            if trace:
                ts, dts = jax.device_get(hist)
        self.nstep += n
        if trace:
            return n, (ts[:n], dts[:n])
        return n

    # ------------------------------------------------------------------
    # in-run fault recovery (resilience/stepguard; &RUN_PARAMS
    # max_step_retries) — shared by every AmrSim solver family via
    # inheritance (sharded, MHD, RHD)
    # ------------------------------------------------------------------
    def _guard_capture(self):
        """Retain a pre-step device-side copy of the advancing state.
        The fused steps DONATE their input buffers, so the capture must
        be real device copies (``.copy()`` — no host transfer), not
        references; the tree/layouts are untouched by step_coarse/
        step_chunk so host references suffice for everything else."""
        snap = {
            "u": {l: self.u[l].copy() for l in self.levels()},
            "t": float(self.t), "nstep": int(self.nstep),
            "dt_old": float(getattr(self, "dt_old", 0.0)),
            "dt_cache": (float(self._dt_cache)
                         if self._dt_cache is not None else None),
        }
        bf = getattr(self, "bf", None)
        if isinstance(bf, dict):
            snap["bf"] = {l: v.copy() for l, v in bf.items()}
        self._guard_snap = snap

    def _guard_restore(self):
        """Reinstate the captured pre-step state with FRESH copies —
        a retried step donates its inputs too, so handing out the
        capture itself would die on the first retry."""
        snap = self._guard_snap
        self.u = {l: v.copy() for l, v in snap["u"].items()}
        if "bf" in snap:
            self.bf = {l: v.copy() for l, v in snap["bf"].items()}
        self.t = snap["t"]
        self.nstep = snap["nstep"]
        self.dt_old = snap["dt_old"]
        self._dt_cache = snap["dt_cache"]

    def _probe_finite(self) -> bool:
        """Did the step just taken stay finite?  Reads the dtnew the
        next ``coarse_dt`` fetches anyway (the fused step's Courant
        reduction touches every updated cell, so a NaN anywhere
        poisons it); when source passes invalidated the cache, one
        Courant fetch is paid and stashed back for coarse_dt."""
        from ramses_tpu.resilience.stepguard import StepGuard
        if self._dt_cache is None:
            self._dt_cache = float(jnp.min(_fused_courant(
                self.u, self.dev, self._fused_spec(),
                self.fg if (self.gravity and self.fg) else None)))
        return StepGuard.ok(float(self._dt_cache), self.t,
                            getattr(self, "dt_old", 0.0))

    def _recover_step(self, tend: float):
        """Redo-step ladder: restore the retained capture, retry ONE
        coarse step at dt halved per attempt, escalating the Riemann
        solver to diffusive LLF from the second attempt
        (``dataclasses.replace`` + spec rebuild; not sticky).  When the
        ladder is spent: restore the clean state, emergency-dump it
        (iout 999) and raise :class:`StepRetryExhausted`."""
        import dataclasses as _dc

        from ramses_tpu.resilience.stepguard import (StepGuard,
                                                     StepRetryExhausted)
        sg = self._sguard
        if self._guard_snap is None:
            raise StepRetryExhausted(
                "non-finite state with no retained pre-step capture "
                "(initial conditions already non-finite?)")
        sg.record_trip(self)
        cfg0 = self.cfg
        can_escalate = hasattr(cfg0, "riemann")   # RhdStatic has none
        try:
            for attempt in range(1, sg.max_retries + 1):
                self._guard_restore()
                escalated = attempt >= 2 and can_escalate
                if escalated:
                    self.cfg = _dc.replace(cfg0, riemann="llf")
                    self._spec = None
                dt = min(self.coarse_dt(), tend - self.t) \
                    * (0.5 ** attempt)
                if not StepGuard.ok(dt) or dt <= 0.0:
                    continue
                sg.record_rollback(self, attempt, dt, escalated)
                t0 = time.perf_counter()
                try:
                    self.step_coarse(dt)
                except FloatingPointError:
                    continue      # jax_debug_nans raised mid-retry
                if self._probe_finite():
                    sg.record_recovered(self, attempt)
                    if self.telemetry.enabled:
                        # one record for the recovered step, keeping
                        # the step-record count identical to a clean
                        # run's (the poisoned window emitted none)
                        self.telemetry.record_step(
                            self, dt=dt,
                            wall_s=time.perf_counter() - t0)
                    return
        finally:
            if self.cfg is not cfg0:
                self.cfg = cfg0
                self._spec = None
        self._guard_restore()     # the abort path leaves a CLEAN state
        out = None
        try:
            out = self.dump(999, str(self.params.output.output_dir))
        except Exception as e:    # the abort itself must not be masked
            print(f"resilience: emergency dump failed: {e}")
        sg.record_abort(self, out)
        raise StepRetryExhausted(
            f"coarse step {self.nstep} non-finite after "
            f"{sg.max_retries} retries (t={self.t:.6g}); last clean "
            f"state dumped to {out}")

    def evolve(self, tend: float, nstepmax: int = 10 ** 9,
               verbose: bool = False, guard=None):
        """Advance to ``tend``.  ``guard``: optional
        :class:`ramses_tpu.utils.ops.OpsGuard` — signal/walltime/stop-file
        handling + the per-``ncontrol`` screen block."""
        ncontrol = max(1, int(self.params.run.ncontrol))
        telem = self.telemetry
        # verbose/telemetry are pure reporting: the chunked fast path
        # stays eligible and reports from its summary (``trace``) —
        # the old behaviour of dropping to the per-step slow path on
        # ``verbose=True`` silently benchmarked a different program
        instrumented = telem.enabled or verbose
        if telem.enabled and not telem.run_info:
            telem.run_info.update(sim_run_info(self))
            import os as _os

            if _os.environ.get("RAMSES_TELEMETRY_HLO", "1") != "0":
                # static gather-traffic inventory of the fused coarse
                # step for this tree: a lowering (trace, no compile),
                # recorded once per run for offline trend tracking
                try:
                    txt = _hlo.lower_fused_step(self)
                    inv = _hlo.gather_inventory(txt)
                    telem.run_info["hlo_gather_elems"] = \
                        sum(n for n, _ in inv)
                    telem.run_info["hlo_gather_ops"] = len(inv)
                    # static-analysis audit of the same lowering:
                    # severity counts of UNBASELINED findings (see
                    # ramses_tpu/analysis) — nonzero error/warn here
                    # means this exact run pays for a hazard the lint
                    # gate would flag
                    from ramses_tpu.analysis import engine as _aeng
                    telem.run_info["analysis_findings"] = \
                        _aeng.audit_sim(self, text=txt)
                except Exception as e:  # pragma: no cover - best effort
                    telem.run_info["hlo_gather_elems"] = None
                    telem.run_info["hlo_gather_error"] = repr(e)
        sguard = self._sguard
        while self.t < tend * (1 - 1e-12) and self.nstep < nstepmax:
            if guard is not None:
                if not guard.check():
                    break
                if self.nstep % ncontrol == 0:
                    print(guard.screen_block())
            if self.regrid_interval and \
                    self.nstep % self.regrid_interval == 0:
                self.regrid()
            # chunk until the next regrid / nstepmax boundary: hydro-only
            # steps need no host work in between, so they run as one
            # fused multi-step program
            if self.regrid_interval:
                to_regrid = self.regrid_interval \
                    - self.nstep % self.regrid_interval
            else:
                to_regrid = 1 << 30
            # cap: bounds compiled-scan length AND the post-tend no-op
            # tail (masked steps still execute inside the scan)
            from ramses_tpu import patch as _patch
            lim = min(to_regrid, nstepmax - self.nstep, 64)
            # canonical power-of-two scan lengths: every (regrid-interval,
            # nstepmax) combination decomposes into the same handful of
            # compiled programs instead of compiling one per remainder
            chunk = 1 << (max(lim, 1).bit_length() - 1)
            if self._fault is not None:
                # pending step-indexed faults must land exactly at
                # their target step, not at a chunk boundary (clamped
                # to 1 this drops to the per-step path below)
                chunk = self._fault.clamp_window(self.nstep, chunk)
            if not self.gravity and not self.pic \
                    and self.cosmo is None and self.sinks is None \
                    and self.tracer_x is None and self.movie is None \
                    and getattr(self, "rt_amr", None) is None \
                    and _patch.hook("source") is None and chunk > 1 \
                    and (self._offload is None
                         or not self._offload.engaged(self)):
                if sguard is not None:
                    # capture BEFORE injection: the injected NaN plays
                    # a transient solver fault, so the retained state
                    # must be the clean pre-fault one
                    self._guard_capture()
                if self._fault is not None:
                    self._fault.maybe_nan(self)
                if not instrumented:
                    with self._step_guard():
                        if self._fault is not None:
                            self._fault.maybe_hang(self.nstep)
                        n = self.step_chunk(chunk, tend)
                    self._wd_note()
                    if sguard is not None \
                            and not sguard.ok(self.t, self.dt_old):
                        self._recover_step(tend)
                        continue
                    if n == 0:
                        break
                    continue
                t0 = time.perf_counter()
                with self._step_guard():
                    if self._fault is not None:
                        self._fault.maybe_hang(self.nstep)
                    n, (ts, dts) = self.step_chunk(chunk, tend,
                                                   trace=True)
                self._wd_note()
                if sguard is not None \
                        and not sguard.ok(self.t, self.dt_old):
                    # rolled-back window: its poisoned records are
                    # dropped; the recovery emits one step record
                    self._recover_step(tend)
                    continue
                if n == 0:
                    break
                wall = time.perf_counter() - t0
                telem.record_chunk(self, ts, dts, n, wall)
                if verbose:
                    print(telemetry_screen.step_line(
                        self, dt=float(dts[-1]), chunk=n))
                continue
            dt = min(self.coarse_dt(), tend - self.t)
            if sguard is not None:
                self._guard_capture()
            if self._fault is not None:
                self._fault.maybe_nan(self)
            t0 = time.perf_counter() if instrumented else 0.0
            with self._step_guard():
                if self._fault is not None:
                    self._fault.maybe_hang(self.nstep)
                self.step_coarse(dt)
            self._wd_note()
            # trip detection BEFORE the telemetry record and before the
            # next iteration's regrid rebuilds the tree on a poisoned
            # state (which would make the capture unrestorable): the
            # probe reads the dtnew the next coarse_dt fetches anyway
            if sguard is not None and not self._probe_finite():
                self._recover_step(tend)
                continue
            if instrumented:
                if telem.enabled:
                    telem.record_step(
                        self, dt=dt, wall_s=time.perf_counter() - t0)
                if verbose:
                    print(telemetry_screen.step_line(self, dt=dt))

    def _step_guard(self):
        """Watchdog deadline guard for one fused window / coarse step
        (nullcontext when the watchdog is off — zero added fetches)."""
        return (self._wd.guard("step") if self._wd is not None
                else nullcontext())

    def _wd_note(self):
        if self._wd is not None:
            self._wd.note(nstep=self.nstep, t=self.t)

    # ------------------------------------------------------------------
    # diagnostics
    # ------------------------------------------------------------------
    def drain(self):
        """Hard device sync: fetch one row per level."""
        jax.device_get([self.u[l][:1, 0] for l in self.levels()])

    def totals(self):
        """Conservation audit over leaf cells (``check_cons``)."""
        cfg = self.cfg
        tot = np.zeros(cfg.nvar)
        for l in self.levels():
            vol = self.dx(l) ** cfg.ndim
            u = self.tree_order_cells(self.u[l], l)
            leaf = ~self.tree.refined_mask(l)
            tot += u[leaf].sum(axis=0) * vol
        return tot

    def leaf_sample(self, lvl: int):
        """(centers [n, ndim], u [n, nvar]) of leaf cells at one level."""
        u = self.tree_order_cells(self.u[lvl], lvl)
        leaf = ~self.tree.refined_mask(lvl)
        return self.tree.cell_centers(lvl, self.boxlen)[leaf], u[leaf]

    def ncell_leaf(self) -> int:
        return sum(int((~self.tree.refined_mask(l)).sum())
                   for l in self.levels())

    # ------------------------------------------------------------------
    # snapshot / restart (SURVEY.md §3.4, §5.4)
    # ------------------------------------------------------------------
    def dump(self, iout: int = 1, base_dir: str = ".",
             namelist_path: Optional[str] = None, ncpu: int = 1,
             dumper=None) -> str:
        """Write a reference-format ``output_NNNNN/`` snapshot
        (``ncpu > 1``: one file set per domain — multi-domain
        checkpoint restorable onto any device count).

        ``dumper``: optional :class:`~ramses_tpu.io.async_writer.
        AsyncDumper` — the host-resident snapshot is assembled
        synchronously, the file writing happens on its background
        thread (the ``pario`` offload, SURVEY.md §2.10).

        ``&OUTPUT_PARAMS pario=.true.`` routes to the elastic sharded
        checkpoint instead (``pario_NNNNN/`` shard dirs, two-phase
        global commit, mesh-shape-elastic restore)."""
        import os
        import shutil

        if bool(getattr(self.params.output, "pario", False)):
            return self.dump_pario(iout, base_dir)

        from ramses_tpu.io import snapshot as snapmod
        snap = snapmod.snapshot_from_amr(self, iout)
        final = os.path.join(base_dir, f"output_{iout:05d}")
        # driver extras (sink/stellar CSVs, clump catalogues, merger
        # tree) are gathered synchronously into a staging dir that
        # dump_all folds into the checkpoint BEFORE the manifest +
        # atomic rename — writing them into the final directory
        # afterwards would leave them outside the manifest
        extra = final + ".extras.tmp"
        if os.path.isdir(extra):
            shutil.rmtree(extra)
        self._dump_csv_extras(extra, iout)
        self._clumpfind_pass(extra, iout)
        if not os.path.isdir(extra) or not os.listdir(extra):
            shutil.rmtree(extra, ignore_errors=True)
            extra = None
        keep = int(getattr(self.params.output, "checkpoint_keep", 0))
        if dumper is not None:
            dumper.submit(snap, iout, base_dir,
                          namelist_path=namelist_path, ncpu=ncpu,
                          extra_dir=extra, keep_last=keep)
            out = final
        else:
            out = snapmod.dump_all(snap, iout, base_dir,
                                   namelist_path=namelist_path,
                                   ncpu=ncpu, extra_dir=extra,
                                   keep_last=keep)
        return out

    def dump_pario(self, iout: int = 1, base_dir: str = ".",
                   io_group_size: Optional[int] = None,
                   split_hosts: Optional[int] = None) -> str:
        """Elastic sharded checkpoint (:mod:`ramses_tpu.io.pario`
        format 2): every process stages its own validated shard dir,
        process 0 seals the set under the watchdogged two-phase
        commit.  Defaults come from ``&OUTPUT_PARAMS io_group_size`` /
        ``pario_split_hosts``; ``checkpoint_keep`` rotation covers
        pario and snapshot checkpoints alike."""
        import os

        import jax

        from ramses_tpu.io.pario import dump_pario as _dp
        out = self.params.output
        if io_group_size is None:
            g = int(getattr(out, "io_group_size", 0))
            io_group_size = g if g > 0 else None
        if split_hosts is None:
            s = int(getattr(out, "pario_split_hosts", 0))
            split_hosts = s if s > 0 else None
        path = _dp(self, iout, base_dir,
                   io_group_size=io_group_size,
                   split_hosts=split_hosts)
        keep = int(getattr(out, "checkpoint_keep", 0))
        if keep > 0 and jax.process_index() == 0 \
                and not path.endswith(".tmp"):
            from ramses_tpu.resilience import rotate_checkpoints
            rotate_checkpoints(os.path.dirname(os.path.abspath(path))
                               or ".", keep, protect=path)
        return path

    def _clumpfind_pass(self, out: str, iout: int):
        """In-run PHEW chain at output time (``clumpfind=.true.``,
        ``pm/clump_finder.f90`` called from ``amr_step``/outputs):
        deposit the LIVE particles, watershed with saddle-relevance
        merging, unbind, write the clump table, and grow the run's
        merger tree across outputs (``pm/merger_tree.f90``).

        Runs synchronously inside ``dump`` (cost bounded by
        ``nx_clump^ndim`` + per-clump unbinding) — an AsyncDumper
        offloads the FILE writing only, like the reference whose
        clump finder also runs inline at outputs.  The tree's halo
        catalogues persist per output (``clump_cat_NNNNN.npz``) so a
        restart rebuilds the cross-output links (the reference
        re-reads progenitor data from prior outputs the same way)."""
        import glob
        import os

        if not bool(getattr(self.params.run, "clumpfind", False)):
            return
        if self.p is None:
            import warnings
            warnings.warn("clumpfind=.true. needs particles (pic or "
                          "SF); no clump tables will be written")
            return
        from ramses_tpu.pm.halo import (Halo, MergerTree,
                                        write_halo_table)
        from ramses_tpu.utils.halos import catalogue_from_arrays
        cf = self.params.clumpfind
        act = np.asarray(self.p.active)
        x = np.asarray(self.p.x)[act]
        if len(x) == 0:
            return
        halos = catalogue_from_arrays(
            x, np.asarray(self.p.v)[act], np.asarray(self.p.m)[act],
            np.asarray(self.p.idp)[act], self.boxlen,
            nx=int(cf.nx_clump), threshold=float(cf.density_threshold),
            relevance=float(cf.relevance_threshold),
            npart_min=int(cf.npart_min), unbind=bool(cf.unbind),
            saddle_pot=bool(cf.saddle_pot),
            nmassbins=int(cf.nmassbins),
            saddle_threshold=max(float(cf.saddle_threshold), 0.0))
        if cf.mass_threshold > 0 and act.any():
            mp = float(np.asarray(self.p.m)[act].mean())
            halos = [h for h in halos
                     if h.mass >= cf.mass_threshold * mp]
        os.makedirs(out, exist_ok=True)
        write_halo_table(halos,
                         os.path.join(out, f"clump_{iout:05d}.txt"))
        if not hasattr(self, "_mergertree"):
            self._mergertree = MergerTree()
            # restart: rebuild the tree from the catalogues persisted
            # alongside earlier outputs (they carry the particle ids
            # the id-based linking needs).  ids ride as a flat int
            # array + offsets — no object arrays, no allow_pickle —
            # and the output index comes from the filename pattern,
            # skipping anything that doesn't match.
            import re
            base = os.path.dirname(os.path.abspath(out))
            for f in sorted(glob.glob(
                    os.path.join(base, "output_*",
                                 "clump_cat_*.npz"))):
                mm_ = re.search(r"clump_cat_(\d+)\.npz$",
                                os.path.basename(f))
                # only catalogues from BEFORE this output (a restart
                # may overwrite later outputs of the aborted run)
                if mm_ is None or int(mm_.group(1)) >= iout:
                    continue
                try:
                    z = np.load(f)
                    if "ids_off" in z.files:
                        off = np.asarray(z["ids_off"], dtype=np.int64)
                        flat = np.asarray(z["ids_flat"], dtype=np.int64)
                        ids = [flat[off[k]:off[k + 1]]
                               for k in range(len(off) - 1)]
                    elif "ids" in z.files:
                        # legacy r04 object-array layout: the one case
                        # allow_pickle is still accepted for, so an
                        # existing run's history survives the format
                        # change
                        z = np.load(f, allow_pickle=True)
                        ids = [np.asarray(i, dtype=np.int64)
                               for i in z["ids"]]
                    else:
                        raise KeyError("no ids_off/ids record")
                    old = [Halo(index=int(i), mass=float(mm),
                                npart=len(hid), pos=pp, vel=vv,
                                ekin=0.0, epot=0.0, ids=hid)
                           for i, mm, pp, vv, hid in zip(
                               z["index"], z["mass"], z["pos"],
                               z["vel"], ids)]
                    t_snap = float(z["t"])
                except Exception as e:      # truncated zip, missing keys
                    import warnings
                    warnings.warn(f"skipping malformed clump "
                                  f"catalogue {f}: {e}")
                    continue
                self._mergertree.add_snapshot(t_snap, old)
        ids_off = np.concatenate(
            [[0], np.cumsum([len(h.ids) for h in halos])]
        ).astype(np.int64)
        np.savez_compressed(
            os.path.join(out, f"clump_cat_{iout:05d}.npz"),
            t=float(self.t),
            index=np.array([h.index for h in halos]),
            mass=np.array([h.mass for h in halos]),
            pos=np.array([h.pos for h in halos]),
            vel=np.array([h.vel for h in halos]),
            ids_off=ids_off,
            ids_flat=(np.concatenate([h.ids for h in halos])
                      if halos else np.zeros(0)).astype(np.int64))
        self._mergertree.add_snapshot(float(self.t), halos)
        if len(self._mergertree.snapshots) > 1:
            self._mergertree.write(
                os.path.join(out, f"mergertree_{iout:05d}.txt"))

    def _dump_csv_extras(self, out: str, iout: int):
        """Sink/stellar CSV companions for the output
        (``pm/output_sink.f90``, ``pm/output_stellar.f90`` — the
        reference oracle reads both).  Tiny host writes into the
        extras staging dir, folded under the checkpoint manifest by
        dump_all before the atomic rename."""
        import os

        from ramses_tpu.io import snapshot as snapmod
        if self.sinks is None and getattr(self, "stellar", None) is None:
            return
        os.makedirs(out, exist_ok=True)
        if self.sinks is not None:
            dmf = (self.stellar.dmf
                   if getattr(self, "stellar", None) is not None else None)
            snapmod.write_sink_csv(
                os.path.join(out, f"sink_{iout:05d}.csv"), self.sinks,
                dmf)
        if getattr(self, "stellar", None) is not None:
            snapmod.write_stellar_csv(
                os.path.join(out, f"stellar_{iout:05d}.csv"),
                self.stellar)

    @classmethod
    def from_snapshot(cls, params: Params, outdir: str,
                      dtype=jnp.float32, **kw) -> "AmrSim":
        """Resume from a snapshot directory (``nrestart`` path); ``kw``
        goes to the constructor (the sharded class's ``devices``)."""
        from ramses_tpu.io.snapshot import prim_out_to_cons
        cfg = cls._make_cfg(params)
        sim, _parts = restore_amr_scaffold(
            cls, params, outdir, dtype,
            to_cons=lambda q: prim_out_to_cons(q, cfg),
            place_level=_place_u_rows, **kw)
        return sim

    @classmethod
    def from_checkpoint_dir(cls, params: Params, outdir: str,
                            dtype=jnp.float32, log=print,
                            **kw) -> "AmrSim":
        """Restore from any checkpoint directory: ``pario_NNNNN``
        elastic sharded dumps go through the mesh-shape-elastic
        reader, everything else through :meth:`from_snapshot`.  A
        pario checkpoint whose surviving shards cannot cover the
        hierarchy is quarantined shard-by-shard and the restore falls
        back to the next-oldest globally-valid checkpoint — the same
        degrade-don't-die contract ``resolve_restart_dir`` applies to
        whole-checkpoint rot."""
        import os

        from ramses_tpu.io import pario as pariomod
        from ramses_tpu.resilience import latest_valid_checkpoint
        cur = outdir
        seen = set()
        while True:
            seen.add(os.path.abspath(cur))
            name = os.path.basename(os.path.normpath(cur))
            if not name.startswith("pario_"):
                return cls.from_snapshot(params, cur, dtype=dtype, **kw)
            try:
                return pariomod.restore_pario(cls, params, cur,
                                              dtype=dtype, log=log,
                                              **kw)
            except pariomod.CorruptShardError as e:
                if log is not None:
                    log(f"resilience: {e}; falling back to the "
                        "next-oldest valid checkpoint")
                base = os.path.dirname(os.path.abspath(cur)) or "."
                nxt = latest_valid_checkpoint(base, log=log)
                if nxt is None or os.path.abspath(nxt) in seen:
                    raise
                cur = nxt
