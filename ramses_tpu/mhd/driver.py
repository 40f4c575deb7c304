"""MHD simulation driver: region ICs, time loop, snapshots.

The ``SOLVER=mhd`` build of the reference selected at compile time via
VPATH shadowing (SURVEY.md §1 L0); here it is a runtime solver choice.
Region ICs follow ``mhd/init_flow_fine.f90:475-596``: square regions set
[d, u, v, w, P] plus a uniform field [A_region, B_region, C_region]
(both faces, ``:529-532``).
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.config import Params
from ramses_tpu.grid import boundary as bmod
from ramses_tpu.mhd import core, uniform as mu
from ramses_tpu.mhd.core import IBX, IP, MhdStatic, NCOMP
from ramses_tpu.telemetry import make_telemetry, sim_run_info
from ramses_tpu.telemetry import screen as telemetry_screen
from ramses_tpu.utils.timers import NullTimers, Timers


def _region_mask(x, k, init, ndim, period=None):
    """Cells of region ``k``.  ``period``: per dimension the box length
    where the box is periodic there, else None — a region that crosses
    a periodic face continues on the other side (its nearest image
    counts), so a region moved through a periodic box is the same region
    translated."""
    centers = [init.x_center, init.y_center, init.z_center]
    lengths = [init.length_x, init.length_y, init.length_z]

    def reach(d):
        r = np.abs(x[d] - centers[d][k])
        if period is not None and period[d]:
            r = np.mod(r, period[d])
            r = np.minimum(r, period[d] - r)
        return 2.0 * r / lengths[d][k]

    en = float(init.exp_region[k])
    if en < 10.0:
        r = sum(reach(d) ** en for d in range(ndim)) ** (1.0 / en)
    else:
        r = np.maximum.reduce([reach(d) for d in range(ndim)])
    return r < 1.0


def region_periods(p: Params, lengths):
    """What :func:`_region_mask` takes as ``period``: per dimension the
    box length where both faces are periodic, else None."""
    faces = bmod.BoundarySpec.from_params(p).faces
    return [ext if faces[d][0].kind == faces[d][1].kind == bmod.PERIODIC
            else None for d, ext in enumerate(lengths)]


def mhd_condinit(shape, dx: float, p: Params, cfg: MhdStatic):
    """(u [nvar, *sp], bf [3, *sp]): conservative cell state + staggered
    faces from &INIT_PARAMS regions (uniform B per region)."""
    from ramses_tpu import patch
    if patch.hook("condinit") is not None:
        import warnings
        warnings.warn(
            "patch condinit hook is not applied to the MHD solver: MHD "
            "ICs need divergence-free STAGGERED face fields, which the "
            "primitive-state hook cannot provide; using &INIT_PARAMS "
            "regions instead")
    init = p.init
    ndim = cfg.ndim
    axes_c = [(np.arange(n) + 0.5) * dx for n in shape]

    q = np.zeros((cfg.nvar,) + tuple(shape))
    q[0] = cfg.smallr
    q[IP] = cfg.smallr * cfg.smallc ** 2 / cfg.gamma
    vels = [init.u_region, init.v_region, init.w_region]
    bvals = [init.A_region, init.B_region, init.C_region]

    # staggered faces: each cell's LOW face takes the owning cell's region
    # value — exactly how the reference seeds both face fields from the
    # cell's region (``mhd/init_flow_fine.f90:529-532``); evaluating at
    # face centres would leave faces that sit exactly on a region border
    # (including the domain edge) unset
    bf = np.zeros((NCOMP,) + tuple(shape))
    xc = np.meshgrid(*axes_c, indexing="ij")
    period = region_periods(p, [n * dx for n in shape])
    for k in range(init.nregion):
        if str(init.region_type[k]).strip() != "square":
            raise NotImplementedError("mhd ICs: square regions only")
        m = _region_mask(xc, k, init, ndim, period)
        q[0][m] = init.d_region[k]
        for c in range(NCOMP):
            q[1 + c][m] = vels[c][k]
            bf[c][m] = bvals[c][k]
        q[IP][m] = init.p_region[k]

    for c in range(NCOMP):
        if c < ndim:
            q[IBX + c] = 0.5 * (bf[c] + np.roll(bf[c], -1, axis=c))
        else:
            q[IBX + c] = bf[c]
    u = np.asarray(core.prim_to_cons(jnp.asarray(q), cfg))
    return u, bf


class MhdSimulation:
    """Uniform-grid MHD run (CT solver, SURVEY.md §7 stage 7)."""

    # the kernel whose block picks the ``[kernel]`` line and
    # ``run_header.sweep_block`` show (``telemetry/screen.sweep_kernel``)
    sweep_kernel = "pallas_ct"

    def __init__(self, params: Params, dtype=jnp.float64):
        self.params = params
        self.cfg = MhdStatic.from_params(params)
        base = [params.amr.nx, params.amr.ny, params.amr.nz][:params.ndim]
        if any(b != 1 for b in base):
            # this solver family builds cubic grids; only the hydro
            # uniform driver supports non-cubic coarse boxes
            raise NotImplementedError(
                f"MHD requires nx=ny=nz=1 (got {base})")
        lmin = params.amr.levelmin
        n = 2 ** lmin
        shape = tuple([n] * params.ndim)
        self.dx = params.amr.boxlen / n
        spec = bmod.BoundarySpec.from_params(params)
        bc_kinds = tuple((f[0].kind, f[1].kind) for f in spec.faces)
        for lo, hi in bc_kinds:
            for k in (lo, hi):
                if k not in (bmod.PERIODIC, bmod.OUTFLOW):
                    raise NotImplementedError(
                        "mhd boundaries: periodic/outflow only")
        self.grid = mu.MhdGrid(cfg=self.cfg, shape=shape, dx=self.dx,
                               bc_kinds=bc_kinds)
        u0, bf0 = mhd_condinit(shape, self.dx, params, self.cfg)
        self.u = jnp.asarray(u0, dtype=dtype)
        self.bf = jnp.asarray(bf0, dtype=dtype)
        self.t = 0.0
        self.nstep = 0
        self.iout = 1
        self.cell_updates = 0
        self.wall_s = 0.0
        self.telemetry = make_telemetry(params)
        # phase spans as driver.Simulation has them: ``evolve`` holds
        # ``evolve: dispatch`` and ``evolve: wait``
        self.timers = Timers() if self.telemetry.enabled else NullTimers()
        from ramses_tpu.resilience.faultinject import FaultInjector
        from ramses_tpu.resilience.stepguard import StepGuard
        self._sguard = StepGuard.from_params(params,
                                             telemetry=self.telemetry)
        self._fault = FaultInjector.from_params(params)
        from ramses_tpu.resilience.watchdog import Watchdog
        self._wd = Watchdog.from_params(params, telemetry=self.telemetry)

    def mus_per_cell_update(self) -> float:
        return 1e6 * self.wall_s / max(self.cell_updates, 1)

    def evolve(self, tend: Optional[float] = None, chunk: int = 16,
               nstepmax: int = 10 ** 9, verbose: bool = False,
               guard=None):
        p = self.params
        tend = tend if tend is not None else (
            p.output.tout[-1] if p.output.tout else p.output.tend)
        tdtype = (jnp.float64 if jax.config.jax_enable_x64
                  else jnp.float32)
        telem = self.telemetry
        if telem.enabled:
            telem.run_info.update(sim_run_info(self))
        while self.t < tend * (1.0 - 1e-12) and self.nstep < nstepmax:
            with self.timers.section("evolve"):
                if guard is not None and not guard.check():
                    break
                ndone = self._window(min(chunk, nstepmax - self.nstep),
                                     tend, tdtype, verbose)
            if ndone == 0:
                break

    def _window(self, n: int, tend: float, tdtype, verbose: bool) -> int:
        """One fused window of up to ``n`` steps towards ``tend``: the
        dispatch, the blocking fetch, the bookkeeping.  Returns the
        steps done."""
        telem = self.telemetry
        # redo-step guard: run_steps does not donate, so plain
        # references retain the pre-window state for rollback
        prev = ((self.u, self.bf, self.t, self.nstep)
                if self._sguard is not None else None)
        if self._fault is not None:
            n = self._fault.clamp_window(self.nstep, n)
            self._fault.maybe_nan(self)
        t0 = time.perf_counter()
        t_before = self.t
        with (self._wd.guard("step") if self._wd is not None
                else nullcontext()):
            if self._fault is not None:
                self._fault.maybe_hang(self.nstep)
            with self.timers.section("evolve: dispatch"):
                u, bf, t, ndone = mu.run_steps(
                    self.grid, self.u, self.bf,
                    jnp.asarray(self.t, tdtype),
                    jnp.asarray(tend, tdtype), n)
            # only dispatched so far: these block until the device has
            # run the window
            with self.timers.section("evolve: wait"):
                u.block_until_ready()
                ndone = int(ndone)
                t = float(t)
        wall = time.perf_counter() - t0
        self.wall_s += wall
        self.u, self.bf, self.t = u, bf, t
        self.nstep += ndone
        if self._wd is not None:
            self._wd.note(nstep=self.nstep, t=self.t)
        self.cell_updates += ndone * self.grid.ncell
        if prev is not None and not self._sguard.ok(self.t):
            ndone = self._retry_window(prev, tend, tdtype)
        if telem.enabled and ndone:
            telem.record_step(
                self, dt=(self.t - t_before) / ndone, wall_s=wall,
                steps=ndone, t=self.t, nstep=self.nstep,
                chunked=ndone, extra={"divb": self.divb()})
        if verbose:
            print(telemetry_screen.step_line(
                self, dt=((self.t - t_before) / ndone
                          if ndone else None), chunk=ndone,
                extra=f"divb={float(self.max_divb()):.2e}"))
        return ndone

    def _retry_window(self, prev, tend, tdtype) -> int:
        """Redo-step ladder after a non-finite window (RAMSES redo-step):
        rollback, halve dt, escalate the 1D Riemann solver to LLF on the
        second retry, emergency-dump + abort when exhausted."""
        import dataclasses as _dc

        from ramses_tpu.resilience.stepguard import (StepGuard,
                                                     StepRetryExhausted)
        sg = self._sguard
        u0, bf0, t0, nstep0 = prev
        sg.record_trip(self)
        grid0 = self.grid
        try:
            for attempt in range(1, sg.max_retries + 1):
                self.u, self.bf, self.t = u0, bf0, t0
                self.nstep = nstep0
                escalated = attempt >= 2
                if escalated:
                    self.grid = _dc.replace(
                        grid0, cfg=_dc.replace(grid0.cfg, riemann="llf"))
                scale = 0.5 ** attempt
                sg.record_rollback(self, attempt, scale, escalated)
                tw = time.perf_counter()
                u, bf, t, ndone = mu.run_steps(
                    self.grid, u0, bf0, jnp.asarray(t0, tdtype),
                    jnp.asarray(tend, tdtype), 1, dt_scale=scale)
                u.block_until_ready()
                tf = float(t)
                if StepGuard.ok(tf):
                    ndone = int(ndone)
                    self.u, self.bf, self.t = u, bf, tf
                    self.nstep = nstep0 + ndone
                    self.cell_updates += ndone * self.grid.ncell
                    self.wall_s += time.perf_counter() - tw
                    sg.record_recovered(self, attempt)
                    return ndone
        finally:
            self.grid = grid0
        self.u, self.bf, self.t = u0, bf0, t0
        self.nstep = nstep0
        out = None
        try:
            out = self.dump(999, str(self.params.output.output_dir))
        except Exception as e:             # noqa: BLE001 - abort path
            print(f"resilience: emergency dump failed: {e}")
        sg.record_abort(self, out)
        raise StepRetryExhausted(
            f"mhd step at t={t0:.6g} still non-finite after "
            f"{sg.max_retries} retries")

    def max_divb(self):
        return jnp.max(jnp.abs(core.div_b(
            [self.bf[c] for c in range(NCOMP)],
            (self.dx,) * self.cfg.ndim, self.cfg.ndim)))

    def divb(self) -> float:
        """max |div B| * dx / max |B|: round-off under CT."""
        bmax = float(jnp.max(jnp.abs(self.bf)))
        return float(self.max_divb()) * self.dx / bmax if bmax else 0.0

    def totals(self):
        return mu.totals(self.u, self.cfg, self.dx)

    # ------------------------------------------------------------------
    # snapshot output (reference MHD layout: B left/right columns,
    # mhd/output_hydro.f90:88-149)
    # ------------------------------------------------------------------
    def var_names(self) -> List[str]:
        dims = "xyz"
        names = ["density"]
        names += [f"velocity_{dims[d]}" for d in range(self.cfg.ndim)]
        names += [f"B_{dims[c]}_left" for c in range(3)]
        names += [f"B_{dims[c]}_right" for c in range(3)]
        names += ["pressure"]
        names += [f"scalar_{i:02d}" for i in range(self.cfg.npassive)]
        return names

    def output_vars(self) -> np.ndarray:
        """[*sp, nvar_out] float64 in var_names() order."""
        cfg = self.cfg
        u = np.asarray(self.u, dtype=np.float64)
        bf = np.asarray(self.bf, dtype=np.float64)
        rho = np.maximum(u[0], cfg.smallr)
        cols = [u[0]]
        cols += [u[1 + d] / rho for d in range(cfg.ndim)]
        b_left, b_right = [], []
        for c in range(3):
            if c < cfg.ndim:
                b_left.append(bf[c])
                br = np.roll(bf[c], -1, axis=c)
                if self.grid.bc_kinds[c][1] != bmod.PERIODIC:
                    # outflow: the wrap would import the opposite edge;
                    # replicate the local edge face instead (zero-gradient)
                    idx = [slice(None)] * cfg.ndim
                    idx[c] = -1
                    br[tuple(idx)] = bf[c][tuple(idx)]
                b_right.append(br)
            else:
                b_left.append(u[IBX + c])
                b_right.append(u[IBX + c])
        cols += b_left + b_right
        ek = 0.5 * sum(u[1 + c] ** 2 for c in range(NCOMP)) / rho
        em = 0.5 * sum((0.5 * (bl + br)) ** 2
                       for bl, br in zip(b_left, b_right))
        cols.append((cfg.gamma - 1.0) * (u[IP] - ek - em))
        for s in range(cfg.npassive):
            cols.append(u[8 + s] / rho)
        return np.stack(cols, axis=-1)

    def dump(self, iout: int = 1, base_dir: str = ".",
             namelist_path: Optional[str] = None) -> str:
        from ramses_tpu.io import snapshot as sm
        from ramses_tpu.units import units as units_fn
        params = self.params
        lmin = params.amr.levelmin
        ndim = self.cfg.ndim
        dense = self.output_vars()
        levels = sm.uniform_levels_from_dense(dense, lmin, ndim)
        snap = sm.Snapshot(
            ndim=ndim, nlevelmax=max(params.amr.levelmax, lmin),
            levels=levels, boxlen=float(params.amr.boxlen), t=float(self.t),
            gamma=self.cfg.gamma, var_names=self.var_names(),
            units=units_fn(params), levelmin=lmin, nstep=self.nstep,
            nstep_coarse=self.nstep, tout=[params.output.tend or 0.0])
        return sm.dump_all(snap, iout, base_dir,
                           namelist_path=namelist_path,
                           keep_last=int(getattr(params.output,
                                                 "checkpoint_keep", 0)))

    @classmethod
    def from_snapshot(cls, params: Params, outdir: str,
                      dtype=jnp.float64) -> "MhdSimulation":
        """Rebuild from a :meth:`dump` directory (auto-resume restore).

        The MHD columns store B as left/right face pairs: the staggered
        ``bf`` comes straight back from the left columns and the
        cell-centred field from their average, so dump→restore round
        trips exactly at file precision.  Velocity components beyond
        ndim are not written by :meth:`output_vars` and restore as zero.
        """
        from ramses_tpu.amr.tree import cell_offsets
        from ramses_tpu.io.restart import restore_tree_state
        cfg = MhdStatic.from_params(params)
        lmin = params.amr.levelmin
        tree_og, rows_lv, meta, _parts = restore_tree_state(
            outdir, cfg, lmin, to_cons=lambda q: q)   # raw output rows
        if lmin not in rows_lv:
            raise ValueError(f"snapshot has no level {lmin} data")
        ndim = cfg.ndim
        n = 1 << lmin
        og = tree_og[lmin]
        offs = cell_offsets(ndim)
        cc = (2 * og[:, None, :] + offs[None, :, :]).reshape(-1, ndim)
        rows = rows_lv[lmin]                          # [ncell, nvar_out]
        dense = np.zeros((rows.shape[1],) + (n,) * ndim)
        idx = tuple(cc[:, d] for d in range(ndim))
        for iv in range(rows.shape[1]):
            dense[iv][idx] = rows[:, iv]
        ib = 1 + ndim                                 # first B_left column
        bl = dense[ib:ib + 3]
        br = dense[ib + 3:ib + 6]
        q = np.zeros((cfg.nvar,) + (n,) * ndim)
        q[0] = dense[0]
        for d in range(ndim):
            q[1 + d] = dense[1 + d]
        for c in range(NCOMP):
            q[IBX + c] = 0.5 * (bl[c] + br[c])
        q[IP] = dense[ib + 6]
        for s in range(cfg.npassive):
            q[8 + s] = dense[ib + 7 + s]              # per-mass scalar
        sim = cls(params, dtype=dtype)
        sim.u = jnp.asarray(np.asarray(core.prim_to_cons(
            jnp.asarray(q), cfg)), dtype=dtype)
        sim.bf = jnp.asarray(bl, dtype=dtype)
        sim.t = float(meta["t"])
        sim.nstep = int(meta["nstep"])
        sim.iout = max(int(meta["iout"]), 0) + 1
        return sim
