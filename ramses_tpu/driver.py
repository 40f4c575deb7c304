"""Simulation driver: config → initial state → time loop → outputs.

The equivalent of ``program ramses → adaptive_loop`` (``amr/ramses.f90:13``,
``amr/adaptive_loop.f90:79-230``) for the single-level path: host keeps
wall-clock/output bookkeeping; device advances in fused multi-step chunks.
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.config import Params, load_params
from ramses_tpu.grid import boundary as bmod
from ramses_tpu.grid.uniform import UniformGrid, run_steps, step
from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.init.regions import condinit
from ramses_tpu.pm.coupling import PMSpec, run_steps_pm, total_density
from ramses_tpu.pm.cosmology import Cosmology
from ramses_tpu.pm.particles import ParticleSet
from ramses_tpu.poisson.coupling import GravitySpec, gravity_field
from ramses_tpu.telemetry import make_telemetry, sim_run_info
from ramses_tpu.telemetry import screen as telemetry_screen
from ramses_tpu.utils.timers import NullTimers, Timers


@dataclass
class SimState:
    u: jax.Array
    t: float = 0.0
    nstep: int = 0
    dt: float = 0.0
    iout: int = 1  # next output slot (1-based, like the reference)
    f: Optional[jax.Array] = None  # gravity field [ndim, *sp] (poisson)
    p: Optional[ParticleSet] = None
    dt_old: float = 0.0            # previous step (split particle kick)


def load_cosmo_ics(params, cosmo, cfg, shape):
    """(ParticleSet, gas u [nvar, *shape] | None) from the namelist's
    ``initfile``/``filetype`` (``amr/init_time.f90:303-414`` init_file)."""
    from ramses_tpu.pm import init_part as ip

    path = params.init.initfile[0]
    want_gas = bool(params.run.hydro)
    if params.init.filetype == "grafic":
        x, v, m, ghdr = ip.particles_from_grafic(
            path, cosmo, omega_b=(cosmo.omega_b if want_gas else None))
        u0 = None
        if want_gas:
            dense, _ = ip.baryons_from_grafic(path, cosmo, cfg.gamma,
                                              cosmo.omega_b)
            if dense.shape[1:] != tuple(shape):
                raise ValueError(
                    f"grafic grid {dense.shape[1:]} != run grid {shape} "
                    "(levelmin must match the IC resolution)")
            u0 = np.zeros((cfg.nvar,) + tuple(shape))
            u0[:dense.shape[0]] = dense
        if abs(ghdr.astart - cosmo.aexp_ini) > 1e-3 * ghdr.astart:
            import warnings
            warnings.warn(f"grafic astart={ghdr.astart} != namelist "
                          f"aexp_ini={cosmo.aexp_ini}; file wins for "
                          "displacements, namelist for the time axis")
    else:
        x, v, m, _ = ip.particles_from_gadget(path, cosmo)
        u0 = None
    p = ParticleSet.make(jnp.asarray(x), jnp.asarray(v), jnp.asarray(m))
    return p, u0


class Simulation:
    """Single-level simulation (SURVEY.md §7 stage 2).

    Resolution is ``2**levelmin`` per dimension scaled by nx/ny/nz coarse
    cells, cell size ``boxlen / 2**levelmin`` in user units — matching the
    reference's fully-refined base mesh.
    """

    def __init__(self, params: Params, dtype=jnp.float32,
                 particles: Optional[ParticleSet] = None):
        from ramses_tpu import patch
        patch.maybe_install_from_params(params)
        self.params = params
        if getattr(params.hydro, "difmag", 0.0):
            import warnings
            warnings.warn("HYDRO_PARAMS difmag requested but not yet "
                          "implemented in this solver; running without.")
        self.cfg = HydroStatic.from_params(params)
        lmin = params.amr.levelmin
        n = 2 ** lmin
        base = [params.amr.nx, params.amr.ny, params.amr.nz][:params.ndim]
        shape = tuple(b * n for b in base)
        self.dx = params.amr.boxlen / n
        self.bc = bmod.BoundarySpec.from_params(params)
        self.grid = UniformGrid(cfg=self.cfg, shape=shape, dx=self.dx,
                                bc=self.bc)
        self.pspec = PMSpec.from_params(params)
        self.cosmo = (Cosmology.from_params(params) if params.run.cosmo
                      else None)
        # SF/sink specs early: the particle-lane budget below needs to
        # know whether the run keeps creating particles
        from ramses_tpu.pm.sinks import SinkSet, SinkSpec
        from ramses_tpu.pm.star_formation import SfSpec
        self.sf_spec = SfSpec.from_params(params)
        self.sink_spec = SinkSpec.from_params(params)
        # cosmological IC files (grafic/gadget): particles + baryons
        # (init_part.f90 / init_flow_fine.f90 'file' branches)
        u0 = None
        if (self.cosmo is not None and params.init.initfile
                and params.init.filetype in ("grafic", "gadget")
                and particles is None):
            particles, u0 = load_cosmo_ics(params, self.cosmo, self.cfg,
                                           shape)
        if u0 is None:
            u0 = condinit(shape, self.dx, params, self.cfg)
        self.state = SimState(u=jnp.asarray(u0, dtype=dtype))
        if self.pspec.enabled:
            from ramses_tpu.pm.particles import lane_headroom
            # pic without IC particles: an empty set whose lane budget
            # must leave room for SF/sink creation (a 1-lane set would
            # silently drop every new star)
            grows = self.sf_spec.enabled or self.sink_spec.enabled
            self.state.p = particles if particles is not None else \
                ParticleSet.make(jnp.zeros((0, params.ndim)),
                                 jnp.zeros((0, params.ndim)),
                                 jnp.zeros((0,)),
                                 nmax=lane_headroom(params, grows) or 1)
        self.gspec = GravitySpec.from_params(params)
        box_periodic = all(f.kind == bmod.PERIODIC
                           for pair in self.bc.faces for f in pair)
        if not box_periodic:
            if self.pspec.enabled:
                # the uniform PM stepper (pm/coupling.run_steps_pm)
                # wraps drift and CIC indices periodically — an open box
                # would teleport escapers to the far wall (gravity on or
                # off makes no difference to the drift)
                raise NotImplementedError(
                    "uniform-grid particles require a periodic box; "
                    "use the AMR driver for open-box PM runs")
            if self.cosmo is not None:
                raise NotImplementedError(
                    "cosmology requires a periodic box")
            if self.gspec.enabled and self.gspec.gravity_type == 0 \
                    and any(f.kind == bmod.REFLECTING
                            for pair in self.bc.faces for f in pair):
                raise NotImplementedError(
                    "self-gravity with reflecting walls is unsupported "
                    "(isolated solve covers outflow/inflow boxes)")
        if self.gspec.enabled:
            # initial force so the first -0.5dt "un-kick" cancels exactly
            # (the reference's nstep==0 save_phi_old, amr/amr_step.f90:260);
            # cosmology solves with the supercomoving source coefficient
            # 1.5*omega_m*aexp, not 4pi
            rho0 = total_density(self.pspec, self.state.u, self.state.p,
                                 shape, self.dx)
            fourpi0 = (1.5 * self.cosmo.omega_m * self.cosmo.aexp_ini
                       if self.cosmo is not None else None)
            self.state.f = gravity_field(self.gspec, rho0, self.dx, fourpi0)
        elif self.pspec.enabled or self.cosmo is not None:
            fdt = (jnp.float64 if jax.config.jax_enable_x64
                   else jnp.float32)
            self.state.f = jnp.zeros((params.ndim,) + shape, fdt)
        if self.cosmo is not None:
            self.state.t = self.cosmo.tau_ini
            # aexp-ladder outputs: convert aout -> conformal time
            if params.output.aout:
                taus = [float(self.cosmo.tau_of_aexp(a))
                        for a in params.output.aout
                        if a <= 1.0]
                params.output.tout = sorted(set(params.output.tout + taus))
                params.output.noutput = len(params.output.tout)
        # cooling microphysics (&COOLING_PARAMS → tables at this epoch)
        self.cool_tables = None
        self.cool_spec = None
        if params.cooling.cooling:
            from ramses_tpu.hydro.cooling import CoolingSpec, build_tables
            from ramses_tpu.units import units as units_fn
            un = units_fn(params, cosmo=self.cosmo,
                          aexp=(self.cosmo.aexp_ini if self.cosmo else 1.0))
            self.cool_spec = CoolingSpec.from_params(params, un)
            c = params.cooling
            self.cool_tables = build_tables(
                aexp=(self.cosmo.aexp_ini if self.cosmo else 1.0),
                J21=float(c.J21), a_spec=float(c.a_spec),
                z_reion=float(c.z_reion),
                haardt_madau=bool(c.haardt_madau))
            if (self.pspec.enabled or self.gspec.enabled
                    or self.cosmo is not None):
                import warnings
                warnings.warn("cooling is wired into the pure-hydro path "
                              "only for now; gravity/PM runs ignore it")
        # star formation / feedback / sinks (coarse-step cadence passes)
        from ramses_tpu.units import units as units_fn
        self.units = units_fn(params, cosmo=self.cosmo,
                              aexp=(self.cosmo.aexp_ini if self.cosmo
                                    else 1.0))
        self.sinks = (SinkSet.empty(params.ndim)
                      if self.sink_spec.enabled else None)
        self._sf_rng = np.random.default_rng(1234)
        self._next_star_id = 1
        # turbulence forcing (&TURB_PARAMS)
        from ramses_tpu.turb.forcing import TurbForcing, TurbSpec
        self.turb_spec = TurbSpec.from_params(params)
        self.turb = (TurbForcing(shape, self.turb_spec)
                     if self.turb_spec.enabled else None)
        # radiative transfer in the driver (rt=.true.): subcycled M1 +
        # thermochemistry against the live gas (amr_step.f90:594-672)
        self.rt = None
        if params.run.rt:
            from ramses_tpu.rt.coupling import RtCoupled
            from ramses_tpu.units import units as units_fn
            self.rt = RtCoupled(params, self.grid,
                                units_fn(params, cosmo=self.cosmo),
                                self.state.u)
        if self.sf_spec.enabled and not self.pspec.enabled:
            import dataclasses as _dc
            self.pspec = _dc.replace(self.pspec, enabled=True)
            if self.state.p is None:
                from ramses_tpu.pm.particles import lane_headroom
                self.state.p = ParticleSet.make(
                    jnp.zeros((0, params.ndim)),
                    jnp.zeros((0, params.ndim)), jnp.zeros((0,)),
                    nmax=lane_headroom(params, True))
        # &MOVIE_PARAMS on-the-fly frames (amr/movie.f90)
        from ramses_tpu.io.movie import MovieWriter
        self.movie, self.movie_imov = MovieWriter.from_params(params)
        if self.movie is not None:
            self._movie_next = 0
        self.output_times = list(params.output.tout[:params.output.noutput])
        self.on_output: Optional[Callable] = None
        # perf accounting (mus/pt of adaptive_loop.f90:204-212)
        self.cell_updates = 0
        self.wall_s = 0.0
        # structured run telemetry (&OUTPUT_PARAMS telemetry=; the
        # shared no-op NULL when off — zero-overhead contract)
        self.telemetry = make_telemetry(params)
        self.timers = Timers() if self.telemetry.enabled else NullTimers()
        # in-run fault recovery (&RUN_PARAMS max_step_retries) + the
        # deterministic fault-injection harness (fault_inject)
        from ramses_tpu.resilience.faultinject import FaultInjector
        from ramses_tpu.resilience.stepguard import StepGuard
        self._sguard = StepGuard.from_params(params,
                                             telemetry=self.telemetry)
        self._fault = FaultInjector.from_params(params)
        # hang watchdog (&RUN_PARAMS *_deadline_s): None when every
        # deadline is unset — evolve() then skips the guard entirely
        from ramses_tpu.resilience.watchdog import Watchdog
        self._wd = Watchdog.from_params(params, telemetry=self.telemetry)

    @property
    def nstep(self) -> int:
        return int(self.state.nstep)

    @property
    def t(self) -> float:
        return float(self.state.t)

    @property
    def tend(self) -> float:
        if self.output_times:
            return self.output_times[-1]
        return float("inf")

    def evolve(self, chunk: int = 16, verbose: bool = False, guard=None):
        """Run to the final output time, firing outputs on the way.
        ``guard``: optional :class:`ramses_tpu.utils.ops.OpsGuard`
        (signal dumps, stop_run file, walltime watchdog)."""
        st = self.state
        nstepmax = self.params.run.nstepmax
        telem = self.telemetry
        if telem.enabled:
            telem.run_info.update(sim_run_info(self))
        from ramses_tpu import patch
        if patch.hook("source") is not None:
            # the source hook is documented at coarse-step cadence
            # (patch.py): fused multi-step chunks would hand it one
            # aggregated ~chunk*dt — run step-at-a-time instead
            chunk = 1
        # Time is integrated in f64 (f32 if x64 is disabled) regardless of
        # the state dtype: with a bf16 state, t += dt would stall once
        # dt < eps(t) and the run would spin to nstepmax.
        tdtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        for tout in self.output_times[st.iout - 1:]:
            # sign-safe tolerance: cosmology runs in (negative) conformal
            # time, so a relative factor on tout would flip direction
            ttol = 1e-12 * (abs(tout) + 1.0)
            while st.t < tout - ttol and st.nstep < nstepmax:
                with self.timers.section("evolve"):
                    if guard is not None and not guard.check():
                        return st
                    n = min(chunk, nstepmax - st.nstep)
                    if self.movie is not None:
                        # fused chunks may not run past the movie cadence
                        # (frames sample at chunk boundaries)
                        n = min(n, self.movie_imov)
                    if self._fault is not None:
                        # pending step-indexed faults must land exactly at
                        # their target step, not at a chunk boundary
                        n = self._fault.clamp_window(int(st.nstep), n)
                    t_before = st.t
                    if self.rt is not None and self.params.run.static:
                        # frozen gas: pure RT evolution to the output time
                        # (the reference's static Stromgren tests)
                        st.u = self.rt.advance(st.u, tout - st.t)
                        st.t = tout
                        st.nstep += 1
                        if self.movie is not None \
                                and st.nstep >= self._movie_next:
                            self.movie.emit(self)
                            self._movie_next = st.nstep + self.movie_imov
                        continue
                    # redo-step guard: on the plain-hydro dispatch (no
                    # donation — these are live references, not copies) the
                    # pre-step state is retained so a non-finite window can
                    # roll back; pm/cool scans expose no dt_scale hook and
                    # rely on OpsGuard's trap instead
                    plain = not (self.pspec.enabled or self.gspec.enabled
                                 or self.cosmo is not None
                                 or self.cool_tables is not None)
                    prev = ((st.u, st.t, st.nstep, st.dt_old)
                            if self._sguard is not None and plain else None)
                    if self._fault is not None:
                        self._fault.maybe_nan(self)
                    t0 = time.perf_counter()
                    # the whole dispatch + blocking fetch runs under the
                    # step deadline (first window: compile deadline) —
                    # nullcontext when the watchdog is off keeps this path
                    # fetch-identical to the unguarded one
                    with (self._wd.guard("step") if self._wd is not None
                            else nullcontext()):
                        if self._fault is not None:
                            self._fault.maybe_hang(int(st.nstep))
                        with self.timers.section("evolve: dispatch"):
                            u, t, ndone, dt_old, hist = self._dispatch(
                                n, tout, tdtype)
                        # only dispatched so far: these block until the
                        # device has run the window
                        with self.timers.section("evolve: wait"):
                            u.block_until_ready()
                            ndone = int(ndone)
                            t = float(t)
                            if dt_old is not None:
                                st.dt_old = float(dt_old)
                    wall = time.perf_counter() - t0
                    self.wall_s += wall
                    st.u, st.t, st.nstep = u, t, st.nstep + ndone
                    if self._wd is not None:
                        self._wd.note(nstep=st.nstep, t=st.t)
                    self.cell_updates += ndone * self.grid.ncell
                    if prev is not None and not self._sguard.ok(st.t):
                        # non-finite window: roll back and redo at halved
                        # dt (raises StepRetryExhausted after the ladder)
                        ndone = self._retry_window(prev, tout, tdtype)
                        hist = None
                    if telem.enabled and ndone:
                        if hist is not None:
                            ts, dts = jax.device_get(hist)
                            telem.record_chunk(self, ts[:ndone], dts[:ndone],
                                               ndone, wall,
                                               nstep_end=st.nstep)
                        else:
                            # pm/cool scans don't expose per-step history:
                            # one aggregate record per dispatch
                            telem.record_step(
                                self, dt=(st.t - t_before) / ndone,
                                wall_s=wall, steps=ndone, t=st.t,
                                nstep=st.nstep, chunked=ndone)
                    self._source_passes(st.t - t_before)
                    if self.rt is not None and st.t > t_before:
                        st.u = self.rt.advance(st.u, st.t - t_before)
                    if self.movie is not None \
                            and st.nstep >= self._movie_next:
                        self.movie.emit(self)
                        self._movie_next = st.nstep + self.movie_imov
                    if verbose:
                        print(telemetry_screen.step_line(
                            self, dt=((st.t - t_before) / ndone
                                      if ndone else None), chunk=ndone))
                    if ndone == 0:
                        break
            if st.t < tout - ttol:
                break  # budget exhausted before this output time: no dump
            if self.on_output is not None:
                self.on_output(self, st.iout)
            st.iout += 1
        return st

    def _dispatch(self, n: int, tout: float, tdtype):
        """Dispatch one fused window of up to ``n`` steps towards
        ``tout``; nothing here waits for the device.  Returns ``(u, t,
        ndone, dt_old, hist)``: ``dt_old`` only from the pm scan,
        ``hist`` (stacked per-step ``(t, dt)``) only when telemetry is
        on, else None."""
        st = self.state
        t0, t1 = jnp.asarray(st.t, tdtype), jnp.asarray(tout, tdtype)
        if (self.pspec.enabled or self.gspec.enabled
                or self.cosmo is not None):
            u, st.p, st.f, t, dt_old, ndone = run_steps_pm(
                self.grid, self.gspec, self.pspec, st.u, st.p, st.f, t0,
                t1, jnp.asarray(st.dt_old, tdtype), n, cosmo=self.cosmo)
            return u, t, ndone, dt_old, None
        if self.cool_tables is not None:
            from ramses_tpu.grid.uniform import run_steps_cool
            u, t, ndone = run_steps_cool(self.grid, st.u, t0, t1, n,
                                         self.cool_tables, self.cool_spec)
            return u, t, ndone, None, None
        if self.telemetry.enabled:
            # instrumented run: the scan additionally stacks per-step
            # (t, dt) so the event log gets one record per coarse step
            # from this single summary fetch — the chunk stays one
            # device program
            u, t, ndone, hist = run_steps(self.grid, st.u, t0, t1, n,
                                          trace=True)
            return u, t, ndone, None, hist
        u, t, ndone = run_steps(self.grid, st.u, t0, t1, n)
        return u, t, ndone, None, None

    def _source_passes(self, dt_chunk: float):
        """Coarse-step-cadence source terms: star formation, SN feedback,
        sink creation/accretion/merging/motion (``amr_step`` order
        ``:369-380,493,549-567``)."""
        if dt_chunk <= 0.0:
            return
        st = self.state
        if self.turb is not None:
            from ramses_tpu.turb.forcing import apply_forcing
            self.turb.update(dt_chunk)
            acc = self.turb.acceleration()
            st.u = apply_forcing(st.u, acc, dt_chunk,
                                 self.turb_spec.turb_min_rho)
        if self.sf_spec.enabled:
            from ramses_tpu.pm.star_formation import (kinetic_feedback,
                                                      star_formation,
                                                      thermal_feedback)
            u_np = np.asarray(st.u, dtype=np.float64)
            u_np, p2, self._next_star_id = star_formation(
                u_np, st.p, self._sf_rng, self.sf_spec, self.units,
                self.dx, st.t, dt_chunk, self._next_star_id)
            # f_w > 0 selects the mass-loaded kinetic wind scheme
            # (feedback.f90's f_w branch); otherwise thermal dumps
            if self.sf_spec.f_w > 0:
                u_np, p2 = kinetic_feedback(u_np, p2, self.sf_spec,
                                            self.units, self.dx, st.t,
                                            bc=self.bc)
            else:
                u_np, p2 = thermal_feedback(u_np, p2, self.sf_spec,
                                            self.units, self.dx, st.t)
            st.u = jnp.asarray(u_np, st.u.dtype)
            st.p = p2
        if self.sinks is not None:
            from ramses_tpu.pm.sinks import (accrete, create_sinks,
                                             drift_kick, merge_sinks)
            u_np = np.asarray(st.u, dtype=np.float64)
            u_np, self.sinks = create_sinks(
                u_np, self.sinks, self.sink_spec, self.units, self.dx,
                st.t, self.cfg.gamma)
            u_np, self.sinks = accrete(
                u_np, self.sinks, self.sink_spec, self.units, self.dx,
                dt_chunk, self.cfg.gamma)
            self.sinks = merge_sinks(self.sinks, self.sink_spec, self.dx)
            self.sinks = drift_kick(self.sinks, st.f, self.dx, dt_chunk,
                                    self.params.amr.boxlen,
                                    spec=self.sink_spec,
                                    units=self.units)
            st.u = jnp.asarray(u_np, st.u.dtype)
        from ramses_tpu import patch
        user_source = patch.hook("source")
        if user_source is not None:
            # AFTER the stock passes, like the AMR driver — a hook that
            # post-processes this step's SF/feedback sees the same state
            # in both drivers
            user_source(self, dt_chunk)

    def _retry_window(self, prev, tout, tdtype) -> int:
        """Redo-step ladder for a non-finite fused window: restore the
        retained pre-step state, retry ONE step at halved dt (halving
        again per attempt), escalating the Riemann solver to diffusive
        LLF from the second attempt; emergency-dump the last clean
        state and raise :class:`StepRetryExhausted` when the ladder is
        spent.  Returns the number of steps recovered (for the
        telemetry aggregate record)."""
        import dataclasses as _dc

        from ramses_tpu.resilience.stepguard import (StepGuard,
                                                     StepRetryExhausted)
        sg = self._sguard
        st = self.state
        u0, t0, nstep0, dt_old0 = prev
        sg.record_trip(self)
        grid0 = self.grid
        try:
            for attempt in range(1, sg.max_retries + 1):
                st.u, st.t, st.nstep, st.dt_old = u0, t0, nstep0, dt_old0
                escalated = attempt >= 2
                if escalated:
                    self.grid = _dc.replace(
                        grid0, cfg=_dc.replace(grid0.cfg, riemann="llf"))
                scale = 0.5 ** attempt
                sg.record_rollback(self, attempt, scale, escalated)
                tw0 = time.perf_counter()
                with (self._wd.guard("step") if self._wd is not None
                        else nullcontext()):
                    u, t, ndone = run_steps(
                        self.grid, u0, jnp.asarray(t0, tdtype),
                        jnp.asarray(tout, tdtype), 1, dt_scale=scale)
                    u.block_until_ready()
                    tf = float(t)
                if StepGuard.ok(tf):
                    st.u, st.t, st.nstep = u, tf, nstep0 + int(ndone)
                    self.cell_updates += int(ndone) * self.grid.ncell
                    self.wall_s += time.perf_counter() - tw0
                    sg.record_recovered(self, attempt)
                    return int(ndone)
        finally:
            self.grid = grid0     # escalation is per-retry, not sticky
        st.u, st.t, st.nstep, st.dt_old = u0, t0, nstep0, dt_old0
        out = None
        try:
            out = self.dump(999, self.params.output.output_dir)
        except Exception as e:    # the abort itself must not be masked
            print(f"resilience: emergency dump failed: {e}")
        sg.record_abort(self, out)
        raise StepRetryExhausted(
            f"step {nstep0} non-finite after {sg.max_retries} retries "
            f"(t={t0:.6g}); last clean state dumped to {out}")

    def mus_per_cell_update(self) -> float:
        return 1e6 * self.wall_s / max(self.cell_updates, 1)

    def totals(self):
        """Conservation audit (``check_cons``) over the active grid."""
        from ramses_tpu.grid.uniform import totals as _totals
        return _totals(self.state.u, self.cfg, self.dx)

    # ------------------------------------------------------------------
    # snapshot / restart (SURVEY.md §3.4, §5.4)
    # ------------------------------------------------------------------
    def dump(self, iout: Optional[int] = None, base_dir: Optional[str] = None,
             namelist_path: Optional[str] = None) -> str:
        """Write a reference-format ``output_NNNNN/`` snapshot."""
        import os

        from ramses_tpu.io import snapshot as snapmod
        with (self._wd.guard("io") if self._wd is not None
                else nullcontext()):
            iout = iout if iout is not None else self.state.iout
            snap = snapmod.snapshot_from_uniform(self, iout)
            base = base_dir or self.params.output.output_dir
            extra = None
            if self.turb is not None:
                # the OU spectral state + RNG key ride in every snapshot
                # (``turb/write_turb_fields.f90``) so a driven-turbulence
                # restart continues the SAME forcing realization instead
                # of silently re-seeding; staged alongside the file set
                # so it lands under the checkpoint manifest, not after
                # the rename
                extra = os.path.join(base,
                                     f"output_{iout:05d}.extras.tmp")
                os.makedirs(extra, exist_ok=True)
                self.turb.save(os.path.join(extra, "turb_fields.npz"))
            if getattr(self.params.output, "savegadget", False) \
                    and self.state.p is not None:
                # &OUTPUT_PARAMS savegadget: each particle output also
                # lands as a Gadget SnapFormat=1 file, staged into the
                # extras dir so it rides the checkpoint manifest
                from ramses_tpu.io.gadget import dump_gadget_particles
                if extra is None:
                    extra = os.path.join(
                        base, f"output_{iout:05d}.extras.tmp")
                    os.makedirs(extra, exist_ok=True)
                dump_gadget_particles(
                    os.path.join(extra, f"gadget_{iout:05d}.dat"),
                    self.state.p, boxlen=self.params.amr.boxlen,
                    time=self.state.t)
            return snapmod.dump_all(
                snap, iout, base, namelist_path=namelist_path,
                extra_dir=extra,
                keep_last=int(getattr(self.params.output,
                                      "checkpoint_keep", 0)))

    @classmethod
    def from_snapshot(cls, params: Params, outdir: str,
                      dtype=jnp.float32) -> "Simulation":
        """Resume from a snapshot directory (``nrestart`` path)."""
        from ramses_tpu.io.restart import restore_particles, restore_uniform
        from ramses_tpu.pm.particles import lane_headroom
        from ramses_tpu.pm.sinks import SinkSpec
        from ramses_tpu.pm.star_formation import SfSpec
        cfg = HydroStatic.from_params(params)
        dense, meta, parts = restore_uniform(outdir, params, cfg)
        # particle-creating runs need free lanes after the restart too
        grows = (SfSpec.from_params(params).enabled
                 or SinkSpec.from_params(params).enabled)
        p = (restore_particles(parts, params.ndim,
                               nmax=lane_headroom(params, grows))
             if parts else None)
        sim = cls(params, dtype=dtype, particles=p)
        if p is not None:
            # new star ids must not collide with restored particles'
            sim._next_star_id = int(np.asarray(p.idp).max()) + 1
        sim.state.u = jnp.asarray(dense, dtype=dtype)
        sim.state.t = float(meta["t"])
        sim.state.nstep = int(meta["nstep"])
        iout_meta = int(meta["iout"])
        if iout_meta < 900:
            sim.state.iout = max(iout_meta, 1) + 1
        else:
            # emergency checkpoint (OpsGuard 900+, StepGuard 999): its
            # iout is NOT an output-schedule index — derive the next
            # pending output from the restored time so the resumed
            # evolve() continues the tout schedule instead of indexing
            # past its end
            sim.state.iout = 1 + sum(
                1 for tt in sim.output_times
                if sim.state.t >= tt - 1e-12 * (abs(tt) + 1.0))
        if sim.turb is not None:
            import os

            from ramses_tpu.turb.forcing import TurbForcing
            tpath = os.path.join(outdir, "turb_fields.npz")
            if os.path.exists(tpath):
                # restore the OU field + RNG key (read_turb_fields.f90):
                # the restarted run reproduces the continuous run's
                # forcing sequence bitwise
                sim.turb = TurbForcing.load(tpath, sim.turb_spec)
            else:
                import warnings
                warnings.warn(f"no turb_fields.npz in {outdir}: the "
                              "forcing re-seeds from turb_seed and the "
                              "restart will not reproduce the original "
                              "driving sequence")
        if sim.gspec.enabled:
            rho = total_density(sim.pspec, sim.state.u, sim.state.p,
                                sim.grid.shape, sim.dx)
            # supercomoving source uses aexp AT the restored time, not
            # aexp_ini — restart must continue the original trajectory
            fourpi = (1.5 * sim.cosmo.omega_m
                      * float(sim.cosmo.aexp_of_tau(sim.state.t))
                      if sim.cosmo is not None else None)
            sim.state.f = gravity_field(sim.gspec, rho, sim.dx, fourpi)
        return sim


def run_namelist(path: str, ndim: int = 3, dtype=jnp.float32,
                 verbose: bool = False,
                 max_attempts: int = 1) -> Simulation:
    """Build-and-evolve from a namelist.  With ``max_attempts > 1`` or
    ``&RUN_PARAMS auto_resume``/``nrestart=-1`` the run is supervised:
    an interrupted attempt resumes from the newest manifest-valid
    checkpoint with exponential backoff between attempts.

    ``&ENSEMBLE_PARAMS nmember > 1`` dispatches to the batched
    ensemble engine instead (one compiled program advances every
    member) and returns the :class:`~ramses_tpu.ensemble.batch.
    EnsembleEngine` in place of a :class:`Simulation`."""
    params = load_params(path, ndim=ndim)
    if params.ensemble.nmember > 1:
        from ramses_tpu.ensemble.batch import EnsembleEngine, EnsembleSpec
        spec = EnsembleSpec.from_params(params)
        return EnsembleEngine(spec, dtype=dtype).run(verbose=verbose)
    supervised = (max_attempts > 1 or params.run.auto_resume
                  or params.run.nrestart == -1)
    if supervised:
        from ramses_tpu.resilience import supervisor as rsup

        def build(restart):
            if restart is not None:
                return Simulation.from_snapshot(params, restart,
                                                dtype=dtype)
            return Simulation(params, dtype=dtype)

        return rsup.supervise(build,
                              lambda sim: sim.evolve(verbose=verbose),
                              params,
                              base_dir=params.output.output_dir,
                              max_attempts=max(2, int(max_attempts)))
    sim = Simulation(params, dtype=dtype)
    sim.evolve(verbose=verbose)
    return sim
