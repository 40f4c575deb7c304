"""Single-level (uniform Cartesian) hydro solver.

The degenerate one-level octree of SURVEY.md §7 stage 2: the whole grid is
one dense device array, a full step is one fused XLA program
(pad → ctoprim → slopes → trace → riemann → update), and N steps run as
one device loop with zero host round-trips (a ``lax.scan``; on the fused
Pallas kernel a ``lax.while_loop`` that ends when no step is owed) — the
design replaces the per-nvector-batch sweep of ``godunov_fine``
(``hydro/godunov_fine.f90:5-35``) with whole-grid fusion.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ramses_tpu.grid import boundary as bmod
from ramses_tpu.hydro import muscl
from ramses_tpu.hydro.core import HydroStatic
from ramses_tpu.hydro.timestep import compute_dt


@dataclass(frozen=True)
class UniformGrid:
    """Static description of a uniform-grid problem (hashable, jit-static)."""
    cfg: HydroStatic
    shape: Tuple[int, ...]
    dx: float
    bc: bmod.BoundarySpec
    # devices the state array spans (the simulation's mesh, not the
    # host's device count): more than one keeps the XLA step, which
    # GSPMD can partition; the fused Pallas kernel has no such rule
    ndev: int = 1

    @property
    def ncell(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _pallas_ok(grid: UniformGrid, dtype) -> bool:
    """True when the fused Pallas TPU kernel covers this grid."""
    if grid.cfg.ndim != 3:
        return False
    from ramses_tpu.hydro import pallas_muscl as pk
    return pk.kernel_available(grid.cfg, grid.shape, grid.bc.faces, dtype,
                               grid.ndev)


@partial(jax.jit, static_argnames=("grid",))
def step(grid: UniformGrid, u, dt):
    """One conservative MUSCL-Hancock step on the active grid.

    Dispatches to the fused Pallas kernel
    (:mod:`ramses_tpu.hydro.pallas_muscl`) when it covers the config;
    the XLA path below is the reference implementation (bit-identical)."""
    cfg = grid.cfg
    # the time axis runs in f64 while the state may be f32/bf16: keep
    # the sweep in the state dtype
    dt = jnp.asarray(dt, u.dtype)
    if _pallas_ok(grid, u.dtype):
        from ramses_tpu.hydro import pallas_muscl as pk
        up, _ = pk.pad_xy(u, grid.bc, cfg)
        return pk.fused_step_padded(up, dt, cfg, grid.dx, grid.shape)
    up = bmod.pad(u, grid.bc, cfg, muscl.NGHOST, dx=grid.dx)
    flux, tmp = muscl.unsplit(up, None, dt, (grid.dx,) * cfg.ndim, cfg)
    un = muscl.apply_fluxes(up, flux, cfg)
    if cfg.pressure_fix or cfg.nener:
        un = muscl.dual_energy_fix(up, un, tmp, dt,
                                   (grid.dx,) * cfg.ndim, cfg)
    return bmod.unpad(un, cfg.ndim, muscl.NGHOST)


@partial(jax.jit, static_argnames=("grid",))
def step_with_flux(grid: UniformGrid, u, dt):
    """Like :func:`step` but also returns the mass flux·dt/dx at the LOW
    face of every active cell, ``[ndim, *sp]`` — the quantity the
    Monte-Carlo tracers sample (``hydro/godunov_fine.f90:685-715``)."""
    cfg = grid.cfg
    dt = jnp.asarray(dt, u.dtype)
    up = bmod.pad(u, grid.bc, cfg, muscl.NGHOST, dx=grid.dx)
    flux, tmp = muscl.unsplit(up, None, dt, (grid.dx,) * cfg.ndim, cfg)
    un = muscl.apply_fluxes(up, flux, cfg)
    if cfg.pressure_fix or cfg.nener:
        un = muscl.dual_energy_fix(up, un, tmp, dt,
                                   (grid.dx,) * cfg.ndim, cfg)
    mass_flux = jnp.stack([bmod.unpad(flux[d][0], cfg.ndim, muscl.NGHOST)
                           for d in range(cfg.ndim)])
    return bmod.unpad(un, cfg.ndim, muscl.NGHOST), mass_flux


@partial(jax.jit, static_argnames=("grid",))
def cfl_dt(grid: UniformGrid, u):
    return compute_dt(u, None, grid.dx, grid.cfg)


@partial(jax.jit, static_argnames=("grid", "nsteps", "trace", "dt_scale"))
def run_steps(grid: UniformGrid, u, t, tend, nsteps: int,
              trace: bool = False, dt_scale: float = 1.0):
    """Advance up to ``nsteps`` steps entirely on device.

    dt is recomputed each step (``courant_fine``), clipped to land exactly
    on ``tend``; steps past ``tend`` are masked no-ops of the scan below
    and are not run at all on the kernel path (same results).  Returns
    (u, t, n_done); ``trace=True`` (telemetry-instrumented runs)
    additionally returns the per-step ``(t_after, dt)`` history,
    ``[nsteps]`` each (rows past ``n_done``: the final ``t``, ``dt`` 0),
    so the driver can emit one record per coarse step from a single
    summary fetch.

    ``dt_scale < 1`` shrinks every Courant dt by that factor — the
    redo-step retry ladder (resilience/stepguard) re-runs a tripped
    window at halved dt, mirroring the reference's dtnew halving.

    On the Pallas path (:func:`_run_steps_pallas`) the Courant reduction
    of the updated state comes out of the step kernel itself (free — the
    primitives are already in VMEM), so each iteration is ``pad_xy`` and
    exactly one kernel launch.
    """
    if _pallas_ok(grid, u.dtype):
        return _run_steps_pallas(grid, u, t, tend, nsteps, trace=trace,
                                 dt_scale=dt_scale)

    def body(carry, _):
        u, t, ndone = carry
        dt = cfl_dt(grid, u) * dt_scale
        dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
        active = t < tend
        un = step(grid, u, jnp.where(active, dt, 0.0))
        u = jnp.where(active, un, u)
        t = jnp.where(active, t + dt, t)
        ndone = ndone + jnp.where(active, 1, 0)
        ys = (t, jnp.where(active, dt, 0.0)) if trace else None
        return (u, t, ndone), ys

    (u, t, ndone), hist = jax.lax.scan(body, (u, t, jnp.array(0)), None,
                                       length=nsteps)
    if trace:
        return u, t, ndone, hist
    return u, t, ndone


@partial(jax.jit, static_argnames=("grid", "nsteps", "trace", "dt_scale"))
def _run_steps_pallas(grid: UniformGrid, u, t, tend, nsteps: int,
                      trace: bool = False, dt_scale: float = 1.0):
    """:func:`run_steps` on the fused kernel: a ``lax.while_loop`` that
    runs a step only while one is owed (``ndone < nsteps`` and
    ``t < tend``), so no pass over the state masks a step out — the
    loop body is ``pad_xy`` and the kernel, nothing else of the state's
    size.  Same results as the scan form, bit for bit; with
    ``trace=True`` the history rows past ``ndone`` hold the final ``t``
    and a zero ``dt``."""
    from ramses_tpu.hydro import pallas_muscl as pk

    cfg = grid.cfg
    dtmax = cfg.courant_factor * grid.dx / cfg.smallc
    dt0 = compute_dt(u, None, grid.dx, cfg) * dt_scale

    def owed(carry):
        _, t, ndone = carry[:3]
        return (ndone < nsteps) & (t < tend)

    def body(carry):
        u, t, ndone, dtc = carry[:4]
        dt = jnp.minimum(dtc, jnp.maximum(tend - t, 0.0))
        up, _ = pk.pad_xy(u, grid.bc, cfg)
        un, crt = pk.fused_step_padded(up, dt, cfg, grid.dx, grid.shape,
                                       courant=True)
        dtn = jnp.minimum(dtmax, crt[0, 0] * dt_scale)
        t = t + dt
        hist = tuple(h.at[ndone].set(v)
                     for h, v in zip(carry[4:], (t, dt)))
        return (un, t, ndone + 1, dtn) + hist

    carry = (u, t, jnp.array(0), dt0)
    if trace:       # (t_after, dt) of each step, written by index
        carry += (jnp.zeros(nsteps, t.dtype),
                  jnp.zeros(nsteps, jnp.result_type(dt0, tend, t)))
    u, t, ndone, _, *hist = jax.lax.while_loop(owed, body, carry)
    if trace:
        t_hist = jnp.where(jnp.arange(nsteps) < ndone, hist[0], t)
        return u, t, ndone, (t_hist, hist[1])
    return u, t, ndone


@partial(jax.jit, static_argnames=("grid", "cspec", "nsteps", "dt_scale"))
def run_steps_cool(grid: UniformGrid, u, t, tend, nsteps: int,
                   tables, cspec, dt_scale: float = 1.0):
    """:func:`run_steps` with the cooling source applied after each hydro
    step (the ``cooling_fine`` call that follows ``godunov_fine`` in
    ``amr/amr_step.f90:448-474``).  ``dt_scale < 1`` is the redo-step
    retry knob, as on :func:`run_steps`."""
    from ramses_tpu.hydro.cooling import cooling_step

    def body(carry, _):
        u, t, ndone = carry
        dt = cfl_dt(grid, u) * dt_scale
        dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
        active = t < tend
        dt_eff = jnp.where(active, dt, 0.0)
        un = step(grid, u, dt_eff)
        un = cooling_step(un, tables, cspec, dt_eff, grid.cfg)
        u = jnp.where(active, un, u)
        t = jnp.where(active, t + dt, t)
        ndone = ndone + jnp.where(active, 1, 0)
        return (u, t, ndone), None

    (u, t, ndone), _ = jax.lax.scan(body, (u, t, jnp.array(0)), None,
                                    length=nsteps)
    return u, t, ndone


def batch_summary(u, ndim: int, dx: float, ienergy: int, bf=None):
    """Per-member conserved/finiteness summary ``[B, 3]`` for the
    batched guard (resilience/stepguard.BatchGuard): columns are
    (all-finite flag, mass total, energy total).  A NaN that lands on
    the *last* step of a fused window leaves the member's ``t`` finite,
    so the guard needs a state-derived channel too; computed on device
    so arming the guard only widens the existing per-dispatch fetch
    instead of adding one."""
    axes = tuple(range(1, u.ndim))
    finite = jnp.all(jnp.isfinite(u), axis=axes)
    if bf is not None:
        finite &= jnp.all(jnp.isfinite(bf),
                          axis=tuple(range(1, bf.ndim)))
    vol = dx ** ndim
    sp = tuple(range(1, u.ndim - 1))     # spatial axes of u[:, ivar]
    mass = jnp.sum(u[:, 0], axis=sp)
    energy = jnp.sum(u[:, ienergy], axis=sp)
    return jnp.stack([finite.astype(u.dtype),
                      mass * vol, energy * vol], axis=-1)


@partial(jax.jit,
         static_argnames=("grid", "nsteps", "dt_scale", "summarize"))
def run_steps_batch(grid: UniformGrid, u, t, tend, nsteps: int,
                    dt_scale: float = 1.0, summarize: bool = False):
    """:func:`run_steps` vmapped over a leading ensemble axis.

    ``u`` is ``[B, nvar, *sp]``, ``t``/``tend`` are ``[B]`` — one
    compiled program advances every member; the per-step
    ``active = t < tend`` masking inside :func:`run_steps` becomes a
    per-member ``lax.select`` under vmap (the kernel path's
    ``while_loop`` runs until the last member is done and vmap masks
    each finished member's carry the same way), so members that reach
    their own ``tend`` idle cheaply until the batch drains.  Returns
    ``(u, t, ndone)`` with ``ndone[B]`` counting each member's real
    steps.  ``summarize=True`` (batched step-guard armed) additionally
    returns the :func:`batch_summary` ``[B, 3]``.  The batch shares
    one jit cache entry per ``grid`` — the frozen static dataclass is
    the cache key (ensemble/batch groups members by it)."""
    def solo(u_, t_, tend_):
        return run_steps(grid, u_, t_, tend_, nsteps, dt_scale=dt_scale)
    u, t, ndone = jax.vmap(solo)(u, t, tend)
    if summarize:
        cfg = grid.cfg
        return u, t, ndone, batch_summary(u, cfg.ndim, grid.dx,
                                          cfg.ndim + 1)
    return u, t, ndone


@partial(jax.jit, static_argnames=("grid", "cspec", "nsteps",
                                   "dt_scale", "summarize"))
def run_steps_cool_batch(grid: UniformGrid, u, t, tend, nsteps: int,
                         tables, cspec, dt_scale: float = 1.0,
                         summarize: bool = False):
    """:func:`run_steps_cool` over a leading ensemble axis; ``tables``
    is stacked per-member too (cooling-constant sweeps are traced table
    data, not jit keys — only ``cspec`` splits the cache)."""
    def solo(u_, t_, tend_, tb_):
        return run_steps_cool(grid, u_, t_, tend_, nsteps, tb_, cspec,
                              dt_scale=dt_scale)
    u, t, ndone = jax.vmap(solo)(u, t, tend, tables)
    if summarize:
        cfg = grid.cfg
        return u, t, ndone, batch_summary(u, cfg.ndim, grid.dx,
                                          cfg.ndim + 1)
    return u, t, ndone


def totals(u, cfg: HydroStatic, dx: float):
    """Conservation audit (mass, momentum, energy) — ``check_cons``
    (``hydro/courant_fine.f90:161``)."""
    vol = dx ** cfg.ndim
    return {
        "mass": jnp.sum(u[0]) * vol,
        "momentum": [jnp.sum(u[1 + d]) * vol for d in range(cfg.ndim)],
        "energy": jnp.sum(u[cfg.ndim + 1]) * vol,
    }
