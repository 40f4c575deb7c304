"""Batched ensemble engine: one compiled program, a fleet of runs.

ROADMAP item 3 (and JANC, arXiv:2504.13750, as the existence proof):
production scale for this framework is *many* simulations, so the
fused uniform step chains (``grid/uniform.run_steps``/``run_steps_cool``,
``mhd/uniform.run_steps``, ``rhd/uniform.run_steps``) are vmapped over a
leading member axis.  :class:`EnsembleSpec` expands one base namelist
into N members by sweeping parameters; anything *traced* (region
densities/pressures, IC perturbation seeds, cooling table data) batches
freely inside one compiled program, while sweeps that touch a *static*
config field (EOS gamma, the Riemann solver, a CoolingSpec knob) change
the frozen dataclass that IS the jit cache key — those members are
grouped into sub-batches by frozen-config hash so each distinct config
compiles exactly once (``platform.enable_compile_cache`` makes even that
cold-start O(load) for a known namelist).

Per-member time is carried as a batched ``t[B]`` array and completion is
the per-step ``t < tend`` test already inside every ``run_steps`` loop —
the scans' mask becomes a per-member ``lax.select`` under vmap, and so
does the carry of the fused-kernel path's ``while_loop``, which runs
until the last member is done — so finished members idle cheaply until
their sub-batch drains.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import time
from contextlib import nullcontext
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.config import Params

_INDEXED = re.compile(r"^(?P<name>\w+)\[(?P<idx>\d+)\]$")

#: round-off slack shared with the drivers' "reached tend" checks
_TEND_EPS = 1e-12


def apply_override(params: Params, key: str, value: Any) -> None:
    """Set a dotted sweep path (``"hydro.gamma"``, ``"init.p_region[1]"``)
    on a :class:`Params` in place.  Unknown groups/fields raise — a
    silently ignored sweep would make every member identical."""
    group, _, fname = key.partition(".")
    if not fname:
        raise ValueError(f"sweep key '{key}' is not of the form "
                         "'group.field' or 'group.field[i]'")
    sub = getattr(params, group)
    m = _INDEXED.match(fname)
    if m:
        lst = list(getattr(sub, m.group("name")))
        lst[int(m.group("idx"))] = value
        setattr(sub, m.group("name"), lst)
    else:
        cur = getattr(sub, fname)          # AttributeError when unknown
        if isinstance(cur, bool):
            value = bool(value)
        elif isinstance(cur, int) and not isinstance(value, bool):
            value = int(value)
        elif isinstance(cur, float):
            value = float(value)
        setattr(sub, fname, value)


def solver_from_params(params: Params) -> str:
    """Solver-family auto-detect shared with ``__main__``: MHD when any
    region seeds a magnetic field, hydro otherwise (rhd is explicit)."""
    init = params.init
    return ("mhd" if any(init.A_region) or any(init.B_region)
            or any(init.C_region) else "hydro")


@dataclass
class EnsembleSpec:
    """One base namelist + per-member parameter sweeps.

    ``sweeps`` maps dotted parameter paths to per-member value lists
    (every list must have length ``nmember``).  ``perturb_amp > 0``
    additionally multiplies each member's IC density by
    ``1 + amp * U[-1, 1)`` drawn from ``default_rng(perturb_seed + k)``
    — a traced-only sweep that never splits the jit cache.
    """
    base: Params
    nmember: int
    sweeps: Dict[str, List[Any]] = field(default_factory=dict)
    perturb_amp: float = 0.0
    perturb_seed: int = 0
    solver: str = ""               # "" -> auto (hydro/mhd)

    def __post_init__(self):
        if self.nmember < 1:
            raise ValueError(f"nmember must be >= 1 (got {self.nmember})")
        if not self.solver:
            self.solver = solver_from_params(self.base)
        for key, vals in self.sweeps.items():
            if len(vals) != self.nmember:
                raise ValueError(
                    f"sweep '{key}' has {len(vals)} values for "
                    f"{self.nmember} members")

    @classmethod
    def from_params(cls, params: Params,
                    sweeps: Optional[Dict[str, Sequence[Any]]] = None,
                    nmember: Optional[int] = None,
                    solver: str = "") -> "EnsembleSpec":
        """Build from ``&ENSEMBLE_PARAMS`` (plus optional explicit
        sweeps, e.g. from a queue job record).  Namelist ``sweep_name``
        rows ramp linearly ``sweep_start -> sweep_stop`` across the
        members; explicit ``sweeps`` win on key collision."""
        e = params.ensemble
        sweeps = {k: list(v) for k, v in (sweeps or {}).items()}
        nm = int(nmember or 0) or int(e.nmember) or \
            (max(len(v) for v in sweeps.values()) if sweeps else 1)
        for i, name in enumerate(e.sweep_name):
            if name in sweeps:
                continue
            lo = float(e.sweep_start[i]) if i < len(e.sweep_start) else 0.0
            hi = float(e.sweep_stop[i]) if i < len(e.sweep_stop) else lo
            sweeps[name] = [lo + (hi - lo) * (k / (nm - 1) if nm > 1
                                              else 0.0)
                            for k in range(nm)]
        return cls(base=params, nmember=nm, sweeps=sweeps,
                   perturb_amp=float(e.perturb_amp),
                   perturb_seed=int(e.perturb_seed), solver=solver)

    def member_params(self, k: int) -> Params:
        """Member k's full Params (a private copy with its sweeps
        applied).  The clone goes through a pickle round-trip with the
        serialized base cached on first use — ~6x cheaper than
        ``copy.deepcopy`` and paid once per member when expanding a
        batch, so it dominates small-job engine construction.  Mutating
        ``self.base`` after the first call is not supported."""
        if not 0 <= k < self.nmember:
            raise IndexError(k)
        blob = self.__dict__.get("_base_blob")
        if blob is None:
            blob = pickle.dumps(self.base, pickle.HIGHEST_PROTOCOL)
            self.__dict__["_base_blob"] = blob
        p = pickle.loads(blob)
        for key, vals in self.sweeps.items():
            apply_override(p, key, vals[k])
        return p

    def fingerprint(self) -> str:
        """Stable id of the expansion (checkpoint compatibility check)."""
        blob = json.dumps({"nmember": self.nmember, "solver": self.solver,
                           "sweeps": {k: [repr(v) for v in vs]
                                      for k, vs in sorted(self.sweeps.items())},
                           "perturb": [self.perturb_amp, self.perturb_seed]},
                          sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _uniform_shape(p: Params, cubic: bool) -> Tuple[Tuple[int, ...], float]:
    n = 2 ** p.amr.levelmin
    base = [p.amr.nx, p.amr.ny, p.amr.nz][:p.ndim]
    if cubic and any(b != 1 for b in base):
        raise NotImplementedError(
            f"{'mhd/rhd'} ensembles require nx=ny=nz=1 (got {base})")
    shape = tuple(b * n for b in base)
    return shape, p.amr.boxlen / n


def _check_uniform_only(p: Params, solver: str) -> None:
    if p.amr.levelmax > p.amr.levelmin:
        raise NotImplementedError(
            "ensemble engine covers the uniform fused step chains only "
            f"(levelmin={p.amr.levelmin} < levelmax={p.amr.levelmax}); "
            "run AMR namelists solo")
    r = p.run
    if r.poisson or r.pic or r.cosmo or r.rt:
        raise NotImplementedError(
            "ensemble engine: pure (M/R)HD uniform runs only — "
            "poisson/pic/cosmo/rt namelists run solo")
    if solver == "hydro" and p.run.patch:
        # patch hooks are process-global state; per-member patches
        # cannot coexist inside one batch
        raise NotImplementedError("ensemble engine does not support "
                                  "&RUN_PARAMS patch plug-ins")


def _perturb(u0: np.ndarray, spec: EnsembleSpec, k: int) -> np.ndarray:
    if spec.perturb_amp <= 0.0:
        return u0
    rng = np.random.default_rng(spec.perturb_seed + k)
    u0 = np.array(u0, copy=True)
    u0[0] = u0[0] * (1.0 + spec.perturb_amp
                     * (2.0 * rng.random(u0[0].shape) - 1.0))
    return u0


def build_member(spec: EnsembleSpec, k: int, dtype=jnp.float64):
    """(grid, state, tend, params) for member k — the single source of
    truth for ICs, shared by the engine and by bitwise solo-run tests.

    ``state`` is a tuple of device arrays: ``(u,)`` for hydro/rhd,
    ``(u, bf)`` for MHD.  ``grid`` is the frozen static dataclass that
    doubles as the jit cache key (and the sub-batch group key)."""
    from ramses_tpu.grid import boundary as bmod

    # no-sweep fast path: every member shares one (grid, ICs, params)
    # template — cached on the spec — and differs only by the traced
    # perturbation, so an N-member expansion builds the grid and runs
    # condinit once instead of N times (this dominates small-job engine
    # construction).  The shared ``p`` is the same object for every
    # member; callers treat it as read-only.
    tmpl = (spec.__dict__.get("_member_template")
            if not spec.sweeps else None)
    if tmpl is not None and spec.solver == "hydro":
        grid, u0, tend, p = tmpl
        u0k = _perturb(u0, spec, k)
        return grid, (jnp.asarray(u0k, dtype),), tend, p

    p = spec.member_params(k)
    _check_uniform_only(p, spec.solver)
    tend = float(p.output.tout[-1] if p.output.tout else p.output.tend)
    if spec.solver == "hydro":
        from ramses_tpu.grid.uniform import UniformGrid
        from ramses_tpu.hydro.core import HydroStatic
        from ramses_tpu.init.regions import condinit
        cfg = HydroStatic.from_params(p)
        shape, dx = _uniform_shape(p, cubic=False)
        grid = UniformGrid(cfg=cfg, shape=shape, dx=dx,
                           bc=bmod.BoundarySpec.from_params(p))
        u0 = np.asarray(condinit(shape, dx, p, cfg))
        if not spec.sweeps:
            spec.__dict__["_member_template"] = (grid, u0, tend, p)
        u0k = _perturb(u0, spec, k)
        return grid, (jnp.asarray(u0k, dtype),), tend, p
    if spec.solver == "mhd":
        from ramses_tpu.mhd.driver import mhd_condinit
        from ramses_tpu.mhd.core import MhdStatic
        from ramses_tpu.mhd import uniform as mu
        cfg = MhdStatic.from_params(p)
        shape, dx = _uniform_shape(p, cubic=True)
        spec_bc = bmod.BoundarySpec.from_params(p)
        bc_kinds = tuple((f[0].kind, f[1].kind) for f in spec_bc.faces)
        for lo, hi in bc_kinds:
            for kk in (lo, hi):
                if kk not in (bmod.PERIODIC, bmod.OUTFLOW):
                    raise NotImplementedError(
                        "mhd ensembles: periodic/outflow only")
        grid = mu.MhdGrid(cfg=cfg, shape=shape, dx=dx, bc_kinds=bc_kinds)
        u0, bf0 = mhd_condinit(shape, dx, p, cfg)
        u0 = _perturb(np.asarray(u0), spec, k)
        return grid, (jnp.asarray(u0, dtype),
                      jnp.asarray(bf0, dtype)), tend, p
    if spec.solver == "rhd":
        from ramses_tpu.rhd.driver import rhd_condinit
        from ramses_tpu.rhd.core import RhdStatic
        from ramses_tpu.rhd import uniform as ru
        cfg = RhdStatic.from_params(p)
        shape, dx = _uniform_shape(p, cubic=True)
        spec_bc = bmod.BoundarySpec.from_params(p)
        bc_kinds = tuple((f[0].kind, f[1].kind) for f in spec_bc.faces)
        for lo, hi in bc_kinds:
            for kk in (lo, hi):
                if kk not in (bmod.PERIODIC, bmod.OUTFLOW):
                    raise NotImplementedError(
                        "rhd ensembles: periodic/outflow only")
        grid = ru.RhdGrid(cfg=cfg, shape=shape, dx=dx, bc_kinds=bc_kinds)
        u0 = _perturb(np.asarray(rhd_condinit(shape, dx, p, cfg)), spec, k)
        return grid, (jnp.asarray(u0, dtype),), tend, p
    raise ValueError(f"unknown solver '{spec.solver}'")


def member_cooling(p: Params):
    """(tables, cspec) for a member's &COOLING_PARAMS, or (None, None).
    Table *data* is traced (J21 sweeps batch freely); ``cspec`` is the
    frozen static part that splits the sub-batch grouping."""
    if not p.cooling.cooling:
        return None, None
    from ramses_tpu.hydro.cooling import CoolingSpec, build_tables
    from ramses_tpu.units import units as units_fn
    cspec = CoolingSpec.from_params(p, units_fn(p, cosmo=None, aexp=1.0))
    c = p.cooling
    tables = build_tables(aexp=1.0, J21=float(c.J21),
                          a_spec=float(c.a_spec),
                          z_reion=float(c.z_reion),
                          haardt_madau=bool(c.haardt_madau))
    return tables, cspec


@dataclass
class SubBatch:
    """One frozen-config group: members that share a jit cache key."""
    grid: Any
    cspec: Any                       # cooling static part (hydro only)
    members: List[int]               # member indices, batch order
    state: Tuple[Any, ...]           # each [B, ...]
    tables: Any                      # stacked cooling tables or None
    t: Any                           # [B] device
    tend: np.ndarray                 # [B] host
    nstep: np.ndarray                # [B] host, real steps done
    t_host: np.ndarray               # [B] host mirror of t (refreshed
    #                                  by the per-dispatch fetch)
    quarantined: np.ndarray          # [B] host bool (evicted members)
    replicas: int = 1                # packed-mode replica count (the
    #                                  member axis shards over this
    #                                  many devices; 1 = single-device)

    @property
    def size(self) -> int:
        return len(self.members)


class EnsembleEngine:
    """Advance every member of an :class:`EnsembleSpec` to its tend.

    Members are grouped by ``(grid, cspec)`` — the frozen static
    dataclasses that are the jit cache keys — so each distinct config
    compiles once and a traced-only sweep compiles exactly once total.
    The drive loop dispatches fused ``chunk_steps``-step windows per
    group until all members complete (per-member ``tend`` or
    ``&RUN_PARAMS nstepmax``).
    """

    def __init__(self, spec: EnsembleSpec, dtype=jnp.float64,
                 telemetry=None, plan=None):
        from ramses_tpu.ensemble.meshplan import MeshPlan
        from ramses_tpu.telemetry import make_telemetry
        self.spec = spec
        self.params = spec.base
        self.dtype = dtype
        #: two-level packing (ensemble/meshplan): how this job's
        #: sub-batches land on the assigned devices
        self.plan = plan if plan is not None else MeshPlan.single()
        self._slab_mesh = None
        # checkpoint dirty-tracking: save() skips the rewrite when no
        # step has landed since the last snapshot (run_job's final save
        # immediately after the last on_chunk beat is otherwise a full
        # redundant checkpoint — measurable per-job cost for small jobs)
        self._dirty = True
        self._last_snap = ""
        tdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        by_key: Dict[Any, Dict[str, list]] = {}
        for k in range(spec.nmember):
            grid, state, tend, p = build_member(spec, k, dtype=dtype)
            tables, cspec = (member_cooling(p) if spec.solver == "hydro"
                             else (None, None))
            g = by_key.setdefault((grid, cspec), dict(
                grid=grid, cspec=cspec, members=[], states=[],
                tables=[], tend=[]))
            g["members"].append(k)
            g["states"].append(state)
            g["tables"].append(tables)
            g["tend"].append(tend)
        self.groups: List[SubBatch] = []
        for g in by_key.values():
            ncomp = len(g["states"][0])
            state = tuple(jnp.stack([s[c] for s in g["states"]])
                          for c in range(ncomp))
            tables = (jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *g["tables"])
                if g["tables"][0] is not None else None)
            b = len(g["members"])
            self.groups.append(SubBatch(
                grid=g["grid"], cspec=g["cspec"], members=g["members"],
                state=state, tables=tables, t=jnp.zeros(b, tdt),
                tend=np.asarray(g["tend"], np.float64),
                nstep=np.zeros(b, np.int64),
                t_host=np.zeros(b, np.float64),
                quarantined=np.zeros(b, bool)))
        self.wall_s = 0.0
        self.cell_updates = 0
        self._iout = 0
        #: member isolation ladder state: {member: {reason, nstep, t,
        #: dump}} for members evicted by the batched step-guard
        self.quarantined: Dict[int, Dict[str, Any]] = {}
        #: correlation fields (trace_id/job/worker — ramses_tpu/obs)
        #: the serve loop sets after construction; folded into every
        #: checkpoint manifest meta so artifacts join the job's trace
        self.trace_meta: Dict[str, Any] = {}
        self.telemetry = (telemetry if telemetry is not None
                          else make_telemetry(spec.base,
                                              run_info=self.run_info()))
        from ramses_tpu.resilience.faultinject import FaultInjector
        from ramses_tpu.resilience.stepguard import BatchGuard
        self._bguard = BatchGuard.from_params(spec.base,
                                              telemetry=self.telemetry)
        self._fault = FaultInjector.from_params(spec.base)
        # hang watchdog: &ENSEMBLE_PARAMS *_deadline_s (None when off)
        from ramses_tpu.resilience.watchdog import Watchdog
        self._wd = Watchdog.from_params(spec.base, scope="ensemble",
                                        telemetry=self.telemetry)
        if self.plan.mode == "slab":
            from ramses_tpu.parallel import halo
            if spec.solver != "hydro" or any(g.tables is not None
                                             for g in self.groups):
                raise NotImplementedError(
                    "slab-mode ensembles: pure hydro without cooling "
                    "only (parallel/halo pipeline scope)")
            if self._bguard is not None:
                raise NotImplementedError(
                    "slab-mode ensembles do not support the batched "
                    "step-guard (run_steps_halo has no summarize/"
                    "dt_scale surface); disable &RESILIENCE_PARAMS "
                    "step_guard or run packed/single")
            self._slab_mesh = halo.make_halo_mesh(self.plan.devices())
            for g in self.groups:
                halo._check(g.grid, self._slab_mesh)
        elif self.plan.mode == "packed":
            for g in self.groups:
                self._place_group(g)

    def _place_group(self, g: SubBatch) -> None:
        """Packed-mode placement: shard one sub-batch's member axis
        over the replica mesh.  The replica count is the largest
        divisor of the batch size within the assigned device count
        (NamedSharding needs an even split — and an even split keeps
        the per-device replica programs identical, which is what makes
        packed execution bitwise-equal to single-device).  Called at
        construction and again after a checkpoint load, so a
        checkpoint written under any packing restores under any
        other."""
        if self.plan.mode != "packed":
            return
        from ramses_tpu.ensemble.meshplan import largest_divisor
        from ramses_tpu.parallel.mesh import (replica_mesh,
                                              replica_sharding)
        devs = self.plan.devices()
        cap = int(self.plan.max_replicas) or len(devs)
        r = largest_divisor(g.size, min(cap, len(devs)))
        g.replicas = r
        if r <= 1:
            return
        if hasattr(g.grid, "ndev"):
            # the member axis spans r devices: the uniform step's
            # kernel gate must see that (grid/uniform.UniformGrid.ndev)
            g.grid = replace(g.grid, ndev=r)
        mesh = replica_mesh(devs[:r])
        g.state = tuple(
            jax.device_put(c, replica_sharding(mesh, c.ndim))
            for c in g.state)
        g.t = jax.device_put(g.t, replica_sharding(mesh, 1))
        if g.tables is not None:
            g.tables = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, replica_sharding(mesh, x.ndim)), g.tables)

    # ------------------------------------------------------------------
    # status surface (duck-typed like the solo sims, for the supervisor,
    # telemetry close() and OpsGuard-style callers)
    @property
    def nmember(self) -> int:
        return self.spec.nmember

    @property
    def t(self) -> float:
        """Least-advanced *healthy* member time (monotone; tend when
        all done).  Host-cached — no device fetch."""
        vals = [float(g.t_host[~g.quarantined].min())
                for g in self.groups if (~g.quarantined).any()]
        if not vals:                   # everything quarantined
            vals = [float(g.t_host.min()) for g in self.groups]
        return float(min(vals))

    @property
    def nstep(self) -> int:
        """Largest member step count (monotone checkpoint ordinal)."""
        return int(max(int(g.nstep.max()) for g in self.groups))

    @property
    def quarantined_count(self) -> int:
        """Members evicted by the member isolation ladder (telemetry
        folds this into step/chunk records)."""
        return len(self.quarantined)

    def run_info(self) -> Dict[str, Any]:
        info = {"driver": f"ensemble-{self.spec.solver}"
                if hasattr(self, "spec") else "ensemble",
                "nmember": self.spec.nmember,
                "ngroup": len(getattr(self, "groups", [])),
                "sweeps": sorted(self.spec.sweeps)}
        plan = getattr(self, "plan", None)
        if plan is not None:
            info["packing"] = plan.describe()
            groups = getattr(self, "groups", None)
            if groups:
                info["packing"]["group_replicas"] = [
                    int(g.replicas) for g in groups]
        return info

    def _member_pos(self, k: int) -> Tuple[SubBatch, int]:
        for g in self.groups:
            if k in g.members:
                return g, g.members.index(k)
        raise IndexError(k)

    def member_state(self, k: int) -> Dict[str, Any]:
        """Member k's current state: ``u`` (+ ``bf`` for MHD), t, nstep."""
        g, i = self._member_pos(k)
        out = {"u": g.state[0][i], "t": float(np.asarray(g.t)[i]),
               "nstep": int(g.nstep[i]),
               "quarantined": bool(g.quarantined[i])}
        if len(g.state) > 1:
            out["bf"] = g.state[1][i]
        return out

    def _group_done(self, g: SubBatch, nstepmax: int) -> np.ndarray:
        """Per-member completion from host-cached time: reached tend,
        hit the step budget, or quarantined (evicted members count as
        terminally done so the batch — and the job — can drain)."""
        reached = g.t_host >= g.tend * (1.0 - _TEND_EPS) - 1e-300
        return reached | (g.nstep >= nstepmax) | g.quarantined

    def run_complete(self, params=None, tend=None) -> bool:
        """Every member individually reached its tend or the step
        budget (the supervisor's completion hook)."""
        nmax = int(self.params.run.nstepmax)
        return all(bool(self._group_done(g, nmax).all())
                   for g in self.groups)

    # ------------------------------------------------------------------
    def _dispatch(self, g: SubBatch, nsteps: int, eff_tend,
                  dt_scale: float = 1.0, summarize: bool = False,
                  fetch: bool = True):
        """One fused window for one sub-batch.

        With ``fetch`` (the default) returns ``(ndone[B], summ)`` with
        ``summ`` the per-member guard summary ``[B, 3]`` (None unless
        ``summarize``).  Exactly ONE host<->device fetch per call —
        ``jax.device_get`` on the ``(ndone, t[, summary])`` tuple — so
        arming the batched guard widens the existing fetch instead of
        adding one, and the zero-overhead pin can count
        ``jax.device_get`` calls honestly.  ``g.t_host`` is refreshed
        from the same fetch.

        With ``fetch=False`` the window is dispatched asynchronously
        and the un-fetched device refs ``(ndone, t[, summary])`` are
        returned instead: the chunk driver stacks every group's refs
        into a SINGLE ``jax.device_get`` (one host round-trip per
        chunk regardless of group count) and folds each tuple back via
        :meth:`_apply_fetch`."""
        tdt = g.t.dtype
        tend = jnp.asarray(eff_tend, tdt)
        summ_ref = None
        if self._slab_mesh is not None:
            t, ndone = self._dispatch_slab(g, nsteps, eff_tend)
        elif self.spec.solver == "hydro" and g.tables is not None:
            from ramses_tpu.grid.uniform import run_steps_cool_batch
            out = run_steps_cool_batch(
                g.grid, g.state[0], g.t, tend, nsteps, g.tables,
                g.cspec, dt_scale=dt_scale, summarize=summarize)
            u, t, ndone = out[:3]
            g.state = (u,)
            summ_ref = out[-1] if summarize else None
        elif self.spec.solver == "hydro":
            from ramses_tpu.grid.uniform import run_steps_batch
            out = run_steps_batch(
                g.grid, g.state[0], g.t, tend, nsteps,
                dt_scale=dt_scale, summarize=summarize)
            u, t, ndone = out[:3]
            g.state = (u,)
            summ_ref = out[-1] if summarize else None
        elif self.spec.solver == "mhd":
            from ramses_tpu.mhd.uniform import run_steps_batch
            out = run_steps_batch(
                g.grid, g.state[0], g.state[1], g.t, tend, nsteps,
                dt_scale=dt_scale, summarize=summarize)
            u, bf, t, ndone = out[:4]
            g.state = (u, bf)
            summ_ref = out[-1] if summarize else None
        else:
            from ramses_tpu.rhd.uniform import run_steps_batch
            out = run_steps_batch(
                g.grid, g.state[0], g.t, tend, nsteps,
                dt_scale=dt_scale, summarize=summarize)
            u, t, ndone = out[:3]
            g.state = (u,)
            summ_ref = out[-1] if summarize else None
        g.t = t
        refs = ((ndone, t) if summ_ref is None
                else (ndone, t, summ_ref))
        if not fetch:
            return refs
        return self._apply_fetch(g, jax.device_get(refs))

    @staticmethod
    def _apply_fetch(g: SubBatch, vals):
        """Fold one fetched ``(ndone, t[, summary])`` tuple back into
        the group's host mirrors; returns ``(ndone[B], summ)``."""
        g.t_host = np.asarray(vals[1], np.float64)
        summ = (np.asarray(vals[2], np.float64) if len(vals) > 2
                else None)
        return np.asarray(vals[0], np.int64), summ

    def _dispatch_slab(self, g: SubBatch, nsteps: int, eff_tend):
        """Slab-mode window: stream each active member through the
        explicit slab pipeline (:func:`ramses_tpu.parallel.halo.
        run_steps_halo`) on the full assigned mesh, one member at a
        time.  Per-member arrays, mesh and window sizes are identical
        to a standalone sharded run — the bitwise parity pin.  Members
        whose effective tend cannot advance them (done, frozen at the
        step budget, quarantined) are skipped with state untouched
        rather than burning a mesh-wide no-op window."""
        from ramses_tpu.parallel.halo import run_steps_halo
        eff = np.asarray(eff_tend, np.float64)
        us, ts, nds = [], [], []
        for i in range(g.size):
            if eff[i] <= g.t_host[i]:
                us.append(g.state[0][i])
                ts.append(g.t[i])
                nds.append(jnp.zeros((), jnp.int32))
                continue
            u, t, nd = run_steps_halo(g.grid, self._slab_mesh,
                                      g.state[0][i], g.t[i],
                                      float(eff[i]), nsteps)
            us.append(u)
            ts.append(t)
            nds.append(nd)
        g.state = (jnp.stack(us),)
        return jnp.stack(ts), jnp.stack(nds)

    def begin_chunk(self, chunk: Optional[int] = None,
                    nstepmax: Optional[int] = None) -> Dict[str, Any]:
        """Dispatch one fused window for every unfinished sub-batch
        WITHOUT blocking on the host fetch; returns the chunk context
        for :meth:`finish_chunk`.

        The begin/finish split exists for the gang driver
        (``ensemble/service.run_gang``): every co-scheduled job's
        windows are dispatched back-to-back — all submeshes compute
        concurrently — before any host thread blocks on results."""
        chunk = int(chunk or self.params.ensemble.chunk_steps or 16)
        nmax = int(nstepmax if nstepmax is not None
                   else self.params.run.nstepmax)
        guard = self._bguard
        if self._fault is not None:
            # top of chunk: the previous chunk's on_chunk beat has
            # already checkpointed, so a sigterm@K resume restarts
            # at nstep >= K and strict arming prevents a re-fire
            self._fault.maybe_signal(self.nstep)
            # zombie@K: stall the host thread past stale_timeout,
            # then resume — the queue's fencing token must refuse
            # this worker's writes from here on
            self._fault.maybe_zombie(self.nstep)
        t0 = time.perf_counter()
        pending: List[Tuple[SubBatch, np.ndarray, Any, Any]] = []
        for g in self.groups:
            done = self._group_done(g, nmax)
            if done.all():
                continue
            # members at tend idle via the in-scan mask; members at
            # the step budget (or quarantined) are frozen by
            # clamping their effective tend below their current t
            rem = nmax - int(g.nstep[~done].max()) if (~done).any() \
                else 0
            n = max(1, min(chunk, rem))
            if self._fault is not None:
                n = self._fault.clamp_window_batch(
                    n, self.nstep,
                    lambda j, _g=g: int(_g.nstep[_g.members.index(j)])
                    if j in _g.members else self.nstep)
            eff_tend = np.where((g.nstep >= nmax) | g.quarantined,
                                -1.0, g.tend)
            # the guard's retained pre-window state: plain refs
            # (run_steps_batch does not donate its inputs)
            prev = ((g.state, g.t, g.nstep.copy(),
                     g.t_host.copy()) if guard is not None else None)
            if self._fault is not None:
                self._fault.maybe_nan_batch(g)
            with (self._wd.guard("step") if self._wd is not None
                    else nullcontext()):
                if self._fault is not None:
                    self._fault.maybe_hang_batch(g, self.nstep)
                refs = self._dispatch(g, n, eff_tend,
                                      summarize=guard is not None,
                                      fetch=False)
            pending.append((g, done, prev, refs))
        return {"pending": pending, "t0": t0}

    def finish_chunk(self, ctx: Dict[str, Any]) -> int:
        """Fetch and fold back one chunk's results.

        A SINGLE stacked ``jax.device_get`` over every pending group's
        ``(ndone, t[, summary])`` refs — one host round-trip per chunk
        regardless of group count (pinned by the zero-overhead
        device_get counter tests) — then guard screening/recovery and
        step accounting per group.  Returns the steps advanced."""
        guard = self._bguard
        stepped = 0
        pending = ctx["pending"]
        fetched = []
        if pending:
            with (self._wd.guard("step") if self._wd is not None
                    else nullcontext()):
                fetched = jax.device_get([p[3] for p in pending])
        for (g, done, prev, _refs), vals in zip(pending, fetched):
            ndone, summ = self._apply_fetch(g, vals)
            if self._wd is not None:
                self._wd.note(nstep=self.nstep, t=self.t)
            if guard is not None:
                bad = guard.screen(g.t_host, summ, active=~done)
                if bad.any():
                    ndone = self._recover(g, bad, prev, ndone)
                    self._dirty = True
            g.nstep = g.nstep + ndone
            stepped += int(ndone.sum())
            self.cell_updates += int(ndone.sum()) * g.grid.ncell
        if stepped > 0 or self._fault is not None:
            self._dirty = True
        self.wall_s += time.perf_counter() - ctx["t0"]
        return stepped

    def run(self, chunk: Optional[int] = None,
            nstepmax: Optional[int] = None, verbose: bool = False,
            on_chunk: Optional[Callable[["EnsembleEngine"], None]] = None):
        """Drive every sub-batch until all members complete.

        ONE stacked host round-trip per chunk (``finish_chunk``),
        however many sub-batch groups the sweep split into;
        ``on_chunk`` (service heartbeats) runs after each chunk."""
        chunk = int(chunk or self.params.ensemble.chunk_steps or 16)
        nmax = int(nstepmax if nstepmax is not None
                   else self.params.run.nstepmax)
        while not self.run_complete():
            ctx = self.begin_chunk(chunk, nmax)
            stepped = self.finish_chunk(ctx)
            self.telemetry.record_event(
                "ensemble_chunk", nmember=self.nmember,
                ngroup=len(self.groups), steps=stepped,
                t_min=self.t, nstep_max=self.nstep,
                quarantined=self.quarantined_count,
                wall_s=round(self.wall_s, 6))
            if verbose:
                print(f"ensemble: {self.nmember} members "
                      f"{len(self.groups)} groups t_min={self.t:.5e} "
                      f"steps+={stepped} "
                      f"quarantined={self.quarantined_count}")
            if on_chunk is not None:
                on_chunk(self)
            if stepped == 0:
                # every active member was clamped to a no-op window —
                # cannot happen unless tend/nstepmax are inconsistent;
                # bail rather than spin
                break
        return self

    # ------------------------------------------------------------------
    # member isolation ladder: trip -> masked rollback -> halved-dt
    # retry -> LLF escalation regroup -> quarantine
    def _restore_members(self, g: SubBatch, mask: np.ndarray, prev):
        """Masked select of the retained pre-window state into the
        tripped lanes only — healthy members keep their advanced
        arrays bitwise untouched."""
        prev_state, prev_t, _prev_nstep, prev_t_host = prev
        m = jnp.asarray(mask)
        g.state = tuple(
            jnp.where(m.reshape((-1,) + (1,) * (cur.ndim - 1)), ps, cur)
            for ps, cur in zip(prev_state, g.state))
        g.t = jnp.where(m, prev_t, g.t)
        g.t_host = np.where(mask, prev_t_host, g.t_host)

    def _retry_masked(self, g: SubBatch, still: np.ndarray,
                      dt_scale: float):
        """Re-advance only the tripped lanes one step at reduced dt;
        everyone else idles via the effective-tend clamp (their state
        passes through the in-scan select bitwise unchanged)."""
        eff = np.where(still, g.tend, -1.0)
        ndone, summ = self._dispatch(g, 1, eff, dt_scale=dt_scale,
                                     summarize=True)
        ok = ~self._bguard.screen(g.t_host, summ)
        return ndone, ok

    def _retry_escalated(self, g: SubBatch, still: np.ndarray,
                         dt_scale: float):
        """LLF escalation as a *regroup*: the Riemann knob is a field
        of the frozen static config (a jit cache key), so the tripped
        members are gathered into an escalation sub-batch whose grid
        carries ``riemann='llf'``, advanced one step, and scattered
        back — never a traced branch."""
        import dataclasses as _dc
        idx = np.nonzero(still)[0]
        jidx = jnp.asarray(idx)
        esc = SubBatch(
            grid=_dc.replace(g.grid, cfg=_dc.replace(g.grid.cfg,
                                                     riemann="llf")),
            cspec=g.cspec,
            members=[g.members[i] for i in idx],
            state=tuple(c[jidx] for c in g.state),
            tables=(jax.tree_util.tree_map(lambda x: x[jidx], g.tables)
                    if g.tables is not None else None),
            t=g.t[jidx], tend=g.tend[idx],
            nstep=g.nstep[idx].copy(), t_host=g.t_host[idx].copy(),
            quarantined=np.zeros(len(idx), bool))
        nd_sub, summ = self._dispatch(esc, 1, esc.tend,
                                      dt_scale=dt_scale, summarize=True)
        ok_sub = ~self._bguard.screen(esc.t_host, summ)
        g.state = tuple(c.at[jidx].set(sc)
                        for c, sc in zip(g.state, esc.state))
        g.t = g.t.at[jidx].set(esc.t)
        g.t_host[idx] = esc.t_host
        ndone = np.zeros(g.size, np.int64)
        ndone[idx] = nd_sub
        ok = np.ones(g.size, bool)
        ok[idx] = ok_sub
        return ndone, ok

    def _recover(self, g: SubBatch, bad: np.ndarray, prev,
                 ndone: np.ndarray) -> np.ndarray:
        """Run the member isolation ladder for the tripped lanes of
        one window; returns the corrected per-member ndone (tripped
        lanes contribute only their recovered retry steps)."""
        sg = self._bguard
        _ps, _pt, prev_nstep, prev_t_host = prev
        ndone = np.array(ndone, np.int64)
        ndone[bad] = 0
        sg.record_trip([g.members[i] for i in np.nonzero(bad)[0]],
                       prev_nstep[bad], prev_t_host[bad])
        self._restore_members(g, bad, prev)
        still = bad.copy()
        riemann = getattr(g.grid.cfg, "riemann", None)
        can_llf = riemann is not None and riemann != "llf"
        for attempt in range(1, sg.max_retries + 1):
            scale = 0.5 ** attempt
            escalated = attempt >= 2 and can_llf
            sg.record_rollback(
                [g.members[i] for i in np.nonzero(still)[0]],
                attempt, scale, escalated)
            if escalated:
                nd_r, ok = self._retry_escalated(g, still, scale)
            else:
                nd_r, ok = self._retry_masked(g, still, scale)
            rec = still & ok
            if rec.any():
                ndone[rec] += nd_r[rec]
                sg.record_recovered(
                    [g.members[i] for i in np.nonzero(rec)[0]], attempt)
            still &= ~ok
            if not still.any():
                return ndone
            self._restore_members(g, still, prev)
        for i in np.nonzero(still)[0]:
            self._quarantine_member(g, int(i), int(prev_nstep[i]),
                                    float(prev_t_host[i]))
        return ndone

    def _quarantine_member(self, g: SubBatch, i: int, nstep0: int,
                           t0: float):
        """Evict lane ``i`` of group ``g``: emergency-dump its last
        clean state (already restored by the ladder), record the
        census entry, and freeze the lane so the batch drains without
        it.  The census rides every subsequent checkpoint manifest."""
        k = int(g.members[i])
        dump = ""
        try:
            dump = self._dump_member(g, i, k, nstep0, t0)
        except Exception as e:  # noqa: BLE001 — dump is best-effort
            print(f" batch guard: member {k} emergency dump failed: "
                  f"{e!r}")
        info = {"reason": "nonfinite_state", "nstep": nstep0,
                "t": t0, "dump": dump}
        self.quarantined[k] = info
        g.quarantined[i] = True
        self._bguard.record_quarantine(k, info)

    def _dump_member(self, g: SubBatch, i: int, k: int, nstep0: int,
                     t0: float) -> str:
        """Manifest-valid single-member emergency dump
        (``quarantine_mNNN/`` beside the ensemble checkpoints; the
        ``output_`` prefix is avoided so auto-resume never selects
        it)."""
        from ramses_tpu.resilience.checkpoint import finalize_checkpoint
        base = str(self.params.output.output_dir or ".")
        os.makedirs(base, exist_ok=True)
        final = os.path.join(base, f"quarantine_m{k:03d}")
        stage = final + ".tmp"
        os.makedirs(stage, exist_ok=True)
        arrays = {f"s{ci}": np.asarray(comp[i])
                  for ci, comp in enumerate(g.state)}
        np.savez(os.path.join(stage, "member_state.npz"),
                 t=np.float64(t0), nstep=np.int64(nstep0), **arrays)
        return finalize_checkpoint(
            stage, final, meta={"kind": "quarantine_member",
                                "member": k,
                                "reason": "nonfinite_state",
                                "nstep": nstep0, "t": t0,
                                **self.trace_meta})

    # ------------------------------------------------------------------
    # manifest-valid checkpoints (resilience/checkpoint) so a supervised
    # ensemble job resumes exactly like a solo run
    def save(self, base_dir: str, iout: Optional[int] = None) -> str:
        from ramses_tpu.resilience.checkpoint import finalize_checkpoint
        if (iout is None and not self._dirty and self._last_snap
                and os.path.dirname(self._last_snap)
                == os.path.abspath(base_dir)
                and os.path.isdir(self._last_snap)):
            # nothing stepped since the last snapshot: the checkpoint
            # on disk is bit-identical to what a rewrite would produce
            return self._last_snap
        self._iout = int(iout if iout is not None else self._iout + 1)
        final = os.path.join(base_dir, f"output_{self._iout:05d}")
        stage = final + ".tmp"
        os.makedirs(stage, exist_ok=True)
        try:
            if self._fault is not None:
                # enospc@K: the staging write raises OSError(ENOSPC)
                # — diskguard absorbs it one layer up
                self._fault.maybe_enospc(self.nstep)
            arrays: Dict[str, np.ndarray] = {}
            for gi, g in enumerate(self.groups):
                for ci, comp in enumerate(g.state):
                    arrays[f"g{gi}_s{ci}"] = np.asarray(comp)
                arrays[f"g{gi}_t"] = np.asarray(g.t)
                arrays[f"g{gi}_nstep"] = g.nstep
            np.savez(os.path.join(stage, "ensemble_state.npz"),
                     **arrays)
            census = {str(k): v
                      for k, v in sorted(self.quarantined.items())}
            with open(os.path.join(stage, "ensemble.json"), "w") as f:
                json.dump({"fingerprint": self.spec.fingerprint(),
                           "nmember": self.nmember,
                           "solver": self.spec.solver,
                           "groups": [g.members for g in self.groups],
                           "quarantined": census,
                           # informational: the packing the checkpoint
                           # was written under.  State arrays are saved
                           # host-global, so restore is elastic across
                           # packings — from_checkpoint re-places under
                           # whatever plan the restoring worker passes.
                           "packing": self.plan.describe(),
                           "iout": self._iout}, f, indent=1)
            meta = {"kind": "ensemble", "iout": self._iout,
                    "nstep": self.nstep, "t": self.t,
                    "nmember": self.nmember, **self.trace_meta}
            if census:
                # per-member quarantine census in the manifest meta:
                # the durable record (read_quarantine_census) of which
                # members were evicted, with reason/nstep/t
                meta["quarantined"] = census
            snap = finalize_checkpoint(stage, final, meta)
        except OSError:
            # a failed staging write (ENOSPC, dying disk) must not
            # leave a half-staged output_NNNNN.tmp behind — remove it
            # and retract the iout bump so the next save reuses it
            import shutil
            shutil.rmtree(stage, ignore_errors=True)
            self._iout -= 1
            raise
        self._dirty = False
        self._last_snap = os.path.abspath(snap)
        return snap

    @classmethod
    def from_checkpoint(cls, spec: EnsembleSpec, outdir: str,
                        dtype=jnp.float64, telemetry=None, plan=None
                        ) -> "EnsembleEngine":
        """Rebuild from an ensemble checkpoint dir (manifest-validated
        by the caller/supervisor); the spec must expand to the same
        members the checkpoint was written from.  ``plan`` names the
        packing for the *restored* run — it need not match the one the
        checkpoint was written under (cross-packing restore: the state
        arrays are host-global, and the loaded groups are simply
        re-placed under the new plan)."""
        with open(os.path.join(outdir, "ensemble.json")) as f:
            meta = json.load(f)
        eng = cls(spec, dtype=dtype, telemetry=telemetry, plan=plan)
        if meta["fingerprint"] != spec.fingerprint():
            raise ValueError(
                f"checkpoint {outdir} was written by a different "
                f"ensemble spec (fingerprint {meta['fingerprint']} != "
                f"{spec.fingerprint()})")
        if meta["groups"] != [g.members for g in eng.groups]:
            raise ValueError(f"checkpoint {outdir}: sub-batch grouping "
                             "changed; cannot restore")
        data = np.load(os.path.join(outdir, "ensemble_state.npz"))
        for gi, g in enumerate(eng.groups):
            g.state = tuple(jnp.asarray(data[f"g{gi}_s{ci}"], dtype)
                            for ci in range(len(g.state)))
            # cast to the engine's time dtype (g.t was initialised to
            # it): a checkpoint written under a different x64 mode must
            # not leak its dtype into the scan carry
            g.t = jnp.asarray(data[f"g{gi}_t"], g.t.dtype)
            g.t_host = np.asarray(data[f"g{gi}_t"], np.float64)
            g.nstep = np.asarray(data[f"g{gi}_nstep"], np.int64)
            # re-place the loaded arrays under THIS engine's plan (the
            # checkpoint's own packing is irrelevant — elastic restore)
            eng._place_group(g)
        eng.quarantined = {int(k): dict(v) for k, v in
                           (meta.get("quarantined") or {}).items()}
        for k in eng.quarantined:
            g, i = eng._member_pos(k)
            g.quarantined[i] = True
        eng._iout = int(meta.get("iout", 0))
        # the restored-from snapshot is current until a step lands
        eng._dirty = False
        eng._last_snap = os.path.abspath(outdir)
        return eng
