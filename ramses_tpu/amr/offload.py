"""Out-of-core AMR: host-parked inactive levels around the subcycle.

The hierarchy is HBM-resident, so ``levelmax`` is capped by device
memory long before the blocked sweep or the halo engine become the
bottleneck.  GAMER (arXiv:1007.3818) ran AMR out-of-core by staging
inactive levels off the accelerator; this module mirrors that for the
fused step chain:

* a **residency planner** linearizes the ``advance(i, dtl)`` recursion
  of ``hierarchy._advance_traced`` into an op schedule
  (enter/sweep/restrict/courant) and computes each op's working set —
  the active level plus the coarse/fine neighbors its interpolation,
  restriction, and flux-correction touch; everything else may park;
* a **transfer engine** keeps each level's state either on device or
  in a :class:`HostBuffer`.  Eviction is ``copy_to_host_async`` into
  host staging followed by deletion of the device copy; prefetch is an
  async ``jax.device_put`` issued one op ahead (double buffer) so the
  upload of op k+1's working set overlaps op k's compute.  A fetch the
  prefetcher did not land in time is a **stall** and is counted.

The fused step is re-run as per-level jitted segments with swap points
between them.  Each segment replays the exact kernel calls of the
monolithic trace on the same operands in the same order, and the
subcycle dt is formed as ``dt * 2**-i`` (a static power-of-two scale,
bitwise equal to the recursion's successive ``0.5 * dtl`` halvings),
so the segmented step is bitwise identical to the single-window
program — pinned by ``tests/test_offload.py``.

Gated behind ``&AMR_PARAMS offload`` (off/auto/on); ``off`` leaves the
monolithic fast path untouched (zero new HLO, zero device fetches —
pinned by the zero-overhead test).  ``auto`` engages only when the
estimated resident set exceeds ``offload_hbm_budget_mb`` (default read
from the device's reported ``bytes_limit``; platforms that report none
never auto-engage, which keeps CPU test runs deterministic).
"""

from __future__ import annotations

import warnings
from functools import lru_cache, partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.amr import kernels as K


class HostBuffer:
    """A level's state parked in host RAM.

    Stands in for the device array inside ``sim.u`` while parked:
    exposes ``shape``/``dtype``/``nbytes`` (regrid's reuse check and
    the residency planner read them) and zero-copy ``__array__`` so
    pario format 2 dumps parked levels straight from host staging
    without a device round-trip.  ``__getitem__`` serves the tiny
    probe slices ``drain()`` takes.
    """

    __slots__ = ("host",)

    def __init__(self, host: np.ndarray):
        self.host = host

    @property
    def shape(self):
        return self.host.shape

    @property
    def dtype(self):
        return self.host.dtype

    @property
    def nbytes(self) -> int:
        return self.host.nbytes

    def __array__(self, dtype=None, copy=None):
        if dtype is None or dtype == self.host.dtype:
            return self.host
        return self.host.astype(dtype)

    def __getitem__(self, key):
        return self.host[key]

    def __len__(self):
        return len(self.host)

    def __repr__(self):
        return (f"HostBuffer(shape={self.host.shape}, "
                f"dtype={self.host.dtype})")


def is_parked(arr) -> bool:
    return isinstance(arr, HostBuffer)


def as_device(arr):
    """Fetch a possibly-parked array onto the device (blocking)."""
    if isinstance(arr, HostBuffer):
        return jax.device_put(arr.host)
    return arr


# ----------------------------------------------------------------------
# residency planner: linearize the subcycle recursion into an op
# schedule with per-op working sets
# ----------------------------------------------------------------------
class _Op(NamedTuple):
    kind: str          # "enter" | "sweep" | "restrict" | "courant"
    i: int             # index into spec.levels
    scale: float       # static power-of-two dt scale (sweep ops)
    ws: frozenset      # levels that must be device-resident for the op


def _working_set(spec, kind: str, i: int) -> frozenset:
    levels = spec.levels
    l = levels[i]
    if kind == "enter":
        return frozenset()              # host-side alias only
    if kind == "sweep":
        if spec.complete[i]:
            return frozenset((l,))
        return frozenset((l - 1, l))    # interp source + corr fold
    if kind == "restrict":
        return frozenset((l, levels[i + 1]))
    if kind == "courant":
        return frozenset((l,))
    raise AssertionError(kind)


@lru_cache(maxsize=None)
def plan_schedule(spec) -> tuple:
    """The linearized subcycle schedule for one coarse step.

    Emits ops in the exact order the ``advance`` recursion executes
    them, then inserts each level's Courant op directly after the LAST
    op that writes that level's state (``u[l]`` never changes again, so
    this equals the monolithic end-of-step Courant evaluation while
    letting the level park immediately afterwards).
    """
    levels = spec.levels
    ops = []

    def rec(i, scale):
        ops.append(("enter", i, scale))
        if i + 1 < len(levels):
            rec(i + 1, scale * 0.5)
            rec(i + 1, scale * 0.5)
        ops.append(("sweep", i, scale))
        if i + 1 < len(levels):
            ops.append(("restrict", i, 0.0))

    rec(0, 1.0)
    last_write = {}
    for k, (kind, i, _) in enumerate(ops):
        if kind in ("sweep", "restrict"):
            last_write[i] = k
    out = []
    for k, (kind, i, scale) in enumerate(ops):
        out.append(_Op(kind, i, scale, _working_set(spec, kind, i)))
        for j, kk in last_write.items():
            if kk == k:
                out.append(_Op("courant", j, 0.0,
                               _working_set(spec, "courant", j)))
    return tuple(out)


# ----------------------------------------------------------------------
# per-level jitted segments — each replays the exact monolithic kernel
# calls for one op, so the segmented step is bitwise identical
# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames=("spec", "i", "scale"))
def _seg_sweep(u_l, u_lm1, unew_l, unew_lm1, d, dt, spec, i: int,
               scale: float):
    l = spec.levels[i]
    dtl = dt * scale       # static power-of-two: bitwise == 0.5*dtl chain
    du, corr, _ = K.sweep_level(spec, i, u_l, u_lm1, d, dtl)
    unew_l = unew_l + du
    if corr is not None and l > spec.lmin:
        unew_lm1 = K.scatter_corrections(unew_lm1, corr, d["corr_idx"],
                                         spec.cfg)
    return unew_l, unew_lm1


@partial(jax.jit, static_argnames=("spec", "i"))
def _seg_restrict(u_l, u_fine, d, spec, i: int):
    return K.restrict_upload(u_l, u_fine, d["ref_cell"], d["son_oct"],
                             spec.cfg)


@partial(jax.jit, static_argnames=("spec", "i"))
def _seg_courant(u_l, d, spec, i: int):
    l = spec.levels[i]
    dt_l = K.level_courant(u_l, d["valid_cell"],
                           spec.boxlen / (1 << l), spec.cfg, None)
    return dt_l * (2.0 ** (l - spec.lmin))


@partial(jax.jit, static_argnames=("spec", "i", "eg", "fls", "itype",
                                   "ttd"))
def _seg_flags(u_l, u_lm1, d, spec, i: int, eg, fls, itype: int,
               ttd: int):
    """One level of ``hierarchy._fused_flags`` + the uint8 bitpack."""
    fl = K.flags_level(spec, i, u_l, u_lm1, d, eg, fls, itype)
    shifts = jnp.arange(ttd, dtype=jnp.uint32)
    return (fl.astype(jnp.uint32) << shifts[None, :]).sum(
        axis=1).astype(jnp.uint8)


# ----------------------------------------------------------------------
# transfer engine
# ----------------------------------------------------------------------
class OffloadEngine:
    """Residency manager for the level-state dict ``sim.u``.

    v1 scope: parks the conservative-state arrays only; the per-level
    device index maps (``sim.dev``) stay resident — they are integer
    tables a small fraction of the state size, and parking them would
    break the regrid map-reuse fast path.  The reported high-water is
    therefore the *managed-state* device footprint.
    """

    #: ops of lookahead the prefetcher runs ahead of compute (the
    #: double buffer); 0 disables prefetch (every fetch stalls) — the
    #: stall-accounting test uses that
    prefetch_depth = 1
    #: ops of lookahead whose working sets are protected from eviction
    keep_ahead = 2

    def __init__(self, mode: str, budget_mb: float = 0.0,
                 min_park_mb: float = 0.0):
        self.mode = mode
        self.budget_mb = float(budget_mb)
        self.min_park_bytes = int(float(min_park_mb) * (1 << 20))
        self._cache_maps = None     # identity of sim.maps at last decide
        self._cache_val = False
        self._warned = False
        self._inflight: Dict[int, object] = {}   # level -> device array
        self._pending = []                       # [(level, device array)]
        # cumulative transfer counters; per-step stats are deltas
        # between run_step boundaries (so regrid/dt/flags traffic lands
        # in the step record that follows it)
        self._tot = dict(stalls=0, prefetches=0, overlapped=0,
                         fetches=0, parks=0, bytes_parked=0,
                         bytes_fetched=0)
        self._mark = dict(self._tot)
        self._hwm = 0
        self.last_step_stats: Optional[dict] = None

    # -- construction ---------------------------------------------------
    @classmethod
    def from_params(cls, params) -> Optional["OffloadEngine"]:
        mode = str(getattr(params.amr, "offload", "off")
                   or "off").strip().lower()
        if mode in ("off", "", "false", ".false."):
            return None
        if mode not in ("auto", "on"):
            raise ValueError(f"&AMR_PARAMS offload={mode!r}: expected "
                             f"off, auto, or on")
        return cls(mode,
                   float(getattr(params.amr, "offload_hbm_budget_mb",
                                 0.0)),
                   float(getattr(params.amr, "offload_min_park_mb",
                                 0.0)))

    # -- engagement -----------------------------------------------------
    def ineligible_reason(self, sim) -> Optional[str]:
        """Why the segmented path cannot serve this sim (None = it can).

        Offload composes with the plain fused hydro step (incl. RHD).
        Anything that runs extra physics inside or around the step —
        gravity kicks, in-step cooling, PIC/cosmology drifts, tracer
        flux capture — or that holds extra references into ``sim.u``
        (step-guard snapshots, fault injection) keeps the monolithic
        window.
        """
        if not getattr(sim, "_offload_capable", False):
            return "solver family has its own step driver"
        if getattr(sim, "ndev", 1) != 1:
            return "multi-device mesh"
        checks = [(sim.gravity, "self-gravity"), (sim.pic, "particles"),
                  (sim.cosmo is not None, "cosmology"),
                  (sim.cool_spec is not None, "in-step cooling"),
                  (sim.tracer_x is not None, "MC tracers"),
                  (sim.sinks is not None, "sinks"),
                  (getattr(sim, "rt_amr", None) is not None,
                   "radiative transfer"),
                  (sim.movie is not None, "movie frames"),
                  (sim.sf_spec.enabled, "star formation"),
                  (sim._sguard is not None, "step retries"),
                  (sim._fault is not None, "fault injection")]
        for bad, why in checks:
            if bad:
                return why
        from ramses_tpu import patch as _patch
        if _patch.hook("source") is not None:
            return "patch source hook"
        return None

    def _budget_bytes(self) -> Optional[int]:
        if self.budget_mb > 0:
            return int(self.budget_mb * (1 << 20))
        try:
            stats = jax.local_devices()[0].memory_stats()
            if stats and stats.get("bytes_limit"):
                return int(stats["bytes_limit"])
        except Exception:
            pass
        return None

    def estimated_bytes(self, sim) -> int:
        return sum(int(a.nbytes) for a in sim.u.values())

    def engaged(self, sim) -> bool:
        """Decide (and cache per tree rebuild) whether offload runs.

        ``_rebuild_maps`` replaces ``sim.maps`` with a fresh dict, so
        the decision is re-taken exactly when the level structure (and
        hence the resident-set estimate) changes.
        """
        if self._cache_maps is sim.maps:
            return self._cache_val
        reason = self.ineligible_reason(sim)
        if reason is not None:
            if self.mode == "on" and not self._warned:
                warnings.warn(f"&AMR_PARAMS offload=on ignored: "
                              f"{reason}")
                self._warned = True
            val = False
        elif self.mode == "on":
            val = True
        else:                                   # auto
            budget = self._budget_bytes()
            val = (budget is not None
                   and self.estimated_bytes(sim) > budget)
        if not val:
            self.unpark_all(sim)
        self._cache_maps = sim.maps
        self._cache_val = val
        return val

    # -- residency mechanics --------------------------------------------
    def _fetch(self, u: dict, unew: dict, l: int):
        """Make level ``l`` device-resident; account overlap vs stall."""
        buf = u.get(l)
        if not isinstance(buf, HostBuffer):
            return
        arr = self._inflight.pop(l, None)
        if arr is not None:
            try:
                ready = bool(arr.is_ready())
            except Exception:
                ready = True
            if ready:
                self._tot["overlapped"] += 1
            else:
                self._tot["stalls"] += 1
        else:
            self._tot["stalls"] += 1
            arr = jax.device_put(buf.host)
        self._tot["fetches"] += 1
        self._tot["bytes_fetched"] += buf.nbytes
        if unew.get(l) is buf:
            unew[l] = arr
        u[l] = arr

    def _prefetch(self, u: dict, wanted):
        if self.prefetch_depth <= 0:
            return                # stall-accounting / debugging mode
        for l in wanted:
            if isinstance(u.get(l), HostBuffer) and l not in self._inflight:
                self._inflight[l] = jax.device_put(u[l].host)
                self._tot["prefetches"] += 1

    def _evict(self, u: dict, unew: dict, l: int):
        arr = u.get(l)
        if isinstance(arr, HostBuffer) or arr is None:
            return
        if unew.get(l) is not None and unew[l] is not arr:
            return        # children folded corrections in — pinned
        if arr.nbytes < self.min_park_bytes:
            return
        if any(a is arr for _, a in self._pending):
            return
        try:
            arr.copy_to_host_async()
        except Exception:
            pass          # backends without async D2H fall back to the
        self._pending.append((l, arr))          # blocking asarray below

    def _drain(self, u: dict, unew: dict):
        """Finish pending evictions: park the host copy, free HBM."""
        keep = []
        for l, arr in self._pending:
            if u.get(l) is not arr:
                continue                        # re-fetched meanwhile
            host = np.asarray(arr)
            buf = HostBuffer(host)
            u[l] = buf
            if unew.get(l) is arr:
                unew[l] = buf
            self._tot["parks"] += 1
            self._tot["bytes_parked"] += buf.nbytes
            try:
                arr.delete()
            except Exception:
                pass
        self._pending = keep

    def _cancel_inflight(self, l: int):
        self._inflight.pop(l, None)

    def _note_hwm(self, u: dict, unew: dict):
        seen, tot = set(), 0
        for d_ in (u, unew):
            for a in d_.values():
                if isinstance(a, HostBuffer) or a is None:
                    continue
                if id(a) in seen:
                    continue
                seen.add(id(a))
                tot += int(a.nbytes)
        for a in self._inflight.values():
            tot += int(a.nbytes)
        if tot > self._hwm:
            self._hwm = tot

    def unpark_all(self, sim):
        """Fetch every parked level back to device (blocking)."""
        self._inflight.clear()
        self._pending = []
        for l, a in list(sim.u.items()):
            if isinstance(a, HostBuffer):
                sim.u[l] = jax.device_put(a.host)

    # -- the segmented coarse step --------------------------------------
    def run_step(self, sim, dt: float, spec):
        """One coarse step via per-level segments with swap points.

        Returns ``(u, dtn)`` exactly like ``_fused_coarse_step`` (flux
        capture and gravity never reach here — see
        :meth:`ineligible_reason`).
        """
        plan = plan_schedule(spec)
        u = dict(sim.u)
        unew: Dict[int, object] = {}
        dts: Dict[int, object] = {}
        levels = spec.levels
        dt_dev = jnp.asarray(float(dt), sim.dtype)
        n = len(plan)
        for k, op in enumerate(plan):
            for l in op.ws:
                self._fetch(u, unew, l)
            # double buffer: issue the next ops' uploads so they ride
            # under this op's compute
            ahead = set()
            for kk in range(k + 1, min(n, k + 1 + self.prefetch_depth)):
                ahead |= plan[kk].ws
            self._prefetch(u, ahead)
            l = levels[op.i]
            if op.kind == "enter":
                unew[l] = u[l]
            elif op.kind == "sweep":
                if spec.complete[op.i]:
                    unew[l], _ = _seg_sweep(u[l], None, unew[l], None,
                                            sim.dev[l], dt_dev, spec,
                                            op.i, op.scale)
                else:
                    unew[l], unew[l - 1] = _seg_sweep(
                        u[l], u[l - 1], unew[l], unew.get(l - 1),
                        sim.dev[l], dt_dev, spec, op.i, op.scale)
                u[l] = unew[l]
            elif op.kind == "restrict":
                u[l] = _seg_restrict(u[l], u[levels[op.i + 1]],
                                     sim.dev[l], spec, op.i)
                # the pre-restrict unew is dead until the next coarse
                # step's ENTER re-aliases it; re-alias now so the
                # corrections pin does not keep this level resident
                unew[l] = u[l]
            elif op.kind == "courant":
                dts[op.i] = _seg_courant(u[l], sim.dev[l], spec, op.i)
            self._note_hwm(u, unew)
            # park whatever the next few ops do not touch
            keep = set()
            for kk in range(k + 1, min(n, k + 1 + self.keep_ahead)):
                keep |= plan[kk].ws
            for lv in list(u):
                if lv not in keep and not isinstance(u[lv], HostBuffer):
                    self._evict(u, unew, lv)
            self._drain(u, unew)
        dtn = jnp.min(jnp.stack([dts[i] for i in range(len(levels))]))
        # between steps keep only what the next step touches first
        first = plan[0].ws | (plan[1].ws if n > 1 else frozenset())
        for kk in range(n):
            if plan[kk].kind == "sweep":
                first = first | plan[kk].ws
                break
        for lv in list(u):
            if lv not in first and not isinstance(u[lv], HostBuffer):
                self._evict(u, unew, lv)
        self._drain(u, unew)
        self._emit_stats()
        return u, dtn

    def _emit_stats(self):
        d = {k: self._tot[k] - self._mark[k] for k in self._tot}
        d["overlap_frac"] = (d["overlapped"] / d["fetches"]
                             if d["fetches"] else 1.0)
        d["device_hwm_bytes"] = self._hwm
        self.last_step_stats = d
        self._mark = dict(self._tot)
        self._hwm = 0

    # -- segmented auxiliaries (dt, flags, restrict-all) ----------------
    def coarse_dt_min(self, sim, spec) -> float:
        """Per-level Courant min with the same residency discipline."""
        u, unew = sim.u, {}
        parked0 = {l for l, a in u.items() if isinstance(a, HostBuffer)}
        dts = []
        levels = spec.levels
        for i, l in enumerate(levels):
            self._fetch(u, unew, l)
            if i + 1 < len(levels):
                self._prefetch(u, (levels[i + 1],))
            dts.append(_seg_courant(u[l], sim.dev[l], spec, i))
            if l in parked0:
                self._evict(u, unew, l)
                self._drain(u, unew)
        return float(jnp.min(jnp.stack(dts)))

    def criteria_flags_packed(self, sim, spec, eg, fls, itype: int,
                              ttd: int) -> tuple:
        """All levels' packed refinement flags, one level resident at a
        time (plus its interp source)."""
        u, unew = sim.u, {}
        parked0 = {l for l, a in u.items() if isinstance(a, HostBuffer)}
        out = []
        levels = spec.levels
        for i, l in enumerate(levels):
            need = (l,) if (spec.complete[i] or l == spec.lmin) \
                else (l - 1, l)
            for lv in need:
                self._fetch(u, unew, lv)
            if i + 1 < len(levels):
                self._prefetch(u, (levels[i + 1],))
            ulm1 = u.get(l - 1) if l > spec.lmin else None
            out.append(_seg_flags(u[l], ulm1, sim.dev[l], spec, i, eg,
                                  fls, itype, ttd))
            for lv in list(u):
                if lv < l and lv in parked0 \
                        and not isinstance(u[lv], HostBuffer):
                    self._evict(u, unew, lv)
            self._drain(u, unew)
        return tuple(out)

    def restrict_all_segmented(self, sim, spec):
        """``_restrict_all`` with at most two levels resident."""
        u, unew = sim.u, {}
        parked0 = {l for l, a in u.items() if isinstance(a, HostBuffer)}
        levels = spec.levels
        for i in range(len(levels) - 2, -1, -1):
            l, lf = levels[i], levels[i + 1]
            for lv in (l, lf):
                self._fetch(u, unew, lv)
            if i > 0:
                self._prefetch(u, (levels[i - 1],))
            u[l] = _seg_restrict(u[l], u[lf], sim.dev[l], spec, i)
            if lf in parked0:
                self._evict(u, unew, lf)
                self._drain(u, unew)
