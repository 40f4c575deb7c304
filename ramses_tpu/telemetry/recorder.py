"""Structured run telemetry: the JSONL event log + end-of-run sinks.

The reference's only observability is label-based wallclock timers and
ad-hoc stdout blocks (``amr/update_time.f90:38-56``,
``hydro/write_screen.f90``); this subsystem gives every driver one
:class:`Telemetry` recorder with three sinks:

  1. a JSONL event log — one record per coarse step (run-header /
     run-footer records bracket them) carrying the phase wallclock from
     :class:`ramses_tpu.utils.timers.Timers` labels, µs-per-cell-update
     with subcycle weighting (the reference's ``mus/pt``,
     ``amr/adaptive_loop.f90:204-212``), per-level oct counts,
     ``balance_stats`` imbalance, conservation drift from ``totals()``,
     memory high-water marks, a recompile counter, and captured
     XLA/SPMD warnings;
  2. the RAMSES-style ``write_screen`` console block
     (:mod:`ramses_tpu.telemetry.screen`);
  3. the end-of-run ``output_timer`` breakdown.

Zero overhead when off is the design contract: a disabled recorder is
the shared :data:`NULL` singleton whose methods are no-ops — no host
syncs, no device fetches, no label switches reach an un-instrumented
run, and the chunked fast path (``step_chunk``) reports from chunk
summaries instead of falling back to the per-step slow path.

Enabled from the namelist (&OUTPUT_PARAMS ``telemetry='run.jsonl'``,
``telemetry_interval=N``); rendered by ``tools/telemetry_report.py``.
"""

from __future__ import annotations

import atexit
import json
import os
import time
import warnings as _warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 1

# keys every kind="step" record must carry (tests + report tool key off
# this tuple; extend it together with _make_step_record)
REQUIRED_STEP_KEYS = (
    "kind", "nstep", "t", "dt", "steps", "wall_s", "phases_s",
    "cell_updates", "mus_per_cell_update", "octs",
    "rss_mb", "device_mb", "rss_hwm_mb", "device_hwm_mb",
    "recompiles", "recompiles_total",
)

# substrings that qualify a Python warning for capture into the event
# log (SPMD partitioner / sharding health — the class of message
# tools/multichip.py greps out of subprocess stderr)
WARN_PATTERNS = (
    "rematerialization", "sharding", "spmd", "all-gather", "all-reduce",
    "donat", "replicat",
)

# ---------------------------------------------------------------------
# process-wide recompile counter (jax.monitoring listener).  Listeners
# cannot be unregistered individually, so exactly one is registered,
# lazily, the first time an ENABLED recorder exists — un-instrumented
# processes never register it.
# ---------------------------------------------------------------------
_COMPILES = {"count": 0, "secs": 0.0}
_listener_installed = False


def _install_compile_listener():
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax import monitoring

        def _on_duration(name, secs, **kw):
            if name.endswith("backend_compile_duration"):
                _COMPILES["count"] += 1
                _COMPILES["secs"] += float(secs)

        monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True
    except Exception:       # monitoring API drift must not kill a run
        pass


def compile_count() -> int:
    return _COMPILES["count"]


# ---------------------------------------------------------------------
# per-sim probes (host-side only; called from ENABLED recorders)
# ---------------------------------------------------------------------
def cell_updates_per_step(sim) -> int:
    """Subcycle-weighted cell updates of ONE coarse step — the
    reference's ``mus/pt`` denominator (``adaptive_loop.f90:204-212``):
    every level's cells times its substep count ``2^(l-lmin)``."""
    tree = getattr(sim, "tree", None)
    if tree is not None:
        ttd = 2 ** sim.cfg.ndim
        return sum(int(tree.noct(l)) * ttd * (1 << (l - sim.lmin))
                   for l in sim.levels())
    grid = getattr(sim, "grid", None)
    if grid is not None:
        return int(grid.ncell)
    return 0


def mesh_census(sim) -> Dict[int, int]:
    """Per-level oct counts.  A uniform grid is its complete coarse
    level: ``ncell / 2^ndim`` octs at ``levelmin``."""
    tree = getattr(sim, "tree", None)
    if tree is not None:
        return {int(l): int(tree.noct(l)) for l in sim.levels()}
    grid = getattr(sim, "grid", None)
    if grid is not None:
        lmin = int(sim.params.amr.levelmin)
        return {lmin: int(grid.ncell) >> int(sim.cfg.ndim)}
    return {}


def _device_hwm_mb() -> float:
    """Device-memory high-water proxy: accelerator ``memory_stats``
    peak when the backend reports one, else the live-buffer census."""
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            return float(stats["peak_bytes_in_use"]) / 2 ** 20
    except Exception:
        pass
    from ramses_tpu.utils.ops import device_mb
    return device_mb()


# ---------------------------------------------------------------------
# spec + recorder
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class TelemetrySpec:
    """&OUTPUT_PARAMS telemetry keys."""
    path: str = ""                 # JSONL event-log path ('' = off)
    interval: int = 1              # coarse steps per emitted record

    @property
    def enabled(self) -> bool:
        return bool(self.path)

    @classmethod
    def from_params(cls, params) -> "TelemetrySpec":
        out = getattr(params, "output", None)
        return cls(
            path=str(getattr(out, "telemetry", "") or ""),
            interval=max(1, int(getattr(out, "telemetry_interval", 1))))


class NullTelemetry:
    """Disabled recorder: every hook is a no-op (shared singleton).

    Drivers hold a reference unconditionally; the ``enabled`` flag lets
    hot paths skip even the method call.
    """

    enabled = False

    def record_step(self, sim, **kw):
        pass

    def record_chunk(self, sim, ts, dts, n, wall_s, **kw):
        pass

    def record_event(self, kind, **fields):
        pass

    def bind(self, **fields):
        pass

    def mark_resumed(self, outdir, attempt=1):
        pass

    def warn(self, msg, source=""):
        pass

    def close(self, sim=None, **kw):
        pass


NULL = NullTelemetry()


class Telemetry:
    """One run's JSONL event log + screen/output_timer sinks.

    Construct via :func:`make_telemetry`; a disabled spec yields the
    :data:`NULL` singleton instead, so every code path below may assume
    the recorder is live.
    """

    def __init__(self, spec: TelemetrySpec,
                 run_info: Optional[Dict[str, Any]] = None,
                 cons_every: int = 10):
        self.spec = spec
        self.enabled = True
        self.run_info = dict(run_info or {})
        # conservation audits download the whole device state
        # (``totals()``) — amortized over emitted records like the
        # OpsGuard screen block's cons_every
        self.cons_every = max(1, int(cons_every))
        self._fh = None
        self._closed = False
        self._t_open = time.perf_counter()
        self._nstep_rec = 0            # emitted step records
        self._steps_pending = 0        # coarse steps since last record
        self._wall_pending = 0.0
        self._phases_last: Dict[str, float] = {}
        self._compiles_last = 0
        self._rss_hwm = 0.0
        self._dev_hwm = 0.0
        self._cons0: Optional[List[float]] = None
        self._warn_pending: List[Dict[str, str]] = []
        self._nwarn = 0
        self._prev_showwarning = None
        self._append = False           # resume: keep prior attempts' log
        self._event_counts: Dict[str, int] = {}
        # correlation fields (trace_id/job/worker — ramses_tpu/obs)
        # stamped onto every record via setdefault; see bind()
        self._bound: Dict[str, Any] = {}
        # out-of-core residency totals (&AMR_PARAMS offload) — summed
        # from per-step stats, surfaced flat in the run footer
        self._off_totals: Dict[str, int] = {
            "offload_stalls": 0, "offload_prefetches": 0,
            "offload_fetches": 0, "offload_overlapped": 0,
            "offload_bytes_parked": 0, "offload_bytes_fetched": 0,
            "offload_device_hwm_bytes": 0}
        _install_compile_listener()

    # -- sinks ---------------------------------------------------------
    def _write(self, rec: Dict[str, Any]):
        if self._closed:
            return
        if self._fh is None:
            d = os.path.dirname(self.spec.path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._fh = open(self.spec.path,
                            "a" if self._append else "w")
            atexit.register(self.close)
            # refresh the halo traffic counters at header-write time:
            # the header lands lazily with the first record, i.e. after
            # the first step traced, so the per-step traced byte counts
            # are populated by now (they are zero at sim construction);
            # so is what the run's sweep kernel's block rule picked
            from ramses_tpu.parallel import dma_halo
            from ramses_tpu.telemetry import screen
            self.run_info.update(dma_halo.traffic_snapshot())
            self.run_info["sweep_block"] = screen.sweep_blocks(
                self.run_info.get("sweep_kernel", "pallas_muscl"))
            header = {
                "kind": "run_header",
                "schema_version": SCHEMA_VERSION,
                "time_unix": time.time(),
                "pid": os.getpid(),
                "telemetry_interval": self.spec.interval,
                "run_info": self.run_info,
            }
            for k, v in self._bound.items():
                header.setdefault(k, v)
            self._fh.write(json.dumps(header) + "\n")
        for k, v in self._bound.items():
            rec.setdefault(k, v)
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()               # a killed run still leaves records

    # -- warning capture ----------------------------------------------
    def warn(self, msg: str, source: str = ""):
        """Fold a captured warning (SPMD partitioner, sharding fallback,
        subprocess stderr grep, ...) into the next record."""
        self._nwarn += 1
        if len(self._warn_pending) < 50:
            self._warn_pending.append(
                {"msg": str(msg)[:500], "source": source})

    def install_warning_capture(self):
        """Tee Python warnings matching :data:`WARN_PATTERNS` (or raised
        from ramses_tpu/jax modules) into the event log.  XLA's C++
        warnings go to raw stderr and are folded in by the subprocess
        tools (tools/multichip.py) instead."""
        if self._prev_showwarning is not None:
            return
        prev = _warnings.showwarning

        def _hook(message, category, filename, lineno,
                  file=None, line=None):
            text = str(message)
            low = text.lower()
            if any(p in low for p in WARN_PATTERNS) \
                    or "ramses_tpu" in filename or "jax" in filename:
                self.warn(text, source=f"{filename}:{lineno}")
            prev(message, category, filename, lineno, file, line)

        self._prev_showwarning = prev
        _warnings.showwarning = _hook

    # -- records -------------------------------------------------------
    def _mem_sample(self):
        from ramses_tpu.utils.ops import device_mb, rss_mb
        rss, dev = rss_mb(), device_mb()
        self._rss_hwm = max(self._rss_hwm, rss)
        self._dev_hwm = max(self._dev_hwm, dev, _device_hwm_mb())
        return rss, dev

    def _phase_delta(self, sim) -> Dict[str, float]:
        timers = getattr(sim, "timers", None)
        if timers is None:
            return {}
        snap = timers.snapshot()
        delta = {k: round(v - self._phases_last.get(k, 0.0), 6)
                 for k, v in snap.items()
                 if v - self._phases_last.get(k, 0.0) > 0.0}
        self._phases_last = snap
        return delta

    def _cons_sample(self, sim) -> Optional[Dict[str, float]]:
        if not hasattr(sim, "totals"):
            return None
        import numpy as np
        raw = sim.totals()
        if isinstance(raw, dict):          # uniform-grid totals() dicts
            mass = float(raw.get("mass", 0.0))
            energy = float(raw["energy"]) if "energy" in raw else None
        else:                              # AMR drivers: flat nvar array
            arr = np.asarray(raw)
            mass = float(arr[0])
            ie = getattr(getattr(sim, "cfg", None), "ienergy", None)
            energy = (float(arr[ie])
                      if ie is not None and ie < len(arr) else None)
        if self._cons0 is None:
            self._cons0 = [mass, energy]
        m0 = self._cons0[0] or 1.0
        out = {"mcons": mass,
               "mcons_drift": (mass - self._cons0[0]) / m0}
        if energy is not None and self._cons0[1] is not None:
            e0 = self._cons0[1] or 1.0
            out["econs"] = energy
            out["econs_drift"] = (energy - self._cons0[1]) / e0
        return out

    def record_step(self, sim, dt: Optional[float] = None,
                    wall_s: float = 0.0, steps: int = 1,
                    t: Optional[float] = None,
                    nstep: Optional[int] = None,
                    state_current: bool = True,
                    phases: Optional[Dict[str, float]] = None,
                    chunked: int = 0,
                    extra: Optional[Dict[str, Any]] = None):
        """One coarse step (or an aggregate of ``steps`` fused coarse
        steps the caller could not split).  Emits every
        ``telemetry_interval``-th coarse step; wallclock between
        emissions accumulates onto the next record.

        ``state_current``: False for backfilled mid-chunk records whose
        device state no longer exists — skips the conservation audit.
        """
        self._steps_pending += steps
        self._wall_pending += wall_s
        if self._steps_pending < self.spec.interval:
            return
        nsteps = self._steps_pending
        wall = self._wall_pending
        self._steps_pending = 0
        self._wall_pending = 0.0
        self._nstep_rec += 1
        upd = cell_updates_per_step(sim) * nsteps
        rss, dev = self._mem_sample()
        ncomp = _COMPILES["count"]
        rec = {
            "kind": "step",
            "nstep": int(nstep if nstep is not None
                         else getattr(sim, "nstep", 0)),
            "t": float(t if t is not None else getattr(sim, "t", 0.0)),
            "dt": (float(dt) if dt is not None
                   else float(getattr(sim, "dt_old", 0.0))),
            "steps": int(nsteps),
            "wall_s": round(wall, 6),
            "phases_s": (phases if phases is not None
                         else self._phase_delta(sim)),
            "cell_updates": int(upd),
            "mus_per_cell_update": (round(1e6 * wall / upd, 6)
                                    if upd else None),
            "octs": mesh_census(sim),
            "rss_mb": round(rss, 1),
            "device_mb": round(dev, 1),
            "rss_hwm_mb": round(self._rss_hwm, 1),
            "device_hwm_mb": round(self._dev_hwm, 1),
            "recompiles": ncomp - self._compiles_last,
            "recompiles_total": ncomp,
        }
        self._compiles_last = ncomp
        if rec["phases_s"]:
            # timers on: surface how much of each exchanged slab the
            # overlap split computes behind the in-flight DMA (0.0 on
            # the ppermute path or when shards are stencil-thin)
            from ramses_tpu.parallel import dma_halo
            rec["halo_overlap_frac"] = \
                dma_halo.traffic_snapshot()["halo_overlap_frac"]
        if chunked:
            rec["chunked"] = int(chunked)
        bs = getattr(sim, "balance_stats", None)
        if bs is not None:
            rec["balance"] = {
                "max_cost": float(bs.max_cost),
                "mean_cost": float(bs.mean_cost),
                "imbalance": float(bs.imbalance),
                "nreb": int(getattr(sim, "_rebalance_count", 0)),
            }
        bst = getattr(sim, "block_stats", None)
        if bst and "blocked_frac" in bst:
            # fraction of partial-level octs on the blocked tile sweep
            rec["blocked_frac"] = round(float(bst["blocked_frac"]), 4)
            # tiles of the live tile tables, and those of them the native
            # pass wrote (equal wherever ``ramses_tpu.native`` loaded)
            rec["blocks_total"] = int(bst.get("blocks_total", 0))
            rec["tiles_native"] = int(bst.get("tiles_native", 0))
        fst = getattr(sim, "flag_stats", None)
        if fst:
            # the newest regrid's flag decode: octs (bytes) fetched,
            # non-zero ones decoded, flagged-cell indices produced
            rec["flag_stats"] = dict(fst)
        off = getattr(sim, "_offload", None)
        ost = getattr(off, "last_step_stats", None)
        if ost is not None:
            # out-of-core residency traffic of the step cycle that
            # ENDED with this step (regrid/dt fetches included)
            rec["offload"] = {
                "stalls": int(ost["stalls"]),
                "prefetches": int(ost["prefetches"]),
                "fetches": int(ost["fetches"]),
                "overlap_frac": round(float(ost["overlap_frac"]), 4),
                "bytes_parked": int(ost["bytes_parked"]),
                "bytes_fetched": int(ost["bytes_fetched"]),
                "device_hwm_bytes": int(ost["device_hwm_bytes"]),
            }
            self._off_totals["offload_stalls"] += int(ost["stalls"])
            self._off_totals["offload_prefetches"] += \
                int(ost["prefetches"])
            self._off_totals["offload_fetches"] += int(ost["fetches"])
            self._off_totals["offload_overlapped"] += \
                int(ost["overlapped"])
            self._off_totals["offload_bytes_parked"] += \
                int(ost["bytes_parked"])
            self._off_totals["offload_bytes_fetched"] += \
                int(ost["bytes_fetched"])
            hwm = int(ost["device_hwm_bytes"])
            if hwm > self._off_totals["offload_device_hwm_bytes"]:
                self._off_totals["offload_device_hwm_bytes"] = hwm
        nq = getattr(sim, "quarantined_count", None)
        if nq:
            # member isolation ladder (ensemble engines): evicted
            # members surface in step records, not just fault events
            rec["quarantined"] = int(nq)
        if state_current and (self._nstep_rec - 1) % self.cons_every == 0:
            cons = self._cons_sample(sim)
            if cons is not None:
                rec["cons"] = cons
        if self._warn_pending:
            rec["warnings"] = self._warn_pending
            self._warn_pending = []
        if extra:
            rec.update(extra)
        self._write(rec)

    def record_chunk(self, sim, ts, dts, n: int, wall_s: float,
                     nstep_end: Optional[int] = None):
        """Report ``n`` fused coarse steps from ONE ``step_chunk``
        dispatch — per-step ``(t, dt)`` come from the scan's stacked
        outputs, wallclock and phase time are amortized evenly.  The
        fast path stays a single device program; only this summary
        fetch (already paid by the caller) touches the host."""
        if n <= 0:
            return
        phases = self._phase_delta(sim)
        share = {k: round(v / n, 6) for k, v in phases.items()}
        if nstep_end is None:
            nstep_end = int(getattr(sim, "nstep", n))
        for i in range(n):
            self.record_step(
                sim, dt=float(dts[i]), wall_s=wall_s / n, steps=1,
                t=float(ts[i]), nstep=nstep_end - (n - 1 - i),
                state_current=(i == n - 1), phases=share, chunked=n)

    def record_event(self, kind: str, **fields):
        """Free-form record (tool integrations: multichip dryruns,
        bench summaries, resilience rollback/resume/fault events,
        XLA warning folds)."""
        k = str(kind)
        self._event_counts[k] = self._event_counts.get(k, 0) + 1
        rec = {"kind": k}
        rec.update(fields)
        self._write(rec)

    def bind(self, **fields):
        """Stamp correlation fields (``trace_id``, ``job``,
        ``worker`` — ramses_tpu/obs) onto every subsequent record:
        header, steps, events and footer alike.  Applied via
        ``setdefault`` so an explicit field on any record wins; falsy
        values are dropped so an unstamped legacy job binds nothing."""
        self._bound.update({k: v for k, v in fields.items() if v})

    def mark_resumed(self, outdir: str, attempt: int = 1):
        """Flip the sink to append mode (must run before the first
        write opens the file) and log a ``resume`` event — a supervised
        restart extends the same JSONL log rather than truncating the
        earlier attempts' records."""
        self._append = True
        self.record_event("resume", outdir=str(outdir),
                          attempt=int(attempt))

    # -- end of run ----------------------------------------------------
    def close(self, sim=None, print_timers: bool = True):
        """Write the run-footer record and the ``output_timer``
        breakdown (sink 3).  Idempotent."""
        if self._closed:
            return
        if self._prev_showwarning is not None:
            _warnings.showwarning = self._prev_showwarning
            self._prev_showwarning = None
        timers = getattr(sim, "timers", None) if sim is not None else None
        footer = {
            "kind": "run_footer",
            "time_unix": time.time(),
            "wall_s": round(time.perf_counter() - self._t_open, 3),
            "records": self._nstep_rec,
            "recompiles_total": _COMPILES["count"],
            "compile_s_total": round(_COMPILES["secs"], 3),
            "rss_hwm_mb": round(self._rss_hwm, 1),
            "device_hwm_mb": round(self._dev_hwm, 1),
            "warnings_total": self._nwarn,
        }
        if self._event_counts:
            footer["events"] = dict(self._event_counts)
        off_ran = (sim is not None and getattr(
            getattr(sim, "_offload", None), "last_step_stats", None)
            is not None)
        if off_ran or self._off_totals["offload_fetches"] \
                or self._off_totals["offload_bytes_parked"]:
            footer.update(self._off_totals)
            f = self._off_totals["offload_fetches"]
            footer["offload_overlap_frac"] = round(
                self._off_totals["offload_overlapped"] / f, 4) if f \
                else 1.0
        if sim is not None:
            footer["nstep"] = int(getattr(sim, "nstep", 0))
            footer["t"] = float(getattr(sim, "t", 0.0))
        if timers is not None:
            footer["phases_total_s"] = {
                k: round(v, 6) for k, v in timers.snapshot().items()}
            footer["phase_calls"] = dict(timers.count)
        self._write(footer)
        if self._fh is not None:
            self._fh.close()
        self._closed = True
        if print_timers and timers is not None and timers.acc:
            print(timers.output_timer())


def make_telemetry(params, run_info: Optional[Dict[str, Any]] = None):
    """Driver-side factory: a live :class:`Telemetry` when
    &OUTPUT_PARAMS enables it, else the shared no-op :data:`NULL`."""
    spec = TelemetrySpec.from_params(params)
    if not spec.enabled:
        return NULL
    tel = Telemetry(spec, run_info=run_info)
    tel.install_warning_capture()
    return tel


def sim_run_info(sim) -> Dict[str, Any]:
    """Header metadata shared by all drivers."""
    p = getattr(sim, "params", None)
    from ramses_tpu.telemetry import screen
    info = {
        "driver": type(sim).__name__,
        "ndev": int(getattr(sim, "ndev", 1)),
        "sweep_kernel": screen.sweep_kernel(sim),
    }
    if p is not None:
        from ramses_tpu.parallel import dma_halo
        info.update(ndim=int(p.ndim), levelmin=int(p.amr.levelmin),
                    levelmax=int(p.amr.levelmax),
                    boxlen=float(p.amr.boxlen),
                    halo_backend=dma_halo.resolve_backend(
                        getattr(p.amr, "halo_backend", "auto")))
    cfg = getattr(sim, "cfg", None)
    if cfg is not None and hasattr(cfg, "nvar"):
        info["nvar"] = int(cfg.nvar)
    bst = getattr(sim, "block_stats", None)
    if bst and "blocked_frac" in bst:
        info["blocked_frac"] = round(float(bst["blocked_frac"]), 4)
        info["blocks_total"] = int(bst.get("blocks_total", 0))
        info["tiles_native"] = int(bst.get("tiles_native", 0))
    off = getattr(sim, "_offload", None)
    if off is not None:
        info["offload"] = off.mode
        info["offload_hbm_budget_mb"] = float(off.budget_mb)
    from ramses_tpu import platform
    cs = platform.compile_cache_stats()
    if cs["dir"]:
        info["compile_cache_dir"] = cs["dir"]
        info["compile_cache_hits"] = int(cs["hits"])
        info["compile_cache_misses"] = int(cs["misses"])
    return info
