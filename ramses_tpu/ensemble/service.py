"""Run service: drain the job queue under supervised execution.

``serve(queue_dir)`` is the worker loop: reclaim stale records, plan
which queued jobs to claim next (cost-aware gang scheduling by
default — :func:`ramses_tpu.ensemble.queue.plan_gang` — with blind
FIFO as the fallback knob), and run them through the batched
:class:`~ramses_tpu.ensemble.batch.EnsembleEngine`.

A gang of small jobs is bin-packed onto disjoint submesh slices of the
local device mesh (each job's :class:`~ramses_tpu.ensemble.meshplan.
MeshPlan` shards its member axis over its slice) and driven
concurrently by the interleaved chunk loop in :func:`run_gang` —
every job's fused windows are dispatched before any host thread blocks
on results, so all submeshes compute at once.  A mesh-wide job (or a
calibrate) drains the gang and runs alone through the fully
supervised :func:`run_job` path (auto-resume from the newest
manifest-valid checkpoint, hang kill-and-requeue).

Every job defaults its persistent compile cache to the queue's shared
``<queue_dir>/compile_cache`` dir (``&ENSEMBLE_PARAMS
shared_compile_cache``), so fleet workers warm-start each other: the
second worker to claim a known config compiles nothing.
"""

from __future__ import annotations

import json
import os
import re
import signal
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ramses_tpu.ensemble import breaker as bkr
from ramses_tpu.ensemble import queue as jq
from ramses_tpu.resilience.diskguard import DiskGuard, guarded_save
from ramses_tpu.resilience.watchdog import HangDetected

#: jax.config keys the serve loop snapshots on entry and restores on
#: exit: defaulting the shared compile cache must not leak persistent-
#: cache config into whatever the process does after serving
_JAX_CACHE_KEYS = ("jax_compilation_cache_dir",
                   "jax_persistent_cache_min_compile_time_secs",
                   "jax_persistent_cache_min_entry_size_bytes",
                   "jax_persistent_cache_enable_xla_caches")


class DrainRequested(Exception):
    """Raised out of a job's chunk beat after a drain request
    (SIGTERM): the in-flight chunk finished and a checkpoint was
    attempted, so the serve loop requeues the job with
    ``stage="drain"`` (attempt refunded) and exits cleanly — the next
    worker resumes from the drain checkpoint."""


#: process-wide drain latch — SIGTERM's handler only sets an event, so
#: the signal is safe to take mid-chunk; the beat acts on it at the
#: next chunk boundary
_DRAIN = threading.Event()


def request_drain() -> None:
    """Ask every serve loop in this process to graceful-drain: finish
    the current chunk, checkpoint, requeue held jobs with
    ``stage="drain"``, exit.  The public API for embedders/tests;
    SIGTERM routes here when :func:`serve` runs on the main thread."""
    _DRAIN.set()


def drain_requested() -> bool:
    return _DRAIN.is_set()


def _backoff_knobs() -> Tuple[float, float]:
    """Requeue-backoff (base, cap) seconds — env-configured per worker
    (``RAMSES_QUEUE_BACKOFF_S`` / ``RAMSES_QUEUE_BACKOFF_CAP_S``);
    base 0 disables the eligibility gate."""
    def _f(name, dflt):
        try:
            raw = os.environ.get(name)
            return float(raw) if raw not in (None, "") else dflt
        except (TypeError, ValueError):
            return dflt
    return _f("RAMSES_QUEUE_BACKOFF_S", 1.0), \
        _f("RAMSES_QUEUE_BACKOFF_CAP_S", 60.0)


def _job_setup(queue_dir: str, job: "jq.Job", log=print):
    """Shared per-job setup for both the supervised solo path and the
    gang driver: materialize the namelist, default the shared compile
    cache, arm auto-resume, scrub rotten checkpoints.  Returns
    ``(params, rdir, dtype)``."""
    import jax.numpy as jnp

    from ramses_tpu.config import params_from_string
    from ramses_tpu.platform import setup_compile_cache
    from ramses_tpu.resilience import scrub_checkpoints

    rec = job.record
    rdir = jq.results_dir(queue_dir, job.id)
    os.makedirs(rdir, exist_ok=True)
    nml_path = os.path.join(rdir, "run.nml")
    with open(nml_path, "w") as f:
        f.write(rec["namelist"])
    params = params_from_string(rec["namelist"],
                                ndim=int(rec.get("ndim", 3)))
    # persistent compile cache before the first trace: a fleet worker
    # re-claiming a known namelist cold-starts in O(load), not
    # O(compile).  Default: the queue's shared dir, so workers warm-
    # start EACH OTHER; an explicit &RUN_PARAMS compile_cache_dir
    # still wins (and JAX_COMPILATION_CACHE_DIR outranks both —
    # platform._engage_cache), and
    # &ENSEMBLE_PARAMS shared_compile_cache=.false. opts out.
    if (not (params.run.compile_cache_dir or "").strip()
            and params.ensemble.shared_compile_cache):
        params.run.compile_cache_dir = os.path.join(queue_dir,
                                                    "compile_cache")
    setup_compile_cache(params)
    params.output.output_dir = rdir
    if not params.output.telemetry:
        params.output.telemetry = os.path.join(rdir, "telemetry.jsonl")
    # a re-claimed job (stale worker) must continue from the dead
    # worker's last checkpoint, so the restart resolution picks the
    # newest manifest-valid dir instead of starting fresh
    params.run.auto_resume = True
    # checkpoints can rot between beats (torn shard, truncated file on
    # a dying node): quarantine them NOW so the auto-resume scan below
    # never loops over a dir that validates at scan time but fails at
    # restore time
    scrub_checkpoints(rdir, log=log)
    dtype = getattr(jnp, rec.get("dtype") or "float32")
    return params, rdir, dtype


def _bind_trace(eng, rec: Dict[str, Any]) -> None:
    """Correlate the engine's artifacts with the job's trace: the
    submit-time ``trace_id`` (plus job/worker ids) lands in every
    telemetry record (:meth:`Telemetry.bind`) and every checkpoint
    manifest meta (``EnsembleEngine.trace_meta``) — one id joins
    submit -> claim -> telemetry -> failure_log -> manifest however
    many workers the job bounces through."""
    fields = {"trace_id": str(rec.get("trace_id") or ""),
              "job": str(rec.get("id") or ""),
              "worker": str(rec.get("worker") or "")}
    eng.trace_meta = {k: v for k, v in fields.items() if v}
    # the claim's fencing token rides into telemetry records and
    # checkpoint manifest meta: any artifact a fenced-out zombie still
    # managed to write is attributable (and dismissible) by generation
    fence = int(rec.get("fence", 0) or 0)
    if fence:
        eng.trace_meta["fence"] = fence
    eng.telemetry.bind(**eng.trace_meta)


def _job_result(eng, rdir: str, params, rec: Dict[str, Any],
                snap: str, cache0: Dict[str, int],
                log=print, gang_info: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """The result dict recorded on ``done`` — shared by the solo and
    gang paths.  ``cache0`` is the ``compile_cache_stats()`` snapshot
    taken before the job started; the recorded hit/miss counts are the
    deltas this job (or its gang) produced.  Must run before the job's
    telemetry closes: the summary is also emitted as a ``job_summary``
    event so the packing economics (queue wait, gang busy_frac,
    scenarios/device/s) are tailable without opening the queue record."""
    from ramses_tpu.platform import compile_cache_stats

    stats = compile_cache_stats()
    result = {"results_dir": rdir, "snapshot": snap,
              "telemetry": params.output.telemetry,
              "nmember": eng.nmember, "ngroup": len(eng.groups),
              "t_min": eng.t, "nstep_max": eng.nstep,
              "cell_updates": eng.cell_updates,
              "compile_cache_hits":
                  int(stats["hits"]) - int(cache0.get("hits", 0)),
              "compile_cache_misses":
                  int(stats["misses"]) - int(cache0.get("misses", 0)),
              "packing": eng.run_info().get("packing")}
    sub = float(rec.get("submitted_unix") or 0.0)
    claimed = float(rec.get("claimed_unix") or 0.0)
    if sub and claimed:
        result["queue_wait_s"] = round(max(0.0, claimed - sub), 3)
    if eng.wall_s > 0.0:
        result["scenarios_per_device_s"] = round(
            eng.nmember / eng.wall_s / eng.plan.n_devices, 4)
    if eng.quarantined:
        # partial completion: quarantined members are a property of the
        # job's *result*, not a worker failure — the job lands in
        # done/ with the census attached and never burns another queue
        # attempt on behalf of its healthy members
        result["partial"] = True
        result["failed_members"] = [
            {"member": int(k), **info}
            for k, info in sorted(eng.quarantined.items())]
        log(f"serve: {rec.get('id', '?')} partial completion — "
            f"{eng.quarantined_count}/{eng.nmember} members "
            f"quarantined")
    summary = {k: result[k] for k in
               ("queue_wait_s", "scenarios_per_device_s",
                "compile_cache_hits", "compile_cache_misses",
                "nmember", "cell_updates") if k in result}
    if gang_info:
        result["gang"] = gang_info
        summary["busy_frac"] = gang_info.get("busy_frac")
        summary["gang_jobs"] = gang_info.get("jobs")
    if eng.quarantined:
        summary["quarantined"] = eng.quarantined_count
    try:
        eng.telemetry.record_event("job_summary", **summary)
    except Exception:           # noqa: BLE001 — reporting only
        pass
    return result


def run_job(queue_dir: str, job: "jq.Job", max_attempts: int = 2,
            verbose: bool = False, log=print,
            device_ids: Optional[Sequence[int]] = None,
            plan=None) -> Dict[str, Any]:
    """Execute one claimed job; returns the result dict recorded on
    ``done``.  Raises on failure (caller moves the record).

    ``device_ids`` is the submesh slice the scheduler assigned (None =
    every local device); ``plan`` overrides the automatic
    :func:`~ramses_tpu.ensemble.meshplan.plan_for` packing choice."""
    from ramses_tpu.ensemble.batch import EnsembleEngine, EnsembleSpec
    from ramses_tpu.ensemble.meshplan import plan_for
    from ramses_tpu.platform import compile_cache_stats
    from ramses_tpu.resilience import supervisor as rsup

    rec = job.record
    cache0 = compile_cache_stats()
    params, rdir, dtype = _job_setup(queue_dir, job, log=log)
    if jq.job_kind(rec) == "calibrate" or params.calibration.calibrate:
        # calibrate-kind job: gradient-descent calibration through the
        # differentiable rollout (ramses_tpu/diff) — same artifact shape
        # (results dir + telemetry JSONL + resumable output_NNNNN
        # checkpoints), heartbeating the claim once per optimizer
        # iteration instead of per fused window
        from ramses_tpu.diff.calibrate import run_calibration_job

        result = run_calibration_job(
            params, dtype=dtype, base_dir=rdir, log=log,
            on_iter=lambda it, loss: jq.heartbeat(job))
        result["results_dir"] = rdir
        result["telemetry"] = params.output.telemetry
        stats = compile_cache_stats()
        result["compile_cache_hits"] = (int(stats["hits"])
                                        - int(cache0["hits"]))
        result["compile_cache_misses"] = (int(stats["misses"])
                                          - int(cache0["misses"]))
        return result
    spec = EnsembleSpec.from_params(params, sweeps=rec.get("sweeps"),
                                    solver=rec.get("solver", ""))
    if plan is None:
        plan = plan_for(params, spec.nmember, device_ids=device_ids,
                        solver=spec.solver)

    def build(restart):
        if restart:
            eng = EnsembleEngine.from_checkpoint(spec, restart,
                                                 dtype=dtype,
                                                 plan=plan)
        else:
            eng = EnsembleEngine(spec, dtype=dtype, plan=plan)
        _bind_trace(eng, rec)
        return eng

    from ramses_tpu.obs.profile import ProfileRequestWatcher
    watcher = ProfileRequestWatcher(rdir, log=log)

    dguard = DiskGuard.from_params(params, rdir, log=log)

    def drive(eng):
        from ramses_tpu.resilience.checkpoint import rotate_checkpoints

        def beat(e):
            # worker liveness + resumability advance together: every
            # fused window refreshes the fenced claim heartbeat and
            # lands a manifest-valid checkpoint (keep the newest two).
            # A reclaimed zombie dies HERE — heartbeat() raises
            # FenceLost, which escalates straight out of supervise.
            jq.heartbeat(job)

            def _save():
                e.save(rdir)
                rotate_checkpoints(rdir, keep=2)
            # disk-pressure degradation: below the soft watermark (or
            # after an injected/real ENOSPC) the checkpoint is shed and
            # the run keeps stepping — resumability gets coarser, the
            # worker survives
            guarded_save(_save, dguard, telemetry=e.telemetry, log=log,
                         where="chunk-beat")
            if drain_requested() and not e.run_complete():
                raise DrainRequested(
                    f"job {job.id}: worker draining (SIGTERM)")
            # on-demand profiling (ramses_tpu/obs/profile): the chunk
            # boundary is the one point with no fused window in flight
            watcher.poll(telemetry=e.telemetry)
        eng.run(verbose=verbose, on_chunk=beat)

    # hang_retries=0: a deadline-expired chunk escapes immediately so
    # the serve loop can kill-and-requeue with stage="hang" instead of
    # retrying inside a worker the queue already believes is live;
    # escalate: fence loss and drain are serve-loop control flow, not
    # run failures — they must never burn a supervised retry
    try:
        eng = rsup.supervise(build, drive, params, base_dir=rdir,
                             max_attempts=max_attempts, log=log,
                             hang_retries=0,
                             escalate=(jq.FenceLost, DrainRequested))
    finally:
        # never leave a device trace open across attempts/errors —
        # jax.profiler allows one active trace per process
        watcher.stop()
    snap = eng.save(rdir)
    eng.telemetry.record_event("ensemble_done", nmember=eng.nmember,
                               ngroup=len(eng.groups), t_min=eng.t,
                               nstep_max=eng.nstep, snapshot=snap,
                               quarantined=eng.quarantined_count)
    if not eng.run_complete():
        eng.telemetry.close(eng, print_timers=False)
        raise RuntimeError(
            f"job {job.id}: incomplete after {max_attempts} attempts "
            f"(t_min={eng.t:.6g} nstep_max={eng.nstep})")
    result = _job_result(eng, rdir, params, rec, snap, cache0, log=log)
    eng.telemetry.close(eng, print_timers=False)
    return result


def _dispose(job: "jq.Job", err: BaseException, counts: Dict[str, int],
             max_attempts: int, telemetry, log, stage: str = "requeue"
             ) -> None:
    """Requeue-or-fail one errored job, mirroring the serve loop's
    attempt accounting.  Requeues carry the jittered-exponential
    backoff gate (:func:`_backoff_knobs`) so a crash-looping job can't
    thundering-herd the fleet's claim scans.  A :class:`FenceLost`
    raised by the disposal itself means the record was reclaimed out
    from under this worker mid-error — the job is simply abandoned
    (its new owner carries it) and no count is taken."""
    text = "".join(traceback.format_exception_only(type(err),
                                                   err)).strip()
    log(f"serve: {job.id} "
        f"{'hang' if stage == 'hang' else 'failed'}: {err!r}")
    base_s, cap_s = _backoff_knobs()
    try:
        if int(job.record.get("attempts", 0)) < max_attempts:
            jq.requeue(job, error=text, telemetry=telemetry,
                       stage=stage, backoff_base_s=base_s,
                       backoff_cap_s=cap_s)
            counts["requeued"] += 1
        else:
            jq.fail(job, error=text, telemetry=telemetry, stage=stage)
            counts["failed"] += 1
    except jq.FenceLost as fe:
        log(f"serve: {job.id} disposal refused (claim reclaimed): "
            f"{fe}")


def run_gang(queue_dir: str,
             gang: List[Tuple["jq.Job", Tuple[int, ...]]],
             max_attempts: int = 2, verbose: bool = False, log=print,
             telemetry=None) -> Dict[str, int]:
    """Drive a gang of co-scheduled small jobs concurrently, each on
    its assigned submesh slice.

    The interleaved chunk loop is the whole trick: every live job's
    fused window is *dispatched* (``EnsembleEngine.begin_chunk`` —
    async, no host block) before any window's results are *fetched*
    (``finish_chunk``), so the disjoint submeshes compute at the same
    time even though one host thread drives them all.  Each job keeps
    its own heartbeat/checkpoint beat and its own failure handling —
    one member blowing up requeues that job alone, the rest of the
    gang keeps running.  Returns done/failed/requeued counts."""
    import jax

    from ramses_tpu.ensemble.batch import EnsembleEngine, EnsembleSpec
    from ramses_tpu.ensemble.meshplan import plan_for
    from ramses_tpu.platform import compile_cache_stats
    from ramses_tpu.resilience import (resolve_restart_dir,
                                       rotate_checkpoints)

    from ramses_tpu.obs.profile import ProfileRequestWatcher

    counts = {"done": 0, "failed": 0, "requeued": 0}
    ndev = len(jax.devices())
    cache0 = compile_cache_stats()
    busy = sum(len(d) for _, d in gang)
    gang_info = {"jobs": len(gang), "busy_devices": int(busy),
                 "ndev": int(ndev),
                 "busy_frac": round(busy / max(1, ndev), 3)}
    active: List[Dict[str, Any]] = []
    for job, dev_ids in gang:
        try:
            params, rdir, dtype = _job_setup(queue_dir, job, log=log)
            spec = EnsembleSpec.from_params(
                params, sweeps=job.record.get("sweeps"),
                solver=job.record.get("solver", ""))
            plan = plan_for(params, spec.nmember, device_ids=dev_ids,
                            solver=spec.solver)
            restart = resolve_restart_dir(params, base_dir=rdir,
                                          log=log)
            eng = (EnsembleEngine.from_checkpoint(
                spec, restart, dtype=dtype, plan=plan) if restart
                else EnsembleEngine(spec, dtype=dtype, plan=plan))
        except Exception as e:  # noqa: BLE001 — worker boundary
            _dispose(job, e, counts, max_attempts, telemetry, log)
            continue
        _bind_trace(eng, job.record)
        log(f"serve: gang member {job.id} on devices "
            f"{list(dev_ids)} ({plan.mode})")
        active.append({"job": job, "rdir": rdir, "params": params,
                       "eng": eng,
                       "dguard": DiskGuard.from_params(params, rdir,
                                                       log=log),
                       "watch": ProfileRequestWatcher(rdir, log=log)})
    if telemetry is not None:
        try:
            telemetry.record_event(
                "gang_schedule",
                job_ids=[st["job"].id for st in active], **gang_info)
        except Exception:
            pass
    while active:
        if drain_requested():
            # SIGTERM graceful drain: the in-flight chunks are done
            # (we only reach a loop top between chunks) — checkpoint
            # every held job and hand it back with stage="drain"; the
            # attempt is refunded because the drain is this worker's
            # doing, not the job's
            for st in list(active):
                st["watch"].stop()
                dg = st.get("dguard")
                guarded_save(lambda _st=st: _st["eng"].save(
                    _st["rdir"]), dg, telemetry=st["eng"].telemetry,
                    log=log, where="drain")
                st["eng"].telemetry.close(st["eng"],
                                          print_timers=False)
                try:
                    jq.requeue(st["job"],
                               error="worker draining (SIGTERM)",
                               telemetry=telemetry, stage="drain",
                               count_attempt=False)
                    counts["requeued"] += 1
                    log(f"serve: {st['job'].id} drained -> queued")
                except jq.FenceLost as fe:
                    log(f"serve: {st['job'].id} drain requeue "
                        f"refused (claim reclaimed): {fe}")
            return counts
        begun: List[Tuple[Dict[str, Any], Any]] = []
        for st in list(active):
            try:
                begun.append((st, st["eng"].begin_chunk()))
            except jq.FenceLost as e:
                st["watch"].stop()
                log(f"serve: {st['job'].id} fence lost — abandoning "
                    f"(new owner carries it): {e}")
                active.remove(st)
            except BaseException as e:  # noqa: BLE001
                stage = "hang" if isinstance(e, HangDetected) \
                    else "requeue"
                st["watch"].stop()
                _dispose(st["job"], e, counts, max_attempts,
                         telemetry, log, stage=stage)
                active.remove(st)
        for st, ctx in begun:
            if st not in active:
                continue
            try:
                eng = st["eng"]
                stepped = eng.finish_chunk(ctx)
                eng.telemetry.record_event(
                    "ensemble_chunk", nmember=eng.nmember,
                    ngroup=len(eng.groups), steps=stepped,
                    t_min=eng.t, nstep_max=eng.nstep,
                    quarantined=eng.quarantined_count,
                    wall_s=round(eng.wall_s, 6))
                jq.heartbeat(st["job"])
                guarded_save(lambda _st=st: (
                    _st["eng"].save(_st["rdir"]),
                    rotate_checkpoints(_st["rdir"], keep=2)),
                    st.get("dguard"), telemetry=eng.telemetry,
                    log=log, where="gang-beat")
                st["watch"].poll(telemetry=eng.telemetry)
                if stepped == 0 and not st["eng"].run_complete():
                    raise RuntimeError(
                        f"job {st['job'].id}: no progress in a chunk "
                        "(inconsistent tend/nstepmax)")
            except jq.FenceLost as e:
                st["watch"].stop()
                log(f"serve: {st['job'].id} fence lost — abandoning "
                    f"(new owner carries it): {e}")
                active.remove(st)
            except BaseException as e:  # noqa: BLE001
                stage = "hang" if isinstance(e, HangDetected) \
                    else "requeue"
                st["watch"].stop()
                _dispose(st["job"], e, counts, max_attempts,
                         telemetry, log, stage=stage)
                active.remove(st)
        for st in list(active):
            eng = st["eng"]
            if not eng.run_complete():
                continue
            st["watch"].stop(telemetry=eng.telemetry)
            snap = eng.save(st["rdir"])
            eng.telemetry.record_event(
                "ensemble_done", nmember=eng.nmember,
                ngroup=len(eng.groups), t_min=eng.t,
                nstep_max=eng.nstep, snapshot=snap,
                quarantined=eng.quarantined_count)
            result = _job_result(eng, st["rdir"], st["params"],
                                 st["job"].record, snap, cache0,
                                 log=log, gang_info=gang_info)
            eng.telemetry.close(eng, print_timers=False)
            try:
                jq.complete(st["job"], result=result)
                counts["done"] += 1
                log(f"serve: {st['job'].id} done -> {snap}")
            except jq.FenceLost as fe:
                log(f"serve: {st['job'].id} completion refused "
                    f"(claim reclaimed): {fe}")
            active.remove(st)
    return counts


def _counts_line(queue_dir: str) -> str:
    c = jq.queue_counts(queue_dir)
    return (f"queued={c['queued']} running={c['running']} "
            f"done={c['done']} failed={c['failed']} "
            f"parked={c.get('parked', 0)}")


def _worker_telemetry(queue_dir: str, worker: str):
    """Per-worker telemetry sink at ``<queue_dir>/workers/<worker>
    .jsonl``: queue lifecycle events (serve_start/serve_idle/requeue/
    fail/reclaim/gang_schedule) in the same JSONL schema as run
    telemetry, so ``tools/telemetry_report.py`` renders it and the obs
    ``/metrics`` scrape reads the file's mtime as worker liveness."""
    from ramses_tpu.obs.metrics import WORKERS_DIR
    from ramses_tpu.telemetry.recorder import Telemetry, TelemetrySpec

    safe = re.sub(r"[^A-Za-z0-9_.-]", "_", worker) or "worker"
    path = os.path.join(queue_dir, WORKERS_DIR, safe + ".jsonl")
    tel = Telemetry(TelemetrySpec(path=path),
                    run_info={"driver": "serve-worker",
                              "worker": worker,
                              "queue_dir": os.path.abspath(queue_dir)})
    # a restarted worker of the same name extends its history instead
    # of truncating it — the sink is a fleet log, not a run log
    tel._append = True
    tel.bind(worker=worker)
    return tel


def serve(queue_dir: str, worker: str = "", max_jobs: int = 0,
          idle_exit: bool = False, poll_s: float = 1.0,
          stale_s: Optional[float] = None, max_attempts: int = 2,
          verbose: bool = False, log=print, beat_s: float = 30.0,
          telemetry=None, order: str = "cost",
          gang_starve_s: float = 600.0,
          obs_port: Optional[int] = None,
          obs_bind: str = "127.0.0.1",
          startup_fsck: bool = True) -> Dict[str, int]:
    """Worker loop: claim and run jobs until the queue is drained
    (``idle_exit``) or ``max_jobs`` jobs have been processed
    (0 = unbounded).  Returns done/failed counts for this worker.

    ``order`` is the claim order: ``"cost"`` (default) plans each
    claim with the cost-aware gang scheduler — bin-packing small jobs
    concurrently onto submesh slices, draining to exclusive mode for
    mesh-wide jobs, with ``gang_starve_s`` bounding how long a big job
    can be overtaken — while ``"fifo"`` restores the blind
    oldest-first single-job behavior.

    Fleet hardening: on the main thread SIGTERM triggers a **graceful
    drain** (finish the in-flight chunk, checkpoint, requeue held
    jobs with ``stage="drain"`` and the attempt refunded, exit 0);
    embedders/tests call :func:`request_drain` directly.  Startup runs
    the always-safe queue-fsck repairs (``startup_fsck=False`` opts
    out).  Claims honor the requeue-backoff eligibility gate and the
    poison-config circuit breaker (matching queued jobs are parked
    while a breaker is open; TTL expiry half-opens it from this poll
    loop).  Under hard disk pressure (``RAMSES_DISK_HARD_MB``) the
    worker pauses claiming — alive and heartbeating — until space
    returns.

    Observability: ``telemetry`` defaults to a per-worker sink under
    ``<queue_dir>/workers/`` receiving the queue lifecycle events
    (requeue/fail/reclaim/gang_schedule) plus a structured
    ``serve_idle`` heartbeat with queue counts every ``beat_s``
    seconds while idle — fleet idleness is scrapeable, not just
    greppable.  ``obs_port`` (0 = ephemeral) arms the streaming
    results/metrics HTTP server (ramses_tpu/obs) over the queue dir
    for the lifetime of the loop."""
    jq.init_queue(queue_dir)
    worker = worker or f"{os.uname().nodename}:{os.getpid()}"
    counts = {"done": 0, "failed": 0, "requeued": 0}
    own_tel = None
    if telemetry is None:
        telemetry = own_tel = _worker_telemetry(queue_dir, worker)
    obs = None
    if obs_port is not None:
        from ramses_tpu.obs.server import ObsServer
        obs = ObsServer(queue_dir, port=int(obs_port), bind=obs_bind,
                        log=log if verbose else None).start()
        if log is not None:
            log(f"serve: obs server on {obs.url}")
    last_beat = 0.0
    # the shared-compile-cache default mutates process-global jax
    # config; snapshot it so an in-process caller (tests, a notebook)
    # gets its compilation-cache settings back when serve returns
    cache_snap = None
    # SIGTERM -> graceful drain.  Only the main thread may install
    # signal handlers; elsewhere (in-process embedding, test threads)
    # request_drain() is the API.  The previous handler is restored on
    # exit so serve-in-a-library never leaks its policy.
    _DRAIN.clear()
    prev_term = None
    try:
        prev_term = signal.signal(signal.SIGTERM,
                                  lambda _s, _f: request_drain())
    except ValueError:
        pass
    if startup_fsck:
        # crash-consistency sweep of the always-safe classes (torn
        # record tmps, orphaned heartbeats, orphaned parks) before
        # touching the queue; anything needing judgement is only
        # logged for the operator CLI
        try:
            from ramses_tpu.ensemble import fsck as qfsck
            qfsck.startup_repair(queue_dir, log=log)
        except Exception as e:  # noqa: BLE001 — advisory pass
            if log is not None:
                log(f"serve: startup fsck skipped: {e!r}")
    # worker-level disk watermark (env): at hard pressure stop
    # claiming, stay alive
    wguard = DiskGuard.from_env(queue_dir, log=log)
    backoff_base_s, backoff_cap_s = _backoff_knobs()
    try:
        telemetry.record_event("serve_start", worker=worker,
                               obs_url=obs.url if obs else "",
                               **jq.queue_counts(queue_dir))
        while True:
            if drain_requested():
                telemetry.record_event("serve_drain", worker=worker,
                                       **jq.queue_counts(queue_dir))
                if log is not None:
                    log(f"serve: drain requested — exiting clean; "
                        f"{_counts_line(queue_dir)}")
                return counts
            if not wguard.allow_claim():
                # hard disk pressure: claiming pauses, the worker
                # stays alive (io_degraded emitted on the transition
                # edge by emit()) and re-checks every poll
                wguard.emit(telemetry, where="claim")
                time.sleep(poll_s)
                continue
            wguard.emit(telemetry, where="claim")   # recovery edge
            # default staleness from the first job's namelist is
            # unknowable before claiming — use the CLI/default value
            jq.reclaim_stale(queue_dir, stale_s=stale_s or 300.0,
                             max_attempts=max_attempts, log=log,
                             telemetry=telemetry,
                             backoff_base_s=backoff_base_s,
                             backoff_cap_s=backoff_cap_s)
            # poison-config breaker maintenance: TTL-expired breakers
            # half-open (one probe released); open breakers park any
            # matching queued jobs before we plan a claim
            bkr.sweep(queue_dir, telemetry=telemetry,
                      log=log if verbose else None)
            records = jq.peek_queued(queue_dir)
            open_fps = bkr.open_fingerprints(queue_dir)
            if open_fps:
                keep = []
                for r in records:
                    fp = bkr.fingerprint_of(r)
                    if fp in open_fps:
                        bkr.park_record(queue_dir, r, open_fps[fp],
                                        telemetry=telemetry, log=log)
                    else:
                        keep.append(r)
                records = keep
            if not records:
                if idle_exit:
                    telemetry.record_event("serve_idle", exiting=True,
                                           **jq.queue_counts(queue_dir))
                    if log is not None:
                        log(f"serve: idle, exiting — "
                            f"{_counts_line(queue_dir)}")
                    return counts
                now = time.monotonic()
                if now - last_beat >= beat_s:
                    # structured idle heartbeat through the telemetry
                    # sink (not a bare print): the obs /metrics scrape
                    # reads the sink's mtime as worker liveness and
                    # the event carries the queue census
                    telemetry.record_event(
                        "serve_idle", **jq.queue_counts(queue_dir))
                    last_beat = now
                time.sleep(poll_s)
                continue
            now_w = time.time()
            eligible = [r for r in records
                        if float(r.get("not_before_unix") or 0.0)
                        <= now_w]
            if not eligible:
                # every queued record is inside its requeue-backoff
                # window: the queue is NOT idle (no idle_exit), the
                # jobs are just not claimable yet
                time.sleep(poll_s)
                continue
            records = eligible
            import jax
            if cache_snap is None:
                from ramses_tpu import platform as _plat
                cache_snap = ({k: getattr(jax.config, k)
                               for k in _JAX_CACHE_KEYS},
                              _plat._CACHE_STATS["dir"])
            ndev = len(jax.devices())
            planned = jq.plan_gang(records, ndev, order=order,
                                   starve_s=gang_starve_s)
            if max_jobs:
                # cap the gang by the remaining job budget so
                # max_jobs=N never over-claims inside one gang round
                left = max_jobs - counts["done"] - counts["failed"]
                planned = planned[:max(0, left)]
            gang: List[Tuple[jq.Job, Tuple[int, ...]]] = []
            offset = 0
            for rec, n in planned:
                job = jq.claim(queue_dir, worker=worker,
                               job_id=rec["id"])
                if job is None:
                    continue           # lost the race to a peer worker
                gang.append((job, tuple(range(offset, offset + n))))
                offset += n
            if not gang:
                time.sleep(poll_s * 0.1)
                continue
            if len(gang) == 1:
                # solo claim (mesh-wide, calibrate, fifo mode, or just
                # a one-job queue): the fully supervised path
                job, dev_ids = gang[0]
                log(f"serve: claimed {job.id} "
                    f"(attempt {job.record['attempts']}/{max_attempts},"
                    f" devices {list(dev_ids)})")
                try:
                    result = run_job(queue_dir, job,
                                     max_attempts=max_attempts,
                                     verbose=verbose, log=log,
                                     device_ids=dev_ids)
                except DrainRequested as e:
                    # graceful drain: the chunk finished and a drain
                    # checkpoint was attempted inside the beat — hand
                    # the job back (attempt refunded) and let the
                    # loop-top drain check exit this worker
                    try:
                        jq.requeue(job, error=str(e),
                                   telemetry=telemetry, stage="drain",
                                   count_attempt=False)
                        counts["requeued"] += 1
                        log(f"serve: {job.id} drained -> queued")
                    except jq.FenceLost as fe:
                        log(f"serve: {job.id} drain requeue refused "
                            f"(claim reclaimed): {fe}")
                except jq.FenceLost as e:
                    # this worker zombied past the stale timeout and
                    # the job was reclaimed: abandon it — the refusal
                    # is already durable in the record's failure_log
                    log(f"serve: {job.id} fence lost — abandoning "
                        f"(new owner carries it): {e}")
                except HangDetected as e:
                    # serve-loop liveness: a deadline-expired chunk
                    # comes back HERE (run_job runs hang_retries=0) —
                    # the wedged job is killed-and-requeued with
                    # stage="hang" immediately instead of zombifying
                    # this worker until stale-reclaim
                    _dispose(job, e, counts, max_attempts, telemetry,
                             log, stage="hang")
                except Exception as e:  # noqa: BLE001 — worker boundary
                    _dispose(job, e, counts, max_attempts, telemetry,
                             log)
                else:
                    try:
                        jq.complete(job, result=result)
                        counts["done"] += 1
                        log(f"serve: {job.id} done -> "
                            f"{result.get('snapshot') or result.get('checkpoint')}")
                    except jq.FenceLost as fe:
                        log(f"serve: {job.id} completion refused "
                            f"(claim reclaimed): {fe}")
            else:
                log(f"serve: gang of {len(gang)} jobs over "
                    f"{sum(len(d) for _, d in gang)}/{ndev} devices")
                gc = run_gang(queue_dir, gang,
                              max_attempts=max_attempts,
                              verbose=verbose, log=log,
                              telemetry=telemetry)
                for k in counts:
                    counts[k] += gc[k]
            if max_jobs and counts["done"] + counts["failed"] >= max_jobs:
                return counts
    finally:
        if prev_term is not None:
            try:
                signal.signal(signal.SIGTERM, prev_term)
            except ValueError:
                pass
        if own_tel is not None:
            try:
                own_tel.record_event("serve_exit", worker=worker,
                                     **counts)
            except Exception:   # noqa: BLE001
                pass
            own_tel.close(print_timers=False)
        if obs is not None:
            obs.close()
        if cache_snap is not None:
            import jax

            from ramses_tpu import platform as _plat
            for k, v in cache_snap[0].items():
                jax.config.update(k, v)
            _plat._CACHE_STATS["dir"] = cache_snap[1]


def submit_namelist(queue_dir: str, namelist_path: str,
                    sweeps: Optional[Dict[str, Any]] = None,
                    solver: str = "", ndim: int = 3,
                    dtype: str = "float32", kind: str = "run") -> str:
    """CLI submit helper: inline the namelist file into the job record
    so workers need no shared checkout."""
    with open(namelist_path) as f:
        text = f.read()
    return jq.submit(queue_dir, text, sweeps=sweeps, solver=solver,
                     ndim=ndim, dtype=dtype, kind=kind,
                     meta={"namelist_path": os.path.abspath(
                         namelist_path)})


def parse_sweep_args(items) -> Dict[str, list]:
    """``--sweep key=v1,v2,...`` CLI rows into a sweeps dict (values
    parsed as JSON scalars when possible, else kept as strings)."""
    sweeps: Dict[str, list] = {}
    for item in items or ():
        key, _, vals = item.partition("=")
        if not vals:
            raise ValueError(f"--sweep '{item}': expected key=v1,v2,...")
        parsed = []
        for v in vals.split(","):
            try:
                parsed.append(json.loads(v))
            except json.JSONDecodeError:
                parsed.append(v)
        sweeps[key.strip()] = parsed
    return sweeps
