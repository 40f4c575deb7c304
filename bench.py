#!/usr/bin/env python
"""Benchmark driver — the BASELINE.md protocol metrics, measured.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Sub-benchmarks (BASELINE.md / BASELINE.json "configs"):
  1. uniform  — sedov3d.nml levelmin=levelmax (config 1): pure hydro
     kernel throughput, cell-updates/sec/chip.
  2. amr      — sedov3d.nml with AMR levelmax=9 (config 2): per-level
     batched sweeps + flux correction + subcycling; cell-updates/sec/chip
     counted like the reference's mus/pt (all cells at each level x its
     substep count per coarse step, amr/adaptive_loop.f90:204-212).
  3. mg       — Poisson multigrid V-cycles/sec at 128^3 (config 3 class;
     the reference's "multigrid iters/sec" driver metric).

The headline metric is the driver's: AMR cell-updates/sec/chip on
sedov3d levelmax=9.  ``vs_baseline`` divides it by the *measured* 64-rank
CPU baseline recorded in BASELINE.json["published"] (produced by
baseline/run_baseline.py; C++ proxy kernels of the reference's hot loops
— no Fortran compiler exists in this image to build the reference
itself).  Nothing here is hard-coded.

Fail-soft design: the parent process never imports jax.  Each sub-bench
runs in its own subprocess with a hard timeout; a backend hang, Mosaic
crash, or OOM in one sub produces a structured ``{"error": ...}`` entry
for that sub and the rest still run.  Backend-init failures and timeouts
are retried once.  A sub that finds no TPU fails (set JAX_PLATFORMS=cpu
to run the protocol on the CPU on purpose).  A GLOBAL wall-clock
budget (BENCH_TOTAL_BUDGET, default 900 s) bounds the whole protocol —
per-sub timeouts are clipped to the remaining budget, retries never
sleep past it, and every completed sub is written incrementally to
BENCH_PARTIAL.json so a driver kill still leaves results on record.
The parent always prints the JSON line; it exits non-zero when any
requested sub returned an ``error``.

Env knobs (small hosts / quick checks): BENCH_LEVEL, BENCH_STEPS,
BENCH_AMR_LMIN, BENCH_AMR_LMAX, BENCH_AMR_STEPS, BENCH_AMR_SS_STEPS,
BENCH_AMR_PROD_STEPS, BENCH_MG_N, BENCH_BF16,
BENCH_ONLY=<comma list of uniform|amr|mg|amr_poisson|ensemble|
profile_amr|halo|offload|grad — profile_amr runs tools/profile_amr.py's
per-kernel probes with incremental partial capture (also auto-escalated
after a hang-classified amr sub); halo times the explicit halo pipeline
(ppermute vs DMA, 1/2/8 shards, bytes/s + fused step time); offload
times the out-of-core deep hierarchy (&AMR_PARAMS offload) on vs off;
grad times the checkpointed adjoint rollout (ramses_tpu/diff) —
grad/forward wall-time and peak-temp-memory ratios at nstep 8 and 32 —
all opt-in like profile_amr>,
BENCH_HALO_LEVEL, BENCH_HALO_STEPS,
BENCH_OFF_LMIN, BENCH_OFF_LMAX, BENCH_OFF_STEPS, BENCH_OFF_WARM,
BENCH_GRAD_N, BENCH_GRAD_REPS,
BENCH_SUB_TIMEOUT, BENCH_TOTAL_BUDGET, BENCH_PARTIAL_PATH,
BENCH_ENS_LEVEL, BENCH_ENS_STEPS, BENCH_ENS_BATCHES,
BENCH_HANG_SUB=<sub> (deliberately wedge that child before its jax
import — the hang-isolation test hook).

Each child writes a phase-marker heartbeat sidecar
(BENCH_HEARTBEAT_<sub>.jsonl, format: ramses_tpu/telemetry/heartbeat.py)
plus an atomic result sidecar (BENCH_RESULT_<sub>.json) once its
measurement finishes; on a timeout the parent folds the child's last
phase into the error object as ``phase_at_timeout`` with
``classification: "hang"`` (also set when a child exits with the
watchdog's hang status 87), or recovers the completed result from the
sidecar when only the exit hung.  A per-pending-sub budget reserve
means one hung sub can never exhaust the global budget for the rest.
"""

import json
import os
import subprocess
import sys
import time
import traceback
import uuid

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

HERE = os.path.dirname(os.path.abspath(__file__))
MARKER = "##BENCH_SUB##"

# one trace id for the whole protocol run (same env contract as
# ramses_tpu/obs/trace, duplicated because this parent never imports
# ramses_tpu): every child heartbeat line and BENCH_RESULT_* sidecar
# carries it, so hang-classified sub-benches join worker telemetry
TRACE_ID = (os.environ.get("RAMSES_TRACE_ID", "").strip()
            or uuid.uuid4().hex)


def _stamp_ids(d):
    """trace_id + worker_id (host:pid) onto a result dict, in place."""
    d.setdefault("trace_id",
                 os.environ.get("RAMSES_TRACE_ID", "") or TRACE_ID)
    d.setdefault("worker_id", f"{os.uname().nodename}:{os.getpid()}")
    return d


def _hb_path(name):
    return os.path.join(HERE, f"BENCH_HEARTBEAT_{name}.jsonl")


def _result_path(name):
    return os.path.join(HERE, f"BENCH_RESULT_{name}.json")


def _write_result(name, d):
    """Atomic sidecar copy of the sub's result dict: the parent reads
    it back when the child was deadline-killed (or its captured stdout
    truncated) AFTER the measurement finished — the healthy value
    still lands in the driver JSON instead of a timeout error."""
    path = _result_path(name)
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(d, f)
        os.replace(tmp, path)
    except OSError:
        pass


def _read_result(name):
    try:
        with open(_result_path(name)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _read_phases(path):
    """Inline reader for the heartbeat sidecar format
    (ramses_tpu/telemetry/heartbeat.py): the parent must never import
    ramses_tpu — the package __init__ may pull jax in."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def _load_heartbeat_mod():
    """Child-side loader of the canonical heartbeat module BY FILE PATH
    so marking 'start' doesn't first import the ramses_tpu package
    (whose compile-cache setup can import jax — the very phase the
    heartbeat exists to time)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_heartbeat",
        os.path.join(HERE, "ramses_tpu", "telemetry", "heartbeat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_baseline():
    with open(os.path.join(HERE, "BASELINE.json")) as f:
        return json.load(f).get("published", {})


def bench_uniform(params, dtype, jnp, hb=lambda *a, **k: None):
    from ramses_tpu.driver import Simulation
    from ramses_tpu.grid.uniform import run_steps

    lvl = int(os.environ.get("BENCH_LEVEL", params.amr.levelmin))
    params.amr.levelmin = params.amr.levelmax = lvl
    sim = Simulation(params, dtype=dtype)
    hb("init")
    nsteps = int(os.environ.get("BENCH_STEPS", "20"))
    u = sim.state.u
    t = jnp.asarray(0.0, jnp.float32)
    tend = jnp.asarray(1e9, jnp.float32)
    # warm with the SAME static nsteps so the timed region holds zero
    # compiles, then hard-sync with a host fetch
    u1, t1, _ = run_steps(sim.grid, u, t, tend, nsteps)
    float(jnp.sum(u1[0]))
    hb("warm")
    t0 = time.perf_counter()
    u2, t2, ndone = run_steps(sim.grid, u1, t1, tend, nsteps)
    float(jnp.sum(u2[0]))
    wall = time.perf_counter() - t0
    updates = sim.grid.ncell * int(ndone)
    return {
        "config": f"sedov3d uniform 2^{lvl}^3",
        "cell_updates_per_sec": updates / wall,
        "mus_per_cell_update": 1e6 * wall / max(updates, 1),
        "n": sim.grid.ncell, "steps": int(ndone), "wall_s": wall,
    }


def bench_ensemble(params, dtype, jnp, hb=lambda *a, **k: None):
    """Batched ensemble throughput (ensemble/batch.py): the uniform
    Sedov scenario vmapped over batch sizes {1, 8, 32} through ONE
    compiled fused step chain.  Reports scenarios/sec (batched scenario
    windows drained per second) and aggregate cell-updates/sec per
    batch size — the fleet-amortisation curve the run service rides."""
    import numpy as np

    from ramses_tpu.ensemble.batch import EnsembleSpec, build_member
    from ramses_tpu.grid.uniform import run_steps_batch

    lvl = int(os.environ.get("BENCH_ENS_LEVEL", "6"))
    nsteps = int(os.environ.get("BENCH_ENS_STEPS", "8"))
    batches = tuple(int(b) for b in os.environ.get(
        "BENCH_ENS_BATCHES", "1,8,32").split(","))
    # BENCH_ENS_POISON=J NaN-poisons member J before the warm window —
    # the chaos hook proving a bad sweep point degrades the sub-bench
    # to a quarantine count instead of killing the whole capture
    poison = os.environ.get("BENCH_ENS_POISON", "")
    params.amr.levelmin = params.amr.levelmax = lvl
    params.ensemble.nmember = max(batches)
    # small IC perturbations make every member's data distinct without
    # splitting the compile group (traced values, not jit keys)
    params.ensemble.perturb_amp = 1e-3
    spec = EnsembleSpec.from_params(params, solver="hydro")
    hb("spec")
    per_batch = {}
    grid = None
    quarantined_max = 0
    for b in batches:
        members = [build_member(spec, k, dtype=dtype) for k in range(b)]
        grid = members[0][0]
        u = jnp.stack([m[1][0] for m in members])
        if poison != "" and int(poison) < b:
            u = u.at[(int(poison),) + (0,) * (u.ndim - 1)].set(
                float("nan"))
        t = jnp.zeros((b,), jnp.float32)
        tend = jnp.full((b,), 1e9, jnp.float32)
        # warm with the SAME (grid, nsteps) so the timed window holds
        # zero compiles — only the leading batch dim changes per b
        u1, t1, _ = run_steps_batch(grid, u, t, tend, nsteps)
        float(jnp.sum(u1[:, 0]))
        hb(f"warm_b{b}")
        t0 = time.perf_counter()
        u2, t2, nd = run_steps_batch(grid, u1, t1, tend, nsteps)
        float(jnp.sum(u2[:, 0]))
        wall = time.perf_counter() - t0
        # a poisoned member freezes (NaN time fails the in-scan
        # t < tend mask) — report it as quarantined and take the
        # throughput numbers over the healthy members only, so one bad
        # sweep point degrades the report instead of erroring it
        finite = np.isfinite(np.asarray(t2, np.float64))
        nq = int((~finite).sum())
        quarantined_max = max(quarantined_max, nq)
        if nq:
            hb("quarantine")
        b_eff = int(finite.sum())
        nd_arr = np.asarray(nd)
        steps = int(nd_arr[finite].min()) if b_eff else 0
        updates = grid.ncell * steps * b_eff
        per_batch[str(b)] = {
            "scenarios_per_sec": b_eff / wall,
            "cell_updates_per_sec": updates / wall,
            "mus_per_cell_update": 1e6 * wall / max(updates, 1),
            "steps_per_member": steps, "wall_s": wall,
            "quarantined": nq,
        }
        hb(f"timed_b{b}")
    one = per_batch.get("1", {}).get("cell_updates_per_sec")
    for d in per_batch.values():
        if one:
            # >1 means the batch amortises fixed per-step costs (launch
            # overhead, reductions) across members
            d["efficiency_vs_solo"] = d["cell_updates_per_sec"] / one
    big = per_batch[str(max(batches))]
    return {
        "config": f"sedov3d ensemble 2^{lvl}^3 x batch "
                  f"{{{','.join(str(b) for b in batches)}}}",
        "cell_updates_per_sec": big["cell_updates_per_sec"],
        "scenarios_per_sec": big["scenarios_per_sec"],
        "n": grid.ncell if grid else 0,
        "quarantined": quarantined_max,
        "per_batch": per_batch,
    }


def bench_ensemble_sharded(params, dtype, jnp,
                           hb=lambda *a, **k: None):
    """Two-level parallelism throughput (ensemble/meshplan + the gang
    service): the same small-job workload served at (members x shards)
    in {(8,1) vmap, (8,8) packed, (1,8) slab}, against the
    one-device-at-a-time FIFO baseline — eight single-member jobs
    claimed and run sequentially on one device, the pre-two-level serve
    behaviour.  Every config goes through the real queue->claim->
    run_job->complete path so per-job costs (params expansion, engine
    build, checkpoint, heartbeat, result record) are in the numbers;
    the grid is deliberately tiny (BENCH_ENSH_LEVEL, default 2^2^3)
    because the subject is job-processing amortisation, not FLOPs — on
    real multi-chip meshes the packed replicas also compute
    concurrently, which forced-host devices on one core cannot show.
    Each config is timed over BENCH_ENSH_ROUNDS rounds and reports the
    minimum (job walls are ~10ms; min-of-rounds is the stable
    structural cost)."""
    import tempfile

    import numpy as np

    from ramses_tpu.ensemble import queue as jq
    from ramses_tpu.ensemble.meshplan import MeshPlan
    from ramses_tpu.ensemble.service import run_job

    lvl = int(os.environ.get("BENCH_ENSH_LEVEL", "2"))
    slab_lvl = int(os.environ.get("BENCH_ENSH_SLAB_LEVEL", "4"))
    nsteps = int(os.environ.get("BENCH_ENSH_STEPS", "4"))
    rounds = int(os.environ.get("BENCH_ENSH_ROUNDS", "5"))
    ndev = min(8, len(__import__("jax").devices()))

    def nml(level, nmember):
        return (
            "&RUN_PARAMS\nhydro=.true.\nnstepmax=%d\n/\n"
            "&AMR_PARAMS\nlevelmin=%d\nlevelmax=%d\n/\n"
            "&OUTPUT_PARAMS\ntend=1e9\n/\n"
            "&INIT_PARAMS\nd_region=1.0\np_region=1e-5\n/\n"
            "&ENSEMBLE_PARAMS\nnmember=%d\nperturb_amp=1e-3\n"
            "perturb_seed=7\nchunk_steps=%d\n/\n"
            % (nsteps, level, level, nmember, nsteps))

    def serve_round(qd, tag, jobs, device_ids, plan):
        # jobs: list of (level, nmember); timed region is the worker
        # side — claim, run, complete — exactly what a serve loop pays
        ids = [jq.submit(qd, nml(lv, nm), job_id=f"{tag}-{i}",
                         dtype=str(dtype.__name__))
               for i, (lv, nm) in enumerate(jobs)]
        t0 = time.perf_counter()
        for jid in ids:
            job = jq.claim(qd, worker="bench", job_id=jid)
            run_job(qd, job, device_ids=device_ids, plan=plan,
                    log=lambda *a, **k: None)
            jq.complete(job, {})
        return time.perf_counter() - t0

    def measure(qd, name_, jobs, device_ids, plan, rep=1):
        # rep repeats the job list back-to-back inside one timed round
        # (wall divided by rep): single-job configs are ~15ms walls and
        # need the smoothing the 8-job FIFO round gets for free
        serve_round(qd, f"warm-{name_}", jobs, device_ids, plan)
        hb(f"warm_{name_}")
        wall = min(serve_round(qd, f"{name_}-r{r}", jobs * rep,
                               device_ids, plan) / rep
                   for r in range(rounds))
        members = sum(nm for _, nm in jobs)
        updates = sum((2 ** lv) ** 3 * nsteps * nm for lv, nm in jobs)
        hb(f"timed_{name_}")
        return {"scenarios_per_sec": members / wall,
                "cell_updates_per_sec": updates / wall,
                "members": members, "n_jobs": len(jobs),
                "devices": len(device_ids), "wall_s": wall}

    small = [(lvl, 1)] * 8
    one8 = [(lvl, 8)]
    all_dev = tuple(range(ndev))
    per_config = {}
    with tempfile.TemporaryDirectory() as td:
        qd = os.path.join(td, "queue")
        per_config["fifo_1x1"] = measure(
            qd, "fifo", small, (0,), MeshPlan.single())
        per_config["8x1"] = measure(
            qd, "8x1", one8, (0,), MeshPlan.single(), rep=3)
        per_config["8x8_packed"] = measure(
            qd, "8x8", one8, all_dev, MeshPlan.packed(all_dev), rep=3)
        try:
            per_config["1x8_slab"] = measure(
                qd, "slab", [(slab_lvl, 1)], all_dev,
                MeshPlan.slab(all_dev))
        except Exception as e:  # slab needs nx % ndev == 0, >= NGHOST
            per_config["1x8_slab"] = {"error": f"{type(e).__name__}: {e}"}
    packed = per_config["8x8_packed"]
    fifo = per_config["fifo_1x1"]
    return {
        "config": f"two-level 2^{lvl}^3 x {{8x1, 8x8, 1x8@2^{slab_lvl}}} "
                  f"on {ndev} devices, min of {rounds} rounds",
        "scenarios_per_sec": packed["scenarios_per_sec"],
        "cell_updates_per_sec": packed["cell_updates_per_sec"],
        "n": (2 ** lvl) ** 3,
        "speedup_packed_vs_fifo": (fifo["wall_s"] / packed["wall_s"]),
        "per_config": per_config,
    }


def bench_amr(params, dtype, jnp, hb=lambda *a, **k: None):
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.utils.timers import Timers

    lmin = int(os.environ.get("BENCH_AMR_LMIN", "7"))
    lmax = int(os.environ.get("BENCH_AMR_LMAX", "9"))
    nsteps = int(os.environ.get("BENCH_AMR_STEPS", "10"))
    params.amr.levelmin, params.amr.levelmax = lmin, lmax
    # The reference sedov3d.nml carries no refinement criteria (it is a
    # uniform-grid production file); the driver's AMR variant needs
    # some — relative density/pressure jumps, the standard shock-
    # tracking choice (hydro/godunov_utils.f90:125-260 semantics).
    params.refine.err_grad_d = 0.1
    params.refine.err_grad_p = 0.1
    sim = AmrSim(params, dtype=dtype)
    # un-instrumented sims now default to NullTimers (telemetry's
    # zero-overhead contract); this bench reads the growth-phase
    # breakdown, so it opts back into live timers explicitly
    sim.timers = Timers()
    hb("init")
    # develop the blast until the refined shell is a real working set
    warm = int(os.environ.get("BENCH_AMR_WARM", "10"))
    sim.evolve(1e9, nstepmax=warm)       # compile + develop the blast
    hb("warm")
    sim.timers.acc.clear()
    ttd = 2 ** sim.cfg.ndim

    def count_updates():
        per = {l: sim.tree.noct(l) * ttd * 2 ** (l - sim.lmin)
               for l in sim.levels()}
        return sum(per.values()), per

    n0 = sim.nstep
    updates = 0
    upd_fine = 0
    t0 = time.perf_counter()
    while sim.nstep < n0 + nsteps:
        tot, per = count_updates()      # octs move per step: count per step
        updates += tot
        upd_fine += sum(v for l, v in per.items() if l > lmin)
        if sim.regrid_interval and sim.nstep % sim.regrid_interval == 0:
            sim.regrid()
        sim.step_coarse(sim.coarse_dt())
    sim.drain()
    wall = time.perf_counter() - t0
    sim.timers.stop()
    hb("growth")
    growth_timers = {k: round(v, 3) for k, v in sim.timers.acc.items()}

    # instrumented pass: drain the device at every section switch so the
    # breakdown attributes device time to the section that enqueued it
    # (async dispatch otherwise books everything on the next sync)
    sim.timers = Timers(sync=sim.drain)
    for _ in range(3):
        if sim.regrid_interval:
            sim.regrid()
        sim.step_coarse(sim.coarse_dt())
    sim.timers.stop()
    inst_timers = {k: round(v, 3) for k, v in sim.timers.acc.items()}
    sim.timers = Timers()
    hb("instrumented")

    # steady-state: frozen tree -> static shapes, the whole window runs
    # as a handful of fused multi-step scans (zero host round-trips).
    # Warm with the SAME step count so the canonical chunk decomposition
    # (evolve's power-of-two scan lengths) is fully compiled before the
    # timed window — the timed region must hold zero compiles.
    sim.regrid_interval = 0
    nss = int(os.environ.get("BENCH_AMR_SS_STEPS", "10"))
    sim.evolve(1e9, nstepmax=sim.nstep + nss)
    sim.drain()
    upd1, _ = count_updates()
    t0 = time.perf_counter()
    sim.evolve(1e9, nstepmax=sim.nstep + nss)
    sim.drain()
    wss = time.perf_counter() - t0
    hb("steady_state")

    # production cadence (VERDICT-r04 Weak #9): regrids back ON at the
    # per-step cadence, on the developed quasi-static blast — the
    # apples-to-apples analogue of the reference's running mus/pt
    # average over normal operation (amr/adaptive_loop.f90:204-212)
    nprod = int(os.environ.get("BENCH_AMR_PROD_STEPS", "6"))
    sim.regrid()
    sim.step_coarse(sim.coarse_dt())        # absorb any fresh compiles
    sim.drain()
    updp = 0
    t0 = time.perf_counter()
    n0p = sim.nstep
    while sim.nstep < n0p + nprod:
        updp += count_updates()[0]
        sim.regrid()
        sim.step_coarse(sim.coarse_dt())
    sim.drain()
    wprod = time.perf_counter() - t0
    hb("production")

    # per-phase regrid wallclock (flag / balance / maps / migrate /
    # restrict — hierarchy.regrid timer sections), folded out of the
    # mixed timer dicts so the regrid cost trend is directly readable:
    # "growth" covers the cadenced-growth window, "production" the
    # regrid-every-step window above
    def _regrid_fold(acc):
        return {k[len("regrid: "):]: round(float(v), 3)
                for k, v in acc.items() if k.startswith("regrid: ")}
    regrid_phases = {"growth": _regrid_fold(growth_timers),
                     "production": _regrid_fold(sim.timers.acc)}

    # run-to-run determinism: the same 3 steps from the same state must
    # be BITWISE identical on this device (north-star "bitwise-stable")
    import numpy as np
    # deep-copy: the fused step donates its state input, so a dict of
    # bare references would be dead buffers after the first replay
    u_saved = {l: jnp.array(v) for l, v in sim.u.items()}
    dt_saved, t_saved, n_saved = sim._dt_cache, sim.t, sim.nstep
    sim.evolve(1e9, nstepmax=sim.nstep + 3)
    run1 = {l: np.asarray(sim.u[l]) for l in sim.levels()}
    sim.u, sim._dt_cache, sim.t, sim.nstep = (dict(u_saved), dt_saved,
                                              t_saved, n_saved)
    sim.evolve(1e9, nstepmax=sim.nstep + 3)
    bitwise = all(run1[l].tobytes() == np.asarray(sim.u[l]).tobytes()
                  for l in sim.levels())
    hb("bitwise")
    return {
        "config": f"sedov3d AMR levelmin={lmin} levelmax={lmax}",
        # headline: all-in growth phase (every regrid + recompile cost)
        "cell_updates_per_sec": updates / wall,
        "mus_per_cell_update": 1e6 * wall / max(updates, 1),
        "steps": nsteps, "wall_s": wall,
        "refined_update_fraction": upd_fine / max(updates, 1),
        "timers_s": growth_timers,
        "timers_instrumented_s": inst_timers,
        "regrid_phase_s": regrid_phases,
        "blocked_frac": float(sim.block_stats.get("blocked_frac", 1.0)),
        "octs_per_level": {l: sim.tree.noct(l) for l in sim.levels()},
        "leaf_cells": sim.ncell_leaf(),
        "steady_state": {
            "cell_updates_per_sec": nss * upd1 / wss,
            "mus_per_cell_update": 1e6 * wss / (nss * upd1),
            "steps": nss, "wall_s": wss,
        },
        "production_cadence": {
            "cell_updates_per_sec": updp / wprod,
            "mus_per_cell_update": 1e6 * wprod / max(updp, 1),
            "steps": nprod, "wall_s": wprod,
        },
        "bitwise_repeatable": bool(bitwise),
    }


def bench_amr_poisson(params, dtype, jnp, hb=lambda *a, **k: None):
    """AMR Poisson: live PCG iterations/sec on the hierarchy (the
    'multigrid iters/sec' driver metric covering partial levels —
    multigrid_fine's role; uniform V-cycles are bench_mg)."""
    from ramses_tpu.amr.hierarchy import AmrSim

    lmin = int(os.environ.get("BENCH_AMR_LMIN", "7"))
    lmax = int(os.environ.get("BENCH_AMR_LMAX", "9"))
    params.amr.levelmin, params.amr.levelmax = lmin, lmax
    params.refine.err_grad_d = 0.1
    params.refine.err_grad_p = 0.1
    params.run.poisson = True
    sim = AmrSim(params, dtype=dtype)
    hb("init")
    sim.evolve(1e9, nstepmax=6)          # compile + develop + warm start
    hb("warm")
    nst = 4
    iters = 0
    t0 = time.perf_counter()
    for _ in range(nst):
        sim.regrid()
        sim.step_coarse(sim.coarse_dt())
        iters += sum(int(v) for v in sim.poisson_iters.values())
    sim.drain()
    wall = time.perf_counter() - t0
    return {
        "config": f"sedov3d AMR+selfgrav levelmin={lmin} levelmax={lmax}",
        "pcg_iters_per_sec": iters / wall,
        "pcg_iters_per_step": iters / nst,
        "steps": nst, "wall_s": wall,
    }


def bench_mg(dtype, jnp, hb=lambda *a, **k: None):
    import numpy as np

    from ramses_tpu.poisson.solver import mg_solve, residual

    n = int(os.environ.get("BENCH_MG_N", "128"))
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32)
    rhs = rhs - jnp.mean(rhs)
    dx = 1.0 / n
    ncyc = 10
    # warm with the phi0 form so the timed calls hit the same compile
    phi = mg_solve(rhs, dx, phi0=rhs * 0.0, ncycle=ncyc)
    float(jnp.sum(phi))    # hard sync (host fetch)
    hb("warm")

    def run(reps):
        # feed phi*0 back as phi0: same problem (phi0 defaults to
        # zeros), but each call now DEPENDS on the previous one, so
        # the final fetch provably waits for all reps — r04's 50,613
        # vcycles/s came from timing independent enqueues
        p = phi
        t0 = time.perf_counter()
        for _ in range(reps):
            p = mg_solve(rhs, dx, phi0=p * 0.0, ncycle=ncyc)
        float(jnp.sum(p))
        return time.perf_counter() - t0, p

    # auto-scale reps until the window is >= 1s of real device work
    reps = 3
    wall, phi = run(reps)
    while wall < 1.0 and reps < 8192:
        reps = min(8192, max(reps * 2, int(reps * 1.3 / max(wall, 1e-3))))
        wall, phi = run(reps)
    r = residual(phi, rhs, dx)
    rel = float(jnp.linalg.norm(r) / jnp.linalg.norm(rhs))
    vps = ncyc * reps / wall
    return {
        "config": f"poisson multigrid {n}^3 f32",
        "vcycles_per_sec": vps,
        "rel_residual_after_10_vcycles": rel,
        "n": n, "wall_s": wall, "reps": reps,
    }


def bench_halo(params, dtype, jnp, hb=lambda *a, **k: None):
    """Explicit halo pipeline: fused sweep step time + halo bytes/s at
    1/2/8 shards, ppermute vs DMA.  The DMA backend is measured only on
    a real TPU (the interpreter is a correctness vehicle, not a perf
    path); elsewhere it reports "unavailable" so the ppermute baseline
    still lands."""
    import jax

    from ramses_tpu.driver import Simulation
    from ramses_tpu.parallel import dma_halo
    from ramses_tpu.parallel.halo import make_halo_mesh, run_steps_halo

    lvl = int(os.environ.get("BENCH_HALO_LEVEL", "6"))
    nsteps = int(os.environ.get("BENCH_HALO_STEPS", "8"))
    params.amr.levelmin = params.amr.levelmax = lvl
    sim = Simulation(params, dtype=dtype)
    u0 = sim.state.u
    nvar = int(u0.shape[0])
    ncell = int(u0.size // nvar)
    t0 = jnp.asarray(0.0, u0.dtype)
    tend = jnp.asarray(1e9, u0.dtype)
    hb("init", level=lvl)

    ndev = len(jax.devices())
    shard_counts = [k for k in (1, 2, 8)
                    if k <= ndev and (1 << lvl) % k == 0]
    backends = ["ppermute"] + (["dma"] if dma_halo.available() else [])
    runs = {}
    for k in shard_counts:
        mesh = make_halo_mesh(jax.devices()[:k])
        for backend in backends:
            key = f"{backend}_x{k}"
            dma_halo.reset_traffic()
            # warm: compile the whole window once
            u, t, n = run_steps_halo(sim.grid, mesh, u0, t0, tend,
                                     nsteps, halo_backend=backend)
            float(jnp.sum(u))
            snap = dma_halo.traffic_snapshot()   # per-STEP traced bytes
            hb("warm", config=key)
            reps, wall = 1, 0.0
            while wall < 0.5 and reps < 512:
                tstart = time.perf_counter()
                for _ in range(reps):
                    u, t, n = run_steps_halo(sim.grid, mesh, u0, t0,
                                             tend, nsteps,
                                             halo_backend=backend)
                float(jnp.sum(u))
                wall = time.perf_counter() - tstart
                if wall < 0.5:
                    reps = min(512, reps * 4)
            steps_per_sec = nsteps * reps / wall
            runs[key] = {
                "steps_per_sec": steps_per_sec,
                "step_ms": 1e3 / steps_per_sec,
                "halo_bytes_per_step": snap["halo_bytes"],
                "halo_bytes_per_sec": snap["halo_bytes"] * steps_per_sec,
                "halo_exchanges_per_step": snap["halo_exchanges"],
                "overlap_frac": snap["halo_overlap_frac"],
            }
            hb("timed", config=key)
    if "dma" not in backends:
        runs["dma"] = "unavailable (no TPU backend)"
    return {
        "config": f"halo sweep sedov3d {1 << lvl}^3 "
                  f"{str(dtype.__name__)} nsteps={nsteps}",
        "ncell": ncell,
        "runs": runs,
    }


def bench_offload(dtype, jnp, hb=lambda *a, **k: None):
    """Out-of-core AMR (amr/offload.py): deep-hierarchy per-step time
    and managed-state device high-water at ``offload=off`` vs ``on``
    under a simulated HBM cap.  Both runs step the SAME schedule from
    the same ICs (the engine is pinned bitwise-identical by
    tests/test_offload.py), so the step-time ratio IS the offload
    overhead and the high-water ratio IS the capacity win."""
    import numpy as np

    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_string

    lmin = int(os.environ.get("BENCH_OFF_LMIN", "4"))
    lmax = int(os.environ.get("BENCH_OFF_LMAX", "8"))
    nsteps = int(os.environ.get("BENCH_OFF_STEPS", "6"))
    warm = int(os.environ.get("BENCH_OFF_WARM", "4"))
    nml = "\n".join([
        "&RUN_PARAMS", "hydro=.true.", "nremap=1", "/",
        "&AMR_PARAMS", f"levelmin={lmin}", f"levelmax={lmax}",
        "boxlen=1.0", "offload='{mode}'", "/",
        "&INIT_PARAMS", "nregion=2", "region_type(1)='square'",
        "region_type(2)='point'", "x_center=0.5,0.5",
        "y_center=0.5,0.5", "length_x=10.0,1.0", "length_y=10.0,1.0",
        "exp_region=10.0,10.0", "d_region=1.0,0.0",
        "p_region=1e-5,0.1", "/",
        "&OUTPUT_PARAMS", "tend=1.0", "/",
        "&HYDRO_PARAMS", "gamma=1.4", "courant_factor=0.8", "/",
        "&REFINE_PARAMS", "err_grad_p=0.1", "/",
    ])

    def run(mode):
        p = params_from_string(nml.format(mode=mode), ndim=2)
        sim = AmrSim(p, dtype=dtype)
        sim.evolve(1e9, nstepmax=warm)     # compile + develop the blast
        sim.drain()
        hb("warm", mode=mode)
        stats = dict(stalls=0, prefetches=0, fetches=0, bytes_parked=0,
                     bytes_fetched=0)
        hwm = 0
        t0 = time.perf_counter()
        for _ in range(nsteps):
            if sim.regrid_interval and \
                    sim.nstep % sim.regrid_interval == 0:
                sim.regrid()
            sim.step_coarse(sim.coarse_dt())
            eng = sim._offload
            if eng is not None and eng.last_step_stats is not None:
                for k in stats:
                    stats[k] += int(eng.last_step_stats.get(k, 0))
                hwm = max(hwm, int(eng.last_step_stats
                                   .get("device_hwm_bytes", 0)))
        sim.drain()
        wall = time.perf_counter() - t0
        hb("timed", mode=mode)
        managed = sum(int(np.asarray(sim.u[l]).nbytes)
                      for l in sim.levels())
        return sim, wall, managed, stats, hwm

    s_off, w_off, managed, _, _ = run("off")
    s_on, w_on, _, stats, hwm = run("on")
    engaged = (s_on._offload is not None
               and s_on._offload.engaged(s_on))
    # cheap end-to-end cross-check: both runs stepped the same physics
    bitwise = all(
        np.array_equal(np.asarray(s_off.u[l]), np.asarray(s_on.u[l]))
        for l in s_off.levels()) and s_off.t == s_on.t
    fetches = max(stats["fetches"], 1)
    return {
        "config": f"offload sedov2d lmin={lmin} lmax={lmax} "
                  f"{str(dtype.__name__)} nsteps={nsteps}",
        "engaged": engaged,
        "bitwise_equal_on_vs_off": bitwise,
        "nsteps": nsteps,
        "off": {"step_ms": 1e3 * w_off / nsteps,
                "managed_resident_bytes": managed},
        "on": {"step_ms": 1e3 * w_on / nsteps,
               "device_hwm_bytes": hwm, **stats,
               "overlap_frac": round(
                   (stats["fetches"] - stats["stalls"]) / fetches, 3)},
        "overhead_frac": round(w_on / max(w_off, 1e-9) - 1.0, 3),
        "hwm_reduction_frac": round(1.0 - hwm / max(managed, 1), 3),
    }


def bench_grad(dtype, jnp, hb=lambda *a, **k: None):
    """Checkpointed adjoint rollout cost profile (ramses_tpu/diff):
    grad/forward wall-time ratio and adjoint peak-temp-memory ratio at
    nstep in {8, 32} on a 2D Sedov uniform grid.  The memory baseline
    is the UN-checkpointed adjoint of the plain driver's scan (what a
    naive jax.grad would pay, O(nstep) residuals), so
    ``mem_vs_plain_adjoint < 1`` is direct evidence the sqrt-schedule
    remat (diff/rollout._scan_windows) is engaged — reported as
    ``checkpoint_engaged``."""
    import numpy as np

    import jax
    from ramses_tpu.diff.rollout import (checkpointed_run_steps,
                                         default_inner)
    from ramses_tpu.grid.boundary import BoundarySpec
    from ramses_tpu.grid.uniform import UniformGrid, run_steps
    from ramses_tpu.hydro.core import HydroStatic

    n = int(os.environ.get("BENCH_GRAD_N", "64"))
    reps = int(os.environ.get("BENCH_GRAD_REPS", "5"))
    cfg = HydroStatic(ndim=2, riemann="llf")
    grid = UniformGrid(cfg=cfg, shape=(n, n), dx=1.0 / n,
                       bc=BoundarySpec.periodic(2))
    c = n // 2
    p = np.full((n, n), 1e-5)
    p[c - 1:c + 1, c - 1:c + 1] = 0.1
    u = np.zeros((cfg.nvar, n, n))
    u[0], u[cfg.ndim + 1] = 1.0, p / (cfg.gamma - 1.0)
    uj = jnp.asarray(u, dtype)
    t0 = jnp.zeros((), uj.dtype)
    tend = jnp.asarray(1e9, uj.dtype)

    def best_of(fn, *a):
        w = []
        for _ in range(reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*a))
            w.append(time.perf_counter() - t)
        return min(w)

    def temp_bytes(compiled):
        ma = compiled.memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes", 0) or 0)

    out = {"config": f"grad sedov2d n={n} {str(dtype.__name__)} "
                     f"inner=sqrt reps={reps}"}
    engaged = True
    for ns in (8, 32):
        def loss_fwd(u, ns=ns):
            return jnp.mean(run_steps(grid, u, t0, tend, ns)[0] ** 2)

        def loss_ckpt(u, ns=ns):
            return jnp.mean(
                checkpointed_run_steps(grid, u, t0, tend, ns)[0] ** 2)

        cf = jax.jit(loss_fwd).lower(uj).compile()
        cg = jax.jit(jax.grad(loss_ckpt)).lower(uj).compile()
        # memory baseline only — never timed (its compile alone shows
        # the O(nstep) residual footprint remat exists to avoid)
        cgp = jax.jit(jax.grad(loss_fwd)).lower(uj).compile()
        hb("compiled", nstep=ns)
        f_ms = 1e3 * best_of(cf, uj)
        g_ms = 1e3 * best_of(cg, uj)
        hb("timed", nstep=ns)
        fb, gb, pb = temp_bytes(cf), temp_bytes(cg), temp_bytes(cgp)
        engaged = engaged and 0 < gb < pb
        out[f"nstep{ns}"] = {
            "inner": default_inner(ns),
            "forward_ms": round(f_ms, 3),
            "grad_ms": round(g_ms, 3),
            "grad_over_forward": round(g_ms / max(f_ms, 1e-9), 3),
            "forward_temp_bytes": fb,
            "grad_temp_bytes": gb,
            "plain_adjoint_temp_bytes": pb,
            "mem_vs_forward": round(gb / max(fb, 1), 3),
            "mem_vs_plain_adjoint": round(gb / max(pb, 1), 3),
        }
    out["checkpoint_engaged"] = engaged
    return out


# the default protocol; profile_amr (the per-kernel breakdown of
# tools/profile_amr.py) and halo (the backend comparison above) are
# opt-in via BENCH_ONLY — too slow for every protocol run
DEFAULT_SUBS = ("uniform", "amr", "mg", "amr_poisson", "ensemble")
SUBS = DEFAULT_SUBS + ("profile_amr", "halo", "offload", "grad",
                       "ensemble_sharded")
# ceilings per sub; the GLOBAL budget (BENCH_TOTAL_BUDGET) always wins —
# four rounds of rc=124 driver kills came from these summing past the
# driver's wall clock whenever a sub hung
SUB_TIMEOUTS = {"uniform": 300, "amr": 700, "mg": 240, "amr_poisson": 500,
                "ensemble": 300, "profile_amr": 700, "halo": 300,
                "offload": 600, "grad": 400, "ensemble_sharded": 400}
# share of the REMAINING budget each sub may claim at launch
SUB_WEIGHTS = {"uniform": 0.20, "amr": 0.50, "mg": 0.35,
               "amr_poisson": 0.95, "ensemble": 0.95,
               "profile_amr": 0.95, "halo": 0.95, "offload": 0.95,
               "grad": 0.95, "ensemble_sharded": 0.95}


def run_sub_inproc(name):
    """Child-process entry: run ONE sub-bench, print its dict after MARKER."""
    hb = _load_heartbeat_mod().Heartbeat.from_env()
    hb.mark("start", sub=name)

    if os.environ.get("BENCH_HANG_SUB", "") == name:
        # deliberate-hang hook (CI/tests): wedge BEFORE the jax import
        # so the parent's deadline-kill + hang-classification path is
        # exercised in seconds, not a backend-init timeout
        hb.mark("deliberate_hang")
        while True:
            time.sleep(0.5)

    if name == "ensemble_sharded" and \
            os.environ.get("BENCH_ENSH_FORCE_CPU", "1") != "0":
        # the two-level sub runs against 8 forced host devices by
        # default (its subject is packing/claim amortisation, not
        # FLOPs); BENCH_ENSH_FORCE_CPU=0 opts into the real backend
        from ramses_tpu.platform import force_cpu_mesh
        force_cpu_mesh(8)

    import jax
    import jax.numpy as jnp
    hb.mark("import jax")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not os.environ.get(
            "JAX_PLATFORMS", "").strip().lower().startswith("cpu"):
        # no hidden fallback: a protocol number is a chip number unless
        # the caller asked for the CPU by name
        raise SystemExit(f"sub-bench {name}: platform is {platform!r}, "
                         "not 'tpu' (set JAX_PLATFORMS=cpu to run on "
                         "the CPU on purpose)")

    from ramses_tpu.config import load_params
    hb.mark("load params")

    dtype = jnp.bfloat16 if os.environ.get("BENCH_BF16") else jnp.float32
    nml = os.path.join(HERE, "namelists", "sedov3d.nml")
    if name == "uniform":
        d = bench_uniform(load_params(nml, ndim=3), dtype, jnp,
                          hb=hb.mark)
    elif name == "amr":
        d = bench_amr(load_params(nml, ndim=3), dtype, jnp, hb=hb.mark)
    elif name == "mg":
        d = bench_mg(dtype, jnp, hb=hb.mark)
    elif name == "amr_poisson":
        d = bench_amr_poisson(load_params(nml, ndim=3), dtype, jnp,
                              hb=hb.mark)
    elif name == "ensemble":
        d = bench_ensemble(load_params(nml, ndim=3), dtype, jnp,
                           hb=hb.mark)
    elif name == "ensemble_sharded":
        d = bench_ensemble_sharded(load_params(nml, ndim=3), dtype, jnp,
                                   hb=hb.mark)
    elif name == "halo":
        d = bench_halo(load_params(nml, ndim=3), dtype, jnp, hb=hb.mark)
    elif name == "offload":
        d = bench_offload(dtype, jnp, hb=hb.mark)
    elif name == "grad":
        d = bench_grad(dtype, jnp, hb=hb.mark)
    elif name == "profile_amr":
        # per-kernel breakdown (tools/profile_amr.py): its probes emit
        # incrementally into the result sidecar with completed=False,
        # so a deadline-killed child still leaves a classified partial
        # capture with the phase timings gathered so far
        from tools.profile_amr import collect
        os.environ.setdefault("PROF_PROBE_DEADLINE_S", "120")
        d = collect(hb=hb.mark,
                    emit=lambda r: _write_result(name,
                                                 _stamp_ids(dict(r))))
    else:
        raise SystemExit(f"unknown sub-bench {name!r}")
    hb.mark("done")
    d["_device"] = str(jax.devices()[0].platform)
    d["_dtype"] = str(dtype.__name__)
    _stamp_ids(d)
    _write_result(name, d)
    print(MARKER + json.dumps(d), flush=True)


def _backend_ish(msg):
    return any(s in msg for s in (
        "UNAVAILABLE", "Unable to initialize backend", "DEADLINE",
        "timed out", "TimeoutExpired", "backend setup",
        "Socket closed", "Connection reset"))


def run_sub(name, deadline, weight=None, reserve=0.0):
    """Parent side: launch the sub-bench subprocess with a timeout
    bounded by BOTH the per-sub ceiling and this sub's share of the
    remaining global budget; retry on backend-init failures/timeouts
    only while budget remains.  ``reserve`` (seconds) is held back for
    the subs still pending after this one, so one hung sub burns its
    own share of the budget, never the whole remainder.  Returns the
    sub dict (or error)."""
    ceiling = float(os.environ.get("BENCH_SUB_TIMEOUT",
                                   SUB_TIMEOUTS.get(name, 600)))
    if weight is None:
        weight = SUB_WEIGHTS.get(name, 0.5)
    hb_path = _hb_path(name)
    # RAMSES_TRACE_ID: the child's Heartbeat.from_env stamps it (plus
    # its host:pid) onto every sidecar marker and result JSON
    env = dict(os.environ, BENCH_HEARTBEAT_PATH=hb_path,
               RAMSES_TRACE_ID=TRACE_ID)

    def _hb_diag():
        """phase_at_timeout + recent phase trail from the child's
        heartbeat sidecar — the diagnosis BENCH_r05's four identical
        timeout errors lacked."""
        phases = _read_phases(hb_path)
        if not phases:
            return {"phase_at_timeout": "no heartbeat (child never "
                                        "started or sidecar unwritable)"}
        return {"phase_at_timeout": phases[-1].get("phase"),
                "phase_t_s": phases[-1].get("t_s"),
                "heartbeat": phases[-5:]}

    last = None
    for attempt in (1, 2):
        remaining = deadline - time.monotonic()
        if remaining < 45.0:
            return last or {"error": "skipped: global bench budget "
                                     "exhausted", "attempt": attempt}
        timeout = min(ceiling, max(45.0, weight * remaining))
        if reserve > 0.0:
            # hold back >=45s for each still-pending sub (never raising
            # the per-sub ceiling)
            timeout = min(timeout, max(45.0, remaining - reserve))
        for stale in (hb_path, _result_path(name)):
            try:
                # stale sidecars from a previous attempt/run must not
                # masquerade as this child's phase trail or result
                os.path.exists(stale) and os.remove(stale)
            except OSError:
                pass
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--sub", name],
                capture_output=True, text=True, timeout=timeout,
                cwd=HERE, env=env)
            for line in reversed(r.stdout.splitlines()):
                if line.startswith(MARKER):
                    return json.loads(line[len(MARKER):])
            got = _read_result(name)
            if got is not None and got.get("completed") is not False:
                return got        # stdout lost, sidecar survived
            tail = (r.stderr or r.stdout or "")[-2000:]
            last = {"error": f"sub-bench exited rc={r.returncode} "
                             f"without result", "tail": tail,
                    "attempt": attempt, **_hb_diag()}
            if got is not None:
                # incremental sidecar (profile_amr): keep the partial
                # phase timings alongside the diagnosis
                last["partial"] = got
            if r.returncode == 87:
                # the watchdog's HANG_EXIT_CODE, as a literal — the
                # parent never imports ramses_tpu
                last["classification"] = "hang"
                return last
            if not _backend_ish(tail):
                return last
        except subprocess.TimeoutExpired:
            got = _read_result(name)
            if got is not None and got.get("completed") is False:
                # incremental sidecar: the child was killed mid-capture
                # — classify as hang but KEEP the partial phase timings
                got.update({"error": f"sub-bench timed out after "
                                     f"{timeout:.0f}s",
                            "classification": "hang",
                            "attempt": attempt, **_hb_diag()})
                return got
            if got is not None:
                # the measurement finished; the child hung afterwards
                got["late"] = True
                return got
            last = {"error": f"sub-bench timed out after {timeout:.0f}s",
                    "classification": "hang",
                    "attempt": attempt, **_hb_diag()}
        except Exception:
            last = {"error": traceback.format_exc()[-2000:],
                    "attempt": attempt}
        if attempt == 1:
            # a backend-init failure can outlast a short pause — but
            # never sleep the budget away; pacing shared with the namelist
            # supervisor so both retry loops back off identically
            from ramses_tpu.resilience.supervisor import backoff_delay
            time.sleep(min(backoff_delay(attempt, base=30.0, cap=30.0),
                           max(0.0,
                               deadline - time.monotonic() - 60.0)))
    return last


def main():
    only = os.environ.get("BENCH_ONLY", "")
    wanted = (tuple(s.strip() for s in only.split(",") if s.strip())
              if only else DEFAULT_SUBS)
    bad = [s for s in wanted if s not in SUBS]
    if bad:
        raise SystemExit(
            f"BENCH_ONLY={only!r}: unknown sub(s) {bad}; expected a "
            f"comma list of "
            f"uniform|amr|mg|amr_poisson|ensemble|profile_amr|halo")
    budget = float(os.environ.get("BENCH_TOTAL_BUDGET", "900"))
    deadline = time.monotonic() + budget
    partial_path = os.environ.get(
        "BENCH_PARTIAL_PATH", os.path.join(HERE, "BENCH_PARTIAL.json"))

    sub = {}
    device = dtype_name = None
    # clear any stale partial from a previous run BEFORE the first sub:
    # a driver kill during sub 1 must not leave run N-1's numbers
    # masquerading as run N's
    try:
        with open(partial_path, "w") as f:
            json.dump({"budget_s": budget, "sub": {}}, f)
    except OSError:
        pass
    for i, name in enumerate(wanted):
        sub[name] = run_sub(name, deadline,
                            weight=0.95 if len(wanted) == 1 else None,
                            reserve=45.0 * (len(wanted) - 1 - i))
        device = device or sub[name].pop("_device", None)
        dtype_name = dtype_name or sub[name].pop("_dtype", None)
        sub[name].pop("_device", None)
        sub[name].pop("_dtype", None)
        # incremental emission: whatever has completed is ALWAYS on
        # record, even if the driver kills this process mid-protocol
        try:
            with open(partial_path, "w") as f:
                json.dump({"budget_s": budget, "device": device,
                           "dtype": dtype_name, "sub": sub}, f)
        except OSError:
            pass

    # amr-hang escalation: a hang-classified amr capture alone says
    # nothing about WHERE the step wedged — run the per-kernel
    # breakdown (incremental sidecar) so even a wedged run leaves
    # classified partial phase timings on record
    if (sub.get("amr", {}).get("classification") == "hang"
            and "profile_amr" not in wanted
            and deadline - time.monotonic() > 60.0):
        sub["profile_amr"] = run_sub("profile_amr", deadline, weight=0.95)
        sub["profile_amr"]["escalated_from"] = "amr hang"
        try:
            with open(partial_path, "w") as f:
                json.dump({"budget_s": budget, "device": device,
                           "dtype": dtype_name, "sub": sub}, f)
        except OSError:
            pass

    published = _load_baseline()
    base_hydro = (published.get("hydro", {})
                  .get("cell_updates_per_sec_64rank"))
    base_mg = (published.get("multigrid", {})
               .get("vcycles_per_sec_128_64rank"))
    if base_mg and "vcycles_per_sec" in sub.get("mg", {}):
        sub["mg"]["vs_baseline_64rank"] = (
            sub["mg"]["vcycles_per_sec"] / base_mg)
    if base_hydro and "cell_updates_per_sec" in sub.get("uniform", {}):
        sub["uniform"]["vs_baseline_64rank"] = (
            sub["uniform"]["cell_updates_per_sec"] / base_hydro)
    if base_hydro and "steady_state" in sub.get("amr", {}):
        sub["amr"]["steady_state"]["vs_baseline_64rank"] = (
            sub["amr"]["steady_state"]["cell_updates_per_sec"] / base_hydro)

    def ok(name):
        d = sub.get(name)
        return d if d and "error" not in d else None

    head = (ok("amr") or ok("uniform") or ok("mg") or ok("amr_poisson")
            or ok("ensemble") or {"config": "all sub-benches failed"})
    hydro_head = "cell_updates_per_sec" in head
    value = head.get("cell_updates_per_sec",
                     head.get("vcycles_per_sec",
                              head.get("pcg_iters_per_sec")))
    vs = (value / base_hydro if base_hydro and hydro_head else
          (value / base_mg if base_mg and value is not None
           and "vcycles_per_sec" in head else None))
    out = {
        "trace_id": TRACE_ID,
        "metric": (f"cell-updates/sec/chip {head['config']}" if hydro_head
                   else (f"vcycles/sec/chip {head['config']}"
                         if "vcycles_per_sec" in head
                         else f"pcg-iters/sec/chip {head['config']}")),
        "value": value,
        "unit": ("cell-updates/s" if "cell_updates_per_sec" in head
                 else ("vcycles/s" if "vcycles_per_sec" in head
                       else "pcg-iters/s")),
        "vs_baseline": vs,
        "detail": {
            "device": device,
            "dtype": dtype_name,
            "baseline": {"hydro_64rank_cell_updates_per_sec": base_hydro,
                         "mg_64rank_vcycles_per_sec": base_mg,
                         "method": published.get("method", "unpublished")},
            "sub": sub,
        },
    }
    print(json.dumps(out))
    return 1 if any("error" in d for d in sub.values()) else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--sub":
        run_sub_inproc(sys.argv[2])
    else:
        sys.exit(main())
