#!/usr/bin/env python
"""Component-level device-time breakdown of the steady-state AMR step.

The VERDICT-r04 mandate: find the measured 678x per-cell-update overhead
of the AMR path vs the uniform kernel WITH A MEASUREMENT, not a guess.
This tool times each device kernel of the fused coarse step in
isolation, at the exact live shapes of the bench configuration
(sedov3d levelmin=7 levelmax=9 by default), plus the candidate
conversions (index-gather vs bit-permutation transpose) side by side,
the blocked Morton-tile sweep vs the 6^3 stencil sweep, the regrid
sub-phases (flag/maps/migrate/restrict), and the static HLO
gather-element inventory of the fused step.

Results land in a machine-readable JSON file (``PROF_JSON``, default
``PROF_AMR.json`` next to the repo root), rewritten ATOMICALLY after
every probe — a deadline-killed run leaves a classified partial capture
(``completed: false``, ``probe_errors``), never an empty one.  The
``##PROF##`` stdout line carries the same object.

Hang-proofing (the PR 7 ladder): run WITHOUT ``PROF_CHILD`` and the
parent re-executes itself as a killed-on-deadline subprocess
(``PROF_DEADLINE_S``, default 900) and classifies the outcome — rc 87
(watchdog hard-exit) and timeouts read the partial JSON back and stamp
``classification: "hang"`` plus the probe in flight.  Inside the child
every probe runs under a :class:`ramses_tpu.resilience.watchdog.
Watchdog` step guard (``PROF_PROBE_DEADLINE_S``, default 120 when
deadlines are armed): a wedged probe raises HangDetected (recorded,
remaining probes still run) and a truly uninterruptible one hard-exits
87 for the parent to classify.  ``bench.py`` runs the same probes as
the ``profile_amr`` sub under its own subprocess isolation.

Optionally wraps 3 steady-state steps in a ``jax.profiler.trace``
(PROFILE_TRACE_DIR env) for op-level inspection where the tensorboard
profile plugin exists.

Env: PROF_LMIN, PROF_LMAX, PROF_WARM, PROF_REPS, PROF_JSON,
PROF_DEADLINE_S, PROF_PROBE_DEADLINE_S, PROF_CHILD, PROFILE_TRACE_DIR.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MARKER = "##PROF##"


def timeit(fn, reps, sync):
    """Median-free simple wall: warm once (compile), sync, run reps,
    sync; returns seconds per call."""
    out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / reps


def _sync(x):
    """Hard sync: host-fetch one element of every leaf."""
    import jax
    leaves = jax.tree_util.tree_leaves(x)
    jax.device_get([l.ravel()[:1] for l in leaves if hasattr(l, "ravel")])


def _json_path():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.environ.get("PROF_JSON", os.path.join(here, "PROF_AMR.json"))


def _write_json(res):
    """Atomic incremental emission: the capture on disk is always a
    valid JSON object, partial or complete."""
    path = _json_path()
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(res, f, indent=1, default=str)
        os.replace(tmp, path)
    except OSError:
        pass


def collect(hb=lambda *a, **k: None, emit=None):
    """Run every probe, returning the result dict.  ``hb(phase)`` marks
    progress (bench.py heartbeat); ``emit(res)`` is called after every
    probe with the partial result (defaults to the atomic PROF_JSON
    write)."""
    import jax
    import jax.numpy as jnp

    from ramses_tpu.amr import bitperm
    from ramses_tpu.amr import kernels as K
    from ramses_tpu.amr.hierarchy import (AmrSim, _fused_coarse_step,
                                          _fused_courant)
    from ramses_tpu.config import load_params
    from ramses_tpu.utils.timers import NullTimers, Timers

    if emit is None:
        emit = _write_json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lmin = int(os.environ.get("PROF_LMIN", "7"))
    lmax = int(os.environ.get("PROF_LMAX", "9"))
    warm = int(os.environ.get("PROF_WARM", "15"))
    reps = int(os.environ.get("PROF_REPS", "10"))
    params = load_params(os.path.join(here, "namelists", "sedov3d.nml"),
                        ndim=3)
    params.amr.levelmin, params.amr.levelmax = lmin, lmax
    params.refine.err_grad_d = 0.1
    params.refine.err_grad_p = 0.1

    t = {}
    res = {"device": str(jax.devices()[0].platform),
           "reps": reps, "completed": False, "timings_s": t,
           "probe_errors": {}}

    # watchdog around every probe: armed when the parent mode or the
    # caller set a probe deadline — an interruptible wedge is recorded
    # and skipped, an uninterruptible one hard-exits HANG_EXIT_CODE
    dl = float(os.environ.get("PROF_PROBE_DEADLINE_S", "0") or 0.0)
    wd = None
    HangDetected = ()
    if dl > 0.0:
        from ramses_tpu.resilience import watchdog as wmod
        HangDetected = wmod.HangDetected
        wd = wmod.Watchdog(step_deadline_s=dl, hard_exit=True)
        wd._warmed = True              # no separate compile budget here

    def probe(name, fn):
        """One guarded probe; failures/hangs become probe_errors
        entries instead of killing the capture."""
        res["probe"] = name
        try:
            if wd is not None:
                with wd.guard("step"):
                    fn()
            else:
                fn()
        except HangDetected as e:      # soft-interrupted wedge
            res["probe_errors"][name] = f"hang: {e}"
        except Exception as e:         # noqa: BLE001 - capture survives
            res["probe_errors"][name] = repr(e)
        hb(name)
        emit(res)

    state = {}

    def p_init():
        sim = AmrSim(params, dtype=jnp.float32)
        # no telemetry here, so the sim defaults to NullTimers; install
        # a draining accumulator so the warm-up's changed-tree regrids
        # leave a growth-phase sub-phase breakdown for p_regrid
        sim.timers = Timers(sync=sim.drain)
        sim.evolve(1e9, nstepmax=warm)      # develop the blast + compile
        sim.timers.stop()
        state["growth_acc"] = dict(sim.timers.acc)
        sim.timers = NullTimers()   # don't let drains skew later probes
        sim.regrid_interval = 0             # freeze the tree
        state["sim"] = sim
        state["spec"] = sim._fused_spec()
        state["dt"] = jnp.asarray(sim.coarse_dt(), sim.dtype)
        res["octs_per_level"] = {str(l): sim.tree.noct(l)
                                 for l in sim.levels()}
        res["levels"] = list(sim.levels())
        res["blocked_levels"] = sorted(sim.blocks)
        res["block_stats"] = dict(sim.block_stats)
        res["tile_occupancy"] = {
            str(l): round(b.noct / (b.ntile * (1 << (3 * b.shift))), 4)
            for l, b in sim.blocks.items()}
    probe("init", p_init)
    if "sim" not in state:
        res["error"] = ("init probe failed: "
                        + str(res["probe_errors"].get("init")))
        emit(res)
        return res
    sim, spec, dt = state["sim"], state["spec"], state["dt"]

    # --- static HLO gather inventory of the fused step ---------------
    def p_hlo():
        from ramses_tpu.analysis import engine as aeng
        from ramses_tpu.telemetry import hlo as hmod
        txt = hmod.lower_fused_step(sim)
        inv = hmod.gather_inventory(txt)
        res["hlo_gather_elems"] = sum(n for n, _ in inv)
        res["hlo_gather_ops"] = len(inv)
        # unbaselined static-analysis findings of the same lowering
        res["analysis_findings"] = aeng.audit_sim(sim, text=txt)
    probe("hlo_inventory", p_hlo)

    # --- full fused coarse step (the steady-state unit of work) ------
    def p_step():
        # the step jit donates its state argument, so thread the
        # returned state through exactly like the evolve loop does
        def _step():
            out = _fused_coarse_step(sim.u, sim.dev, {}, dt, spec, None)
            sim.u = out[0]
            return out
        t["fused_coarse_step"] = timeit(_step, reps, _sync)
    probe("fused_coarse_step", p_step)

    # --- per-component, exact live shapes ----------------------------
    lb = sim.lmin
    d = sim.dev[lb]
    u0 = sim.u[lb]
    shape = (1 << lb,) * sim.cfg.ndim
    ncell = shape[0] ** sim.cfg.ndim

    def p_dense():
        t["dense_sweep_base"] = timeit(
            lambda: K.dense_sweep(u0, d.get("inv_perm"), d.get("perm"),
                                  d["ok_dense"], dt, sim.dx(lb), shape,
                                  sim.bspec, sim.cfg), reps, _sync)
    probe("dense_sweep_base", p_dense)

    def p_conv():
        # conversions: bit-permutation transpose vs index gather
        f2d = jax.jit(lambda u: bitperm.flat_to_dense(u, lb, 3))
        d2f = jax.jit(lambda ud: bitperm.dense_to_flat(ud, lb, 3))
        ud = f2d(u0)
        state["ud"] = ud
        t["flat_to_dense_bitperm"] = timeit(lambda: f2d(u0), reps, _sync)
        t["dense_to_flat_bitperm"] = timeit(lambda: d2f(ud), reps, _sync)
        m = sim.maps[lb]
        inv_perm = jnp.asarray(m.inv_perm)
        perm = jnp.asarray(m.perm)
        gat = jax.jit(lambda u, i: u[i])
        t["flat_to_dense_gather"] = timeit(lambda: gat(u0, inv_perm),
                                           reps, _sync)
        rows = u0[:ncell]
        t["dense_to_flat_gather"] = timeit(lambda: gat(rows, perm), reps,
                                           _sync)
    probe("conversions", p_conv)

    def p_pallas_dense():
        # pure dense kernel (what the uniform bench runs per 128^3)
        from ramses_tpu.hydro import pallas_muscl as pk
        if not pk.kernel_available(sim.cfg, shape, sim.bspec.faces,
                                   u0.dtype) or "ud" not in state:
            return
        ok = (d["ok_dense"].reshape(shape)
              if d.get("ok_dense") is not None else None)
        udm = jnp.moveaxis(state["ud"], -1, 0)

        @jax.jit
        def dense_kernel(udm):
            up, okp = pk.pad_xy(udm, sim.bspec, sim.cfg, ok=ok)
            return pk.fused_step_padded(up, dt, sim.cfg, sim.dx(lb),
                                        shape, ok_pad=okp)
        t["pallas_dense_kernel"] = timeit(lambda: dense_kernel(udm),
                                          reps, _sync)
    probe("pallas_dense_kernel", p_pallas_dense)

    def p_levels():
        for l in sim.levels():
            if sim.maps[l].complete:
                continue
            dl_ = sim.dev[l]
            if "stencil_src" in dl_:
                # the 6^3 stencil sweep, where the level has its tables
                # (oct_blocking=.false.; a tile-path level has none)
                itp = K.interp_cells(sim.u[l - 1], dl_["interp_cell"],
                                     dl_["interp_nb"], dl_["interp_sgn"],
                                     sim.cfg, itype=spec.itype)
                t[f"interp_cells_L{l}"] = timeit(
                    lambda: K.interp_cells(sim.u[l - 1],
                                           dl_["interp_cell"],
                                           dl_["interp_nb"],
                                           dl_["interp_sgn"],
                                           sim.cfg, itype=spec.itype),
                    reps, _sync)
                t[f"level_sweep_L{l}"] = timeit(
                    lambda: K.level_sweep(sim.u[l], itp,
                                          dl_["stencil_src"], dl_["vsgn"],
                                          dl_["ok_ref"], None, dt,
                                          sim.dx(l), sim.cfg), reps,
                    _sync)
            if l in sim.blocks:
                # the gather-fused blocked tile sweep of the same level
                bi = K.interp_cells(
                    sim.u[l - 1], dl_["b_interp_cell"],
                    dl_["b_interp_nb"], dl_["b_interp_sgn"], sim.cfg,
                    itype=spec.itype)
                t[f"tile_sweep_L{l}"] = timeit(
                    lambda: K.tile_sweep(
                        sim.u[l], bi, dl_["tile_src"], dl_["tile_vsgn"],
                        dl_["tile_ok"], dl_["cell_tile"],
                        dl_["cell_slot"], dl_["oct_tile"],
                        dl_["oct_slot"], dt, sim.dx(l), sim.cfg,
                        sim.blocks[l].shift), reps, _sync)
            t[f"scatter_corr_L{l}"] = timeit(
                lambda: K.scatter_corrections(
                    sim.u[l - 1],
                    jnp.zeros((sim.maps[l].noct_pad, 3, 2,
                               sim.cfg.nvar), sim.dtype),
                    dl_["corr_idx"], sim.cfg),
                reps, _sync)
    probe("level_kernels", p_levels)

    def p_restrict():
        t["restrict_upload_base"] = timeit(
            lambda: K.restrict_upload(sim.u[lb], sim.u[lb + 1],
                                      d["ref_cell"], d["son_oct"],
                                      sim.cfg),
            reps, _sync) if sim.tree.has(lb + 1) else None
    probe("restrict_upload", p_restrict)

    def p_courant():
        t["fused_courant"] = timeit(
            lambda: _fused_courant(sim.u, sim.dev, spec), reps, _sync)
    probe("fused_courant", p_courant)

    def p_regrid():
        # regrid sub-phases (flag/maps/migrate/restrict): instrumented
        # timers with a device drain at each section switch, plus the
        # incremental-rebuild counters — steady state (unchanged tree)
        # must rebuild ZERO per-block maps
        saved = sim.timers
        sim.timers = Timers(sync=sim.drain)
        for _ in range(3):
            sim.regrid()
        sim.timers.stop()
        res["regrid_phase_s"] = {
            k: round(v, 4) for k, v in sim.timers.acc.items()
            if k.startswith("regrid")}
        # the steady-state loop above short-circuits after balance, so
        # maps/migrate/restrict come from the growth-phase accumulator
        # captured during the warm-up evolve (changed-tree regrids)
        res["regrid_phase_growth_s"] = {
            k: round(v, 4) for k, v in state["growth_acc"].items()
            if k.startswith("regrid")}
        res["regrid_block_stats"] = dict(sim.block_stats)
        sim.timers = saved
    probe("regrid_phases", p_regrid)

    def p_steady():
        # steady-state chunk throughput (the bench's steady_state
        # number); warm with the SAME step count so the canonical chunk
        # decomposition is fully compiled before the timed window
        nss = 8
        sim.evolve(1e9, nstepmax=sim.nstep + nss)
        sim.drain()
        ttd = 2 ** sim.cfg.ndim
        upd = sum(sim.tree.noct(l) * ttd * 2 ** (l - sim.lmin)
                  for l in sim.levels())
        t0 = time.perf_counter()
        sim.evolve(1e9, nstepmax=sim.nstep + nss)
        sim.drain()
        wss = time.perf_counter() - t0
        res["steady_state_cell_updates_per_sec"] = nss * upd / wss
        res["steady_state_s_per_coarse_step"] = wss / nss
        res["updates_per_coarse_step"] = upd
    probe("steady_state", p_steady)

    def p_offload():
        # segmented out-of-core step (amr/offload.py) on the same
        # frozen tree: per-step wall with inactive levels cycling
        # through host parks — side-by-side with fused_coarse_step
        # above, the segmentation + transfer overhead is the delta;
        # the residency counters land under res["offload"]
        from ramses_tpu.amr.offload import OffloadEngine
        eng = OffloadEngine("on")
        why = eng.ineligible_reason(sim)
        if why is not None:
            res["offload"] = {"skipped": why}
            return
        dtf = float(sim.coarse_dt())
        spec_now = sim._fused_spec()

        def _ostep():
            sim.u, sim._dt_cache = eng.run_step(sim, dtf, spec_now)
            return sim.u[sim.lmin]
        t["offload_step"] = timeit(_ostep, max(3, reps // 2), _sync)
        res["offload"] = dict(eng.last_step_stats or {})
        eng.unpark_all(sim)       # later probes expect device arrays
        sim._dt_cache = None
    probe("offload_step", p_offload)

    def p_trace():
        tdir = os.environ.get("PROFILE_TRACE_DIR")
        if tdir:
            with jax.profiler.trace(tdir):
                sim.evolve(1e9, nstepmax=sim.nstep + 3)
                sim.drain()
            res["trace_dir"] = tdir
    probe("profiler_trace", p_trace)

    res["timings_s"] = {k: (round(v, 6) if v is not None else None)
                        for k, v in t.items()}
    res.pop("probe", None)
    res["completed"] = True
    if not res["probe_errors"]:
        res.pop("probe_errors")
    emit(res)
    return res


def _parent():
    """Re-execute as a killed-on-deadline child; classify the outcome
    and always print a ##PROF## line (partial on hang/crash)."""
    deadline = float(os.environ.get("PROF_DEADLINE_S", "900"))
    env = dict(os.environ, PROF_CHILD="1")
    env.setdefault("PROF_PROBE_DEADLINE_S",
                   str(min(120.0, max(30.0, deadline / 6.0))))
    try:
        os.path.exists(_json_path()) and os.remove(_json_path())
    except OSError:
        pass
    rc = None
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, timeout=deadline,
                           capture_output=True, text=True)
        rc = r.returncode
        for line in reversed(r.stdout.splitlines()):
            if line.startswith(MARKER):
                print(line, flush=True)
                return 0
    except subprocess.TimeoutExpired:
        rc = "timeout"
    # no marker: classify from the partial JSON the child left behind
    try:
        with open(_json_path()) as f:
            res = json.load(f)
    except (OSError, ValueError):
        res = {"completed": False}
    res["classification"] = ("hang" if rc in (87, "timeout")
                             else "crash")
    res["child_rc"] = rc
    if not res.get("completed"):
        res.setdefault("probe_at_exit", res.get("probe"))
    _write_json(res)
    print(MARKER + json.dumps(res, default=str), flush=True)
    return 0


def main():
    if os.environ.get("PROF_CHILD") or os.environ.get("PROF_INPROC"):
        res = collect()
        print(MARKER + json.dumps(res, default=str), flush=True)
        return 0
    return _parent()


if __name__ == "__main__":
    sys.exit(main())
