"""Command-line entry point: ``python -m ramses_tpu run.nml``.

The ``program ramses`` equivalent (``amr/ramses.f90:1-15``): parse the
namelist given as first argument, run the adaptive loop, write snapshots
at the configured output times.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="ramses_tpu",
        description="TPU-native AMR astrophysics framework")
    ap.add_argument("namelist", nargs="?", default=None,
                    help="Fortran-namelist runtime config (optional "
                         "with --serve)")
    ap.add_argument("--serve", metavar="QUEUE_DIR", default=None,
                    help="run-service worker: claim jobs from this "
                         "queue dir and run them under the supervised "
                         "ensemble engine (ramses_tpu/ensemble); "
                         "SIGTERM drains gracefully — finish the "
                         "chunk, checkpoint, requeue held jobs with "
                         "stage=drain, exit 0")
    ap.add_argument("--submit", metavar="QUEUE_DIR", default=None,
                    help="enqueue the namelist as a job instead of "
                         "running it; prints the job id")
    ap.add_argument("--calibrate", action="store_true",
                    help="run (or with --submit, enqueue) the namelist "
                         "as a gradient-descent calibration against a "
                         "target rollout (&CALIBRATION_PARAMS, "
                         "ramses_tpu/diff) instead of a forward "
                         "simulation")
    ap.add_argument("--sweep", action="append", metavar="KEY=V1,V2,...",
                    help="with --submit: per-member parameter sweep "
                         "rows, dotted paths into the namelist "
                         "(e.g. init.p_region[1]=0.3,0.5); repeatable")
    ap.add_argument("--max-jobs", type=int, default=0,
                    help="with --serve: stop after this many jobs "
                         "(0 = keep serving)")
    ap.add_argument("--idle-exit", action="store_true",
                    help="with --serve: exit once the queue is drained "
                         "instead of polling")
    ap.add_argument("--stale-timeout", type=float, default=300.0,
                    help="with --serve: reclaim running jobs whose "
                         "heartbeat is older than this many seconds")
    ap.add_argument("--worker-id", default="",
                    help="with --serve: worker name stamped on claimed "
                         "jobs (default host:pid)")
    ap.add_argument("--obs", metavar="QUEUE_DIR", default=None,
                    help="standalone observability server: serve the "
                         "streaming results API + Prometheus /metrics "
                         "over this queue dir (ramses_tpu/obs) without "
                         "running any jobs; Ctrl-C to stop")
    ap.add_argument("--obs-port", type=int, default=None,
                    help="with --serve or --obs: TCP port for the "
                         "observability HTTP server (0 = pick an "
                         "ephemeral port; default with --obs: 9100, "
                         "with --serve: off)")
    ap.add_argument("--obs-bind", default="127.0.0.1",
                    help="bind address for the observability server "
                         "(default loopback; 0.0.0.0 exposes it)")
    ap.add_argument("--claim-order", default="cost",
                    choices=["cost", "fifo"],
                    help="with --serve: job claim order — 'cost' "
                         "(default) gang-schedules by the submit-time "
                         "cost stamp to fill the local device mesh, "
                         "'fifo' restores blind oldest-first claiming")
    ap.add_argument("--ndim", type=int, default=3,
                    help="spatial dimensions (compile-time in the reference)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "float64", "bfloat16"])
    ap.add_argument("--amr", action="store_true",
                    help="force the multi-level AMR driver even when "
                         "levelmin==levelmax")
    ap.add_argument("--solver", default=None,
                    choices=["hydro", "mhd", "rhd"],
                    help="solver family (the reference's SOLVER= make "
                         "variable); default: mhd when &INIT_PARAMS sets "
                         "A/B/C_region, hydro otherwise")
    ap.add_argument("--patch", default=None,
                    help="user plug-in file overriding condinit/gravana/"
                         "boundana/source hooks (the runtime equivalent "
                         "of the reference's compile-time PATCH= VPATH "
                         "shadowing, bin/Makefile:153-160)")
    ap.add_argument("--verbose", "-v", action="store_true")
    ap.add_argument("--walltime", type=float, default=None,
                    help="wall-clock budget in hours; the watchdog dumps "
                         "a restartable snapshot and stops before it "
                         "expires (amr/adaptive_loop.f90:216-226)")
    ap.add_argument("--auto-resume", action="store_true",
                    help="resume from the newest manifest-valid "
                         "checkpoint in the output dir (same as "
                         "&RUN_PARAMS auto_resume=.true.)")
    ap.add_argument("--max-attempts", type=int, default=1,
                    help="supervised retry-with-resume: on an "
                         "interrupted or failed run, rebuild from the "
                         "latest valid checkpoint and continue, up to "
                         "this many attempts (exponential backoff)")
    return ap


def _on_accelerator() -> bool:
    """The platform test of the Pallas and DMA gates."""
    import jax
    return jax.default_backend() == "tpu"


def amr_devices(devices=None) -> list:
    """The devices an AMR hydro run is built over.  Named ``devices``
    are taken as they are.  With none named: every device of the default
    backend when that backend is the accelerator (the platform test of
    the Pallas and DMA gates), else one — forced virtual CPU devices are
    one host's cores, not a mesh.  To use one chip of four, restrict the
    visible devices as any JAX program does."""
    import jax
    if devices is not None:
        return list(devices)
    devs = jax.devices()
    return list(devs) if _on_accelerator() else devs[:1]


def build_amr_sim(params, dtype, devices=None, restart=None, log=print,
                  **kw):
    """The hydro AMR simulation of ``python -m ramses_tpu``, fresh or
    from the checkpoint directory ``restart``: the mesh-sharded class
    over :func:`amr_devices` when they are more than one, ``AmrSim``
    when there is one.  ``kw`` goes to the constructor of a fresh
    build.  Says once what it built and which formulation each level's
    sweep takes."""
    from ramses_tpu.amr.hierarchy import AmrSim
    devs = amr_devices(devices)
    cls, mesh = AmrSim, {}
    if len(devs) > 1:
        from ramses_tpu.parallel.amr_sharded import ShardedAmrSim
        cls, mesh = ShardedAmrSim, {"devices": devs}
    if restart:
        sim = cls.from_checkpoint_dir(params, restart, dtype=dtype, **mesh)
    else:
        sim = cls(params, dtype=dtype, **kw, **mesh)
    if log is not None:
        log(f"amr: {cls.__name__} over {len(devs)} device(s) "
            f"[{devs[0].platform}]; " + "; ".join(
                f"level {l}: {name}"
                for l, name, _ in sim.level_formulations()))
    return sim


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)

    # run-service front-end: --submit enqueues and exits; --serve is
    # the worker loop (no namelist needed — jobs carry their own)
    if args.submit:
        if not args.namelist:
            ap.error("--submit requires a namelist")
        from ramses_tpu.ensemble.service import (parse_sweep_args,
                                                 submit_namelist)
        job_id = submit_namelist(
            args.submit, args.namelist,
            sweeps=parse_sweep_args(args.sweep),
            solver=args.solver or "", ndim=args.ndim, dtype=args.dtype,
            kind="calibrate" if args.calibrate else "run")
        print(job_id)
        return 0
    if args.obs:
        # artifacts-only observability: no jobs run, no devices touched
        # — consumers hit the queue dir's records/telemetry/checkpoints
        import time as _time

        from ramses_tpu.obs.server import ObsServer
        port = 9100 if args.obs_port is None else args.obs_port
        srv = ObsServer(args.obs, port=port, bind=args.obs_bind,
                        log=print if args.verbose else None).start()
        print(f"obs: serving {srv.root} on {srv.url} (Ctrl-C to stop)",
              flush=True)
        try:
            while True:
                _time.sleep(3600.0)
        except KeyboardInterrupt:
            pass
        finally:
            srv.close()
        return 0
    if args.serve:
        from ramses_tpu.ensemble.service import serve
        counts = serve(args.serve, worker=args.worker_id,
                       max_jobs=args.max_jobs, idle_exit=args.idle_exit,
                       stale_s=args.stale_timeout,
                       max_attempts=max(1, args.max_attempts),
                       verbose=args.verbose, order=args.claim_order,
                       obs_port=args.obs_port, obs_bind=args.obs_bind)
        print(f"serve: done={counts['done']} failed={counts['failed']}")
        return 1 if counts["failed"] else 0
    if not args.namelist:
        ap.error("a namelist is required (or use --serve/--submit)")
    run(args)
    return 0


def run(args):
    """Run the namelist ``args`` (a parsed :func:`build_parser` namespace)
    names to its end and return the simulation object (``None`` for
    the calibration and ensemble front-ends, which own no single
    sim) — :func:`main` minus the process exit code, so a caller can
    inspect the state the command line would have produced."""
    import jax.numpy as jnp

    from ramses_tpu.config import load_params

    dtype = getattr(jnp, args.dtype)
    params = load_params(args.namelist, ndim=args.ndim)

    # persistent compile cache (&RUN_PARAMS compile_cache_dir): must
    # land before the first trace
    from ramses_tpu.platform import setup_compile_cache
    setup_compile_cache(params)

    # &OUTPUT_PARAMS obs_port: a solo run serves its own output dir
    # over HTTP as pseudo-job "run" — telemetry tail + artifact files,
    # same endpoints as the fleet server (daemon thread, dies with the
    # process)
    if params.output.obs_port:
        import os as _os

        from ramses_tpu.obs.server import ObsServer
        _os.makedirs(params.output.output_dir, exist_ok=True)
        obs_srv = ObsServer(params.output.output_dir,
                            port=params.output.obs_port,
                            bind=params.output.obs_bind).start()
        print(f"obs: serving {params.output.output_dir} "
              f"on {obs_srv.url}")

    if params.run.debug_nan:
        # jit-level NaN trap (SURVEY.md §5.2): every compiled program
        # re-checks outputs and raises AT the producing op — the
        # runtime analogue of the reference's FPE-trapping debug build
        import jax
        jax.config.update("jax_debug_nans", True)

    if args.patch:
        from ramses_tpu import patch
        patch.install(args.patch, verbose=True)

    solver = args.solver
    if solver is None:
        solver = ("mhd" if any(params.init.A_region) or
                  any(params.init.B_region) or any(params.init.C_region)
                  else "hydro")

    def make_guard(sim):
        from ramses_tpu.utils.ops import OpsGuard
        return OpsGuard(sim, params.output.output_dir,
                        walltime_s=(args.walltime * 3600.0
                                    if args.walltime else None))

    # Supervised retry-with-resume (ramses_tpu/resilience): every branch
    # is phrased as build(restart_dir)/drive(sim) and routed through the
    # supervisor, which resolves nrestart/auto_resume on attempt 1 and
    # rebuilds from the newest manifest-valid checkpoint on later ones.
    if args.auto_resume:
        params.run.auto_resume = True

    # --calibrate (or &CALIBRATION_PARAMS calibrate=.true.): the
    # namelist describes an *inverse* problem — fit IC/EOS parameters
    # to a target rollout by gradient descent through the
    # differentiable step chain (ramses_tpu/diff), resumable from
    # optimizer-state checkpoints like any forward run
    if args.calibrate or params.calibration.calibrate:
        from ramses_tpu.diff.calibrate import run_calibration_job
        res = run_calibration_job(params, dtype=dtype,
                                  base_dir=params.output.output_dir)
        best = (f"gamma_best={res['gamma_best']:.6g} "
                if "gamma_best" in res else "")
        print(f"calibrate: {res['iterations']} iters "
              f"(resumed at {res['start_iter']}) "
              f"nmember={res['nmember']} "
              f"quarantined={res['quarantined']} "
              f"loss {res['loss_first']:.4e} -> "
              f"{res['loss_final']:.4e} "
              f"{best}-> {res['checkpoint']}")
        return None

    supervised = (args.max_attempts > 1 or params.run.auto_resume
                  or params.run.nrestart == -1)
    attempts = max(2, args.max_attempts) if supervised else 1

    def launch(build, drive, tend=None):
        from ramses_tpu.resilience import supervisor as rsup
        return rsup.supervise(build, drive, params,
                              base_dir=params.output.output_dir,
                              max_attempts=attempts, tend=tend)

    # &ENSEMBLE_PARAMS nmember > 1: the whole namelist is an ensemble —
    # one compiled program advances every member (ramses_tpu/ensemble)
    if params.ensemble.nmember > 1:
        from ramses_tpu.ensemble.batch import EnsembleEngine, EnsembleSpec
        spec = EnsembleSpec.from_params(params, solver=args.solver or "")

        def build(restart):
            if restart:
                return EnsembleEngine.from_checkpoint(spec, restart,
                                                      dtype=dtype)
            return EnsembleEngine(spec, dtype=dtype)

        eng = launch(build, lambda e: e.run(verbose=args.verbose))
        snap = eng.save(params.output.output_dir)
        print(f"ensemble: {eng.nmember} members "
              f"{len(eng.groups)} compile groups t_min={eng.t:.5e} "
              f"nstep_max={eng.nstep} "
              f"quarantined={eng.quarantined_count} -> {snap}")
        for k, info in sorted(eng.quarantined.items()):
            print(f"ensemble: member {k} quarantined: "
                  f"{info.get('reason')} at nstep={info.get('nstep')} "
                  f"t={info.get('t')}")
        eng.telemetry.close(eng)
        return None

    def drive_amr(tend):
        def drive(sim):
            guard = make_guard(sim)
            guard.run_guarded(lambda: sim.evolve(
                tend, nstepmax=params.run.nstepmax,
                verbose=args.verbose, guard=guard))
        return drive

    if solver == "rhd":
        if args.amr or params.amr.levelmax > params.amr.levelmin:
            from ramses_tpu.rhd.amr import RhdAmrSim
            tend = (params.output.tout[-1] if params.output.tout
                    else params.output.tend)
            sim = launch(
                lambda restart: (
                    RhdAmrSim.from_checkpoint_dir(params, restart,
                                                  dtype=dtype)
                    if restart else RhdAmrSim(params, dtype=dtype)),
                drive_amr(tend), tend=tend)
            print(f"rhd-amr t={sim.t:.5e} nstep={sim.nstep} "
                  f"lor_max={sim.max_lorentz():.3f} "
                  f"octs={[sim.tree.noct(l) for l in sim.levels()]}")
            sim.dump(1, params.output.output_dir,
                     namelist_path=args.namelist)
        else:
            from ramses_tpu.rhd.driver import RhdSimulation

            def drive(sim):
                guard = make_guard(sim)
                guard.run_guarded(lambda: sim.evolve(
                    nstepmax=params.run.nstepmax, verbose=args.verbose,
                    guard=guard))

            sim = launch(
                lambda restart: (
                    RhdSimulation.from_snapshot(params, restart,
                                                dtype=dtype)
                    if restart else RhdSimulation(params, dtype=dtype)),
                drive)
            sim.dump(1, params.output.output_dir,
                     namelist_path=args.namelist)
    elif solver == "mhd":
        if args.amr or params.amr.levelmax > params.amr.levelmin:
            from ramses_tpu.mhd.amr import MhdAmrSim
            tend = (params.output.tout[-1] if params.output.tout
                    else params.output.tend)
            sim = launch(
                lambda restart: (
                    MhdAmrSim.from_checkpoint_dir(params, restart,
                                                  dtype=dtype)
                    if restart else MhdAmrSim(params, dtype=dtype)),
                drive_amr(tend), tend=tend)
            print(f"mhd-amr t={sim.t:.5e} nstep={sim.nstep} "
                  f"max|divB|/max|B|*dx={sim.max_divb():.3e}")
            sim.dump(1, params.output.output_dir,
                     namelist_path=args.namelist)
        else:
            from ramses_tpu.mhd.driver import MhdSimulation

            def drive(sim):
                guard = make_guard(sim)
                guard.run_guarded(lambda: sim.evolve(
                    nstepmax=params.run.nstepmax, verbose=args.verbose,
                    guard=guard))

            sim = launch(
                lambda restart: (
                    MhdSimulation.from_snapshot(params, restart,
                                                dtype=dtype)
                    if restart else MhdSimulation(params, dtype=dtype)),
                drive)
            sim.dump(1, params.output.output_dir,
                     namelist_path=args.namelist)
    elif args.amr or params.amr.levelmax > params.amr.levelmin:
        def build(restart):
            if restart:
                return build_amr_sim(params, dtype, restart=restart)
            particles = None
            dense = None
            if (params.run.cosmo and params.init.initfile
                    and params.init.filetype in ("grafic", "gadget")):
                from ramses_tpu.driver import load_cosmo_ics
                from ramses_tpu.hydro.core import HydroStatic
                from ramses_tpu.pm.cosmology import Cosmology
                cosmo = Cosmology.from_params(params)
                n = 2 ** params.amr.levelmin
                particles, dense = load_cosmo_ics(
                    params, cosmo, HydroStatic.from_params(params),
                    (n,) * params.ndim)
            return build_amr_sim(params, dtype, particles=particles,
                                 init_dense_u=dense)

        def amr_tend(sim):
            if sim.cosmo is not None and params.output.aout:
                return float(sim.cosmo.tau_of_aexp(
                    min(params.output.aout[-1], 1.0)))
            return (params.output.tout[-1] if params.output.tout
                    else params.output.tend)

        def drive(sim):
            guard = make_guard(sim)
            guard.run_guarded(lambda: sim.evolve(
                amr_tend(sim), nstepmax=params.run.nstepmax,
                verbose=args.verbose, guard=guard))

        sim = launch(build, drive)
        if sim.cosmo is not None:
            print(f"cosmo-amr aexp={sim.aexp_now():.4f} nstep={sim.nstep} "
                  f"octs={[sim.tree.noct(l) for l in sim.levels()]}")
        sim.dump(1, params.output.output_dir, namelist_path=args.namelist)
    else:
        from ramses_tpu.driver import Simulation

        def build(restart):
            sim = (Simulation.from_snapshot(params, restart, dtype=dtype)
                   if restart else Simulation(params, dtype=dtype))
            sim.on_output = lambda s, i: s.dump(
                i, namelist_path=args.namelist)
            return sim

        def drive(sim):
            guard = make_guard(sim)
            guard.run_guarded(lambda: sim.evolve(verbose=args.verbose,
                                                 guard=guard))

        sim = launch(build, drive)
    # run-footer + output_timer breakdown (telemetry also closes via
    # atexit, but a clean exit should flush before the interpreter
    # teardown races the JSONL file handle)
    tel = getattr(sim, "telemetry", None)
    if tel is not None:
        tel.close(sim)
    from ramses_tpu.telemetry import screen
    kernel = screen.sweep_kernel(sim)
    # hydro runs, and a driver that names its kernel (the uniform MHD run)
    if solver == "hydro" or kernel != "pallas_muscl":
        print(screen.kernel_line(screen.sweep_blocks(kernel), kernel))
    return sim


if __name__ == "__main__":
    from ramses_tpu.resilience.watchdog import (HANG_EXIT_CODE,
                                                HangDetected)
    try:
        sys.exit(main())
    except HangDetected as e:
        # hang budget exhausted: exit with the dedicated status so a
        # parent (batch system, bench subprocess capture) classifies
        # hang vs crash without parsing logs
        print(f"ramses_tpu: unrecoverable hang: {e}", file=sys.stderr)
        sys.exit(HANG_EXIT_CODE)
