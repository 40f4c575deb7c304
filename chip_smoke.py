#!/usr/bin/env python3
"""Quickest proof that ramses_tpu still starts on the chip.

One process, one chip (``python chip_smoke.py``): drives the system's
main path — the ``python -m ramses_tpu <namelist>`` entry — once on the
two headline Sedov configurations and checks the result by the repo's
own means (conservation audit, finite state, the step programs really
hold the Pallas kernels):

* uniform: ``namelists/sedov3d.nml`` as committed (256³ f32, 10 steps);
* AMR: ``namelists/sedov3d_amr.nml`` (levels 7→9, regrid every step).

Then the uniform MHD run (``benchmark/configs/mhd-blast3d-uniform-256.nml``,
256³ f32, 10 steps, the same entry) and the parity of its tiled CT kernel
with the XLA step it re-spells (``mhd/uniform.step``), at 128³ on the
chip: the sandbox can only hold the two together interpreted.

``--chips 4`` runs ONLY the sharded path and its comparison
(``ShardedSim`` over four devices against one; the AMR side through
``ramses_tpu.__main__.build_amr_sim``, the command line's own choice:
``ShardedAmrSim`` over four devices against ``AmrSim`` over one, in this
process).

No accelerator ⇒ non-zero exit and no result line; never selects a
platform, never falls back to the CPU.  ``--rehearse`` is the sandbox
rehearsal (tiny levels, Pallas kernels interpreted, whatever backend
``JAX_PLATFORMS`` gives): it exercises the control flow and can never
print an ``"ok": true`` line.  Any phase that raises ends the process
non-zero; nothing here catches a failure and carries on.

Everything the run writes lands under ``<checkout>/chip_out/`` (the
compile cache under ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise).  The seconds printed are
smoke facts of one cold or warm start, not benchmark numbers.
"""

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_out")
NML_UNI = os.path.join(ROOT, "namelists", "sedov3d.nml")
NML_AMR = os.path.join(ROOT, "namelists", "sedov3d_amr.nml")
NML_MHD = os.path.join(ROOT, "benchmark", "configs",
                       "mhd-blast3d-uniform-256.nml")
KERNEL = 'custom_call_target="tpu_custom_call"'

# f32 state, totals audited in f64.  Early Sedov keeps nearly all the
# energy in a handful of blast cells, so their f32 rounding does not
# average out: the sandbox rehearsal measured ~1.4e-7 relative drift per
# finest-level substep, and 12 coarse steps of levels 7->9 make 48 of
# them (~7e-6).  1e-4 is one order above that; a lost coarse-fine flux
# correction or a dropped level shows at >= 1e-3.
CONS_RTOL = 1e-4
# sharded vs one device: same f32 inputs and arithmetic; the partitioner's
# fusion/reduction order differs and the one-device side runs the Pallas
# kernels (the gates ask what the simulation spans, not the host), so
# the states agree to a few ulp per step.  L1(diff)/L1(ref) over <= 12
# steps stays below 1e-5; a wrong halo or a dropped shard is O(1).
SHARD_L1_RTOL = 1e-5
# the tiled CT kernel against ``mu.step``: one expression graph spelt
# twice.  On the chip the two have agreed to the bit (PERF.md, PR 34);
# what is held is a few f32 ulps of the largest value (the interpreted
# rehearsal on the CPU differs by FMA contraction, 7e-7).  A floor, a
# sign or a stencil offset that drifts between the two is >= 1e-3.
CT_PARITY_TOL = 2e-6


def say(msg):
    print(msg, flush=True)


def rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-300)


class Phase:
    """Wall/compile/cache/memory facts of one phase, printed on exit
    from a clean ``with`` block (a raising phase prints nothing more
    and ends the process)."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        from ramses_tpu.platform import compile_cache_stats
        self.c0 = compile_cache_stats()
        self.t0 = time.perf_counter()
        say(f"[{self.name}] start")
        return self

    def __exit__(self, etype, exc, tb):
        if etype is not None:
            return False
        import jax
        from ramses_tpu.platform import compile_cache_stats
        wall = time.perf_counter() - self.t0
        c1 = compile_cache_stats()
        comp = c1["compile_s"] - self.c0["compile_s"]
        stats = jax.devices()[0].memory_stats() or {}
        say(f"[{self.name}] ok wall_s={wall:.2f} "
            f"first_call_compile_s={comp:.2f} rest_s={wall - comp:.2f} "
            f"cache_dir={c1['dir'] or '(off)'} "
            f"cache_hits={c1['hits'] - self.c0['hits']} "
            f"cache_misses={c1['misses'] - self.c0['misses']} "
            f"peak_device_bytes={stats.get('peak_bytes_in_use')}")
        return False


def shrunk(nml, lmin, lmax, nstep):
    """Rehearsal copy of a committed namelist at tiny levels."""
    txt = open(nml).read()
    txt = re.sub(r"levelmin=\d+", f"levelmin={lmin}", txt)
    txt = re.sub(r"levelmax=\d+", f"levelmax={lmax}", txt)
    txt = re.sub(r"nstepmax=\d+", f"nstepmax={nstep}", txt)
    dst = os.path.join(OUT, "rehearse_" + os.path.basename(nml))
    with open(dst, "w") as f:
        f.write(txt)
    return dst


def run_cli(nml, *more):
    """The command line's own path: parse like ``python -m ramses_tpu
    <nml> --ndim 3`` and hand back the sim it ran."""
    from ramses_tpu.__main__ import build_parser, run
    return run(build_parser().parse_args([nml, "--ndim", "3", *more]))


def check_finite(name, arrays):
    import jax.numpy as jnp
    for key, a in arrays:
        assert bool(jnp.isfinite(a).all()), f"{name}: non-finite {key}"


# ----------------------------------------------------------------------
# one-chip phases
# ----------------------------------------------------------------------
def phase_native():
    from ramses_tpu import native
    L = native.lib()
    assert L is not None, f"native build failed: {native.build_error}"
    say(f"[native] ok built from ramses_tpu/native/src/ramses_native.cpp "
        f"-> {os.path.relpath(native.so_path(), ROOT)}")
    # the tile tables of a blocked level both ways: a machine without
    # the one-pass builder must fail here, not measure numpy
    import numpy as np
    from ramses_tpu.amr import maps as mapmod
    from ramses_tpu.amr.tree import Octree
    tree = Octree.base(3, 4, 6)
    for lvl, r in ((5, 5.5), (6, 4.5)):
        # a ball on the reflecting x and the outflow z face, clear of
        # the periodic y faces (graded on every face as it stands)
        og = np.indices((1 << (lvl - 1),) * 3).reshape(3, -1).T
        d = og - np.array([1.5, 1 << (lvl - 2), 1.5])
        tree.set_level(lvl, og[(d * d).sum(axis=1) < r * r])
    bc = [(1, 1), (0, 0), (2, 2)]
    for lvl in (5, 6):
        b = mapmod.build_block_maps(tree, lvl, bc)
        os.environ["RAMSES_TPU_NATIVE"] = "0"
        try:
            a = mapmod.build_block_maps(tree, lvl, bc)
        finally:
            del os.environ["RAMSES_TPU_NATIVE"]
        assert a.tiles_native == 0 and b.tiles_native == b.ntile > 0, \
            (a.tiles_native, b.tiles_native, b.ntile)
        for f in mapmod.BLOCK_TABLES:
            x, y = getattr(a, f), getattr(b, f)
            assert x.dtype == y.dtype and np.array_equal(x, y), (lvl, f)
        say(f"[native] tile tables level {lvl}: {b.ntile} tiles, {b.ni} "
            f"interpolation rows, native == numpy")


def phase_uniform(nml, rehearse):
    import jax
    import jax.numpy as jnp
    from ramses_tpu.config import load_params
    from ramses_tpu.driver import Simulation
    from ramses_tpu.grid import uniform

    with Phase("uniform"):
        params = load_params(nml, ndim=3)
        tot0 = Simulation(params, dtype=jnp.float32).totals()
        m0, e0 = float(tot0["mass"]), float(tot0["energy"])
        sim = run_cli(nml)
        st, grid = sim.state, sim.grid
        n = grid.shape[0]
        assert st.nstep == params.run.nstepmax, st.nstep
        assert st.u.dtype == jnp.float32 and st.u.shape == (5, n, n, n)
        check_finite("uniform", [("u", st.u)])
        tot = sim.totals()
        dm, de = rel(tot["mass"], m0), rel(tot["energy"], e0)
        say(f"[uniform] {n}^3 f32 nstep={st.nstep} t={st.t:.6e} "
            f"mass_rel_err={dm:.3e} energy_rel_err={de:.3e} "
            f"(tol {CONS_RTOL:g})")
        assert dm < CONS_RTOL and de < CONS_RTOL
        # the step program the run used, recompiled from its own
        # arguments (a persistent-cache hit): it must hold the kernel
        tdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        txt = uniform.run_steps.lower(
            grid, st.u, jnp.asarray(st.t, tdt),
            jnp.asarray(sim.tend, tdt), st.nstep).compile().as_text()
        ncall = txt.count(KERNEL)
        fused = uniform._pallas_ok(grid, st.u.dtype)
        say(f"[uniform] step program: "
            f"{'fused Pallas kernel' if fused else 'XLA formulation'}, "
            f"tpu_custom_calls={ncall}")
        if not rehearse:
            assert fused and ncall >= 1, \
                "uniform step program holds no fused Pallas kernel"


def phase_amr(nml, rehearse):
    import jax.numpy as jnp
    from ramses_tpu.amr import hierarchy as H
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import load_params

    with Phase("amr"):
        params = load_params(nml, ndim=3)
        lmax = params.amr.levelmax
        sim0 = AmrSim(params, dtype=jnp.float32)
        tot0 = sim0.totals()
        octs0 = {l: sim0.tree.noct(l) for l in sim0.levels()}
        del sim0
        sim = run_cli(nml)
        octs = {l: sim.tree.noct(l) for l in sim.levels()}
        say(f"[amr] levels {params.amr.levelmin}->{lmax} f32 "
            f"nstep={sim.nstep} t={sim.t:.6e} regrid_interval="
            f"{sim.regrid_interval} octs initial={octs0} final={octs}")
        assert sim.nstep == params.run.nstepmax, sim.nstep
        assert octs.get(lmax, 0) > 0, f"level {lmax} never populated"
        # a regrid runs before every coarse step; the blast must have
        # moved the refined shell at least once for it to count
        assert sim.regrid_interval == 1 and sim.nstep >= 2
        assert octs != octs0, "no regrid changed the tree"
        bst = sim.block_stats
        say(f"[amr] tile tables: tiles_native={bst['tiles_native']} of "
            f"blocks_total={bst['blocks_total']}")
        assert bst["tiles_native"] == bst["blocks_total"] > 0, bst
        check_finite("amr", [(f"u[{l}]", sim.u[l]) for l in sim.levels()])
        tot = sim.totals()
        dm, de = rel(tot[0], tot0[0]), rel(tot[4], tot0[4])
        say(f"[amr] mass_rel_err={dm:.3e} energy_rel_err={de:.3e} "
            f"(tol {CONS_RTOL:g})")
        assert dm < CONS_RTOL and de < CONS_RTOL
        # the coarse-step program of the final tree, recompiled from
        # the sim's own arguments: one kernel call per level substep
        # (level lmin+i is swept 2^i times per coarse step)
        forms = sim.level_formulations()
        spec = sim._fused_spec()
        txt = H._fused_coarse_step.lower(
            sim.u, sim.dev, {}, jnp.asarray(sim.dt_old, sim.dtype), spec,
            sim._cool_bundle()).compile().as_text()
        ncall = txt.count(KERNEL)
        want = sum(1 << (l - spec.lmin) for l, _, k in forms if k)
        for l, name, _ in forms:
            say(f"[amr] level {l}: {name}")
        say(f"[amr] coarse-step program: tpu_custom_calls={ncall} "
            f"(expected {want} from the gates)")
        if not rehearse:
            assert ncall == want and all(k for _, _, k in forms), \
                "a level of the default Sedov path fell off its kernel"


# ----------------------------------------------------------------------
# four-chip phases (builder-run): sharded path vs one device
# ----------------------------------------------------------------------
def phase_mhd(nml, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd import pallas_ct as pc
    from ramses_tpu.mhd import uniform as mu
    from ramses_tpu.mhd.core import MhdStatic
    from ramses_tpu.mhd.driver import MhdSimulation, mhd_condinit

    with Phase("mhd"):
        params = load_params(nml, ndim=3)
        def totals(u):          # f64 on the host: rows 0 (mass), 4 (energy)
            return [float(np.asarray(u[k], np.float64).sum())
                    for k in (0, 4)]

        m0, e0 = totals(MhdSimulation(params, dtype=jnp.float32).u)
        sim = run_cli(nml, "--solver", "mhd", "--dtype", "float32")
        n = sim.grid.shape[0]
        assert sim.nstep == params.run.nstepmax, sim.nstep
        assert sim.u.dtype == jnp.float32 and sim.u.shape == (8, n, n, n)
        check_finite("mhd", [("u", sim.u), ("bf", sim.bf)])
        m1, e1 = totals(sim.u)
        dm, de = rel(m1, m0), rel(e1, e0)
        divb = sim.divb()
        say(f"[mhd] {n}^3 f32 nstep={sim.nstep} t={sim.t:.6e} "
            f"mass_rel_err={dm:.3e} energy_rel_err={de:.3e} "
            f"(tol {CONS_RTOL:g}) divb={divb:.3e}")
        assert dm < CONS_RTOL and de < CONS_RTOL and divb < 1e-5
        tiled = mu.kernel_ok(sim.grid, sim.u.dtype)
        tdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        tend = float(params.output.tout[-1])
        ncall = mu.run_steps.lower(
            sim.grid, sim.u, sim.bf, jnp.asarray(sim.t, tdt),
            jnp.asarray(tend, tdt), 16).compile().as_text().count(KERNEL)
        say(f"[mhd] step program: "
            f"{'tiled Pallas CT kernel' if tiled else 'XLA formulation'}, "
            f"tpu_custom_calls={ncall}")
        if not rehearse:
            assert tiled and ncall == 1, \
                "uniform MHD step program holds no tiled CT kernel"

    with Phase("mhd-parity"):
        # two steps of the blast, each taken by both spellings from the
        # SAME input and dt: every cell row, every face, the next dt
        shape = (8, 8, 128) if rehearse else (128, 128, 128)
        dx = 1.0 / 128
        for key, ext in zip(("x_center", "y_center", "z_center"), shape):
            getattr(params.init, key)[1] = 0.5 * ext * dx
        cfg = MhdStatic.from_params(params)
        grid = mu.MhdGrid(cfg=cfg, shape=shape, dx=dx,
                          bc_kinds=((0, 0),) * 3)
        u, bf = (jnp.asarray(a, jnp.float32)
                 for a in mhd_condinit(shape, dx, params, cfg))
        u0, bf0 = u, bf
        worst = 0.0
        for _ in range(2):
            dt = mu.cfl_dt(grid, u, bf)
            un, bfn = mu._jit_step(grid, u, bf, dt)
            uh, bc, bfk, rate = pc.ct_step_tiled(
                pc.pad_xy(u[:pc.NHYDRO]), pc.pad_xy(bf), dt, cfg, dx,
                shape, interpret=rehearse)
            scale = float(jnp.max(jnp.abs(un)))
            gaps = [float(jnp.max(jnp.abs(jnp.concatenate([uh, bc]) - un))),
                    float(jnp.max(jnp.abs(bfk - bfn))),
                    abs(float(cfg.courant_factor / rate[0, 0])
                        - float(mu.cfl_dt(grid, un, bfn)))
                    / float(dt) * scale]
            worst = max([worst] + [g / scale for g in gaps])
            u, bf = un, bfn
        say(f"[mhd-parity] {'x'.join(map(str, shape))} kernel vs mu.step, "
            f"2 steps: largest gap {worst:.3e} of the largest value "
            f"(cells, faces, next dt; tol {CT_PARITY_TOL:g})")
        assert worst <= CT_PARITY_TOL, "the CT kernel left mu.step"
        # the vmapped step program (``run_steps_batch``: on the chip the
        # batch is a grid axis of the one kernel): each member of an
        # ensemble is its solo run
        t0, t1 = jnp.zeros(2, jnp.float32), jnp.ones(2, jnp.float32)
        ub, bfb, tb, nb = mu.run_steps_batch(
            grid, jnp.stack([u0, u]), jnp.stack([bf0, bf]), t0, t1, 4)
        for i, (us, bs) in enumerate(((u0, bf0), (u, bf))):
            us, bs, ts, ns = mu.run_steps(grid, us, bs, t0[i], t1[i], 4)
            scale = float(jnp.max(jnp.abs(us)))
            gap = max(float(jnp.max(jnp.abs(ub[i] - us))),
                      float(jnp.max(jnp.abs(bfb[i] - bs)))) / scale
            say(f"[mhd-parity] batch member {i} vs its solo run, 4 steps: "
                f"gap {gap:.3e} t {float(tb[i]):.6e} vs {float(ts):.6e}")
            assert int(nb[i]) == int(ns) == 4 and gap <= CT_PARITY_TOL \
                and abs(float(tb[i]) - float(ts)) <= 1e-6 * float(ts)


def assert_spans(name, arrays, ndev):
    for key, a in arrays:
        got = len(a.sharding.device_set)
        assert got == ndev, f"{name}: {key} spans {got} devices, not {ndev}"


def l1_rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).sum() / max(np.abs(b).sum(), 1e-300))


def phase_sharded_uniform(nml, devs):
    import jax.numpy as jnp
    import numpy as np
    from ramses_tpu.config import load_params
    from ramses_tpu.grid.uniform import _pallas_ok, totals
    from ramses_tpu.parallel.sharded import ShardedSim

    with Phase("sharded-uniform"):
        params = load_params(nml, ndim=3)
        nstep = params.run.nstepmax
        res = {}
        for nd in (len(devs), 1):
            sim = ShardedSim(params, devices=devs[:nd], dtype=jnp.float32)
            t0 = totals(sim.u, sim.inner.cfg, sim.inner.dx)
            m0, e0 = float(t0["mass"]), float(t0["energy"])
            sim.run(nstep, tend=sim.inner.tend)
            assert_spans(f"ShardedSim/{nd}", [("u", sim.u)], nd)
            check_finite(f"ShardedSim/{nd}", [("u", sim.u)])
            t1 = totals(sim.u, sim.inner.cfg, sim.inner.dx)
            dm, de = rel(t1["mass"], m0), rel(t1["energy"], e0)
            say(f"[sharded-uniform] {nd} device(s): mesh="
                f"{dict(sim.mesh.shape)} nstep={sim.nstep} "
                f"t={sim.t:.6e} mass_rel_err={dm:.3e} "
                f"energy_rel_err={de:.3e} fused_kernel_gate="
                f"{_pallas_ok(sim.grid, sim.u.dtype)} (the kernel gates "
                f"ask how many devices the simulation spans: {nd})")
            assert sim.nstep == nstep
            assert dm < CONS_RTOL and de < CONS_RTOL
            res[nd] = (np.asarray(sim.u), sim.t)
            del sim
        (u4, t4), (u1, t1) = res[len(devs)], res[1]
        d = l1_rel(u4, u1)
        say(f"[sharded-uniform] L1(u_{len(devs)}dev - u_1dev)/L1(u_1dev)"
            f"={d:.3e} dt_rel={rel(t4, t1):.3e} (tol {SHARD_L1_RTOL:g})")
        assert d < SHARD_L1_RTOL and rel(t4, t1) < SHARD_L1_RTOL


def phase_sharded_amr(nml, devs, nstep):
    import jax
    import jax.numpy as jnp
    from ramses_tpu.__main__ import build_amr_sim
    from ramses_tpu.config import load_params

    with Phase("sharded-amr"):
        params = load_params(nml, ndim=3)
        res = {}
        for nd in (len(devs), 1):
            # the command line's own choice of class: sharded over
            # several devices, plain AmrSim (on its kernels) over one
            sim = build_amr_sim(params, jnp.float32, devices=devs[:nd],
                                log=say)
            name = type(sim).__name__
            tot0 = sim.totals()
            sim.evolve(1e9, nstepmax=nstep)
            octs = {l: sim.tree.noct(l) for l in sim.levels()}
            arrays = [(f"u[{l}]", sim.u[l]) for l in sim.levels()]
            arrays += [(f"dev[{l}][{k}]", v) for l in sim.levels()
                       for k, v in sim.dev[l].items()
                       if isinstance(v, jax.Array)]
            assert_spans(f"{name}/{nd}", arrays, nd)
            check_finite(f"{name}/{nd}", arrays[:len(octs)])
            tot = sim.totals()
            dm, de = rel(tot[0], tot0[0]), rel(tot[4], tot0[4])
            say(f"[sharded-amr] {nd} device(s): nstep={sim.nstep} "
                f"t={sim.t:.6e} octs={octs} mass_rel_err={dm:.3e} "
                f"energy_rel_err={de:.3e}")
            for l, name, _ in sim.level_formulations():
                say(f"[sharded-amr] {nd} device(s) level {l}: {name}")
            assert sim.nstep == nstep
            assert dm < CONS_RTOL and de < CONS_RTOL
            res[nd] = (octs, sim.t, {l: sim.tree_order_cells(sim.u[l], l)
                                     for l in sim.levels()})
            del sim
        (o4, t4, u4), (o1, t1, u1) = res[len(devs)], res[1]
        assert o4 == o1, f"octs per level differ: {o4} vs {o1}"
        for l in sorted(u1):
            d = l1_rel(u4[l], u1[l])
            say(f"[sharded-amr] level {l}: L1 rel diff {d:.3e} "
                f"(tol {SHARD_L1_RTOL:g})")
            assert d < SHARD_L1_RTOL
        assert rel(t4, t1) < SHARD_L1_RTOL


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path and its "
                         "one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal at tiny levels; never "
                         "prints an ok line")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no accelerator — jax found "
              f"{d0.platform!r} ({len(devs)} device(s)); this script "
              f"never falls back to it", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    if args.chips == 1 and len(devs) != 1 and not args.rehearse:
        print(f"chip_smoke: the one-chip phases drive the command "
              f"line, which builds the sharded class over all "
              f"{len(devs)} devices it sees; use --chips 4 here",
              file=sys.stderr)
        return 2
    import ramses_tpu  # noqa: F401  (engages the compile-cache rule)
    os.makedirs(OUT, exist_ok=True)
    os.chdir(OUT)                   # snapshots/telemetry land here
    say(f"[device] platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} chips={args.chips} "
        f"rehearse={args.rehearse}")

    uni, amr, mhd, amr_steps = NML_UNI, NML_AMR, NML_MHD, 3
    if args.rehearse:
        from ramses_tpu.hydro import pallas_oct
        pallas_oct.FORCE_INTERPRET = True
        uni = shrunk(NML_UNI, 5, 5, 4)
        amr = shrunk(NML_AMR, 4, 6, 4)
        mhd = shrunk(NML_MHD, 5, 5, 4)
        amr_steps = 2
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_native()
        phase_uniform(uni, args.rehearse)
        phase_amr(amr, args.rehearse)
        phase_mhd(mhd, args.rehearse)
    else:
        phase_sharded_uniform(uni, devs[:4])
        phase_sharded_amr(amr, devs[:4], amr_steps)
    say(f"[total] wall_s={time.perf_counter() - t0:.2f}")
    if args.rehearse:
        say("rehearsal complete (not a chip run: no result line)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
