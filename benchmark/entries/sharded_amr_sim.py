"""Entry adapter: the AMR path of ``python -m ramses_tpu`` on a host with
several chips - ``ramses_tpu.__main__.build_amr_sim`` over the first
``mesh_devices`` devices, which builds
``ramses_tpu.parallel.amr_sharded.ShardedAmrSim``.  The one place in the
benchmark that knows the sharded class.

Everything a slice, a lap, the held slice and the comparison are is
``entries/amr_sim.Entry``'s (read its docstring first).  This adapter
differs where the decomposition makes it:

* the simulation is built by the program's own function over named
  devices, and the slab decomposition it chose for the complete level is
  held against the configuration's ``slab`` group;
* ``rewind`` puts the marked level state back with the simulation's own
  placement (``sim._place(.., "cells")``: rows over the mesh), not a bare
  ``jnp.asarray`` (one device);
* every level's state and tables are asserted to span all the devices
  after every slice (``chip_smoke.py::assert_spans``), and the rows to be
  in tree order (no load-balance layout), which ``_decode`` assumes;
* the per-level formulations are the program's own
  (``AmrSim.level_formulations``: slab grid, halo backend, per-shard
  kernel, XLA tiles), so ``kernel_cell_updates`` counts the complete
  level only while its per-shard fused kernel runs, and a traced run
  counts the two kinds of custom call of the compiled coarse step by
  name;
* under a profiler session the window's first slice is held too, because
  closing a session over four device planes can outlast the window
  (``run_slice``);
* ``mark`` (end of warm-up) traces the coarse-step and the flags
  programs once more at the window's shapes and prints
  ``dma_halo.traffic_snapshot()`` beside ``harness/halo_work.py``'s count
  of the same bytes.

On a program without ``build_amr_sim`` (the parent of the PR that added
it) the import below fails at once: the cell cannot run there.
"""

import re

import jax
import jax.numpy as jnp

from benchmark.entries import amr_sim
from benchmark.harness import halo_work, work
from ramses_tpu.__main__ import build_amr_sim

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


class Entry(amr_sim.Entry):
    def __init__(self, config, traffic, params):
        self.ndev = int(config["mesh_devices"])
        devices = jax.devices()[:self.ndev]
        if len(devices) < self.ndev:
            raise RuntimeError(f"{len(devices)} device(s) visible, the "
                               f"configuration's mesh is {self.ndev}")
        self.sim = sim = build_amr_sim(params, jnp.float32,
                                       devices=devices)
        # a rehearsal shrinks the levels; the cell's level is the group's
        self.slab = dict(config["slab"], level=int(sim.lmin))
        assert getattr(sim, "ndev", 1) == self.ndev, type(sim).__name__
        # from here to the end of __init__: amr_sim.Entry.__init__, which
        # builds its simulation in its first line and so cannot be called
        self.slice_steps = int(traffic["slice_steps"])
        self.lap_steps = int(traffic.get("lap_steps", 0))
        self.pre = self.mid = self.out = None
        self.holding = False
        self.base = None
        self.first_lap = {}
        self.updates = self.kernel_updates = self.regrids = 0
        self._kernel_levels = None
        self.regrid0, self.step0 = sim.regrid, sim.step_coarse

        def regrid():
            if self.holding:
                self.pre = self._snap()
            with jax.profiler.TraceAnnotation("bench/regrid"):
                self.regrid0()
            self.regrids += 1
            self._kernel_levels = None

        def step_coarse(dt):
            if self.holding:
                self.mid = self._snap()
            per = {l: sim.tree.noct(l) for l in sim.levels()}
            self.updates += work.amr_cell_updates(per, sim.lmin,
                                                  sim.cfg.ndim)
            kl = self.kernel_levels()
            self.kernel_updates += work.amr_cell_updates(
                {l: n for l, n in per.items() if l in kl}, sim.lmin,
                sim.cfg.ndim)
            with jax.profiler.TraceAnnotation("bench/step"):
                self.step0(dt)

        sim.regrid, sim.step_coarse = regrid, step_coarse
        self._check_slab()

    # -- the decomposition --------------------------------------------------
    def _slab_of(self):
        """The program's SlabSpec of the complete base level."""
        spec = self.sim._fused_spec()
        return spec.slab[0] if spec.slab else None

    def _kernel_axes(self):
        from ramses_tpu.hydro import pallas_muscl as pk
        sim, sl = self.sim, self._slab_of()
        return pk.shard_axes(sim.cfg, sl.loc,
                             tuple(p is not None for p in sl.perms),
                             sim.dtype)

    def _check_slab(self):
        sl = self._slab_of()
        if sl is None or list(sl.grid) != list(self.slab["grid"]):
            raise RuntimeError(
                f"the program's slab decomposition of level "
                f"{self.sim.lmin} is {sl and sl.grid}, the configuration "
                f"records {self.slab['grid']}")
        kax = self._kernel_axes()
        if kax is not None and list(kax) != list(self.slab["kernel_axes"]):
            raise RuntimeError(f"per-shard kernel axes {kax}, the "
                               f"configuration records "
                               f"{self.slab['kernel_axes']}")

    def assert_spans(self):
        """Every level's state and device tables span all the devices;
        rows are in tree order (what ``_decode`` reads them as)."""
        sim = self.sim
        assert not sim.layouts, "a load-balance layout permutes the rows"
        for l in sim.levels():
            arrays = [(f"u[{l}]", sim.u[l])] + [
                (f"dev[{l}][{k}]", v) for k, v in sim.dev[l].items()
                if isinstance(v, jax.Array)]
            for key, a in arrays:
                got = len(a.sharding.device_set)
                assert got == self.ndev, \
                    f"{key} spans {got} device(s), not {self.ndev}"

    # -- laps ------------------------------------------------------------
    def mark(self):
        super().mark()
        self._print_halo_traffic()

    def rewind(self):
        sim = self.sim
        attrs, u = self.base
        for k in set(vars(sim)) - set(attrs) - {"u"}:
            delattr(sim, k)
        for k, v in attrs.items():
            setattr(sim, k, self._shallow(v))
        sim.u = {l: sim._place(a, "cells") for l, a in u.items()}
        self._kernel_levels = None
        self.assert_spans()

    def run_slice(self, hold=False):
        # Closing a profiler session over four device planes takes ~20 s
        # of a 30 s window (one plane: ~5 s), so a traced window can end
        # before the slice the seed draws (my chip run, PR 27, call 1: a
        # traced run ended with nothing held).  While a session is on, the
        # window's first slice is held as well; the seed's slice replaces
        # it if the window gets that far.  Untraced runs hold one slice.
        if (not hold and self.base is not None and self.out is None
                and jax.profiler.TraceAnnotation.is_enabled()):
            hold = True
        r = super().run_slice(hold)
        self.assert_spans()
        return r

    # -- what ran ----------------------------------------------------------
    def _level_forms(self):
        return self.sim._fused_spec(), self.sim.level_formulations()

    def _traced_traffic(self):
        """``dma_halo.traffic_snapshot()`` of the coarse-step program and
        of the flags program, each traced afresh at the current shapes
        (their jitted twins are traced once and would count nothing)."""
        from ramses_tpu.amr import hierarchy as H
        from ramses_tpu.parallel import dma_halo
        sim = self.sim
        spec = sim._fused_spec()
        r = sim.params.refine
        eg = (float(r.err_grad_d), float(r.err_grad_u), float(r.err_grad_p))
        fls = (float(r.floor_d), float(r.floor_u), float(r.floor_p))
        out = []
        for fn in (
            lambda u, dev: H._fused_coarse_step.__wrapped__(
                u, dev, {}, jnp.asarray(sim.dt_old, sim.dtype), spec,
                sim._cool_bundle()),
            lambda u, dev: H._fused_flags.__wrapped__(
                u, dev, spec, eg, fls, int(r.interpol_type))):
            dma_halo.reset_traffic()
            jax.eval_shape(fn, sim.u, sim.dev)
            out.append(dma_halo.traffic_snapshot())
        return out

    def _print_halo_traffic(self):
        kernel = self._kernel_axes() is not None
        step, flags = self._traced_traffic()
        want_s = halo_work.sweep_traffic(self.slab, kernel)
        want_f = halo_work.flags_traffic(self.slab)
        n = int(self.slab["sweeps_per_coarse_step"])
        ok = (step["halo_bytes"] == n * want_s["bytes"]
              and step["halo_exchanges"] == n * want_s["slabs"]
              and flags["halo_bytes"] == want_f["bytes"]
              and flags["halo_exchanges"] == want_f["slabs"])
        print(f"[halo] traffic_snapshot, a device: coarse-step program "
              f"{step}; flags program {flags}; halo_work counts "
              f"{n} x {want_s} and {want_f} "
              f"(per-shard kernel {'on' if kernel else 'off'}): "
              + ("the same" if ok else
                 "THEY DIFFER - halo_dma_roofline_pct divides by a wrong "
                 "byte count"), flush=True)
        self.halo_count_ok = ok

    def formulations(self, count_calls=False):
        """[(label, text, on its kernel)] from the program's gates; with
        ``count_calls`` (traced runs: it lowers and compiles the step
        program once more) also the custom calls of the compiled coarse
        step BY NAME: the per-shard kernel's and the halo exchange's."""
        from ramses_tpu.amr import hierarchy as H
        from ramses_tpu.hydro import pallas_muscl as pk
        from ramses_tpu.parallel import dma_halo
        sim = self.sim
        spec, forms = self._level_forms()
        out = [(f"level {l}", name, k) for l, name, k in forms]
        if not count_calls:
            return out
        txt = H._fused_coarse_step.lower(
            sim.u, sim.dev, {}, jnp.asarray(sim.dt_old, sim.dtype), spec,
            sim._cool_bundle()).compile().as_text()
        calls = [m.group(1) for m in re.finditer(
            r"%([A-Za-z_][\w\-]*?)(?:\.\d+)? = [^\n]*" + re.escape(
                CUSTOM_CALL), txt)]
        n_kernel = calls.count(pk.SHARD_KERNEL_NAME)
        n_halo = calls.count(dma_halo.KERNEL_NAME)
        want_kernel = sum(1 << (l - spec.lmin) for l, _, k in forms if k)
        sl = self._slab_of()
        dma = sl is not None and sl.backend == "dma"
        want_halo = (halo_work.sweep_traffic(
            self.slab, self._kernel_axes() is not None)["slabs"] // 2
            * int(self.slab["sweeps_per_coarse_step"])) if dma else 0
        out.append(("coarse-step program",
                    f"tpu_custom_calls={len(calls)}: "
                    f"{pk.SHARD_KERNEL_NAME}={n_kernel} (expected "
                    f"{want_kernel} from the gates), "
                    f"{dma_halo.KERNEL_NAME}={n_halo} (expected "
                    f"{want_halo}: one a cut axis of each halo extension)",
                    n_kernel == want_kernel and n_halo == want_halo))
        return out
