"""Entry adapter: ``ramses_tpu.amr.hierarchy.AmrSim`` (the AMR path of
``python -m ramses_tpu``).

A slice is ``sim.evolve(1e9, nstepmax=nstep + slice_steps)``: with
``regrid_interval == 1`` that is one ``regrid()`` and one coarse step
through ``evolve``'s own per-step path (``hierarchy.py:2075-2160``).  The
adapter wraps ``sim.regrid`` and ``sim.step_coarse`` FROM OUTSIDE (instance
attributes; the program is not edited) to put ``bench/regrid`` and
``bench/step`` host spans on the profiler's clock and to count the cell
updates of the tree each step really swept.

Laps.  The stretch of coarse steps in which no padded shape changes is
short (the mix's ``lap_steps``), so the window goes round it: ``mark``
keeps, at the end of set-up, the simulation's attributes (host objects and
the index arrays already on the device, by reference) and its level state
ON THE HOST; ``rewind`` puts them back and uploads the state again.  A lap
then repeats the first, bit for bit: same trees, same padded shapes, same
compiled programs, same ``t``; a lap that does not is counted
(``laps_off``) and fails the run.

The held slice.  For the one slice the window asks to ``hold``, the
wrappers copy the level state to the host before the regrid, before the
step and after it: three blocking device-to-host copies of ~50 MB, inside
the window, once a run.  Nothing of it stays on the device, so
``peak_hbm_bytes`` holds none of the yardstick's copies except the marked
index arrays of the partial levels (``held_device_bytes``)."""

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import work


class Entry:
    def __init__(self, config, traffic, params):
        from ramses_tpu.amr.hierarchy import AmrSim
        self.sim = sim = AmrSim(params, dtype=jnp.float32)
        self.slice_steps = int(traffic["slice_steps"])
        self.lap_steps = int(traffic.get("lap_steps", 0))
        self.pre = self.mid = self.out = None
        self.holding = False
        self.base = None
        self.first_lap = {}
        self.updates = self.kernel_updates = self.regrids = 0
        self._kernel_levels = None
        # the program's own methods; benchmark/tests plants faults here
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

    def _snap(self):
        """The tree (a regrid makes a new one, it never edits the old) and
        the level state, copied to the host."""
        sim = self.sim
        return {"tree": sim.tree, "nstep": int(sim.nstep), "t": float(sim.t),
                "dt_old": float(getattr(sim, "dt_old", 0.0)),
                "u": {l: np.asarray(sim.u[l]) for l in sim.levels()}}

    # -- laps ------------------------------------------------------------
    @staticmethod
    def _shallow(v):
        return copy.copy(v) if isinstance(v, (dict, list, set)) else v

    def mark(self):
        """End of set-up: the state every lap of the window starts from."""
        sim = self.sim
        self.base = ({k: self._shallow(v) for k, v in vars(sim).items()
                      if k != "u"},
                     {l: np.asarray(a) for l, a in sim.u.items()})

    def rewind(self):
        sim = self.sim
        attrs, u = self.base
        for k in set(vars(sim)) - set(attrs) - {"u"}:
            delattr(sim, k)
        for k, v in attrs.items():
            setattr(sim, k, self._shallow(v))
        sim.u = {l: jnp.asarray(a) for l, a in u.items()}
        self._kernel_levels = None

    def _lap_repeats(self):
        """Does the step just done repeat the first lap's, bit for bit?"""
        sim = self.sim
        seen = (float(sim.t), tuple(sim.tree.noct(l) for l in sim.levels()))
        first = self.first_lap.setdefault(int(sim.nstep), seen)
        return first == seen

    def held_device_bytes(self):
        """Device arrays the mark keeps alive that the simulation itself
        no longer holds (the partial levels' index arrays of the lap's
        first tree): the yardstick's share of ``peak_hbm_bytes``."""
        if self.base is None:
            return 0

        def arrays(d):
            return {id(a): a.nbytes for a in jax.tree_util.tree_leaves(
                {k: v for k, v in d.items() if k != "u"})
                if isinstance(a, jax.Array)}
        mine, live = arrays(self.base[0]), arrays(vars(self.sim))
        return sum(n for i, n in mine.items() if i not in live)

    # -- driving ---------------------------------------------------------
    def develop(self, nsteps, regrid_every=None):
        """Set-up only: bring the blast to the window's start.  With
        ``regrid_every`` the develop steps regrid at that cadence (fewer
        padded shapes to compile and keep cached); the namelist's own
        cadence is back before the warm-up slices and the window.  Prints
        where the time went: each fused chunk (``step_chunk`` returns once
        it has fetched ``t``, so its wall is the program's load and run)
        and the rest (regrids, first dispatches)."""
        sim = self.sim
        own, chunk0, chunks = sim.regrid_interval, sim.step_chunk, []

        def step_chunk(*a, **k):
            t0 = time.perf_counter()
            n = chunk0(*a, **k)
            chunks.append(time.perf_counter() - t0)
            return n

        if regrid_every:
            sim.regrid_interval = int(regrid_every)
        sim.step_chunk = step_chunk
        t0 = time.perf_counter()
        try:
            sim.evolve(1e9, nstepmax=sim.nstep + int(nsteps))
        finally:
            sim.regrid_interval = own
            del sim.step_chunk
        print(f"[develop] {time.perf_counter() - t0:.2f} s for {nsteps} "
              f"steps: fused chunks "
              + " ".join(f"{c:.2f}" for c in chunks) + " s", flush=True)

    def run_slice(self, hold=False):
        sim = self.sim
        if self.lap_steps and self.base is not None \
                and sim.nstep - self.base[0]["nstep"] >= self.lap_steps:
            self.rewind()
        n0, t0, u0, k0, r0 = sim.nstep, sim.t, self.updates, \
            self.kernel_updates, self.regrids
        self.holding = hold
        sim.evolve(1e9, nstepmax=n0 + self.slice_steps)
        if hold:
            self.out = self._snap()
            self.holding = False
        return {"asked": self.slice_steps, "done": sim.nstep - n0,
                "sim_time": float(sim.t) - float(t0),
                "cell_updates": self.updates - u0,
                "kernel_cell_updates": self.kernel_updates - k0,
                "regrids": self.regrids - r0,
                "laps_off": 0 if self.base is None or self._lap_repeats()
                else 1}

    def sync(self):
        self.sim.drain()

    def sim_time(self):
        return float(self.sim.t)

    # -- what ran ----------------------------------------------------------
    def _level_forms(self):
        """Copied from ``chip_smoke.py:174-214`` (``level_formulations``):
        the same gates, asked with the same arguments, as the traced step."""
        from ramses_tpu.hydro import pallas_muscl as pk
        from ramses_tpu.hydro import pallas_oct as po
        sim = self.sim
        spec = sim._fused_spec()
        cfg, dtype = spec.cfg, sim.dtype
        out = []
        for i, l in enumerate(spec.levels):
            if spec.complete[i]:
                root = spec.root or (1,) * cfg.ndim
                shape = tuple(r << l for r in root[:cfg.ndim])
                k = pk.kernel_available(cfg, shape, spec.bspec.faces, dtype)
                out.append((l, "dense fused kernel (pallas_muscl)" if k
                            else "dense XLA sweep", bool(k)))
            elif spec.blocked and spec.blocked[i]:
                nt = sim.blocks[l].ntile_pad
                k = spec.pallas_tiles and po.tile_available(
                    cfg, nt, dtype, spec.block_shift)
                out.append((l, f"tile_sweep kernel (pallas_oct, {nt} tiles)"
                            if k else f"XLA tiles ({nt} tiles)", bool(k)))
            else:
                no = sim.maps[l].noct_pad
                k = po.available(cfg, no, dtype)
                out.append((l, f"oct_sweep kernel (pallas_oct, {no} octs)"
                            if k else f"XLA oct stencils ({no} octs)",
                            bool(k)))
        return spec, out

    def kernel_levels(self):
        if self._kernel_levels is None:
            self._kernel_levels = {l for l, _, k in self._level_forms()[1]
                                   if k}
        return self._kernel_levels

    def formulations(self, count_calls=False):
        """[(label, text, on its kernel)] from the gates; with
        ``count_calls`` (traced runs: it lowers and compiles the step
        program once more) also the ``tpu_custom_call`` count of the
        compiled coarse step (copied check: ``chip_smoke.py:216-262``)."""
        from ramses_tpu.amr import hierarchy as H
        sim = self.sim
        spec, forms = self._level_forms()
        out = [(f"level {l}", name, k) for l, name, k in forms]
        if not count_calls:
            return out
        txt = H._fused_coarse_step.lower(
            sim.u, sim.dev, {}, jnp.asarray(sim.dt_old, sim.dtype), spec,
            sim._cool_bundle()).compile().as_text()
        ncall = txt.count('custom_call_target="tpu_custom_call"')
        want = sum(1 << (l - spec.lmin) for l, _, k in forms if k)
        out.append(("coarse-step program",
                    f"tpu_custom_calls={ncall} (expected {want} from the "
                    f"gates)", ncall == want))
        return out

    def shape_report(self):
        sim = self.sim
        return {l: {"noct": sim.tree.noct(l),
                    "noct_pad": int(sim.maps[l].noct_pad),
                    "ntile_pad": (int(sim.blocks[l].ntile_pad)
                                  if l in getattr(sim, "blocks", {}) else None)}
                for l in sim.levels()}

    # -- what the comparison reads ------------------------------------------
    @staticmethod
    def _decode(tree, u):
        """{level: (cell coords [ncell, 3] int64, values [ncell, nvar])} in
        tree order (single device: rows are tree order, pads last)."""
        out = {}
        for l, arr in u.items():
            n = tree.noct(l) * (1 << tree.ndim)
            out[l] = (np.asarray(tree.cell_coords(l), np.int64),
                      np.asarray(arr[:n]))
        return out

    def snapshot(self):
        """The held slice: state before its regrid, before its step and
        after it (host copies), each with the tree it lives on."""
        sim = self.sim
        pre, mid, out = self.pre, self.mid, self.out
        snap = {
            "lmin": int(sim.lmin), "lmax": int(sim.lmax),
            "boxlen": float(sim.boxlen),
            "mid": self._decode(mid["tree"], mid["u"]),
            "out": self._decode(out["tree"], out["u"]),
            "dt": out["dt_old"], "t_out": out["t"],
            "nstep_out": out["nstep"],
            "nsteps": out["nstep"] - mid["nstep"],
        }
        if pre is not None and pre["nstep"] == mid["nstep"]:
            snap["pre"] = self._decode(pre["tree"], pre["u"])
        return snap

    def finite(self):
        return all(bool(jnp.isfinite(self.sim.u[l]).all())
                   for l in self.sim.levels())

    def free(self):
        self.pre = self.mid = self.out = self.base = None
        self.sim.u = {}
        self.sim = None
