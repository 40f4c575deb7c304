"""Entry adapter: ``ramses_tpu.mhd.driver.MhdSimulation`` (the uniform MHD
path of ``python -m ramses_tpu``), built as ``__main__.run`` builds it.

The adapter is the only place that knows the program's objects.  A slice is
``sim.evolve(nstepmax=sim.nstep + slice_steps)``: one fused multi-step
dispatch of ``mhd/uniform.run_steps``.

The held slice, as ``uniform_simulation`` holds it: input and output (cell
state AND staggered faces) go to the host while the device works; no device
memory is held beyond what the program holds itself, so ``peak_hbm_bytes``
has none of the yardstick in it."""

import jax.numpy as jnp
import numpy as np


class Entry:
    def __init__(self, config, traffic, params):
        from ramses_tpu.mhd.driver import MhdSimulation
        self.sim = MhdSimulation(params, dtype=jnp.float32)
        self.slice_steps = int(traffic["slice_steps"])
        self.ncell = self.sim.grid.ncell
        self.held = None          # the held slice, on the host
        self.pending = None       # its output, still on its way
        self.kernel = None

    # -- driving ---------------------------------------------------------
    def develop(self, nsteps, regrid_every=None):
        self.sim.evolve(nstepmax=self.sim.nstep + int(nsteps))

    def mark(self):
        pass

    def _collect(self):
        if self.pending is not None:
            u, bf = self.pending
            self.held["u_out"], self.held["bf_out"] = (np.asarray(u),
                                                       np.asarray(bf))
            self.pending = None

    def run_slice(self, hold=False):
        sim = self.sim
        u_in, bf_in, t_in, n_in = sim.u, sim.bf, float(sim.t), int(sim.nstep)
        if hold:
            u_in.copy_to_host_async()
            bf_in.copy_to_host_async()
        sim.evolve(nstepmax=n_in + self.slice_steps)
        self._collect()
        done = int(sim.nstep) - n_in
        if hold:
            self.held = {"u_in": np.asarray(u_in), "bf_in": np.asarray(bf_in),
                         "t_in": t_in, "t_out": float(sim.t), "nsteps": done,
                         "nstep_out": int(sim.nstep)}
            self.pending = (sim.u, sim.bf)
            sim.u.copy_to_host_async()
            sim.bf.copy_to_host_async()
        upd = done * self.ncell
        return {"asked": self.slice_steps, "done": done, "cell_updates": upd,
                "sim_time": float(sim.t) - t_in,
                "kernel_cell_updates": upd if self.on_kernel() else 0}

    def sync(self):
        self.sim.u.block_until_ready()
        self.sim.bf.block_until_ready()
        self._collect()

    def sim_time(self):
        return float(self.sim.t)

    def tend(self):
        out = self.sim.params.output
        return float(out.tout[-1] if out.tout else out.tend)

    # -- what ran ----------------------------------------------------------
    def on_kernel(self):
        if self.kernel is None:
            from ramses_tpu.mhd import uniform
            ok = getattr(uniform, "kernel_ok", None)   # absent: XLA only
            self.kernel = bool(ok and ok(self.sim.grid, self.sim.u.dtype))
        return self.kernel

    def formulations(self, count_calls=False):
        """[(label, text, on its kernel)] from the gate; with
        ``count_calls`` (traced runs: it lowers and compiles the step
        program once more) also the ``tpu_custom_call`` count of the
        compiled program."""
        import jax
        from ramses_tpu.mhd import uniform
        sim = self.sim
        tiled = self.on_kernel()
        name = "tiled Pallas CT kernel (pallas_ct)" if tiled \
            else "XLA formulation"
        if not count_calls:
            return [("grid", name, tiled)]
        tdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        txt = uniform.run_steps.lower(
            sim.grid, sim.u, sim.bf, jnp.asarray(sim.t, tdt),
            jnp.asarray(self.tend(), tdt),
            self.slice_steps).compile().as_text()
        ncall = txt.count('custom_call_target="tpu_custom_call"')
        return [("grid", f"{name}, tpu_custom_calls={ncall}",
                 tiled and ncall >= 1)]

    # -- what the comparison reads ------------------------------------------
    def snapshot(self):
        """Input and output of the held slice (host copies) and the grid
        they live on.  Where the seed's share of the window left no slice to
        start after it (the last started just before), the next slice is
        run and held now, after the window: the clock, the counts and the
        peak are already taken."""
        if self.held is None:
            self.run_slice(hold=True)
            self.sync()
        self._collect()
        return dict(self.held, dx=float(self.sim.dx), tend=self.tend())

    def finite(self):
        return bool(jnp.isfinite(self.sim.u).all()
                    & jnp.isfinite(self.sim.bf).all())

    def free(self):
        self.sim.u = self.sim.bf = None
        self.sim = None
        self.held = self.pending = None
