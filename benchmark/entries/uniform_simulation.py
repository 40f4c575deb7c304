"""Entry adapter: ``ramses_tpu.driver.Simulation`` (the uniform-grid path of
``python -m ramses_tpu``), built as ``__main__.run``'s ``build`` builds it.

The adapter is the only place that knows the program's objects.  A slice is
``params.run.nstepmax = nstep + slice_steps; sim.evolve()``: one fused
multi-step dispatch (``driver.py:268-294``).

The held slice.  For the one slice the window asks to ``hold``, input and
output go to the host while the device works: the input's copy is started
before the slice is dispatched and taken when ``evolve`` returns (the
input stays alive inside ``evolve`` anyway: no donation), the output's is
started then and taken after the next slice, whose input it is.  No device
memory is held beyond what the program holds itself, so
``peak_hbm_bytes`` has none of the yardstick in it."""

import jax.numpy as jnp
import numpy as np


class Entry:
    def __init__(self, config, traffic, params):
        from ramses_tpu.driver import Simulation
        self.sim = Simulation(params, dtype=jnp.float32)
        self.slice_steps = int(traffic["slice_steps"])
        self.ncell = self.sim.grid.ncell
        self.held = None          # the held slice, on the host
        self.pending = None       # its output, still on its way
        self.kernel = None

    # -- driving ---------------------------------------------------------
    def develop(self, nsteps, regrid_every=None):
        sim = self.sim
        sim.params.run.nstepmax = sim.state.nstep + int(nsteps)
        sim.evolve()

    def mark(self):
        pass

    def _collect(self):
        if self.pending is not None:
            self.held["u_out"] = np.asarray(self.pending)
            self.pending = None

    def run_slice(self, hold=False):
        sim, st = self.sim, self.sim.state
        u_in, t_in, n_in = st.u, float(st.t), int(st.nstep)
        if hold:
            u_in.copy_to_host_async()
        sim.params.run.nstepmax = st.nstep + self.slice_steps
        sim.evolve()
        self._collect()
        done = int(st.nstep) - n_in
        if hold:
            self.held = {"u_in": np.asarray(u_in), "t_in": t_in,
                         "t_out": float(st.t), "nsteps": done,
                         "nstep_out": int(st.nstep)}
            self.pending = st.u
            self.pending.copy_to_host_async()
        upd = done * self.ncell
        return {"asked": self.slice_steps, "done": done, "cell_updates": upd,
                "sim_time": float(st.t) - t_in,
                "kernel_cell_updates": upd if self.on_kernel() else 0}

    def sync(self):
        self.sim.state.u.block_until_ready()
        self._collect()

    def sim_time(self):
        return float(self.sim.state.t)

    def tend(self):
        return float(self.sim.tend)

    # -- what ran ----------------------------------------------------------
    def on_kernel(self):
        if self.kernel is None:
            from ramses_tpu.grid import uniform
            self.kernel = bool(uniform._pallas_ok(self.sim.grid,
                                                  self.sim.state.u.dtype))
        return self.kernel

    def formulations(self, count_calls=False):
        """[(label, text, on its kernel)] from the gate; with
        ``count_calls`` (traced runs: it lowers and compiles the step
        program once more) also the ``tpu_custom_call`` count of the
        compiled program (copied check: ``chip_smoke.py:155-170``)."""
        import jax
        from ramses_tpu.grid import uniform
        sim, st = self.sim, self.sim.state
        fused = self.on_kernel()
        name = "fused Pallas kernel (pallas_muscl)" if fused \
            else "XLA formulation"
        if not count_calls:
            return [("grid", name, fused)]
        tdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        txt = uniform.run_steps.lower(
            sim.grid, st.u, jnp.asarray(st.t, tdt),
            jnp.asarray(sim.tend, tdt), self.slice_steps).compile().as_text()
        ncall = txt.count('custom_call_target="tpu_custom_call"')
        return [("grid", f"{name}, tpu_custom_calls={ncall}",
                 fused and ncall >= 1)]

    # -- what the comparison reads ------------------------------------------
    def snapshot(self):
        """Input and output of the held slice (host copies) and the grid
        they live on."""
        self._collect()
        return dict(self.held, dx=float(self.sim.dx), tend=self.tend())

    def finite(self):
        return bool(jnp.isfinite(self.sim.state.u).all())

    def free(self):
        self.sim.state.u = None
        self.sim = None
        self.held = self.pending = None
