"""Multi-device uniform-grid simulation (global-view SPMD).

Design (SURVEY.md §7 stage 6): the state array stays a single global-view
jax.Array sharded over the device mesh; the unchanged solver kernels run
under jit and XLA's SPMD partitioner inserts the halo collective-permutes
(P2), min-reductions for CFL (P7), and keeps everything on ICI.  This
replaces the reference's hand-written message schedule
(``amr/virtual_boundaries.f90:373-533``) with compiler-scheduled
communication — the idiomatic TPU answer to two-sided MPI halos.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ramses_tpu.config import Params
from ramses_tpu.driver import Simulation
from ramses_tpu.grid.uniform import run_steps
from ramses_tpu.parallel.mesh import make_mesh, spatial_sharding
from ramses_tpu.pm.coupling import run_steps_pm


class ShardedSim:
    """Uniform-grid simulation with the state sharded over a device mesh."""

    def __init__(self, params: Params,
                 devices: Optional[Sequence[jax.Device]] = None,
                 dtype=jnp.float32):
        self.inner = Simulation(params, dtype=dtype)
        self.mesh = make_mesh(params.ndim, devices)
        # the step's kernel gate asks how many devices the state spans
        self.inner.grid = dataclasses.replace(
            self.inner.grid, ndev=int(self.mesh.devices.size))
        self.sharding = spatial_sharding(self.mesh, n_leading=1)
        self.u = jax.device_put(self.inner.state.u, self.sharding)
        self.inner.state.u = None  # drop the unsharded copy (memory)
        # particles: data-parallel over lanes (flattened mesh); deposits
        # into the spatially-sharded grid become partitioned scatters
        self.p = None
        if self.inner.pspec.enabled and self.inner.state.p is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            flat = Mesh(np.asarray(self.mesh.devices).reshape(-1),
                        ("lane",))
            lane = NamedSharding(flat, PartitionSpec("lane"))
            lane2 = NamedSharding(flat, PartitionSpec("lane", None))
            rep = NamedSharding(flat, PartitionSpec())
            import dataclasses as _dc
            p0 = self.inner.state.p
            ndev = flat.devices.size

            def put(a):
                if a is None:
                    return None
                if a.ndim >= 1 and a.shape[0] % ndev == 0:
                    return jax.device_put(
                        a, lane2 if a.ndim > 1 else lane)
                return jax.device_put(a, rep)

            self.p = _dc.replace(
                p0, **{f.name: put(getattr(p0, f.name))
                       for f in _dc.fields(p0)})
            self.inner.state.p = None
        self.gspec = self.inner.gspec
        if self.gspec.enabled and self.gspec.solver == "fft":
            # the spectral solve is global (all-to-all) and XLA's CPU FFT
            # thunk rejects partitioned layouts; the CG stencil solver
            # partitions cleanly over the mesh (halo permutes only)
            import dataclasses as _dc
            self.gspec = _dc.replace(self.gspec, solver="cg")
        self.pspec = self.inner.pspec
        self.cosmo = self.inner.cosmo
        self.f = (jax.device_put(self.inner.state.f, self.sharding)
                  if self.inner.state.f is not None else None)
        self.inner.state.f = None  # likewise
        self.t = float(self.inner.state.t)
        self.dt_old = 0.0
        self.nstep = 0

    @property
    def grid(self):
        return self.inner.grid

    def run(self, nsteps: int, tend: float = 1e30):
        tdtype = (jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        t0 = jnp.asarray(self.t, tdtype)
        t1 = jnp.asarray(tend, tdtype)
        if (self.gspec.enabled or self.cosmo is not None
                or self.pspec.enabled):
            u, p, f, t, dt_old, ndone = run_steps_pm(
                self.grid, self.gspec, self.pspec, self.u, self.p, self.f,
                t0, t1, jnp.asarray(self.dt_old, tdtype), nsteps,
                cosmo=self.cosmo)
            self.f, self.p, self.dt_old = f, p, float(dt_old)
        else:
            u, t, ndone = run_steps(self.grid, self.u, t0, t1, nsteps)
        u.block_until_ready()
        self.u, self.t = u, float(t)
        self.nstep += int(ndone)
        return self
