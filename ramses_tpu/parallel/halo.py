"""Explicit halo-exchange backend: shard_map slab pipeline.

The global-view path (:mod:`ramses_tpu.parallel.sharded`) leaves halo
communication to XLA's SPMD partitioner.  This module is the EXPLICIT
formulation of the reference's two-sided message schedule
(``amr/virtual_boundaries.f90:373-533`` ``make_virtual_fine``): the
state lives as per-device blocks under ``jax.shard_map``, each step
sends the ``NGHOST``-deep boundary slabs to the ring neighbours
through the backend-dispatched exchange engine
(:mod:`ramses_tpu.parallel.dma_halo` — Pallas async remote-copy DMA on
TPU, ``lax.ppermute`` elsewhere), pads the remaining axes locally, and
runs the unchanged MUSCL kernels on the interior.  The CFL reduction
is a ``lax.pmin`` over the mesh axis (P7).

On the DMA backend the step is region-split for comm/compute overlap:
the boundary slabs start their async remote copy, the interior band
(which reads no cross-device ghosts) is computed while the transfer is
in flight, and two ``NGHOST``-thin strips are finished from the
received ghosts — the hand-scheduled overlap the reference gets from
posting MPI_Isend/Irecv before the interior sweep.  The MUSCL update
is pure per-cell arithmetic, so the split is bitwise-invisible.

Why keep both: the GSPMD path is the idiomatic TPU formulation and
lets the compiler fuse; this path pins the communication schedule —
deterministic slab order, no partitioner heuristics.  All backends
must agree bitwise on periodic boxes (asserted in ``tests/test_halo.py``
and ``tests/test_dma_halo.py``).

Scope: fully periodic boxes, 1-D decomposition over the leading
spatial axis — the Hilbert-order row decomposition every other sharded
path uses (P1).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ramses_tpu.grid import boundary as bmod
from ramses_tpu.grid.uniform import UniformGrid
from ramses_tpu.hydro import muscl
from ramses_tpu.hydro.timestep import compute_dt
from ramses_tpu.parallel import dma_halo

AXIS = "hx"          # mesh axis name of the slab decomposition


def make_halo_mesh(devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (AXIS,))


def _check(grid: UniformGrid, mesh: Mesh):
    n = mesh.shape[AXIS]
    if any(f[0].kind != 0 or f[1].kind != 0 for f in grid.bc.faces):
        raise NotImplementedError(
            "halo backend: fully periodic boxes only (physical "
            "boundary slabs stay on the GSPMD path)")
    if grid.shape[0] % n:
        raise ValueError(
            f"leading axis {grid.shape[0]} not divisible by the "
            f"{n}-device mesh")
    if grid.shape[0] // n < muscl.NGHOST:
        raise ValueError("shard thinner than the stencil halo")


def _ring(n: int):
    fwd = [(i, (i + 1) % n) for i in range(n)]    # data moves +x
    bwd = [(i, (i - 1) % n) for i in range(n)]    # data moves -x
    return fwd, bwd


def _exchange(u_loc, ng: int, n: int, backend: str):
    """Ring exchange of the leading-spatial-axis boundary slabs.

    ``u_loc``: [nvar, nx_loc, ...].  Returns the block extended to
    ``nx_loc + 2*ng`` — each device's low ghost slab is its left
    neighbour's high interior slab and vice versa (periodic ring, so
    device 0's left neighbour is device n-1: the wrap IS the physical
    periodic boundary)."""
    fwd, bwd = _ring(n)
    lo_ghost, hi_ghost = dma_halo.exchange_pair(
        u_loc[:, -ng:], u_loc[:, :ng], AXIS, fwd, bwd, backend=backend)
    return jnp.concatenate([lo_ghost, u_loc, hi_ghost], axis=1)


def _pad_rest(u_ext, ndim: int, ng: int):
    """Periodic-wrap padding of the non-decomposed spatial axes."""
    pads = [(0, 0), (0, 0)] + [(ng, ng)] * (ndim - 1)
    return jnp.pad(u_ext, pads, mode="wrap")


def _muscl_block(up, dt, grid: UniformGrid):
    """The padded-block MUSCL pipeline: ``up`` carries ``NGHOST``
    ghosts on every spatial axis; returns the unpadded interior."""
    cfg = grid.cfg
    flux, tmp = muscl.unsplit(up, None, dt, (grid.dx,) * cfg.ndim, cfg)
    un = muscl.apply_fluxes(up, flux, cfg)
    if cfg.pressure_fix or cfg.nener:
        un = muscl.dual_energy_fix(up, un, tmp, dt,
                                   (grid.dx,) * cfg.ndim, cfg)
    return bmod.unpad(un, cfg.ndim, ng=muscl.NGHOST)


def _local_step(u_loc, dt, grid: UniformGrid, n: int, backend: str,
                split: bool):
    cfg = grid.cfg
    ng = muscl.NGHOST
    if not split:
        up = _pad_rest(_exchange(u_loc, ng, n, backend), cfg.ndim, ng)
        return _muscl_block(up, dt, grid)
    # DMA overlap split: pad the uncut axes first, start the ring
    # exchange of the (rest-padded) boundary slabs, compute the
    # interior band while the copies are in flight, then finish the
    # two NGHOST-thin strips from the received ghosts.  Exchanging
    # rest-padded slabs reproduces the corner values of the sequenced
    # pad-after-exchange order bitwise (the wrap is a per-axis local
    # copy, identical on either side of the exchange).
    upr = _pad_rest(u_loc, cfg.ndim, ng)
    fwd, bwd = _ring(n)
    lo_g, hi_g = dma_halo.exchange_pair(
        upr[:, -ng:], upr[:, :ng], AXIS, fwd, bwd, backend=backend)
    un_int = _muscl_block(upr, dt, grid)          # cells [ng, nx-ng)
    lo_blk = jnp.concatenate([lo_g, upr[:, :2 * ng]], axis=1)
    hi_blk = jnp.concatenate([upr[:, -2 * ng:], hi_g], axis=1)
    un_lo = _muscl_block(lo_blk, dt, grid)        # cells [0, ng)
    un_hi = _muscl_block(hi_blk, dt, grid)        # cells [nx-ng, nx)
    return jnp.concatenate([un_lo, un_int, un_hi], axis=1)


@lru_cache(maxsize=None)
def _build_run(grid: UniformGrid, mesh: Mesh, nsteps: int,
               backend: str):
    cfg = grid.cfg
    n = mesh.shape[AXIS]
    split = backend == "dma" and grid.shape[0] // n > 2 * muscl.NGHOST
    if split:
        nloc = grid.shape[0] // n
        dma_halo.TRAFFIC["overlap_frac"] = (
            (nloc - 2 * muscl.NGHOST) / nloc)

    def shard_body(u_loc, t, tend):
        def body(carry, _):
            u_loc, t, ndone = carry
            dt_loc = compute_dt(u_loc, None, grid.dx, cfg)
            dt = jax.lax.pmin(dt_loc, AXIS)
            dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
            active = t < tend
            un = _local_step(u_loc, jnp.where(active, dt, 0.0)
                             .astype(u_loc.dtype), grid, n, backend,
                             split)
            u_loc = jnp.where(active, un, u_loc)
            t = jnp.where(active, t + dt, t)
            ndone = ndone + jnp.where(active, 1, 0)
            return (u_loc, t, ndone), None

        # seed the step counter FROM t: older shard_map tracks a fresh
        # constant's replication as unknown, and the scan carry check
        # then rejects the (known-replicated) output counter
        ndone0 = (t - t).astype(jnp.int32)
        (u_loc, t, ndone), _ = jax.lax.scan(
            body, (u_loc, t, ndone0), None, length=nsteps)
        return u_loc, t, ndone

    return jax.jit(jax.shard_map(
        shard_body, mesh=mesh, in_specs=(P(None, AXIS), P(), P()),
        out_specs=(P(None, AXIS), P(), P()),
        check_vma=(backend != "dma")))


def run_steps_halo(grid: UniformGrid, mesh: Mesh, u, t, tend,
                   nsteps: int, halo_backend: str = "auto"):
    """``run_steps`` with the explicit slab pipeline: the whole window
    is ONE shard_map program; every step does one ring exchange (two
    slabs) + one pmin.  ``halo_backend``: ``auto``/``dma``/``ppermute``
    (:func:`ramses_tpu.parallel.dma_halo.resolve_backend`).  Returns
    (u, t, n_done) like the global-view version."""
    _check(grid, mesh)
    backend = dma_halo.resolve_backend(halo_backend)
    u = jax.device_put(u, NamedSharding(mesh, P(None, AXIS)))
    return _build_run(grid, mesh, nsteps, backend)(u, jnp.asarray(t),
                                                   jnp.asarray(tend))
