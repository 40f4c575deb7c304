"""Pallas async remote-copy (DMA) halo engine.

Every multi-chip sweep in this codebase moves ``NGHOST``-deep boundary
slabs between ring neighbours.  The portable spelling is
``lax.ppermute`` — correct, but BLOCKING: XLA sequences the collective
against the MUSCL interior update, so every step pays the full ICI
transfer latency on the critical path (the comm/compute serialization
the AMT papers, arXiv:2210.06439 / 2412.15518, identify as the exascale
scaling bottleneck; the reference RAMSES hides the same traffic behind
compute with two-sided MPI).

This module is the EXPLICIT asynchronous formulation: a Pallas kernel
per exchange issues ``pltpu.make_async_remote_copy`` of every boundary
slab to its ring neighbour — the copies stream over ICI while the
issuing core is free — then blocks only on the receive semaphores.
Because the ghost outputs are separate arrays (not data-dependencies of
the interior), the callers split their stencil update into an interior
region (consumes NO ghost data → schedulable while the DMA is in
flight) and thin boundary strips that wait for the ghosts
(:func:`ramses_tpu.parallel.dense_slab.dense_sweep_slab`,
:func:`ramses_tpu.parallel.halo.run_steps_halo`).

Backend contract: :func:`exchange_slabs` is a drop-in
replacement for ``lax.ppermute`` with identical ring semantics —
device ``dst`` receives ``src``'s operand for every ``(src, dst)`` pair
— and the two backends agree BITWISE (pure data movement; asserted in
``tests/test_dma_halo.py`` under interpret mode).  Selection rides the
``&AMR_PARAMS halo_backend`` knob: ``auto`` resolves to ``dma`` on a
real TPU backend and ``ppermute`` everywhere else, so CPU runs (and the
tier-1 suite) never change behaviour unless a test forces interpret
mode via :data:`FORCE_INTERPRET`.

On compiled TPU the kernel first runs a neighbour barrier on the
global barrier semaphore (both ring neighbours must have entered the
kernel before anyone writes into a peer's output buffer — the standard
RDMA safety handshake); interpret mode skips the barrier (unsupported
there, and the interpreter serializes devices anyway).
"""

from __future__ import annotations

import itertools
import os
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DISABLED = bool(os.environ.get("RAMSES_NO_PALLAS"))

# Test hook: run the DMA kernels in Pallas interpreter mode on any
# backend — lets CI drive the REAL async-remote-copy path (not a
# replica) on the CPU test backend.  Module attribute so tests can
# monkeypatch; also settable via env for whole-suite sweeps.
FORCE_INTERPRET = bool(os.environ.get("RAMSES_DMA_HALO_INTERPRET"))

# Trace-time traffic accounting.  jit caching means each compiled
# program traces once, so these counts approximate the per-step traffic
# of the LAST compiled sweep (bytes are per device, one direction).
# telemetry.sim_run_info snapshots them into every run_header.
TRAFFIC = {"bytes": 0, "exchanges": 0, "overlap_frac": 0.0}

# device op name of the exchange kernel: the only thing a trace keeps
# to tell halo traffic from the sweep kernels (both are custom calls)
KERNEL_NAME = "halo_dma_exchange"

# distinct barrier-semaphore ids for kernels that may run concurrently
# inside one program (e.g. the state and mask exchanges of a split
# sweep); trace order is deterministic SPMD so every device agrees
_collective_ids = itertools.count()


def traffic_snapshot() -> dict:
    return {"halo_bytes": int(TRAFFIC["bytes"]),
            "halo_exchanges": int(TRAFFIC["exchanges"]),
            "halo_overlap_frac": float(TRAFFIC["overlap_frac"])}


def reset_traffic():
    TRAFFIC.update(bytes=0, exchanges=0, overlap_frac=0.0)


def _count(*slabs):
    for s in slabs:
        TRAFFIC["bytes"] += int(s.size) * jnp.dtype(s.dtype).itemsize
        TRAFFIC["exchanges"] += 1


def available() -> bool:
    """True when the DMA kernel can run compiled (real TPU backend)."""
    if DISABLED:
        return False
    try:
        return jax.default_backend() == "tpu"
    except Exception:                              # pragma: no cover
        return False


_warned: set = set()


def resolve_backend(requested) -> str:
    """Map the ``&AMR_PARAMS halo_backend`` knob to a concrete backend.

    ``auto`` → ``dma`` on a real TPU, ``ppermute`` elsewhere (CPU
    behaviour untouched).  An explicit ``dma`` request is honoured on
    TPU or under :data:`FORCE_INTERPRET` (tests); otherwise it warns
    once and falls back so a namelist written for TPU still runs on a
    laptop."""
    req = str(requested or "auto").lower()
    if req == "auto":
        return "dma" if available() else "ppermute"
    if req == "dma":
        if available() or FORCE_INTERPRET:
            return "dma"
        if "dma" not in _warned:
            _warned.add("dma")
            warnings.warn(
                "halo_backend='dma' requested but no TPU backend is "
                "available: falling back to ppermute")
        return "ppermute"
    if req != "ppermute" and req not in _warned:
        _warned.add(req)
        warnings.warn(f"unknown halo_backend {requested!r}: using "
                      "ppermute")
    return "ppermute"


def _interpret() -> bool:
    return FORCE_INTERPRET or jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# the kernel
# ----------------------------------------------------------------------
def _exchange_kernel(nslab: int, barrier: bool):
    """Kernel: start one async remote copy per slab (dst device ids in
    SMEM), then wait on every receive semaphore.  All copies are in
    flight together — the issuing core returns to the scheduler until
    the waits, which is what lets XLA overlap downstream independent
    compute with the transfer."""

    def kern(dst_ref, *refs):
        srcs = refs[:nslab]
        outs = refs[nslab:2 * nslab]
        sems = refs[2 * nslab:]
        if barrier:
            # RDMA safety: both peers must be inside the kernel before
            # anyone writes a peer's output buffer.  Each device
            # signals every destination it will write; the devices
            # writing to ME are exactly my destinations' mirror, so
            # waiting for nslab signals completes the handshake.
            bsem = pltpu.get_barrier_semaphore()
            for i in range(nslab):
                pltpu.semaphore_signal(
                    bsem, device_id=dst_ref[i],
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(bsem, nslab)
        copies = [
            pltpu.make_async_remote_copy(
                srcs[i], outs[i], sems[2 * i], sems[2 * i + 1],
                device_id=dst_ref[i],
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            for i in range(nslab)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return kern


def _dma_exchange(slabs, dsts, interpret: bool):
    """One fused pallas_call moving every ``slabs[i]`` to device
    ``dsts[i]`` (traced int32 scalars).  Returns the received arrays —
    ring-symmetric exchanges guarantee the receive shapes match the
    send shapes."""
    n = len(slabs)
    dst_arr = jnp.stack([jnp.asarray(d, jnp.int32) for d in dsts])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=next(_collective_ids) % 32)
    outs = pl.pallas_call(
        _exchange_kernel(n, barrier=not interpret),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                        for _ in range(n)),
        out_shape=tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                        for s in slabs),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * (2 * n),
        interpret=interpret, name=KERNEL_NAME,
        **kwargs)(dst_arr, *slabs)
    return list(outs)


def _dst_from_perm(perm, axis_name):
    """My destination device under a ppermute-style (src, dst) list."""
    tab = [0] * len(perm)
    for s, d in perm:
        tab[s] = d
    return jnp.asarray(tab, jnp.int32)[jax.lax.axis_index(axis_name)]


# ----------------------------------------------------------------------
# public exchange API (ppermute-compatible semantics)
# ----------------------------------------------------------------------
def exchange_slabs(sends: Sequence, perms: Sequence, axis_name: str,
                   backend: str = "ppermute", interpret=None):
    """``[ppermute(sends[i], axis, perms[i]) for i]`` — on the ``dma``
    backend all slabs ride ONE fused async-remote-copy kernel (one
    barrier, all transfers in flight together)."""
    _count(*sends)
    if backend != "dma":
        return [jax.lax.ppermute(s, axis_name, p)
                for s, p in zip(sends, perms)]
    if interpret is None:
        interpret = _interpret()
    dsts = [_dst_from_perm(p, axis_name) for p in perms]
    return _dma_exchange(list(sends), dsts, interpret)


def exchange_pair(lo_send, hi_send, axis_name: str, fwd, bwd,
                  backend: str = "ppermute", interpret=None):
    """The halo pair: ``(ppermute(lo_send, fwd), ppermute(hi_send,
    bwd))`` — my high interior slab becomes the +1 neighbour's low
    ghost and vice versa.  Both directions share one DMA kernel."""
    lo, hi = exchange_slabs([lo_send, hi_send], [fwd, bwd], axis_name,
                            backend, interpret=interpret)
    return lo, hi
