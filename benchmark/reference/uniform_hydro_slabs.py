"""The uniform reference computed in slabs, for a box whose plain step does
not fit the chip.

``uniform_hydro._one_step`` advances the whole box in one jitted step whose
temporaries are ~25 x the state (8.68 GB for the 0.34 GB of 256^3, XLA's own
count); at 512^3 that is tens of GB.  Here every step is the same
arithmetic on x-slabs: (a) the CFL step of the whole state, the minimum of
``muscl_plain.courant_dt`` over the slabs, clipped to the end time, time
summed in float32 exactly as ``uniform_hydro.advance`` does; (b)
``muscl_plain.step`` on each slab with a 2-cell periodic margin each side
(the scheme's reach: slopes one cell, predictor faces one more), interiors
kept — ``muscl_plain`` rolls, so what wraps lands in margin cells nobody
reads.  A cell's update is the same operations on the same numbers as in
the whole-box step.

What is compared (``measure``) and the host-side totals are the 256^3
reference's own.  ``dtype="bfloat16"`` is the lower-precision control.
"""

from functools import partial, reduce

import jax
import jax.numpy as jnp

from benchmark.reference import muscl_plain as mp
from benchmark.reference.uniform_hydro import (  # noqa: F401  (the harness
    measure, program_output, totals)             # finds them here by name)

MARGIN = 2
# A slab with its margins holds at most this much state.  At 512^3 that is 32
# slabs of 16 planes (105 MB each), whose step compiles for the described
# v5e with 1.64 GB of temporaries: beside the state, the state being built
# and, in a control run, the float32 reference (3 x 2.68 GB) that fits one
# chip's 15.75 GiB with room.
SLAB_BYTES = 128 * 2 ** 20


def slab_count(shape, itemsize):
    """Fewest equal x-slabs, each at least a margin thick, whose state,
    margins included, fits ``SLAB_BYTES`` (at least 2, so the margins are
    always exercised)."""
    nvar, nx, ny, nz = shape
    fit = [n for n in range(2, nx // MARGIN + 1) if nx % n == 0
           and nvar * (nx // n + 2 * MARGIN) * ny * nz * itemsize
           <= SLAB_BYTES]
    return fit[0] if fit else nx // MARGIN


def _planes(u, i0, width):
    return jax.lax.dynamic_slice_in_dim(u, i0, width, axis=1)


def _slab_with_margins(u, i0, sx):
    """Planes ``i0-2 .. i0+sx+2`` of the periodic box.  Slabs start at
    multiples of ``sx >= 2``, so neither margin straddles the box's end:
    three contiguous pieces, and no copy of the whole state."""
    nx = u.shape[1]
    return jnp.concatenate([_planes(u, (i0 - MARGIN) % nx, MARGIN),
                            _planes(u, i0, sx),
                            _planes(u, (i0 + sx) % nx, MARGIN)], axis=1)


@partial(jax.jit, static_argnames=("sx", "dx", "ph"))
def _slab_dt(u, i0, sx, dx, ph):
    return mp.courant_dt(_planes(u, i0, sx), dx, ph).astype(jnp.float32)


@partial(jax.jit, static_argnames=("sx", "dx", "ph"), donate_argnums=(1,))
def _slab_step(u, out, i0, dt, sx, dx, ph):
    """The interior of slab ``i0 .. i0+sx`` of ``u`` stepped by ``dt``,
    written into ``out`` (donated: one state is built in place)."""
    new = mp.step(_slab_with_margins(u, i0, sx), dt, dx, ph)
    return jax.lax.dynamic_update_slice_in_dim(
        out, new[:, MARGIN:MARGIN + sx], i0, axis=1)


def advance(snap, config, dtype="float32", nslab=None):
    ph = mp.Physics(config["physics"])
    dx = float(snap["dx"])
    u = jnp.asarray(snap["u_in"]).astype(dtype)
    if nslab is None:
        nslab = slab_count(u.shape, u.dtype.itemsize)
    sx, rest = divmod(u.shape[1], nslab)
    if rest or sx < MARGIN:
        raise ValueError(f"{nslab} slabs do not divide {u.shape[1]} planes "
                         f"into slabs of at least {MARGIN}")
    starts = [jnp.int32(k * sx) for k in range(nslab)]
    t = jnp.float32(snap["t_in"])
    tend = jnp.float32(snap["tend"])
    for _ in range(int(snap["nsteps"])):
        dt = reduce(jnp.minimum,
                    (_slab_dt(u, i0, sx, dx, ph) for i0 in starts))
        dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
        dt_u = dt.astype(u.dtype)
        out = jnp.empty_like(u)
        for i0 in starts:
            out = _slab_step(u, out, i0, dt_u, sx, dx, ph)
        u, t = out, t + dt
    return {"u": u.astype(jnp.float32), "t": float(t)}
