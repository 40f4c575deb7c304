"""Plain reference of a uniform periodic MHD run, and what is compared.

``advance`` follows the window's held slice from the slice's own input:
``nsteps`` steps of ``mhd_plain.step`` under
``jax.default_matmul_precision("highest")``, each with the CFL step
(``courant_factor`` 0.8 over the largest ``sum_d (|v_d| + c_fast,d) / dx``)
of the state it starts from, clipped to the end time, time summed in float32
as the program sums it when x64 is off.  ``dtype="bfloat16"`` is the
lower-precision control (the nearest precision below the float32 the
configuration states).

In slabs.  The plain whole-box step materialises every stage: ~75 x the
state in temporaries (XLA's own count for the program's like formulation:
6.89 GB for the 92 MB of 128^3), 28 GB at 256^3.  So every step is the same
arithmetic on x-slabs: (a) the CFL step of the whole state, from the largest
rate over the slabs; (b) ``mhd_plain.step`` on each slab with a 3-plane
periodic margin each side (the scheme's reach: 2 cells, and the high face of
the second), interiors kept - ``mhd_plain`` rolls, so what wraps lands in
margin cells nobody reads.  A cell's update is the same operations on the
same numbers as in the whole-box step (``whole_box`` below; a test holds
the two to the bit at a small size).

The departures of the scheme from upstream's ``mhd/umuscl.f90`` (faces
stored once, the Hancock predictor, primitive corner states) are the
program's and are listed in ``mhd_plain``'s docstring.
"""

from functools import partial, reduce

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mhd_plain as mp
from benchmark.reference.uniform_hydro import (  # the audits are the hydro
    _plane_sums, ratio, totals)                  # cells': rows 0 and 4

MARGIN = 3
# A slab with its margins holds at most this much state (cells and faces).
# At 256^3 that is 16 slabs of 16 planes (22 with margins: 63 MB), whose
# step's temporaries stay under 5 GB beside the two states being held.
SLAB_BYTES = 64 * 2 ** 20
NROWS = 8 + 3


def slab_count(shape, itemsize):
    """Fewest equal x-slabs, each at least a margin thick, whose state,
    margins included, fits ``SLAB_BYTES`` (at least 2, so the margins are
    always exercised)."""
    nx, ny, nz = shape
    fit = [n for n in range(2, nx // MARGIN + 1) if nx % n == 0
           and NROWS * (nx // n + 2 * MARGIN) * ny * nz * itemsize
           <= SLAB_BYTES]
    return fit[0] if fit else nx // MARGIN


def _planes(a, i0, width):
    return jax.lax.dynamic_slice_in_dim(a, i0, width, axis=1)


def _slab_with_margins(a, i0, sx):
    """Planes ``i0-3 .. i0+sx+3`` of the periodic box.  Slabs start at
    multiples of ``sx >= 3``, so neither margin straddles the box's end."""
    nx = a.shape[1]
    return jnp.concatenate([_planes(a, (i0 - MARGIN) % nx, MARGIN),
                            _planes(a, i0, sx),
                            _planes(a, (i0 + sx) % nx, MARGIN)], axis=1)


@partial(jax.jit, static_argnames=("sx", "dx", "ph"))
def _slab_rate(u, bf, i0, sx, dx, ph):
    """Largest Courant rate of slab ``i0 .. i0+sx`` (one margin plane on
    the high side: a cell's centred field needs its high face)."""
    with jax.default_matmul_precision("highest"):
        nx = u.shape[1]
        us = _planes(u, i0, sx)
        bs = jnp.concatenate([_planes(bf, i0, sx),
                              _planes(bf, (i0 + sx) % nx, 1)], axis=1)
        us = jnp.concatenate([us, us[:, :1]], axis=1)
        return jnp.max(mp.courant_rate(us, bs, dx, ph)[:sx])


@partial(jax.jit, static_argnames=("sx", "dx", "ph"), donate_argnums=(2, 3))
def _slab_step(u, bf, u_out, bf_out, i0, dt, sx, dx, ph):
    """The interior of slab ``i0 .. i0+sx`` stepped by ``dt``, written into
    ``u_out`` / ``bf_out`` (donated: one state is built in place)."""
    with jax.default_matmul_precision("highest"):
        un, bn = mp.step(_slab_with_margins(u, i0, sx),
                         _slab_with_margins(bf, i0, sx), dt, dx, ph)
    keep = slice(MARGIN, MARGIN + sx)
    return (jax.lax.dynamic_update_slice_in_dim(u_out, un[:, keep], i0, 1),
            jax.lax.dynamic_update_slice_in_dim(bf_out, bn[:, keep], i0, 1))


@partial(jax.jit, static_argnames=("dx", "ph"))
def _whole_rate(u, bf, dx, ph):
    with jax.default_matmul_precision("highest"):
        return jnp.max(mp.courant_rate(u, bf, dx, ph))


@partial(jax.jit, static_argnames=("dx", "ph"))
def _whole_step(u, bf, dt, dx, ph):
    with jax.default_matmul_precision("highest"):
        return mp.step(u, bf, dt, dx, ph)


def _cfl_step(rate, t, tend, ph):
    """The step from the largest rate, clipped to the end time: float32
    whatever the state's dtype, as the program's time axis."""
    dt = (ph.courant_factor / rate).astype(jnp.float32)
    return jnp.minimum(dt, jnp.maximum(tend - t, 0.0))


def _start(snap, config, dtype):
    return (mp.Physics(config["physics"]), float(snap["dx"]),
            jnp.asarray(snap["u_in"]).astype(dtype),
            jnp.asarray(snap["bf_in"]).astype(dtype),
            jnp.float32(snap["t_in"]), jnp.float32(snap["tend"]))


def whole_box(snap, config, dtype="float32"):
    """``advance`` without slabs: for boxes whose plain step fits."""
    ph, dx, u, bf, t, tend = _start(snap, config, dtype)
    for _ in range(int(snap["nsteps"])):
        dt = _cfl_step(_whole_rate(u, bf, dx, ph), t, tend, ph)
        u, bf = _whole_step(u, bf, dt.astype(u.dtype), dx, ph)
        t = t + dt
    return {"u": u.astype(jnp.float32), "bf": bf.astype(jnp.float32),
            "t": float(t)}


def advance(snap, config, dtype="float32", nslab=None):
    ph, dx, u, bf, t, tend = _start(snap, config, dtype)
    if nslab is None:
        nslab = slab_count(u.shape[1:], u.dtype.itemsize)
    sx, rest = divmod(u.shape[1], nslab)
    if rest or sx < MARGIN:
        raise ValueError(f"{nslab} slabs do not divide {u.shape[1]} planes "
                         f"into slabs of at least {MARGIN}")
    starts = [jnp.int32(k * sx) for k in range(nslab)]
    for _ in range(int(snap["nsteps"])):
        rate = reduce(jnp.maximum,
                      (_slab_rate(u, bf, i0, sx, dx, ph) for i0 in starts))
        dt = _cfl_step(rate, t, tend, ph)
        dt_u = dt.astype(u.dtype)
        u_out, bf_out = jnp.empty_like(u), jnp.empty_like(bf)
        for i0 in starts:
            u_out, bf_out = _slab_step(u, bf, u_out, bf_out, i0, dt_u, sx,
                                       dx, ph)
        u, bf, t = u_out, bf_out, t + dt
    return {"u": u.astype(jnp.float32), "bf": bf.astype(jnp.float32),
            "t": float(t)}


def program_output(snap):
    return {"u": snap["u_out"], "bf": snap["bf_out"],
            "t": float(snap["t_out"])}


def blast_cells(radius, dx):
    """Cells of a grid of spacing ``dx`` whose centres lie inside a sphere
    of ``radius`` centred on a cell corner (where every seed puts it)."""
    k = int(np.ceil(radius / dx)) + 1
    x = (np.arange(-k, k) + 0.5) * dx
    r2 = x[:, None, None] ** 2 + x[None, :, None] ** 2 + x[None, None, :] ** 2
    return int((r2 < radius * radius).sum())


def initial_totals(config, dx):
    """Mass and total energy of the initial condition, from the numbers the
    configuration states (not from the program's arrays): uniform density
    and field, the blast's over-pressure in the cells of its sphere."""
    ic = config["initial_condition"]
    g = float(config["physics"]["gamma"])
    vol = float(ic["boxlen"]) ** 3
    b2 = sum(float(b) ** 2 for b in ic["b_ambient"])
    mass = float(ic["d_ambient"]) * vol
    energy = (float(ic["p_ambient"]) / (g - 1.0) + 0.5 * b2) * vol \
        + (float(ic["p_blast"]) - float(ic["p_ambient"])) / (g - 1.0) \
        * blast_cells(float(ic["blast_radius"]), dx) * dx ** 3
    return mass, energy


def divb_max(bf, dx):
    """max |div B| * dx / max |B| of a float32 staggered field, the
    differences taken in float64 on the host: what is read is the field's
    own divergence, not the audit's rounding."""
    b = [np.asarray(bf[c], np.float64) for c in range(3)]
    div = sum(np.roll(b[c], -1, axis=c) - b[c] for c in range(3))
    bmax = max(float(np.abs(x).max()) for x in b)
    return float(np.abs(div).max()) / bmax if bmax > 0 else float("inf")


def _gaps(got, ref, start):
    """(worst row's ``sum|got - ref| / sum|ref - start|``, the same with
    the largest cell in place of the sum)."""
    worst = cell = 0.0
    for k in range(got.shape[0]):
        err = jnp.abs(got[k] - ref[k])
        chg = jnp.abs(ref[k] - start[k])
        worst = max(worst, ratio(_plane_sums(err), _plane_sums(chg)))
        cell = max(cell, ratio(float(jnp.max(err)), float(jnp.max(chg))))
    return worst, cell


def measure(got, ref, snap, config):
    """The numbers compared, each a gap that is 0 for identical runs.

    ``state_gap``: worst cell variable's ``sum|got - ref|`` over the
    reference's own change across the slice ``sum|ref - in|`` - a state
    returned unchanged reads 1.  ``cell_gap``: the same with the largest
    cell in place of the sum (one altered cell shows here, not in a sum).
    ``face_gap``: the larger of the two forms over the three rows of the
    staggered field.  ``time_gap``: the same for the simulated time.
    ``mass_drift_per_step`` / ``energy_drift``: totals of the slice's output
    against the initial condition's, relative (the mass drift over the steps
    done since the initial condition, as for the hydro cells).
    ``divb_max``: max |div B| dx / max |B| of the output's faces - round-off
    under constrained transport, whatever the run did."""
    dx = float(snap["dx"])
    u_in = jnp.asarray(snap["u_in"]).astype(jnp.float32)
    bf_in = jnp.asarray(snap["bf_in"]).astype(jnp.float32)
    got_u, got_bf = jnp.asarray(got["u"]), jnp.asarray(got["bf"])
    state, cell = _gaps(got_u, ref["u"], u_in)
    face = max(_gaps(got_bf, ref["bf"], bf_in))
    dt_ref = ref["t"] - float(snap["t_in"])
    m0, e0 = initial_totals(config, dx)
    m1, e1 = totals(got_u, dx)
    return {
        "state_gap": state,
        "cell_gap": cell,
        "face_gap": face,
        "time_gap": abs(got["t"] - ref["t"]) / dt_ref if dt_ref > 0
        else float("inf"),
        "mass_drift": abs(m1 - m0) / m0,
        "mass_drift_per_step": abs(m1 - m0) / m0 / max(
            int(snap["nstep_out"]), 1),
        "energy_drift": abs(e1 - e0) / e0,
        "divb_max": divb_max(got_bf, dx),
    }
