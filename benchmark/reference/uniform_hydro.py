"""Plain reference of a uniform periodic hydro run, and what is compared.

``advance`` follows the window's held slice from the slice's own input:
``nsteps`` steps, each with the CFL step of the state it starts from,
clipped to the end time, time summed in float32 as the program sums it
when x64 is off.  ``dtype="bfloat16"`` is the lower-precision control (the
nearest precision below the float32 the configuration states)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import muscl_plain as mp


@partial(jax.jit, static_argnames=("dx", "ph"))
def _one_step(u, t, tend, dx, ph):
    dt = mp.courant_dt(u, dx, ph).astype(jnp.float32)
    dt = jnp.minimum(dt, jnp.maximum(tend - t, 0.0))
    return mp.step(u, dt.astype(u.dtype), dx, ph), t + dt


def advance(snap, config, dtype="float32"):
    ph = mp.Physics(config["physics"])
    u = jnp.asarray(snap["u_in"]).astype(dtype)
    t = jnp.float32(snap["t_in"])
    tend = jnp.float32(snap["tend"])
    for _ in range(int(snap["nsteps"])):
        u, t = _one_step(u, t, tend, float(snap["dx"]), ph)
    return {"u": u.astype(jnp.float32), "t": float(t)}


def program_output(snap):
    return {"u": snap["u_out"], "t": float(snap["t_out"])}


def _plane_sums(a):
    """Sum of a [nx, ny, nz] device array: planes in float32 on the device,
    the planes' sums in float64 on the host (gaps between two states)."""
    return float(np.asarray(jnp.sum(a, axis=(1, 2)), np.float64).sum())


def totals(u, dx):
    """Mass and total energy of a float32 state, summed in float64 on the
    host: the audit's own rounding stays far under the drift it reads."""
    vol = dx ** 3
    return (float(np.asarray(u[0]).sum(dtype=np.float64)) * vol,
            float(np.asarray(u[4]).sum(dtype=np.float64)) * vol)


def initial_totals(config):
    """Mass and total energy of the initial condition, from the numbers the
    configuration states (not from the program's arrays)."""
    ic = config["initial_condition"]
    vol = float(ic["boxlen"]) ** 3
    g = float(config["physics"]["gamma"])
    mass = float(ic["d_ambient"]) * vol
    energy = float(ic["p_ambient"]) / (g - 1.0) * vol \
        + float(ic["p_point_times_volume"]) / (g - 1.0)
    return mass, energy


def ratio(num, den):
    return num / den if den > 0 else (0.0 if num == 0 else float("inf"))


def measure(got, ref, snap, config):
    """The numbers compared, each a gap that is 0 for identical runs.

    ``state_gap``: worst conserved variable's ``sum|got - ref|`` over the
    reference's own change across the slice ``sum|ref - in|`` — a state
    returned unchanged reads 1.  ``cell_gap``: the same with the largest
    cell in place of the sum (one altered cell shows here, not in a sum).  ``time_gap``: the same for the simulated
    time.  ``mass_drift`` / ``energy_drift``: totals of the slice's output
    against the initial condition's, relative.  The mass drift of a sound
    run grows with the steps done since the initial condition (and a faster
    program does more of them in a window), so what is held to a limit is
    ``mass_drift_per_step``: the drift over those steps."""
    u_in = jnp.asarray(snap["u_in"]).astype(jnp.float32)
    worst = cell = 0.0
    for k in range(5):
        num = _plane_sums(jnp.abs(got["u"][k] - ref["u"][k]))
        den = _plane_sums(jnp.abs(ref["u"][k] - u_in[k]))
        worst = max(worst, ratio(num, den))
        cell = max(cell, ratio(
            float(jnp.max(jnp.abs(got["u"][k] - ref["u"][k]))),
            float(jnp.max(jnp.abs(ref["u"][k] - u_in[k])))))
    dt_ref = ref["t"] - float(snap["t_in"])
    m0, e0 = initial_totals(config)
    m1, e1 = totals(got["u"], float(snap["dx"]))
    return {
        "state_gap": worst,
        "cell_gap": cell,
        "time_gap": abs(got["t"] - ref["t"]) / dt_ref if dt_ref > 0
        else float("inf"),
        "mass_drift": abs(m1 - m0) / m0,
        "mass_drift_per_step": abs(m1 - m0) / m0 / max(
            int(snap["nstep_out"]), 1),
        "energy_drift": abs(e1 - e0) / e0,
    }
