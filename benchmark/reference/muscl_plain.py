"""Plain unsplit MUSCL-Hancock / LLF hydro step, the benchmark's yardstick.

Written from the published scheme (Teyssier 2002, section 3; RAMSES
``hydro/umuscl.f90`` and ``godunov_utils.f90`` as described in SURVEY.md),
in straightforward ``jax.numpy`` with no kernels, tiling or padding.  It
imports nothing of ``ramses_tpu``.

State layout: ``u[0]`` density, ``u[1:4]`` momentum, ``u[4]`` total energy,
shape ``[5, nx, ny, nz]``.  Neighbours are taken with ``jnp.roll``: on a
periodic box that IS the boundary condition; on a box with a margin the
wrapped values land in cells no caller reads.  Every operation runs in
``u.dtype``, so the same code computed in bfloat16 is the lower-precision
control of the comparison.
"""

import jax.numpy as jnp

NDIM = 3
IE = 4


class Physics:
    """The numbers a configuration states (``configs/<name>.json`` →
    ``physics``); floors are the program's documented defaults."""

    def __init__(self, d):
        self.gamma = float(d["gamma"])
        self.courant_factor = float(d["courant_factor"])
        self.smallr = float(d["smallr"])
        self.smallc = float(d["smallc"])
        if int(d["slope_type"]) != 1 or d["riemann"] != "llf" \
                or d["scheme"] != "muscl":
            raise ValueError("the plain reference implements minmod slopes, "
                             "the MUSCL-Hancock predictor and the LLF solver")
        self.smallp = self.smallc ** 2 / self.gamma
        self.smalle = self.smallc ** 2 / self.gamma / (self.gamma - 1.0)

    def _key(self):
        return (self.gamma, self.courant_factor, self.smallr, self.smallc)

    def __hash__(self):           # a jit static argument, equal by value
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Physics) and self._key() == other._key()


def primitives(u, ph):
    """(rho, [vx, vy, vz], P) with the density and energy floors."""
    rho = jnp.maximum(u[0], ph.smallr)
    vel = [u[1 + d] / rho for d in range(NDIM)]
    ekin = 0.5 * (vel[0] * vel[0] + vel[1] * vel[1] + vel[2] * vel[2])
    eint = jnp.maximum(u[IE] / rho - ekin, ph.smalle)
    return rho, vel, (ph.gamma - 1.0) * rho * eint


def minmod(a, b):
    """Minmod of the one-sided differences ``a`` (left) and ``b`` (right)."""
    centred = 0.5 * (a + b)
    lim = jnp.where(a * b <= 0.0, 0.0, jnp.minimum(jnp.abs(a), jnp.abs(b)))
    return jnp.sign(centred) * jnp.minimum(lim, jnp.abs(centred))


def courant_dt(u, dx, ph, valid=None):
    """CFL step of a set of cells: ``courant_factor * dx / (3c + sum|v|)``
    (the gravity-free limit of RAMSES ``cmpdt``), capped as the program
    caps it.  ``u`` is ``[5, ...]``; ``valid`` masks cells that count."""
    rho = jnp.maximum(u[0], ph.smallr)
    vel = [u[1 + d] / rho for d in range(NDIM)]
    eint = u[IE] - 0.5 * rho * (vel[0] * vel[0] + vel[1] * vel[1]
                                + vel[2] * vel[2])
    p = jnp.maximum((ph.gamma - 1.0) * eint, rho * ph.smallp)
    ws = 3.0 * jnp.sqrt(ph.gamma * p / rho) \
        + jnp.abs(vel[0]) + jnp.abs(vel[1]) + jnp.abs(vel[2])
    # cmpdt's gravity-strength form at zero gravity: ratio = 1e-4
    ratio = 1e-4
    dtc = dx / ws * (jnp.sqrt(1.0 + 2.0 * ph.courant_factor * ratio)
                     - 1.0) / ratio
    if valid is not None:
        dtc = jnp.where(valid, dtc, jnp.inf)
    return jnp.minimum(ph.courant_factor * dx / ph.smallc, jnp.min(dtc))


def _llf(left, right, d, ph):
    """Local Lax-Friedrichs flux through a face normal to ``d``.
    ``left``/``right`` are (rho, [v], P) tuples.  Returns the five
    conservative fluxes in state layout."""
    def side(state):
        rho = jnp.maximum(state[0], ph.smallr)
        vel = state[1]
        p = jnp.maximum(state[2], rho * ph.smallp)
        vn = vel[d]
        etot = p / (ph.gamma - 1.0) + 0.5 * rho * (
            vel[0] * vel[0] + vel[1] * vel[1] + vel[2] * vel[2])
        c = jnp.sqrt(jnp.maximum(ph.gamma * p / rho, ph.smallc ** 2))
        cons = [rho, rho * vel[0], rho * vel[1], rho * vel[2], etot]
        flux = [rho * vn, rho * vn * vel[0], rho * vn * vel[1],
                rho * vn * vel[2], vn * (etot + p)]
        flux[1 + d] = flux[1 + d] + p
        return cons, flux, jnp.abs(vn) + c

    cl, fl, sl = side(left)
    cr, fr, sr = side(right)
    smax = jnp.maximum(sl, sr)
    return [0.5 * (fl[k] + fr[k] - smax * (cr[k] - cl[k])) for k in range(5)]


def face_fluxes(u, dt, dx, ph):
    """Time-centred fluxes, already times ``dt/dx``.  ``flux[d]`` is
    ``[5, nx, ny, nz]`` at the LOW face of each cell along ``d`` (between
    cells ``i-1`` and ``i``)."""
    dt = jnp.asarray(dt, u.dtype)
    rho, vel, p = primitives(u, ph)
    fields = [rho] + vel + [p]
    # minmod slopes of the primitive variables, per direction
    slope = []
    for d in range(NDIM):
        ax = d
        slope.append([minmod(f - jnp.roll(f, 1, axis=ax),
                             jnp.roll(f, -1, axis=ax) - f) for f in fields])
    # source terms of the half-step predictor (primitive Euler equations)
    divv = slope[0][1] + slope[1][2] + slope[2][3]
    s_rho = -(vel[0] * slope[0][0] + vel[1] * slope[1][0]
              + vel[2] * slope[2][0]) - divv * rho
    s_p = -(vel[0] * slope[0][4] + vel[1] * slope[1][4]
            + vel[2] * slope[2][4]) - divv * ph.gamma * p
    s_v = [-(vel[0] * slope[0][1 + j] + vel[1] * slope[1][1 + j]
             + vel[2] * slope[2][1 + j]) - slope[j][4] / rho
           for j in range(NDIM)]
    half = 0.5 * dt / dx
    out = []
    for d in range(NDIM):
        def edge(sign):
            r_e = rho + sign * 0.5 * slope[d][0] + s_rho * half
            r_e = jnp.where(r_e < ph.smallr, rho, r_e)
            v_e = [vel[j] + sign * 0.5 * slope[d][1 + j] + s_v[j] * half
                   for j in range(NDIM)]
            p_e = p + sign * 0.5 * slope[d][4] + s_p * half
            return r_e, v_e, p_e

        hi = edge(+1.0)          # state at the cell's high face
        lo = edge(-1.0)          # state at the cell's low face
        left = (jnp.roll(hi[0], 1, axis=d),
                [jnp.roll(v, 1, axis=d) for v in hi[1]],
                jnp.roll(hi[2], 1, axis=d))
        f = _llf(left, lo, d, ph)
        out.append(jnp.stack(f) * (dt / dx))
    return out


def apply(u, flux, keep=None):
    """``u + sum_d (F_low - F_high)``; ``keep[d]`` (bool, low-face layout)
    drops faces whose flux another level supplies."""
    new = u
    for d in range(NDIM):
        f = flux[d] if keep is None else jnp.where(keep[d][None], flux[d], 0)
        new = new + (f - jnp.roll(f, -1, axis=1 + d))
    return new


def step(u, dt, dx, ph):
    """One step of the whole periodic box."""
    return apply(u, face_fluxes(u, dt, dx, ph))
