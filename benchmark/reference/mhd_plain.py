"""Plain constrained-transport MHD step, the yardstick of the MHD cell.

Written from the published scheme in straightforward ``jax.numpy`` with no
kernels, tiling or padding; it imports nothing of ``ramses_tpu``.

The scheme (RAMSES ``mhd/umuscl.f90`` + ``mhd/godunov_fine.f90`` as
SURVEY.md describes them; Fromang, Hennebelle & Teyssier 2006): minmod
slopes of the primitive variables, a half-step predictor, three 1D HLLD
solves (Miyoshi & Kusano 2005) for the hydro fluxes, a 2D Riemann problem at
every cell edge for the electromotive force (``riemann2d='llf'``: the mean
of the four corner EMFs plus the Lax-Friedrichs dissipation of two 1D
problems between side-averaged states), the induction equation on the
staggered field as the curl of those EMFs - so div B stays at round-off -
and a conservative update of mass, momentum and total energy.

Where the program under test departs from ``umuscl.f90``, this reference
follows the PROGRAM (it is the program's semantics that ``correct`` holds a
run to), and says so here:

* faces stored once: ``bf[c]`` is B_c on the LOW face of each cell along
  ``c``; the high face is the neighbour's low face (upstream keeps both
  faces of every cell, ``nvar+1..nvar+3``);
* the predictor is a conservative Hancock half-step from the cell's own
  reconstructed faces, ``U + dt/2dx * sum_d [F(q - dq/2) - F(q + dq/2)]``,
  with the staggered field predicted by the curl of edge-averaged cell
  EMFs, in place of ``trace3d``'s primitive source terms;
* corner states of the 2D problem are the half-step cell state plus the two
  half slopes, in primitive variables, density and pressure floored.

State: ``u[0]`` density, ``u[1:4]`` momentum, ``u[4]`` total energy (with
the magnetic part), ``u[5:8]`` the cell-centred field (derived: the mean of
a cell's two faces), shape ``[8, nx, ny, nz]``; ``bf`` ``[3, nx, ny, nz]``.
Neighbours are taken with ``jnp.roll``: on a periodic box that IS the
boundary condition; on a slab with a margin the wrapped values land in
cells no caller reads (the step reaches 2 cells and 3 faces).  Every
operation runs in ``u.dtype``, so the same code in bfloat16 is the
lower-precision control of the comparison.
"""

import jax.numpy as jnp

NDIM = 3
IE = 4
IB = 5
TINY = 1e-30          # keeps 0/0 out of the HLLD star states


class Physics:
    """The numbers a configuration states (``configs/<name>.json`` →
    ``physics``); floors are the program's documented defaults."""

    def __init__(self, d):
        self.gamma = float(d["gamma"])
        self.courant_factor = float(d["courant_factor"])
        self.smallr = float(d["smallr"])
        self.smallc = float(d["smallc"])
        if int(d["slope_type"]) != 1 or d["riemann"] != "hlld" \
                or d["riemann2d"] != "llf":
            raise ValueError("the plain reference implements minmod slopes, "
                             "the HLLD solver and the LLF corner EMF")
        self.smallp = self.smallr * self.smallc ** 2
        self.smalle = self.smallc ** 2 / self.gamma / (self.gamma - 1.0)

    def _key(self):
        return (self.gamma, self.courant_factor, self.smallr, self.smallc)

    def __hash__(self):           # a jit static argument, equal by value
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Physics) and self._key() == other._key()


def up(a, d):
    """``a`` at the neighbour one cell UP along ``d`` (index + 1)."""
    return jnp.roll(a, -1, axis=d)


def down(a, d):
    return jnp.roll(a, 1, axis=d)


def centred(bf):
    """Cell-centred field: the mean of a cell's low and high face."""
    return [0.5 * (bf[c] + up(bf[c], c)) for c in range(NDIM)]


def primitives(cons, ph):
    """[rho, vx, vy, vz, P, Bx, By, Bz] from eight conservative rows."""
    rho = jnp.maximum(cons[0], ph.smallr)
    vel = [cons[1 + c] / rho for c in range(NDIM)]
    b = [cons[IB + c] for c in range(NDIM)]
    ekin = 0.5 * (vel[0] * vel[0] + vel[1] * vel[1] + vel[2] * vel[2])
    emag = 0.5 * (b[0] * b[0] + b[1] * b[1] + b[2] * b[2]) / rho
    eint = jnp.maximum(cons[IE] / rho - ekin - emag, ph.smalle)
    return [rho] + vel + [(ph.gamma - 1.0) * rho * eint] + b


def conservatives(q, ph):
    rho = jnp.maximum(q[0], ph.smallr)
    vel, b = q[1:4], q[IB:IB + 3]
    etot = q[IE] / (ph.gamma - 1.0) \
        + 0.5 * rho * (vel[0] * vel[0] + vel[1] * vel[1] + vel[2] * vel[2]) \
        + 0.5 * (b[0] * b[0] + b[1] * b[1] + b[2] * b[2])
    return [rho] + [rho * v for v in vel] + [etot] + list(b)


def physical_flux(q, d, ph):
    """Ideal-MHD flux along ``d`` of a primitive state, eight rows."""
    rho = jnp.maximum(q[0], ph.smallr)
    vel, p, b = q[1:4], q[IE], q[IB:IB + 3]
    b2 = b[0] * b[0] + b[1] * b[1] + b[2] * b[2]
    ptot = p + 0.5 * b2
    vdotb = vel[0] * b[0] + vel[1] * b[1] + vel[2] * b[2]
    etot = p / (ph.gamma - 1.0) + 0.5 * rho * (
        vel[0] * vel[0] + vel[1] * vel[1] + vel[2] * vel[2]) + 0.5 * b2
    out = [rho * vel[d]]
    for c in range(NDIM):
        out.append(rho * vel[d] * vel[c] - b[d] * b[c]
                   + (ptot if c == d else 0.0))
    out.append((etot + ptot) * vel[d] - b[d] * vdotb)
    for c in range(NDIM):
        out.append(jnp.zeros_like(rho) if c == d
                   else vel[d] * b[c] - vel[c] * b[d])
    return out


def fast_speed(rho, p, bn, bt1, bt2, ph):
    """Fast magnetosonic speed along the direction of ``bn``."""
    c2 = ph.gamma * p / rho
    b2 = (bn * bn + bt1 * bt1 + bt2 * bt2) / rho
    s = c2 + b2
    disc = jnp.sqrt(jnp.maximum(s * s - 4.0 * c2 * bn * bn / rho, 0.0))
    return jnp.sqrt(jnp.maximum(0.5 * (s + disc), ph.smallc ** 2))


def courant_rate(u, bf, dx, ph):
    """``sum_d (|v_d| + c_fast,d) / dx`` of every cell; the CFL step is
    ``courant_factor`` over its largest value."""
    q = primitives(list(u[:IB]) + centred(bf), ph)
    rho = jnp.maximum(q[0], ph.smallr)
    p = jnp.maximum(q[IE], ph.smallp)
    rate = 0.0
    for d in range(NDIM):
        t1, t2 = (d + 1) % 3, (d + 2) % 3
        rate = rate + (jnp.abs(q[1 + d]) + fast_speed(
            rho, p, q[IB + d], q[IB + t1], q[IB + t2], ph)) / dx
    return rate


def courant_dt(u, bf, dx, ph):
    return ph.courant_factor / jnp.max(courant_rate(u, bf, dx, ph))


def minmod(a, b):
    """Minmod of the one-sided differences ``a`` (left) and ``b`` (right)."""
    centre = 0.5 * (a + b)
    lim = jnp.where(a * b <= 0.0, 0.0, jnp.minimum(jnp.abs(a), jnp.abs(b)))
    return jnp.sign(centre) * jnp.minimum(lim, jnp.abs(centre))


def hlld(left, right, bn, ph):
    """The five hydro fluxes (mass, normal and two transverse momenta,
    energy) of Miyoshi & Kusano's five-wave solver.  ``left``/``right``:
    (rho, vn, vt1, vt2, P, Bt1, Bt2); ``bn`` the face's own normal field."""
    g = ph.gamma

    def side(s):
        rho = jnp.maximum(s[0], ph.smallr)
        p = jnp.maximum(s[4], ph.smallp)
        vn, vt1, vt2, bt1, bt2 = s[1], s[2], s[3], s[5], s[6]
        b2 = bn * bn + bt1 * bt1 + bt2 * bt2
        etot = p / (g - 1.0) + 0.5 * rho * (vn * vn + vt1 * vt1
                                            + vt2 * vt2) + 0.5 * b2
        ptot = p + 0.5 * b2
        vdotb = vn * bn + vt1 * bt1 + vt2 * bt2
        cons = [rho, rho * vn, rho * vt1, rho * vt2, etot]
        flux = [rho * vn, rho * vn * vn - bn * bn + ptot,
                rho * vn * vt1 - bn * bt1, rho * vn * vt2 - bn * bt2,
                (etot + ptot) * vn - bn * vdotb]
        return dict(rho=rho, p=p, vn=vn, vt1=vt1, vt2=vt2, bt1=bt1, bt2=bt2,
                    etot=etot, ptot=ptot, vdotb=vdotb, cons=cons, flux=flux,
                    cf=fast_speed(rho, p, bn, bt1, bt2, ph))

    L, R = side(left), side(right)
    s_l = jnp.minimum(L["vn"] - L["cf"], R["vn"] - R["cf"])
    s_r = jnp.maximum(L["vn"] + L["cf"], R["vn"] + R["cf"])
    d_l = L["rho"] * (s_l - L["vn"])
    d_r = R["rho"] * (s_r - R["vn"])
    s_m = (d_r * R["vn"] - d_l * L["vn"] - R["ptot"] + L["ptot"]) \
        / (d_r - d_l + TINY)
    pt_star = (d_r * L["ptot"] - d_l * R["ptot"]
               + d_l * d_r * (R["vn"] - L["vn"])) / (d_r - d_l + TINY)

    def star(S, dS, s_wave):
        """The state between the outer wave ``s_wave`` and its Alfven wave."""
        rho = dS / (s_wave - s_m + TINY)
        den = dS * (s_wave - s_m) - bn * bn
        flat = jnp.abs(den) < 1e-12 * (
            S["rho"] * (jnp.abs(s_wave) + jnp.abs(S["vn"])) ** 2
            + bn * bn + TINY)
        den = jnp.where(flat, 1.0, den)
        num = dS * (s_wave - S["vn"]) - bn * bn

        def keep(plain, starred):
            return jnp.where(flat, plain, starred)

        vt1 = keep(S["vt1"], S["vt1"] - bn * S["bt1"] * (s_m - S["vn"]) / den)
        vt2 = keep(S["vt2"], S["vt2"] - bn * S["bt2"] * (s_m - S["vn"]) / den)
        bt1 = keep(S["bt1"], S["bt1"] * num / den)
        bt2 = keep(S["bt2"], S["bt2"] * num / den)
        vdotb = s_m * bn + vt1 * bt1 + vt2 * bt2
        etot = ((s_wave - S["vn"]) * S["etot"] - S["ptot"] * S["vn"]
                + pt_star * s_m + bn * (S["vdotb"] - vdotb)) \
            / (s_wave - s_m + TINY)
        return dict(rho=rho, vt1=vt1, vt2=vt2, bt1=bt1, bt2=bt2, etot=etot,
                    vdotb=vdotb)

    A, B = star(L, d_l, s_l), star(R, d_r, s_r)
    root_l = jnp.sqrt(jnp.maximum(A["rho"], ph.smallr))
    root_r = jnp.sqrt(jnp.maximum(B["rho"], ph.smallr))
    s_la = s_m - jnp.abs(bn) / root_l
    s_ra = s_m + jnp.abs(bn) / root_r
    sgn = jnp.sign(bn)
    both = root_l + root_r + TINY
    vt1 = (root_l * A["vt1"] + root_r * B["vt1"]
           + sgn * (B["bt1"] - A["bt1"])) / both
    vt2 = (root_l * A["vt2"] + root_r * B["vt2"]
           + sgn * (B["bt2"] - A["bt2"])) / both
    bt1 = (root_l * B["bt1"] + root_r * A["bt1"]
           + sgn * root_l * root_r * (B["vt1"] - A["vt1"])) / both
    bt2 = (root_l * B["bt2"] + root_r * A["bt2"]
           + sgn * root_l * root_r * (B["vt2"] - A["vt2"])) / both
    vdotb = s_m * bn + vt1 * bt1 + vt2 * bt2
    e_la = A["etot"] - root_l * sgn * (A["vdotb"] - vdotb)
    e_ra = B["etot"] + root_r * sgn * (B["vdotb"] - vdotb)

    def rows(rho, v1, v2, etot):
        return [rho, rho * s_m, rho * v1, rho * v2, etot]

    u_a, u_b = (rows(A["rho"], A["vt1"], A["vt2"], A["etot"]),
                rows(B["rho"], B["vt1"], B["vt2"], B["etot"]))
    u_aa, u_bb = (rows(A["rho"], vt1, vt2, e_la),
                  rows(B["rho"], vt1, vt2, e_ra))
    out = []
    for k in range(5):
        f_a = L["flux"][k] + s_l * (u_a[k] - L["cons"][k])
        f_b = R["flux"][k] + s_r * (u_b[k] - R["cons"][k])
        f_aa = f_a + s_la * (u_aa[k] - u_a[k])
        f_bb = f_b + s_ra * (u_bb[k] - u_b[k])
        out.append(jnp.where(
            s_l > 0.0, L["flux"][k], jnp.where(
                s_la > 0.0, f_a, jnp.where(
                    s_m > 0.0, f_aa, jnp.where(
                        s_ra > 0.0, f_bb, jnp.where(
                            s_r > 0.0, f_b, R["flux"][k]))))))
    return out


def corner_emf(corner, a_top, a_bot, b_right, b_left, ph):
    """``u*B - v*A`` at a cell edge from the four states round it.

    ``corner[(x, y)]``, x in L/R along the pair's first direction and y in
    B/T along its second: (rho, P, u, v, w, C) with u, v the in-plane
    velocities, w and C the velocity and cell field normal to the plane.
    ``a_*``: the first direction's face field above and below the edge;
    ``b_*``: the second's, right and left of it.  LLF: the mean of the four
    corner EMFs, less the dissipation of the 1D problem across the first
    direction (between states averaged over y), plus that across the second."""
    A = {"B": a_bot, "T": a_top}
    B = {"L": b_left, "R": b_right}
    quads = [("L", "B"), ("R", "B"), ("L", "T"), ("R", "T")]
    rho = {k: jnp.maximum(corner[k][0], ph.smallr) for k in quads}
    p = {k: jnp.maximum(corner[k][1], ph.smallp) for k in quads}
    u = {k: corner[k][2] for k in quads}
    v = {k: corner[k][3] for k in quads}
    c = {k: corner[k][5] for k in quads}
    mean = 0.25 * sum(u[k] * B[k[0]] - v[k] * A[k[1]] for k in quads)

    def half(field, axis, side):
        a, b = [k for k in quads if k[axis] == side]
        return 0.5 * (field[a] + field[b])

    def dissipation(axis, vel, bn, bt):
        """``0.5 * max(|vn| + c_fast) * (Bt_right - Bt_left)`` of the 1D
        problem across ``axis`` (0: sides L/R, 1: sides B/T)."""
        lo, hi = ("L", "R") if axis == 0 else ("B", "T")
        speed = [jnp.abs(half(vel, axis, s)) + fast_speed(
            half(rho, axis, s), half(p, axis, s), bn, bt[s],
            half(c, axis, s), ph) for s in (lo, hi)]
        return 0.5 * jnp.maximum(speed[0], speed[1]) * (bt[hi] - bt[lo])

    return (mean - dissipation(0, u, 0.5 * (a_top + a_bot), B)
            + dissipation(1, v, 0.5 * (b_right + b_left), A))


# +1 for the pairs in cyclic order: E_e of the pair is -(v x B)_e
PAIRS = [(0, 1, 1.0), (0, 2, -1.0), (1, 2, 1.0)]


def step(u, bf, dt, dx, ph):
    """One step of the whole periodic box: ``(u', bf')``."""
    dt = jnp.asarray(dt, u.dtype)
    bf = [bf[c] for c in range(NDIM)]
    cell = list(u[:IB]) + centred(bf)
    q = primitives(cell, ph)
    slope = [[minmod(f - down(f, d), up(f, d) - f) for f in q]
             for d in range(NDIM)]

    # half-step predictor of the cell state, from the cell's own faces
    half = [jnp.zeros_like(f) for f in cell]
    lo_hi = []
    for d in range(NDIM):
        hi = [f + 0.5 * s for f, s in zip(q, slope[d])]
        lo = [f - 0.5 * s for f, s in zip(q, slope[d])]
        f_hi, f_lo = physical_flux(hi, d, ph), physical_flux(lo, d, ph)
        half = [h - (0.5 * dt / dx) * (a - b)
                for h, a, b in zip(half, f_hi, f_lo)]
        lo_hi.append((lo, hi))

    # ... and of the staggered field: curl of edge-averaged cell EMFs
    bf_half = list(bf)
    for d1, d2, sig in PAIRS:
        e_cell = sig * (q[1 + d2] * q[IB + d1] - q[1 + d1] * q[IB + d2])
        e_edge = 0.25 * (e_cell + down(e_cell, d1) + down(e_cell, d2)
                         + down(down(e_cell, d1), d2))
        bf_half[d1] = bf_half[d1] - sig * (0.5 * dt / dx) * (
            up(e_edge, d2) - e_edge)
        bf_half[d2] = bf_half[d2] + sig * (0.5 * dt / dx) * (
            up(e_edge, d1) - e_edge)

    # three 1D HLLD solves for the hydro fluxes at the low faces
    new = list(cell[:IB])
    for d in range(NDIM):
        t1, t2 = (d + 1) % 3, (d + 2) % 3
        lo, hi = lo_hi[d]
        left = primitives([down(a + h, d) for a, h in
                           zip(conservatives(hi, ph), half)], ph)
        right = primitives([a + h for a, h in
                            zip(conservatives(lo, ph), half)], ph)

        def rotated(s):
            return (s[0], s[1 + d], s[1 + t1], s[1 + t2], s[IE],
                    s[IB + t1], s[IB + t2])

        f = hlld(rotated(left), rotated(right), bf_half[d], ph)
        flux = [None] * 5
        flux[0], flux[IE] = f[0], f[4]
        flux[1 + d], flux[1 + t1], flux[1 + t2] = f[1], f[2], f[3]
        new = [a + (dt / dx) * (fl - up(fl, d)) for a, fl in zip(new, flux)]

    # edge EMFs from the 2D corner problem, then the induction equation
    q_half = primitives([a + h for a, h in zip(cell, half)], ph)
    bf_new = list(bf)
    for d1, d2, sig in PAIRS:
        dn = 3 - d1 - d2

        def corner(s1, s2, shifts):
            def at(k):
                a = q_half[k] + 0.5 * (s1 * slope[d1][k] + s2 * slope[d2][k])
                if k == 0:
                    a = jnp.maximum(a, ph.smallr)
                if k == IE:
                    a = jnp.maximum(a, ph.smallp)
                for ax in shifts:
                    a = down(a, ax)
                return a
            return tuple(at(k) for k in
                         (0, IE, 1 + d1, 1 + d2, 1 + dn, IB + dn))

        # the edge is the low-low corner of cell (i, j): the cell itself is
        # its right-top quadrant and sees it along its low/low half slopes
        states = {("R", "T"): corner(-1.0, -1.0, ()),
                  ("L", "T"): corner(1.0, -1.0, (d1,)),
                  ("R", "B"): corner(-1.0, 1.0, (d2,)),
                  ("L", "B"): corner(1.0, 1.0, (d1, d2))}
        eps = corner_emf(states, bf_half[d1], down(bf_half[d1], d2),
                         bf_half[d2], down(bf_half[d2], d1), ph)
        e_edge = -sig * eps
        bf_new[d1] = bf_new[d1] - sig * (dt / dx) * (up(e_edge, d2) - e_edge)
        bf_new[d2] = bf_new[d2] + sig * (dt / dx) * (up(e_edge, d1) - e_edge)

    return jnp.stack(new + centred(bf_new)), jnp.stack(bf_new)
