"""2D corner Riemann solvers for the CT edge EMF.

Counterpart of the reference's ``cmp_mag_flx``
(``mhd/umuscl.f90:1453-2024``; namelist ``riemann2d`` =
llf|roe|upwind|hll|hlla|hlld, mapping
``hydro/read_hydro_params.f90:207-221``).  The edge EMF is computed from
the FOUR states surrounding each cell edge instead of the
Gardiner-Stone arithmetic average — the upwinding that keeps strongly
magnetised shear flows (Orszag-Tang, loop advection) stable without the
GS correction terms.

States are labelled (x, y) with x in {L,R} the side along d1 and y in
{B,T} the side along d2.  The staggered fields at the edge are
single-valued per face: A = B_d1 on the two d1-faces (varies with y
only), B = B_d2 on the two d2-faces (varies with x only).

Solver families (all vectorized over the grid, ``jnp.where`` selection):

* ``hll`` / ``hlla`` — the four-state 2D-HLL average of Londrillo & Del
  Zanna (2004) with fast-magnetosonic / Alfven signal speeds.
* ``llf`` / ``roe`` / ``upwind`` — quarter-average of the four corner
  EMFs plus the DISSIPATIVE part of two orthogonal 1D solves on
  side-averaged states (the reference's ``zero_flux=0`` trick,
  ``mhd/umuscl.f90:1978``).
* ``hlld`` — the four-state HLLD with a contact (ustar, vstar), star
  states per quadrant, and Alfven-bounded inner waves
  (``mhd/umuscl.f90:1597-1805`` semantics, re-derived select-based).

Internally everything uses the reference EMF convention
eps = u*B - v*A (u = v_d1, v = v_d2); the caller converts to the code's
edge-EMF sign with ``e_edge = -sig * eps``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

from ramses_tpu.mhd import roe as roemod
from ramses_tpu.mhd.core import MhdStatic

_EPS = 1e-30

# quadrant keys
QUADS = (("L", "B"), ("R", "B"), ("L", "T"), ("R", "T"))


from ramses_tpu.mhd.riemann import _fast


def _alfven(r, bn, smallc):
    return jnp.sqrt(jnp.maximum(bn ** 2 / r, smallc ** 2))


def corner_emf(states: Dict[Tuple[str, str], Tuple], A_T, A_B, B_R, B_L,
               cfg: MhdStatic):
    """eps at each edge from the four surrounding corner states.

    ``states[(x, y)]`` = (r, p, u, v, w, c): density, pressure, the two
    in-plane velocities (u along d1, v along d2), the orthogonal
    velocity and the orthogonal cell field at the corner.  A_T/A_B:
    staggered B_d1 on the d2-above/below faces; B_R/B_L: staggered B_d2
    on the d1-right/left faces.  Returns eps = u*B - v*A upwinded per
    ``cfg.riemann2d``; the caller applies the orientation sign.
    """
    g = cfg.gamma
    sc = cfg.smallc
    rs = {k: jnp.maximum(s[0], cfg.smallr) for k, s in states.items()}
    ps = {k: jnp.maximum(s[1], cfg.smallr * sc ** 2)
          for k, s in states.items()}
    us = {k: s[2] for k, s in states.items()}
    vs = {k: s[3] for k, s in states.items()}
    ws = {k: s[4] for k, s in states.items()}
    cs = {k: s[5] for k, s in states.items()}
    A_of = {"B": A_B, "T": A_T}
    B_of = {"L": B_L, "R": B_R}
    eps = {k: us[k] * B_of[k[0]] - vs[k] * A_of[k[1]] for k in QUADS}

    kind = cfg.riemann2d
    if kind in ("hll", "hlla"):
        if kind == "hll":
            cx = {k: _fast(rs[k], ps[k], A_of[k[1]], B_of[k[0]], cs[k],
                           g, sc) for k in QUADS}
            cy = {k: _fast(rs[k], ps[k], B_of[k[0]], A_of[k[1]], cs[k],
                           g, sc) for k in QUADS}
        else:
            cx = {k: _alfven(rs[k], A_of[k[1]], sc) for k in QUADS}
            cy = {k: _alfven(rs[k], B_of[k[0]], sc) for k in QUADS}

        def mm(d):
            vals = list(d.values())
            lo = vals[0]
            hi = vals[0]
            for v in vals[1:]:
                lo = jnp.minimum(lo, v)
                hi = jnp.maximum(hi, v)
            return lo, hi

        umin, umax = mm(us)
        vmin, vmax = mm(vs)
        _, cxmax = mm(cx)
        _, cymax = mm(cy)
        SL = jnp.minimum(umin - cxmax, 0.0)
        SR = jnp.maximum(umax + cxmax, 0.0)
        SB = jnp.minimum(vmin - cymax, 0.0)
        ST = jnp.maximum(vmax + cymax, 0.0)
        dx_ = SR - SL + _EPS
        dy_ = ST - SB + _EPS
        # Londrillo & Del Zanna (2004) four-state 2D-HLL average
        return ((SL * SB * eps[("R", "T")] - SL * ST * eps[("R", "B")]
                 - SR * SB * eps[("L", "T")] + SR * ST * eps[("L", "B")])
                / (dx_ * dy_)
                - ST * SB / dy_ * (A_T - A_B)
                + SR * SL / dx_ * (B_R - B_L))

    if kind in ("llf", "roe", "upwind"):
        ebar = 0.25 * sum(eps.values())

        def avg(d, idx, side):
            ks = [k for k in QUADS if k[idx] == side]
            return 0.5 * (d[ks[0]] + d[ks[1]])

        # x-solve: rotated layout [rho, vn=u, vt1=v, vt2=w, P, Bn, Bt1=B,
        # Bt2=C] on y-averaged side states
        def pack_x(side):
            return [avg(rs, 0, side), avg(us, 0, side),
                    avg(vs, 0, side), avg(ws, 0, side),
                    avg(ps, 0, side), jnp.zeros_like(A_T),
                    B_of[side], avg(cs, 0, side)]

        def pack_y(side):
            return [avg(rs, 1, side), avg(vs, 1, side),
                    avg(us, 1, side), avg(ws, 1, side),
                    avg(ps, 1, side), jnp.zeros_like(A_T),
                    A_of[side], avg(cs, 1, side)]

        bn_x = 0.5 * (A_T + A_B)
        bn_y = 0.5 * (B_R + B_L)
        if kind == "llf":
            # the corner assembly reads the Bt1 row alone: row lists,
            # no stack (the tiled CT kernel traces this branch)
            def diss5(ql, qr, bn):
                return roemod.llf_dissipation_bt1(ql, qr, bn, cfg)
        else:
            full = {"roe": roemod.roe_dissipation,
                    "upwind": roemod.upwind_dissipation}[kind]

            def diss5(ql, qr, bn):
                return full(jnp.stack(ql), jnp.stack(qr), bn, cfg)[5]
        dx5 = diss5(pack_x("L"), pack_x("R"), bn_x)
        dy5 = diss5(pack_y("B"), pack_y("T"), bn_y)
        return ebar - dx5 + dy5

    if kind == "hlld":
        return _hlld2d(rs, ps, us, vs, cs, eps, A_of, B_of, cfg)

    raise NotImplementedError(f"riemann2d={kind!r}")


def _hlld2d(rs, ps, us, vs, cs, eps, A_of, B_of, cfg: MhdStatic):
    """Four-state HLLD corner EMF (contact + Alfven-bounded fan)."""
    g = cfg.gamma
    sc = cfg.smallc
    LB, RB, LT, RT = (("L", "B"), ("R", "B"), ("L", "T"), ("R", "T"))

    cx = {k: _fast(rs[k], ps[k], A_of[k[1]], B_of[k[0]], cs[k], g, sc)
          for k in (LB, RB, LT, RT)}
    cy = {k: _fast(rs[k], ps[k], B_of[k[0]], A_of[k[1]], cs[k], g, sc)
          for k in (LB, RB, LT, RT)}

    def extr(d, f):
        vals = list(d.values())
        out = vals[0]
        for v in vals[1:]:
            out = f(out, v)
        return out

    cxm = extr(cx, jnp.maximum)
    cym = extr(cy, jnp.maximum)
    SL = extr(us, jnp.minimum) - cxm
    SR = extr(us, jnp.maximum) + cxm
    SB = extr(vs, jnp.minimum) - cym
    ST = extr(vs, jnp.maximum) + cym

    ptot = {k: ps[k] + 0.5 * (A_of[k[1]] ** 2 + B_of[k[0]] ** 2
                              + cs[k] ** 2)
            for k in (LB, RB, LT, RT)}
    # mass-weighted contact speeds (the reference's ustar/vstar)
    rcx = {k: rs[k] * ((us[k] - SL) if k[0] == "L" else (SR - us[k]))
           for k in (LB, RB, LT, RT)}
    rcy = {k: rs[k] * ((vs[k] - SB) if k[1] == "B" else (ST - vs[k]))
           for k in (LB, RB, LT, RT)}
    ustar = ((sum(rcx[k] * us[k] for k in (LB, RB, LT, RT))
              + (ptot[LB] - ptot[RB] + ptot[LT] - ptot[RT]))
             / (sum(rcx.values()) + _EPS))
    vstar = ((sum(rcy[k] * vs[k] for k in (LB, RB, LT, RT))
              + (ptot[LB] - ptot[LT] + ptot[RB] - ptot[RT]))
             / (sum(rcy.values()) + _EPS))

    Sx = {"L": SL, "R": SR}
    Sy = {"B": SB, "T": ST}
    rstar_x, rstar_y, rstar = {}, {}, {}
    Astar, Bstar = {}, {}
    Ex_star, Ey_star, E_star = {}, {}, {}
    for k in (LB, RB, LT, RT):
        fx = (Sx[k[0]] - us[k]) / (Sx[k[0]] - ustar
                                   + jnp.where(Sx[k[0]] >= ustar,
                                               _EPS, -_EPS))
        fy = (Sy[k[1]] - vs[k]) / (Sy[k[1]] - vstar
                                   + jnp.where(Sy[k[1]] >= vstar,
                                               _EPS, -_EPS))
        rstar_x[k] = rs[k] * fx
        rstar_y[k] = rs[k] * fy
        rstar[k] = rs[k] * fx * fy
        Bstar[k] = B_of[k[0]] * fx
        Astar[k] = A_of[k[1]] * fy
        Ex_star[k] = ustar * Bstar[k] - vs[k] * A_of[k[1]]
        Ey_star[k] = us[k] * B_of[k[0]] - vstar * Astar[k]
        E_star[k] = ustar * Bstar[k] - vstar * Astar[k]

    def ca_side(keys, field, fstar, rsx):
        out = jnp.full_like(SL, sc)
        for k in keys:
            out = jnp.maximum(out, jnp.abs(field[k[1] if field is A_of
                                                 else k[0]])
                              / jnp.sqrt(jnp.maximum(rsx[k],
                                                     cfg.smallr)))
            out = jnp.maximum(out, jnp.abs(fstar[k])
                              / jnp.sqrt(jnp.maximum(rstar[k],
                                                     cfg.smallr)))
        return out

    caL = ca_side((LB, LT), A_of, Astar, rstar_x)
    caR = ca_side((RB, RT), A_of, Astar, rstar_x)
    caB = ca_side((LB, RB), B_of, Bstar, rstar_y)
    caT = ca_side((LT, RT), B_of, Bstar, rstar_y)
    SAL = jnp.minimum(ustar - caL, 0.0)
    SAR = jnp.maximum(ustar + caR, 0.0)
    SAB = jnp.minimum(vstar - caB, 0.0)
    SAT = jnp.maximum(vstar + caT, 0.0)
    dax = SAR - SAL + _EPS
    day = SAT - SAB + _EPS
    AstarT = (SAR * Astar[RT] - SAL * Astar[LT]) / dax
    AstarB = (SAR * Astar[RB] - SAL * Astar[LB]) / dax
    BstarR = (SAT * Bstar[RT] - SAB * Bstar[RB]) / day
    BstarL = (SAT * Bstar[LT] - SAB * Bstar[LB]) / day

    # supersonic rows/columns
    e_b = jnp.where(SL > 0.0, eps[LB],
                    jnp.where(SR < 0.0, eps[RB],
                              (SAR * Ex_star[LB] - SAL * Ex_star[RB]
                               + SAR * SAL * (B_of["R"] - B_of["L"]))
                              / dax))
    e_t = jnp.where(SL > 0.0, eps[LT],
                    jnp.where(SR < 0.0, eps[RT],
                              (SAR * Ex_star[LT] - SAL * Ex_star[RT]
                               + SAR * SAL * (B_of["R"] - B_of["L"]))
                              / dax))
    e_l = (SAT * Ey_star[LB] - SAB * Ey_star[LT]
           - SAT * SAB * (A_of["T"] - A_of["B"])) / day
    e_r = (SAT * Ey_star[RB] - SAB * Ey_star[RT]
           - SAT * SAB * (A_of["T"] - A_of["B"])) / day
    e_c = ((SAL * SAB * E_star[RT] - SAL * SAT * E_star[RB]
            - SAR * SAB * E_star[LT] + SAR * SAT * E_star[LB])
           / (dax * day)
           - SAT * SAB / day * (AstarT - AstarB)
           + SAR * SAL / dax * (BstarR - BstarL))
    return jnp.where(SB > 0.0, e_b,
                     jnp.where(ST < 0.0, e_t,
                               jnp.where(SL > 0.0, e_l,
                                         jnp.where(SR < 0.0, e_r,
                                                   e_c))))
