"""Roe-type characteristic-upwind solver for adiabatic MHD.

Counterpart of the reference's ``athena_roe`` 1D solver
(``mhd/godunov_utils.f90:878``, dispatched from ``mhd/umuscl.f90:1396``
for ``riemann='roe'`` and for the 2D corner solver ``riemann2d='roe'``).

Built from the published formulation, not the reference's code:

* Cargo & Gallice (1997) Roe averages — sqrt-density-weighted
  velocities and enthalpy, OPPOSITE-weighted transverse field, and the
  X/Y correction terms in the effective sound speed.
* Roe & Balsara (1996) normalized magnetosonic eigenvectors
  (alpha_f/alpha_s/beta with the degenerate-limit conventions), written
  in PRIMITIVE variables where they are compact and well-conditioned.
* Wave strengths are recovered by a batched 7x7 linear solve
  ``R_p @ alpha = dW`` instead of hand-coded left eigenvectors: the
  expansion is then complete by construction (machine-exact
  ``sum_k alpha_k R_k = dW``), which is the property conservation
  depends on.  The dissipation is mapped to conserved variables through
  the analytic dU/dW Jacobian at the Roe mean.

The 7-wave system (Bn is a constant parameter of the interface):
entropy, 2 Alfven, 2 slow, 2 fast.  No entropy fix (the reference
applies none either).

``zero_flux`` multiplies the centered flux part — the reference's
convention that lets the 2D corner solver reuse the 1D dissipation
(``mhd/umuscl.f90:1978`` passes 0).
"""

from __future__ import annotations

import jax.numpy as jnp

from ramses_tpu.mhd.core import MhdStatic

_EPS = 1e-30


def _prim_jacobian_apply_check():  # pragma: no cover - documentation
    """The quasi-linear primitive system dW/dt + A_p dW/dx = 0 with
    W = (rho, vn, vt1, vt2, P, Bt1, Bt2) and Bn constant:

      rho' : vn rho_x + rho vn_x
      vn'  : vn vn_x + P_x/rho + (Bt1 Bt1_x + Bt2 Bt2_x)/rho
      vt'  : vn vt_x - Bn Bt_x/rho
      P'   : vn P_x + gamma P vn_x
      Bt'  : vn Bt_x + Bt vn_x - Bn vt_x

    tests/test_mhd.py builds this matrix numerically and asserts
    A_p r = lambda r for every eigenvector below at a point state.
    """


def roe_mean(ql, qr, bn, g):
    """Cargo-Gallice averaged state and wave speeds.

    Returns a dict of mean quantities; all arrays broadcast over the
    trailing interface batch."""
    g1, g2 = g - 1.0, g - 2.0
    rl, rr = ql[0], qr[0]
    wl, wr = jnp.sqrt(rl), jnp.sqrt(rr)
    nrm = wl + wr
    d = wl * wr                                   # Roe density
    v = [(wl * ql[k] + wr * qr[k]) / nrm for k in (1, 2, 3)]
    # total enthalpy per unit mass H = (E + Ptot)/rho
    def hside(q, r):
        b2 = bn ** 2 + q[6] ** 2 + q[7] ** 2
        e = q[4] / g1 + 0.5 * r * (q[1] ** 2 + q[2] ** 2 + q[3] ** 2) \
            + 0.5 * b2
        return (e + q[4] + 0.5 * b2) / r
    h = (wl * hside(ql, rl) + wr * hside(qr, rr)) / nrm
    # transverse field: OPPOSITE sqrt-rho weights (CG97)
    bt1 = (wl * qr[6] + wr * ql[6]) / nrm
    bt2 = (wl * qr[7] + wr * ql[7]) / nrm
    x = ((qr[6] - ql[6]) ** 2 + (qr[7] - ql[7]) ** 2) / (2.0 * nrm ** 2)
    y = (rl + rr) / (2.0 * d)

    vsq = v[0] ** 2 + v[1] ** 2 + v[2] ** 2
    btsq = bt1 ** 2 + bt2 ** 2
    bt_starsq = (g1 - g2 * y) * btsq
    vaxsq = bn ** 2 / d
    hp = h - (vaxsq + btsq / d)
    asq = jnp.maximum(g1 * (hp - 0.5 * vsq) - g2 * x, _EPS)
    ct2 = bt_starsq / d
    tsum = vaxsq + ct2 + asq
    tdif = vaxsq + ct2 - asq
    cf2_cs2 = jnp.sqrt(tdif * tdif + 4.0 * asq * ct2)
    cfsq = 0.5 * (tsum + cf2_cs2)
    cf = jnp.sqrt(cfsq)
    cssq = asq * vaxsq / jnp.maximum(cfsq, _EPS)
    cs = jnp.sqrt(cssq)
    a = jnp.sqrt(asq)
    ca = jnp.sqrt(vaxsq)

    bt = jnp.sqrt(jnp.maximum(btsq, 0.0))
    deg_t = bt < 1e-12 * jnp.sqrt(asq * d)        # no transverse field
    isq2 = 1.0 / jnp.sqrt(2.0)
    b1h = jnp.where(deg_t, isq2, bt1 / jnp.maximum(bt, _EPS))
    b2h = jnp.where(deg_t, isq2, bt2 / jnp.maximum(bt, _EPS))
    # alpha_f/alpha_s with the triple-umbilic conventions
    den = jnp.maximum(cfsq - cssq, _EPS)
    af2 = jnp.clip((asq - cssq) / den, 0.0, 1.0)
    as2 = jnp.clip((cfsq - asq) / den, 0.0, 1.0)
    degen = (cfsq - cssq) <= 1e-12 * asq
    alf = jnp.where(degen, 1.0, jnp.sqrt(af2))
    als = jnp.where(degen, 0.0, jnp.sqrt(as2))
    s = jnp.where(bn >= 0.0, 1.0, -1.0)
    return dict(d=d, v=v, h=h, bt1=bt1, bt2=bt2, a=a, asq=asq, ca=ca,
                cf=cf, cs=cs, b1h=b1h, b2h=b2h, alf=alf, als=als, s=s)


def _right_eigenvectors(m):
    """Primitive-variable right eigenvectors (Roe-Balsara normalized).

    Returns (lams [7, ...], R [7 rows(W), 7 waves, ...])."""
    d, v = m["d"], m["v"]
    a, ca, cf, cs = m["a"], m["ca"], m["cf"], m["cs"]
    b1h, b2h, alf, als, s = (m["b1h"], m["b2h"], m["alf"], m["als"],
                             m["s"])
    sqd = jnp.sqrt(d)
    vn = v[0]
    zero = jnp.zeros_like(d)
    one = jnp.ones_like(d)

    def fast(sgn):
        # sgn = -1 for vn - cf, +1 for vn + cf
        return [d * alf,
                sgn * cf * alf,
                -sgn * cs * als * b1h * s,
                -sgn * cs * als * b2h * s,
                d * m["asq"] * alf,
                als * sqd * a * b1h,
                als * sqd * a * b2h]

    def slow(sgn):
        return [d * als,
                sgn * cs * als,
                sgn * cf * alf * b1h * s,
                sgn * cf * alf * b2h * s,
                d * m["asq"] * als,
                -alf * sqd * a * b1h,
                -alf * sqd * a * b2h]

    def alfven(sgn):
        # lambda = vn + sgn*ca ; dvt = -sgn*s*dBt/sqrt(d)
        dbt1, dbt2 = -b2h * sqd, b1h * sqd
        return [zero,
                zero,
                -sgn * s * dbt1 / sqd,
                -sgn * s * dbt2 / sqd,
                zero,
                dbt1,
                dbt2]

    entropy = [one, zero, zero, zero, zero, zero, zero]
    cols = [fast(-1.0), alfven(-1.0), slow(-1.0), entropy,
            slow(1.0), alfven(1.0), fast(1.0)]
    lams = jnp.stack([vn - cf, vn - ca, vn - cs, vn,
                      vn + cs, vn + ca, vn + cf])
    R = jnp.stack([jnp.stack(col) for col in cols], axis=1)  # [row, wave]
    return lams, R


def _cons_of_prim_jac(m, bn, g):
    """dU/dW at the mean state; U=(rho, Mn, Mt1, Mt2, E, Bt1, Bt2)."""
    d, v = m["d"], m["v"]
    bt1, bt2 = m["bt1"], m["bt2"]
    vsq = v[0] ** 2 + v[1] ** 2 + v[2] ** 2
    z = jnp.zeros_like(d)
    o = jnp.ones_like(d)
    ig1 = 1.0 / (g - 1.0)
    rows = [
        [o, z, z, z, z, z, z],
        [v[0], d, z, z, z, z, z],
        [v[1], z, d, z, z, z, z],
        [v[2], z, z, d, z, z, z],
        [0.5 * vsq, d * v[0], d * v[1], d * v[2], ig1 * o, bt1, bt2],
        [z, z, z, z, z, o, z],
        [z, z, z, z, z, z, o],
    ]
    return jnp.stack([jnp.stack(r) for r in rows])   # [7, 7, ...]


def roe_dissipation(ql, qr, bn, cfg: MhdStatic):
    """0.5 * sum_k |lam_k| alpha_k R^cons_k — the upwind half of the Roe
    flux, shared by the 1D solver and the 2D corner EMF."""
    g = cfg.gamma
    m = roe_mean(ql, qr, bn, g)
    lams, R = _right_eigenvectors(m)
    dW = jnp.stack([qr[0] - ql[0], qr[1] - ql[1], qr[2] - ql[2],
                    qr[3] - ql[3], qr[4] - ql[4], qr[6] - ql[6],
                    qr[7] - ql[7]])
    # batched 7x7 solve: move the state axes to batch position
    batch_shape = dW.shape[1:]
    Rb = jnp.moveaxis(R.reshape(7, 7, -1), -1, 0)        # [B, 7, 7]
    dWb = jnp.moveaxis(dW.reshape(7, -1), -1, 0)[..., None]
    alpha = jnp.linalg.solve(Rb, dWb)[..., 0]            # [B, 7]
    alpha = jnp.moveaxis(alpha, 0, -1).reshape((7,) + batch_shape)
    M = _cons_of_prim_jac(m, bn, g)
    # R^cons[:, k] = M @ R[:, k]
    Rc = jnp.einsum("ij...,jk...->ik...", M, R)
    return 0.5 * jnp.einsum("k...,ik...->i...", jnp.abs(lams) * alpha, Rc)


def _flux_cons(q, bn, g):
    """(U, F) with the 7-row layout (Bn row dropped)."""
    r, vn, vt1, vt2, p, bt1, bt2 = (q[0], q[1], q[2], q[3], q[4],
                                    q[6], q[7])
    b2 = bn ** 2 + bt1 ** 2 + bt2 ** 2
    ptot = p + 0.5 * b2
    vdotb = vn * bn + vt1 * bt1 + vt2 * bt2
    e = p / (g - 1.0) + 0.5 * r * (vn ** 2 + vt1 ** 2 + vt2 ** 2) \
        + 0.5 * b2
    U = [r, r * vn, r * vt1, r * vt2, e, bt1, bt2]
    F = [r * vn,
         r * vn * vn - bn * bn + ptot,
         r * vn * vt1 - bn * bt1,
         r * vn * vt2 - bn * bt2,
         (e + ptot) * vn - bn * vdotb,
         vn * bt1 - vt1 * bn,
         vn * bt2 - vt2 * bn]
    return jnp.stack(U), jnp.stack(F)


def _expand8(f7):
    """Insert the zero Bn-flux row back (solver bank layout has 8)."""
    z = jnp.zeros_like(f7[0])
    return jnp.stack([f7[0], f7[1], f7[2], f7[3], f7[4], z, f7[5],
                      f7[6]])


def roe(ql, qr, bn, cfg: MhdStatic, zero_flux=1.0):
    """Roe flux in the rotated interface layout of the solver bank."""
    g = cfg.gamma
    rl = jnp.maximum(ql[0], cfg.smallr)
    rr = jnp.maximum(qr[0], cfg.smallr)
    pl = jnp.maximum(ql[4], cfg.smallr * cfg.smallc ** 2)
    pr = jnp.maximum(qr[4], cfg.smallr * cfg.smallc ** 2)
    qls = ql.at[0].set(rl).at[4].set(pl)
    qrs = qr.at[0].set(rr).at[4].set(pr)
    _, Fl = _flux_cons(qls, bn, g)
    _, Fr = _flux_cons(qrs, bn, g)
    diss = roe_dissipation(qls, qrs, bn, cfg)
    f7 = zero_flux * 0.5 * (Fl + Fr) - diss
    return _expand8(f7)


def upwind(ql, qr, bn, cfg: MhdStatic, zero_flux=1.0):
    """The reference's 1D 'upwind' solver semantics
    (``mhd/godunov_utils.f90:313``): centered flux minus |mean normal
    velocity| times the state jump."""
    g = cfg.gamma
    Ul, Fl = _flux_cons(ql, bn, g)
    Ur, Fr = _flux_cons(qr, bn, g)
    vmean = 0.5 * (ql[1] + qr[1])
    f7 = zero_flux * 0.5 * (Fl + Fr) - 0.5 * jnp.abs(vmean) * (Ur - Ul)
    return _expand8(f7)


def llf_dissipation_bt1(ql, qr, bn, cfg: MhdStatic):
    """Row 5 (Bt1) of ``0.5 * max(|vn|+cfast) * dU`` in the 7-row
    layout — all the 2D corner assembly reads of it (the 1D llf lives
    in mhd.riemann).  ``ql``/``qr`` indexable by row."""
    from ramses_tpu.mhd.riemann import _fast

    def speed(q):
        return jnp.abs(q[1]) + _fast(q[0], q[4], bn, q[6], q[7],
                                     cfg.gamma, cfg.smallc)
    a = jnp.maximum(speed(ql), speed(qr))
    return 0.5 * a * (qr[6] - ql[6])


def upwind_dissipation(ql, qr, bn, cfg: MhdStatic):
    Ul, _ = _flux_cons(ql, bn, cfg.gamma)
    Ur, _ = _flux_cons(qr, bn, cfg.gamma)
    vmean = 0.5 * (ql[1] + qr[1])
    return 0.5 * jnp.abs(vmean) * (Ur - Ul)
