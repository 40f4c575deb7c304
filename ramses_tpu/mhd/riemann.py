"""1D MHD Riemann solvers on rotated interface states.

Counterpart of the reference's per-direction solvers dispatched from
``mag_unsplit`` (``mhd/umuscl.f90:1393``; options llf|hll|hlld,
``hydro/read_hydro_params.f90:184-223``).  HLLD follows Miyoshi & Kusano
(2005), branchless with ``jnp.where`` region selection so the whole face
batch resolves in one fused XLA program.

Interface layout (normal first): [ρ, v_n, v_t1, v_t2, P, B_n, B_t1, B_t2,
passives…].  The normal field ``B_n`` is the staggered face value, shared
by both sides (slot 5 of ql/qr is ignored; ``bn`` is passed separately).
Returned flux layout matches; the B_n flux slot is zero.
"""

from __future__ import annotations

import jax.numpy as jnp

from ramses_tpu.mhd.core import MhdStatic

_EPS = 1e-30


def _split(q, bn):
    return (q[0], q[1], q[2], q[3], q[4], bn, q[6], q[7])


def _cons(r, vn, vt1, vt2, p, bn, bt1, bt2, gamma):
    e = (p / (gamma - 1.0) + 0.5 * r * (vn ** 2 + vt1 ** 2 + vt2 ** 2)
         + 0.5 * (bn ** 2 + bt1 ** 2 + bt2 ** 2))
    return [r, r * vn, r * vt1, r * vt2, e, bn, bt1, bt2]


def _flux(r, vn, vt1, vt2, p, bn, bt1, bt2, gamma):
    b2 = bn ** 2 + bt1 ** 2 + bt2 ** 2
    ptot = p + 0.5 * b2
    vdotb = vn * bn + vt1 * bt1 + vt2 * bt2
    e = (p / (gamma - 1.0) + 0.5 * r * (vn ** 2 + vt1 ** 2 + vt2 ** 2)
         + 0.5 * b2)
    return [r * vn,
            r * vn * vn - bn * bn + ptot,
            r * vn * vt1 - bn * bt1,
            r * vn * vt2 - bn * bt2,
            (e + ptot) * vn - bn * vdotb,
            jnp.zeros_like(r),
            vn * bt1 - vt1 * bn,
            vn * bt2 - vt2 * bn]


def _fast(r, p, bn, bt1, bt2, gamma, smallc):
    c2 = gamma * p / r
    b2 = (bn ** 2 + bt1 ** 2 + bt2 ** 2) / r
    s = c2 + b2
    disc = jnp.sqrt(jnp.maximum(s * s - 4.0 * c2 * bn ** 2 / r, 0.0))
    return jnp.sqrt(jnp.maximum(0.5 * (s + disc), smallc ** 2))


def _sanitize(q, cfg):
    r = jnp.maximum(q[0], cfg.smallr)
    p = jnp.maximum(q[4], cfg.smallr * cfg.smallc ** 2)
    return r, p


def solve(ql, qr, bn, cfg: MhdStatic):
    if cfg.riemann == "llf":
        f = llf(ql, qr, bn, cfg)
    elif cfg.riemann == "hll":
        f = hll(ql, qr, bn, cfg)
    elif cfg.riemann == "hlld":
        f = hlld(ql, qr, bn, cfg)
    elif cfg.riemann == "roe":
        from ramses_tpu.mhd import roe as roemod
        f = roemod.roe(ql, qr, bn, cfg)
    elif cfg.riemann == "upwind":
        from ramses_tpu.mhd import roe as roemod
        f = roemod.upwind(ql, qr, bn, cfg)
    else:
        raise NotImplementedError(f"mhd riemann={cfg.riemann}")
    if cfg.npassive:
        mass = f[0]
        pf = [jnp.where(mass > 0.0, mass * ql[8 + s], mass * qr[8 + s])
              for s in range(cfg.npassive)]
        f = jnp.concatenate([f, jnp.stack(pf)], axis=0)
    return f


def llf(ql, qr, bn, cfg: MhdStatic):
    g = cfg.gamma
    rl, pl = _sanitize(ql, cfg)
    rr, pr = _sanitize(qr, cfg)
    sl = _split(ql, bn)
    sr = _split(qr, bn)
    al = _fast(rl, pl, bn, ql[6], ql[7], g, cfg.smallc) + jnp.abs(ql[1])
    ar = _fast(rr, pr, bn, qr[6], qr[7], g, cfg.smallc) + jnp.abs(qr[1])
    a = jnp.maximum(al, ar)
    fl = _flux(rl, *sl[1:5], bn, *sl[6:], g)
    fr = _flux(rr, *sr[1:5], bn, *sr[6:], g)
    ul = _cons(rl, *sl[1:5], bn, *sl[6:], g)
    ur = _cons(rr, *sr[1:5], bn, *sr[6:], g)
    return jnp.stack([0.5 * (a1 + a2) - 0.5 * a * (u2 - u1)
                      for a1, a2, u1, u2 in zip(fl, fr, ul, ur)])


def _wave_bounds(ql, qr, bn, cfg):
    g = cfg.gamma
    rl, pl = _sanitize(ql, cfg)
    rr, pr = _sanitize(qr, cfg)
    cl = _fast(rl, pl, bn, ql[6], ql[7], g, cfg.smallc)
    cr = _fast(rr, pr, bn, qr[6], qr[7], g, cfg.smallc)
    sl_speed = jnp.minimum(ql[1] - cl, qr[1] - cr)
    sr_speed = jnp.maximum(ql[1] + cl, qr[1] + cr)
    return rl, pl, rr, pr, sl_speed, sr_speed


def hll(ql, qr, bn, cfg: MhdStatic):
    g = cfg.gamma
    rl, pl, rr, pr, SL, SR = _wave_bounds(ql, qr, bn, cfg)
    fl = _flux(rl, ql[1], ql[2], ql[3], pl, bn, ql[6], ql[7], g)
    fr = _flux(rr, qr[1], qr[2], qr[3], pr, bn, qr[6], qr[7], g)
    ul = _cons(rl, ql[1], ql[2], ql[3], pl, bn, ql[6], ql[7], g)
    ur = _cons(rr, qr[1], qr[2], qr[3], pr, bn, qr[6], qr[7], g)
    SLc = jnp.minimum(SL, 0.0)
    SRc = jnp.maximum(SR, 0.0)
    den = SRc - SLc + _EPS
    return jnp.stack([
        (SRc * f1 - SLc * f2 + SLc * SRc * (u2 - u1)) / den
        for f1, f2, u1, u2 in zip(fl, fr, ul, ur)])


def hlld(ql, qr, bn, cfg: MhdStatic):
    """Miyoshi & Kusano (2005) five-wave solver, fully vectorized."""
    return jnp.stack(hlld_rows(ql, qr, bn, cfg))


def hlld_rows(ql, qr, bn, cfg: MhdStatic) -> list:
    """:func:`hlld`'s eight flux rows as a list; ``ql``/``qr`` anything
    indexable by row (the tiled CT kernel passes lists of windows)."""
    g = cfg.gamma
    rl, pl, rr, pr, SL, SR = _wave_bounds(ql, qr, bn, cfg)
    vnl, vt1l, vt2l, bt1l, bt2l = ql[1], ql[2], ql[3], ql[6], ql[7]
    vnr, vt1r, vt2r, bt1r, bt2r = qr[1], qr[2], qr[3], qr[6], qr[7]
    b2l = bn ** 2 + bt1l ** 2 + bt2l ** 2
    b2r = bn ** 2 + bt1r ** 2 + bt2r ** 2
    ptl = pl + 0.5 * b2l
    ptr = pr + 0.5 * b2r

    dl = rl * (SL - vnl)
    dr = rr * (SR - vnr)
    SM = (dr * vnr - dl * vnl - ptr + ptl) / (dr - dl + _EPS)
    pts = (dr * ptl - dl * ptr + dl * dr * (vnr - vnl)) / (dr - dl + _EPS)

    # star states
    rsl = dl / (SL - SM + _EPS)
    rsr = dr / (SR - SM + _EPS)
    denl = dl * (SL - SM) - bn ** 2
    denr = dr * (SR - SM) - bn ** 2
    degl = jnp.abs(denl) < 1e-12 * (rl * (jnp.abs(SL) + jnp.abs(vnl)) ** 2
                                    + bn ** 2 + _EPS)
    degr = jnp.abs(denr) < 1e-12 * (rr * (jnp.abs(SR) + jnp.abs(vnr)) ** 2
                                    + bn ** 2 + _EPS)
    safe_denl = jnp.where(degl, 1.0, denl)
    safe_denr = jnp.where(degr, 1.0, denr)
    vt1sl = jnp.where(degl, vt1l,
                      vt1l - bn * bt1l * (SM - vnl) / safe_denl)
    vt2sl = jnp.where(degl, vt2l,
                      vt2l - bn * bt2l * (SM - vnl) / safe_denl)
    bt1sl = jnp.where(degl, bt1l,
                      bt1l * (dl * (SL - vnl) - bn ** 2) / safe_denl)
    bt2sl = jnp.where(degl, bt2l,
                      bt2l * (dl * (SL - vnl) - bn ** 2) / safe_denl)
    vt1sr = jnp.where(degr, vt1r,
                      vt1r - bn * bt1r * (SM - vnr) / safe_denr)
    vt2sr = jnp.where(degr, vt2r,
                      vt2r - bn * bt2r * (SM - vnr) / safe_denr)
    bt1sr = jnp.where(degr, bt1r,
                      bt1r * (dr * (SR - vnr) - bn ** 2) / safe_denr)
    bt2sr = jnp.where(degr, bt2r,
                      bt2r * (dr * (SR - vnr) - bn ** 2) / safe_denr)

    el = (pl / (g - 1.0) + 0.5 * rl * (vnl ** 2 + vt1l ** 2 + vt2l ** 2)
          + 0.5 * b2l)
    er = (pr / (g - 1.0) + 0.5 * rr * (vnr ** 2 + vt1r ** 2 + vt2r ** 2)
          + 0.5 * b2r)
    vbl = vnl * bn + vt1l * bt1l + vt2l * bt2l
    vbsl = SM * bn + vt1sl * bt1sl + vt2sl * bt2sl
    vbr = vnr * bn + vt1r * bt1r + vt2r * bt2r
    vbsr = SM * bn + vt1sr * bt1sr + vt2sr * bt2sr
    esl = ((SL - vnl) * el - ptl * vnl + pts * SM + bn * (vbl - vbsl)) \
        / (SL - SM + _EPS)
    esr = ((SR - vnr) * er - ptr * vnr + pts * SM + bn * (vbr - vbsr)) \
        / (SR - SM + _EPS)

    # Alfvén (double-star) states
    sq_rsl = jnp.sqrt(jnp.maximum(rsl, cfg.smallr))
    sq_rsr = jnp.sqrt(jnp.maximum(rsr, cfg.smallr))
    SLs = SM - jnp.abs(bn) / sq_rsl
    SRs = SM + jnp.abs(bn) / sq_rsr
    sgn = jnp.sign(bn)
    ssum = sq_rsl + sq_rsr + _EPS
    vt1ss = (sq_rsl * vt1sl + sq_rsr * vt1sr
             + sgn * (bt1sr - bt1sl)) / ssum
    vt2ss = (sq_rsl * vt2sl + sq_rsr * vt2sr
             + sgn * (bt2sr - bt2sl)) / ssum
    bt1ss = (sq_rsl * bt1sr + sq_rsr * bt1sl
             + sgn * sq_rsl * sq_rsr * (vt1sr - vt1sl)) / ssum
    bt2ss = (sq_rsl * bt2sr + sq_rsr * bt2sl
             + sgn * sq_rsl * sq_rsr * (vt2sr - vt2sl)) / ssum
    vbssl = SM * bn + vt1ss * bt1ss + vt2ss * bt2ss
    essl = esl - sq_rsl * sgn * (vbsl - vbssl)
    essr = esr + sq_rsr * sgn * (vbsr - vbssl)

    def pack(r, vn, vt1, vt2, e, bt1, bt2):
        return [r, r * vn, r * vt1, r * vt2, e, bn, bt1, bt2]

    ul = _cons(rl, vnl, vt1l, vt2l, pl, bn, bt1l, bt2l, g)
    ur = _cons(rr, vnr, vt1r, vt2r, pr, bn, bt1r, bt2r, g)
    usl = pack(rsl, SM, vt1sl, vt2sl, esl, bt1sl, bt2sl)
    usr = pack(rsr, SM, vt1sr, vt2sr, esr, bt1sr, bt2sr)
    ussl = pack(rsl, SM, vt1ss, vt2ss, essl, bt1ss, bt2ss)
    ussr = pack(rsr, SM, vt1ss, vt2ss, essr, bt1ss, bt2ss)
    fl = _flux(rl, vnl, vt1l, vt2l, pl, bn, bt1l, bt2l, g)
    fr = _flux(rr, vnr, vt1r, vt2r, pr, bn, bt1r, bt2r, g)

    out = []
    for k in range(8):
        fsl = fl[k] + SL * (usl[k] - ul[k])
        fsr = fr[k] + SR * (usr[k] - ur[k])
        fssl = fsl + SLs * (ussl[k] - usl[k])
        fssr = fsr + SRs * (ussr[k] - usr[k])
        f = jnp.where(SL > 0.0, fl[k],
                      jnp.where(SLs > 0.0, fsl,
                                jnp.where(SM > 0.0, fssl,
                                          jnp.where(SRs > 0.0, fssr,
                                                    jnp.where(SR > 0.0, fsr,
                                                              fr[k])))))
        out.append(f)
    return out
