"""Plain reference of one AMR coarse step and of one regrid, and what is
compared.  Imports nothing of ``ramses_tpu``; takes cell coordinates and
values only.

Semantics followed (RAMSES ``amr_step`` / ``godfine1`` as SURVEY.md describes
them, with the departures the program documents and this reference has to
share to agree to rounding):

* every level is swept with its own CFL-halved step, factor-2 subcycling
  per level (the program ignores ``&RUN_PARAMS nsubcycle``; PERF.md §7);
* a level's sweep reads its own cells where they exist and, in the two
  ghost layers around its octs, values interpolated from the coarser level's
  CURRENT state: conservative variables, minmod-limited halved differences,
  no interpolation in time; a father cell's missing neighbour gives a zero
  slope in that direction (``amr/maps.py:147-152``; RAMSES walks up the tree);
* faces touching a refined cell carry no flux at their own level; the finer
  level's boundary fluxes, summed over the 4 fine faces and divided by 8,
  correct the unrefined coarser neighbour;
* after its sweep a level's refined cells take the mean of their 8 children.

Each level lives in a dense box: the complete base level is the whole
periodic grid, a partial level the bounding box of its cells plus a margin
(``jnp.roll`` garbage stays in the margin).  Regrid: gradient flags
(``hydro_refine``) → one 3^3 smoothing pass → top-down nesting → new octs
copy surviving cells and interpolate new ones, then a restriction sweep.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import muscl_plain as mp

MARGIN = 4          # cells around a partial level's octs (2 ghosts + slack)
ROUND = 16          # box sizes are multiples of this: few compiled shapes


# ---------------------------------------------------------------- geometry
class Box:
    """Where level ``l``'s dense array sits in the periodic grid."""

    def __init__(self, lvl, origin, size):
        self.l, self.n = int(lvl), 1 << int(lvl)
        self.o = tuple(int(v) for v in origin)
        self.s = tuple(int(v) for v in size)

    def key(self):
        """What a compiled program depends on: the level and the array's
        size.  Where the box sits is DATA (``links``), so one compiled
        reference serves every seed."""
        return (self.l, self.s)

    def pos(self, coords):
        """Array index of global cell coords ``[m, 3]``; -1 outside."""
        p = (np.asarray(coords, np.int64) - np.array(self.o)) % self.n
        ok = (p < np.array(self.s)).all(axis=1)
        return np.where(ok[:, None], p, -1)


def _cover(vals, n):
    """Smallest periodic interval [lo, lo+ext) holding all ``vals``."""
    v = np.unique(vals)
    gaps = np.diff(np.concatenate([v, [v[0] + n]]))
    k = int(np.argmax(gaps))
    lo = int(v[(k + 1) % len(v)])
    return lo, n - int(gaps[k]) + 1


def make_box(lvl, coords, lmin):
    n = 1 << lvl
    if lvl == lmin:
        return Box(lvl, (0, 0, 0), (n, n, n))
    o, s = [], []
    for d in range(3):
        lo, ext = _cover(coords[:, d], n)
        lo = (lo - MARGIN) // 4 * 4
        hi = lo + ext + 2 * MARGIN + 4
        size = -(-(hi - lo) // ROUND) * ROUND
        if size >= n:
            lo, size = 0, n
        o.append(lo % n)
        s.append(size)
    return Box(lvl, o, s)


def to_dense(box, coords, vals, dtype):
    p = box.pos(coords)
    if (p < 0).any():
        raise ValueError(f"level {box.l}: cells outside their box")
    u = np.zeros((5,) + box.s, np.float32)
    has = np.zeros(box.s, bool)
    u[:, p[:, 0], p[:, 1], p[:, 2]] = np.asarray(vals, np.float32).T
    has[p[:, 0], p[:, 1], p[:, 2]] = True
    return jnp.asarray(u).astype(dtype), has


def refined_mask(box, fine_coords):
    ref = np.zeros(box.s, bool)
    if fine_coords is not None and len(fine_coords):
        p = box.pos(np.unique(np.asarray(fine_coords) >> 1, axis=0))
        if (p < 0).any():
            raise ValueError(f"level {box.l + 1} octs without a father")
        ref[p[:, 0], p[:, 1], p[:, 2]] = True
    return ref


def _coarse_index(fine, coarse, pad):
    """Per dimension: index into ``coarse``'s array of the coarse cells
    under ``fine``'s box, ``pad`` extra on each side; and whether each lies
    inside the coarse array."""
    idx, ok = [], []
    for d in range(3):
        cc = ((fine.o[d] >> 1) - pad
              + np.arange(fine.s[d] // 2 + 2 * pad)) % coarse.n
        p = (cc - coarse.o[d]) % coarse.n
        good = p < coarse.s[d]
        idx.append(np.where(good, p, 0).astype(np.int32))
        ok.append(good)
    return idx, ok


def make_link(fine, coarse):
    """Index arrays that tie ``fine``'s box to ``coarse``'s (device data,
    not constants of a program): ``near`` with one coarse cell of padding
    for the interpolation, ``under`` (out-of-array → dropped) for the flux
    correction and the restriction."""
    idx1, ok1 = _coarse_index(fine, coarse, 1)
    idx0, ok0 = _coarse_index(fine, coarse, 0)
    under = [np.where(g, i, coarse.s[d]).astype(np.int32)
             for d, (i, g) in enumerate(zip(idx0, ok0))]
    return {"near": [jnp.asarray(i) for i in idx1],
            "near_ok": [jnp.asarray(g) for g in ok1],
            "under": [jnp.asarray(i) for i in under]}


def _outer(ix):
    return ix[0][:, None, None], ix[1][None, :, None], ix[2][None, None, :]


# ------------------------------------------------------- pieces of a step
def prolong(uc, hasc, fine, link):
    """Values of ALL cells of ``fine``'s box interpolated from the coarser
    level ``uc`` (and whether a father exists there)."""
    ox, oy, oz = _outer(link["near"])
    ok = link["near_ok"]
    inb = ok[0][:, None, None] & ok[1][None, :, None] & ok[2][None, None, :]
    sub = uc[:, ox, oy, oz]
    hsub = hasc[ox, oy, oz] & inb
    core = (slice(None), slice(1, -1), slice(1, -1), slice(1, -1))
    a0 = sub[core]
    shp = a0.shape
    seven = (shp[0], shp[1], 1, shp[2], 1, shp[3], 1)
    out = a0.reshape(seven)
    for d in range(3):
        ax = 1 + d
        left = jnp.where(jnp.roll(hsub, 1, axis=d)[None],
                         jnp.roll(sub, 1, axis=ax), sub)[core]
        right = jnp.where(jnp.roll(hsub, -1, axis=d)[None],
                          jnp.roll(sub, -1, axis=ax), sub)[core]
        dl = 0.5 * (a0 - left)
        dr = 0.5 * (right - a0)
        w = jnp.where(dl * dr <= 0.0, 0.0,
                      jnp.sign(dr) * jnp.minimum(jnp.abs(dl), jnp.abs(dr)))
        # child offset along d: -1/2 for the low child, +1/2 for the high
        sgn = jnp.asarray([-0.5, 0.5], a0.dtype).reshape(
            [2 if k == 2 * d + 2 else 1 for k in range(7)])
        out = out + w.reshape(seven) * sgn
    out = jnp.broadcast_to(out, (shp[0], shp[1], 2, shp[2], 2, shp[3], 2))
    fine_vals = out.reshape((5,) + fine.s)
    father = hsub[1:-1, 1:-1, 1:-1]
    for d in range(3):
        father = jnp.repeat(father, 2, axis=d)
    return fine_vals, father


def ghost_filled(u, has, uc, hasc, fine, link):
    vals, father = prolong(uc, hasc, fine, link)
    return jnp.where(has[None], u, jnp.where(father[None], vals, 0.0))


def sweep(ug, has, refined, dt, dx, ph):
    """(du on the level's own cells, masked low-face fluxes)."""
    flux = mp.face_fluxes(ug, dt, dx, ph)
    du = jnp.zeros_like(ug)
    kept = []
    for d in range(3):
        keep = ~(refined | jnp.roll(refined, 1, axis=d))
        f = jnp.where(keep[None], flux[d], 0.0)
        kept.append(f)
        du = du + (f - jnp.roll(f, -1, axis=1 + d))
    return jnp.where(has[None], du, 0.0), kept


def coarse_correction(kept, has, fine, link, unew_c):
    """Fold the fine level's boundary fluxes into the coarser level."""
    acc = jnp.zeros_like(kept[0])
    for d in range(3):
        below = jnp.roll(has, 1, axis=d)          # cell i-1 exists
        hi_missing = below & ~has                 # face i is i-1's high face
        lo_missing = has & ~below                 # face i is i's low face
        acc = acc + jnp.where(hi_missing[None], kept[d], 0.0)
        acc = acc + jnp.roll(jnp.where(lo_missing[None], -kept[d], 0.0),
                             -1, axis=1 + d)
    s = fine.s
    acc = acc.reshape(5, s[0] // 2, 2, s[1] // 2, 2, s[2] // 2, 2) \
        .sum(axis=(2, 4, 6)) * 0.125
    ox, oy, oz = _outer(link["under"])
    return unew_c.at[:, ox, oy, oz].add(acc.astype(unew_c.dtype),
                                        mode="drop")


def restrict(u, refined, ufine, fine, link):
    """Refined cells of ``coarse`` ← mean of their 8 children."""
    s = fine.s
    mean = ufine.reshape(5, s[0] // 2, 2, s[1] // 2, 2, s[2] // 2, 2) \
        .sum(axis=(2, 4, 6)) * 0.125
    ox, oy, oz = _outer(link["under"])
    spread = jnp.zeros_like(u).at[:, ox, oy, oz].set(mean.astype(u.dtype),
                                                     mode="drop")
    return jnp.where(refined[None], spread, u)


class Geometry:
    """Hashable description of the boxes of one tree (a jit static arg)."""

    def __init__(self, boxes, boxlen):
        self.boxes = tuple(boxes)
        self.boxlen = float(boxlen)
        self.lmin = self.boxes[0].l

    def _key(self):
        return (tuple(b.key() for b in self.boxes), self.boxlen)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Geometry) and self._key() == other._key()

    def dx(self, i):
        return self.boxlen / self.boxes[i].n


@partial(jax.jit, static_argnames=("geom", "ph"))
def coarse_step(us, hass, refs, links, dt, geom, ph):
    """One coarse step of the whole hierarchy, recursive and subcycled."""
    us = list(us)
    new = list(us)
    nlev = len(us)

    def advance(i, dtl):
        new[i] = us[i]
        if i + 1 < nlev:
            advance(i + 1, 0.5 * dtl)
            advance(i + 1, 0.5 * dtl)
        box = geom.boxes[i]
        if i == 0:
            ug = us[i]
        else:
            ug = ghost_filled(us[i], hass[i], us[i - 1], hass[i - 1], box,
                              links[i])
        du, kept = sweep(ug, hass[i], refs[i], dtl.astype(ug.dtype),
                         geom.dx(i), ph)
        new[i] = new[i] + du
        if i > 0:
            new[i - 1] = coarse_correction(kept, hass[i], box, links[i],
                                           new[i - 1])
        us[i] = new[i]
        if i + 1 < nlev:
            us[i] = restrict(us[i], refs[i], us[i + 1], geom.boxes[i + 1],
                             links[i + 1])

    advance(0, dt)
    return us


@partial(jax.jit, static_argnames=("geom", "ph"))
def coarse_dt(us, hass, geom, ph):
    dts = [mp.courant_dt(u, geom.dx(i), ph, valid=hass[i])
           .astype(jnp.float32)
           * float(1 << (geom.boxes[i].l - geom.lmin))
           for i, u in enumerate(us)]
    return jnp.min(jnp.stack(dts))


# ----------------------------------------------------------- a whole tree
class Tree:
    """Levels of one snapshot as dense boxes."""

    def __init__(self, levels, lmin, boxlen, dtype):
        self.lv = sorted(levels)
        self.coords = {l: np.asarray(levels[l][0], np.int64) for l in self.lv}
        boxes = [make_box(l, self.coords[l], lmin) for l in self.lv]
        self.geom = Geometry(boxes, boxlen)
        self.us, hass, refs = [], [], []
        for i, l in enumerate(self.lv):
            u, has = to_dense(boxes[i], self.coords[l], levels[l][1], dtype)
            self.us.append(u)
            hass.append(has)
            nxt = self.coords.get(l + 1)
            refs.append(refined_mask(boxes[i], nxt))
        self.has_np = hass
        self.hass = [jnp.asarray(h) for h in hass]
        self.refs = [jnp.asarray(r) for r in refs]
        self.links = [None] + [make_link(boxes[i], boxes[i - 1])
                               for i in range(1, len(boxes))]

    def cells(self, us, lvl):
        """Values ``[ncell, 5]`` (float32) in the order of ``coords``
        (indexed on the host: no program per cell count)."""
        i = self.lv.index(lvl)
        p = self.geom.boxes[i].pos(self.coords[lvl])
        dense = np.asarray(us[i].astype(jnp.float32))
        return dense[:, p[:, 0], p[:, 1], p[:, 2]].T


def advance(snap, config, dtype="float32"):
    """Reference output of the window's held coarse step (and, when the
    snapshot holds the state before its regrid, of that regrid)."""
    ph = mp.Physics(config["physics"])
    tree = Tree(snap["mid"], snap["lmin"], snap["boxlen"], dtype)
    dt = coarse_dt(tree.us, tree.hass, tree.geom, ph)
    us = coarse_step(tree.us, tree.hass, tree.refs, tree.links, dt,
                     tree.geom, ph)
    out = {"dt": float(dt),
           "u": {l: tree.cells(us, l) for l in tree.lv}}
    if "pre" in snap:
        out["regrid"] = regrid(snap["pre"], snap["mid"], snap, config, dtype)
    return out


def program_output(snap):
    out = {"dt": float(snap["dt"]),
           "u": {l: np.asarray(v[1], np.float32)
                 for l, v in snap["out"].items()}}
    if "pre" in snap:
        out["regrid"] = {"tree": {l: v[0] for l, v in snap["mid"].items()},
                         "u": {l: np.asarray(v[1], np.float32)
                               for l, v in snap["mid"].items()}}
    return out


# ------------------------------------------------------------------ regrid
FLAG_EPS = 1e-4     # relative band around a threshold: rounding may flip it


def _rel_err(f, floor):
    err = jnp.zeros_like(f)
    for d in range(3):
        lo, hi = jnp.roll(f, 1, axis=d), jnp.roll(f, -1, axis=d)
        e1 = jnp.abs(hi - f) / (jnp.abs(hi) + jnp.abs(f) + floor)
        e2 = jnp.abs(f - lo) / (jnp.abs(f) + jnp.abs(lo) + floor)
        err = jnp.maximum(err, 2.0 * jnp.maximum(e1, e2))
    return err


@partial(jax.jit, static_argnames=("geom", "ph", "floors"))
def gradient_errors(us, hass, links, geom, ph, floors):
    """Per level: max over (density, pressure) of the relative two-sided
    difference of ``hydro_refine``, each over its own threshold (so 1 is the
    threshold for both)."""
    out = []
    for i, u in enumerate(us):
        ug = u if i == 0 else ghost_filled(u, hass[i], us[i - 1],
                                           hass[i - 1], geom.boxes[i],
                                           links[i])
        rho = jnp.maximum(ug[0], ph.smallr)
        ek = 0.5 * (ug[1] * ug[1] + ug[2] * ug[2] + ug[3] * ug[3]) / rho
        p = (ph.gamma - 1.0) * (ug[4] - ek)
        out.append((_rel_err(rho, floors[0]).astype(jnp.float32),
                    _rel_err(p, floors[1]).astype(jnp.float32)))
    return out


def _keys(coords, n):
    c = np.asarray(coords, np.int64)
    return (c[:, 0] * n + c[:, 1]) * n + c[:, 2]


def _unkeys(k, n):
    return np.stack([k // (n * n), (k // n) % n, k % n], axis=1)


_NB27 = np.array([(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
                  for k in (-1, 0, 1)], np.int64)


def _dilate(coords, n):
    if not len(coords):
        return coords
    ex = (coords[:, None, :] + _NB27[None]).reshape(-1, 3) % n
    return _unkeys(np.unique(_keys(ex, n)), n)


def new_tree(flagged, old_coords, lmin, lmax, nexpand):
    """Oct sets (as the coords of the level-(l-1) cells they refine) of the
    tree that ``flagged`` cell sets give: smoothing, nesting, and a new oct
    only where its father cell exists in the NEW tree."""
    f = {}
    for l in range(lmin, lmax + 1):
        c = flagged.get(l, np.zeros((0, 3), np.int64))
        for _ in range(nexpand):
            c = _dilate(c, 1 << l)
        f[l] = c
    for l in range(lmax, lmin, -1):
        if len(f[l]):
            up = _dilate(f[l], 1 << l) >> 1
            both = np.concatenate([f[l - 1], up])
            n = 1 << (l - 1)
            f[l - 1] = _unkeys(np.unique(_keys(both, n)), n)
    octs = {}
    have = None                       # keys of the cells of the new level l
    for l in range(lmin, lmax):
        c = f[l]
        n = 1 << l
        if l > lmin:
            c = c[np.isin(_keys(c, n), have)] if len(c) else c
        if not len(c):
            break
        octs[l + 1] = c                           # refined level-l cells
        kids = (2 * c[:, None, :] + _CHILD[None]).reshape(-1, 3)
        have = _keys(kids, 2 * n)
    return octs


_CHILD = np.array([(i, j, k) for i in (0, 1) for j in (0, 1)
                   for k in (0, 1)], np.int64)


def regrid(pre, mid, snap, config, dtype):
    """Reference regrid of the ``pre`` snapshot: the band of trees the
    thresholds allow, and the migrated state on the program's new tree."""
    ph = mp.Physics(config["physics"])
    rf = config["refinement"]
    # the snapshot's level range is the configuration's (a rehearsal's is
    # smaller; benchmark/tests holds the two equal at full size)
    lmin, lmax = int(snap["lmin"]), int(snap["lmax"])
    old = Tree(pre, snap["lmin"], snap["boxlen"], dtype)
    errs = gradient_errors(old.us, old.hass, old.links, old.geom, ph,
                           (float(rf["floor_d"]), float(rf["floor_p"])))
    band = {}
    for name, scale in (("must", 1.0 + FLAG_EPS), ("may", 1.0 - FLAG_EPS)):
        flagged = {}
        for i, l in enumerate(old.lv):
            ed, ep = (np.asarray(e) for e in errs[i])
            p = old.geom.boxes[i].pos(old.coords[l])
            at = (p[:, 0], p[:, 1], p[:, 2])
            hit = (ed[at] > float(rf["err_grad_d"]) * scale) \
                | (ep[at] > float(rf["err_grad_p"]) * scale)
            flagged[l] = old.coords[l][hit]
        band[name] = new_tree(flagged, old.coords, lmin, lmax,
                              int(rf["nexpand"]))
    # migration onto the PROGRAM's new tree (the tree itself is judged by
    # the band above): survivors copy, new octs interpolate, then restrict
    new = Tree({l: (c, np.zeros((len(c), 5), np.float32))
                for l, (c, _) in mid.items()}, snap["lmin"], snap["boxlen"],
               dtype)
    kept, fresh = [], []
    for i, l in enumerate(new.lv):
        box = new.geom.boxes[i]
        u = np.zeros((5,) + box.s, np.float32)
        survived = np.zeros(box.s, bool)
        if l in old.lv:
            p_old = box.pos(old.coords[l])
            keep = (p_old >= 0).all(axis=1)
            keep &= new.has_np[i][tuple(np.where(keep[:, None], p_old, 0).T)]
            pk = p_old[keep]
            u[:, pk[:, 0], pk[:, 1], pk[:, 2]] = old.cells(old.us, l)[keep].T
            survived[pk[:, 0], pk[:, 1], pk[:, 2]] = True
        kept.append(jnp.asarray(u).astype(dtype))
        fresh.append(jnp.asarray(new.has_np[i] & ~survived))
    us = migrate(kept, fresh, new.hass, new.refs, new.links, new.geom)
    return {"band": band, "u": {l: new.cells(us, l) for l in new.lv}}


@partial(jax.jit, static_argnames=("geom",))
def migrate(kept, fresh, hass, refs, links, geom):
    us = list(kept)
    for i in range(1, len(us)):
        vals, _ = prolong(us[i - 1], hass[i - 1], geom.boxes[i], links[i])
        us[i] = jnp.where(fresh[i][None], vals, us[i])
    for i in range(len(us) - 2, -1, -1):
        us[i] = restrict(us[i], refs[i], us[i + 1], geom.boxes[i + 1],
                         links[i + 1])
    return us


# ---------------------------------------------------------------- compare
def _l1(levels_a, levels_b, boxlen, var):
    tot = 0.0
    for l in levels_a:
        vol = (boxlen / (1 << l)) ** 3
        tot += vol * float(np.abs(
            levels_a[l][:, var].astype(np.float64)
            - levels_b[l][:, var].astype(np.float64)).sum())
    return tot


def _linf(levels_a, levels_b, var):
    return max(float(np.abs(levels_a[l][:, var].astype(np.float64)
                            - levels_b[l][:, var].astype(np.float64)).max())
               for l in levels_a)


def _zero_like(levels):
    return {l: np.zeros_like(v) for l, v in levels.items()}


def leaf_totals(coords, vals, boxlen):
    """Mass and total energy over the leaf cells (float64)."""
    mass = energy = 0.0
    lv = sorted(coords)
    for l in lv:
        n = 1 << l
        leaf = np.ones(len(coords[l]), bool)
        if l + 1 in coords:
            dads = np.unique(_keys(coords[l + 1] >> 1, n))
            leaf = ~np.isin(_keys(coords[l], n), dads)
        vol = (boxlen / n) ** 3
        v = vals[l][leaf].astype(np.float64)
        mass += vol * v[:, 0].sum()
        energy += vol * v[:, 4].sum()
    return mass, energy


def measure(got, ref, snap, config):
    """``state_gap``, ``time_gap``, ``mass_drift``, ``energy_drift`` as for
    the uniform grid (sums over all levels' cells, volume-weighted; a step
    that returns its state unchanged reads 1) and ``cell_gap`` (largest
    cell of any level).  With a regrid in the
    snapshot: ``tree_missing`` (octs every admissible rounding refines and
    the program's tree lacks), ``tree_extra`` (octs of the program's tree no
    admissible rounding refines) and ``migrate_gap`` / ``migrate_cell_gap`` (migrated
    state of the partial levels against the reference's magnitude, summed
    and by the largest cell)."""
    from benchmark.reference.uniform_hydro import initial_totals, ratio
    boxlen = float(snap["boxlen"])
    u_in = {l: np.asarray(v[1], np.float32) for l, v in snap["mid"].items()}
    worst = cell = 0.0
    for k in range(5):
        worst = max(worst, ratio(_l1(got["u"], ref["u"], boxlen, k),
                                 _l1(ref["u"], u_in, boxlen, k)))
        cell = max(cell, ratio(_linf(got["u"], ref["u"], k),
                               _linf(ref["u"], u_in, k)))
    coords = {l: np.asarray(v[0], np.int64) for l, v in snap["out"].items()}
    m0, e0 = initial_totals(config)
    m1, e1 = leaf_totals(coords, got["u"], boxlen)
    out = {
        "state_gap": worst,
        "cell_gap": cell,
        "time_gap": abs(got["dt"] - ref["dt"]) / ref["dt"],
        "mass_drift": abs(m1 - m0) / m0,
        "energy_drift": abs(e1 - e0) / e0,
    }
    if "regrid" in ref:
        band = ref["regrid"]["band"]
        if "tree" in got["regrid"]:
            mine = {l: np.unique(np.asarray(c, np.int64) >> 1, axis=0)
                    for l, c in got["regrid"]["tree"].items()
                    if l > snap["lmin"]}
        else:                   # the control: its own 'must' tree
            mine = got["regrid"]["band"]["must"]
        missing = extra = 0
        for l in set(band["must"]) | set(band["may"]) | set(mine):
            n = 1 << (l - 1)
            have = _keys(mine[l], n) if l in mine else np.zeros(0, np.int64)
            must = _keys(band["must"][l], n) if l in band["must"] \
                else np.zeros(0, np.int64)
            may = _keys(band["may"][l], n) if l in band["may"] \
                else np.zeros(0, np.int64)
            missing += int((~np.isin(must, have)).sum())
            extra += int((~np.isin(have, may)).sum())
        fine = [l for l in ref["regrid"]["u"] if l > snap["lmin"]]
        gap = cgap = 0.0
        a = {l: got["regrid"]["u"][l] for l in fine}
        b = {l: ref["regrid"]["u"][l] for l in fine}
        zero = _zero_like(b)
        for k in range(5):
            gap = max(gap, ratio(_l1(a, b, boxlen, k),
                                 _l1(b, zero, boxlen, k)))
            cgap = max(cgap, ratio(_linf(a, b, k), _linf(b, zero, k)))
        out.update({"tree_missing": float(missing),
                    "tree_extra": float(extra), "migrate_gap": gap,
                    "migrate_cell_gap": cgap})
    return out
