"""Cost-weighted Hilbert load balancing for the sharded AMR path.

The reference's ``load_balance.f90`` (``cost_weighting``) assigns each oct
a cost — solver sweeps plus particle work — and cuts the Hilbert curve
into per-CPU segments of near-equal summed cost.  Here the analog: each
partial level's dense row batch is a padded ``[noct_pad, ...]`` block
row-sharded over the 1-D "oct" mesh axis, device ``d`` owning rows
``[d*cap, (d+1)*cap)`` with ``cap = noct_pad // ndev``.  The seed layout
was the identity (tree/Morton order, trailing pads) — blind equal row
splits.  A :class:`LevelLayout` generalizes this to an arbitrary
permutation: device ``d``'s row segment holds a *contiguous Hilbert-key
range* of ``n_d <= cap`` real octs (pads fill the remainder of each
segment), with the ``n_d`` chosen by a capacity-constrained weighted cut
so per-device summed cost is balanced within the bucket-padding bound.

Layouts are applied *after* the tree-order map builders
(`amr/maps.py`) as a pure index transform — ``apply_layout_level`` /
``apply_layout_gravity`` permute oct/cell rows and remap stored row
values.  Ownership is purely ``row // rows_per_device`` (the row
sharding), so the programs GSPMD partitions from transformed maps are
correct against the new cuts — no comm-layer changes.

Complete levels always keep the identity layout: their dense bit-permute
sweep path depends on lexicographic row order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ramses_tpu.amr.hilbert import hilbert_order

__all__ = [
    "LevelLayout", "BalanceStats", "oct_costs", "balanced_cuts",
    "make_layout", "compute_layouts", "measure", "enabled",
    "apply_layout_level", "apply_layout_blocks", "apply_layout_gravity",
    "remap_son_oct",
    "remap_octs", "remap_cells", "layout_sig", "layouts_same",
    "merge_ranges", "ranges_cover",
]


@dataclass(frozen=True)
class LevelLayout:
    """Row placement of one partial level's ``noct`` real octs inside its
    padded ``noct_pad`` batch, split over ``ndev`` equal row segments.

    ``oct_row[i]`` is the row slot of tree oct ``i``; ``row_oct[r]`` the
    inverse (-1 on pad rows).  Real rows are NOT contiguous — each device
    segment carries its own trailing pads — so consumers must gather
    through ``oct_row`` instead of slicing ``[:noct]``.
    """
    noct: int
    noct_pad: int
    ndev: int
    oct_row: np.ndarray      # [noct] int64, tree oct idx -> row slot
    row_oct: np.ndarray      # [noct_pad] int64, row slot -> oct idx | -1
    counts: np.ndarray       # [ndev] int64 real octs per device segment
    sig: int                 # value hash for cache keys / reuse checks


@dataclass(frozen=True)
class BalanceStats:
    """Per-device summed cost under the current layouts."""
    per_dev: np.ndarray      # [ndev] float64
    max_cost: float
    mean_cost: float
    imbalance: float         # max/mean, 1.0 when perfectly balanced

    def __str__(self):
        return (f"max/mean={self.max_cost:.4g}/{self.mean_cost:.4g} "
                f"imb={self.imbalance:.3f}")


def layout_sig(lay: Optional[LevelLayout]) -> Optional[int]:
    return None if lay is None else lay.sig


def layouts_same(a: Dict[int, LevelLayout], b: Dict[int, LevelLayout],
                 levels=None) -> bool:
    keys = (set(a) | set(b)) if levels is None else set(levels)
    return all(layout_sig(a.get(l)) == layout_sig(b.get(l)) for l in keys)


def merge_ranges(ranges) -> list:
    """Coalesce ``[start, length]`` (or ``(start, length)``) row
    intervals into a sorted list of maximal disjoint ``[start, end)``
    pairs.  Empty/zero-length intervals are dropped."""
    ivs = sorted((int(r0), int(r0) + int(n)) for r0, n in ranges
                 if int(n) > 0)
    out: list = []
    for lo, hi in ivs:
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def ranges_cover(ranges, total: int):
    """Whether ``[start, length]`` intervals cover ``[0, total)`` with
    no gap.  Returns ``(covered, first_gap)`` — ``first_gap`` is the
    ``[lo, hi)`` of the first uncovered span (None when covered).
    Elastic restore uses this to decide whether a surviving shard
    subset still reconstructs every row of the saved hierarchy."""
    total = int(total)
    if total <= 0:
        return True, None
    merged = merge_ranges(ranges)
    pos = 0
    for lo, hi in merged:
        if lo > pos:
            return False, [pos, lo]
        pos = max(pos, hi)
        if pos >= total:
            return True, None
    return pos >= total, (None if pos >= total else [pos, total])


# ---------------------------------------------------------------- cost model

def oct_costs(sim, l: int) -> np.ndarray:
    """Per-oct cost [noct] at level ``l`` — the ``cost_weighting`` analog.

    Base term: cells per oct times a solver weight (MHD/RT sweeps cost
    more than plain hydro) times the subcycle factor ``2^(l-lmin)`` (a
    level-``l`` oct is swept that many times per coarse step).  Particle
    term: per-oct particle counts times ``cost_weight_part``.
    """
    amr = sim.params.amr
    tree = sim.tree
    noct = tree.noct(l)
    ttd = 1 << tree.ndim
    physics = getattr(sim.cfg, "physics", "hydro")
    if physics == "mhd":
        w_solver = float(getattr(amr, "cost_weight_mhd", 2.0))
    else:
        w_solver = float(getattr(amr, "cost_weight_hydro", 1.0))
    if getattr(sim, "rt_amr", None) is not None:
        w_solver += float(getattr(amr, "cost_weight_rt", 1.5))
    sub = float(1 << (l - sim.lmin))
    w = np.full(noct, w_solver * ttd * sub, dtype=np.float64)

    p = getattr(sim, "p", None)
    w_part = float(getattr(amr, "cost_weight_part", 0.3))
    if p is not None and w_part > 0.0:
        x = np.asarray(p.x, dtype=np.float64)[:, :tree.ndim]
        act = np.asarray(p.active, dtype=bool)
        if act.any():
            x = x[act]
            boxlen = float(amr.boxlen)
            dx_oct = boxlen / (1 << (l - 1))   # oct size, assign_levels conv
            og = np.floor(x / dx_oct).astype(np.int64)
            og = np.clip(og, 0, (1 << (l - 1)) - 1)
            idx = tree.lookup(l, og)
            idx = idx[idx >= 0]
            if len(idx):
                w += w_part * np.bincount(idx, minlength=noct)[:noct]
    return w


# ------------------------------------------------------------ weighted cuts

def balanced_cuts(w: np.ndarray, ndev: int, cap: int) -> np.ndarray:
    """Split ``w`` (costs in curve order) into ``ndev`` contiguous runs of
    at most ``cap`` items each, greedily equalizing summed cost.

    Returns per-device counts summing to ``len(w)``.  Feasibility
    (``len(w) <= ndev*cap``) is the caller's padding invariant; the
    per-segment clamp ``end >= n - remaining*cap`` keeps every later
    device within capacity.
    """
    n = len(w)
    if n > ndev * cap:
        raise ValueError(f"infeasible cut: {n} octs > {ndev}x{cap}")
    cw = np.concatenate([[0.0], np.cumsum(np.asarray(w, dtype=np.float64))])
    total = cw[-1]
    counts = np.zeros(ndev, dtype=np.int64)
    start = 0
    for d in range(ndev):
        rem = ndev - d
        if d == ndev - 1:
            end = n
        else:
            lo = max(start, n - (rem - 1) * cap)
            hi = min(start + cap, n)
            target = cw[start] + (total - cw[start]) / rem
            end = int(np.searchsorted(cw, target, side="left"))
            # the cut just below may sit closer to the target
            if end - 1 >= start and end <= n and \
                    target - cw[end - 1] <= cw[min(end, n)] - target:
                end -= 1
            end = min(max(end, lo), hi)
        counts[d] = end - start
        start = end
    assert start == n
    return counts


def make_layout(order: np.ndarray, counts: np.ndarray, noct_pad: int,
                ndev: int) -> LevelLayout:
    """Layout placing curve-order octs ``order`` into per-device segments
    of ``counts`` real rows each (pads trail inside every segment)."""
    noct = len(order)
    cap = noct_pad // ndev
    oct_row = np.empty(noct, dtype=np.int64)
    row_oct = np.full(noct_pad, -1, dtype=np.int64)
    start = 0
    for d in range(ndev):
        c = int(counts[d])
        seg = order[start:start + c]
        rows = d * cap + np.arange(c, dtype=np.int64)
        oct_row[seg] = rows
        row_oct[rows] = seg
        start += c
    sig = hash((noct, noct_pad, ndev, oct_row.tobytes()))
    return LevelLayout(noct=noct, noct_pad=noct_pad, ndev=ndev,
                       oct_row=oct_row, row_oct=row_oct,
                       counts=np.asarray(counts, dtype=np.int64), sig=sig)


def _is_identity(lay: LevelLayout) -> bool:
    return bool(np.array_equal(lay.oct_row, np.arange(lay.noct)))


def compute_layouts(sim) -> Dict[int, LevelLayout]:
    """Candidate layouts for every partial level of ``sim.tree`` —
    cost-weighted cuts along the Hilbert curve (``run.ordering``
    'hilbert'; tree/Morton order otherwise).  Identity results are
    dropped so absent == identity holds everywhere."""
    tree = sim.tree
    ndev = int(getattr(sim, "ndev", 1))
    hilbert = getattr(sim.params.run, "ordering", "hilbert") == "hilbert"
    out: Dict[int, LevelLayout] = {}
    for l in sim.levels():
        noct = tree.noct(l)
        if noct == int(np.prod(tree.oct_dims(l))):
            continue                       # complete level: keep identity
        noct_pad = sim._noct_pad(l, noct)
        cap = noct_pad // ndev
        if hilbert:
            og = tree.levels[l].og
            nbits = max(1, int(np.max(og)).bit_length())
            order = hilbert_order(og, tree.ndim, nbits)
        else:
            order = np.arange(noct, dtype=np.int64)
        w = oct_costs(sim, l)
        counts = balanced_cuts(w[order], ndev, cap)
        lay = make_layout(order, counts, noct_pad, ndev)
        if not _is_identity(lay):
            out[l] = lay
    return out


def measure(sim, layouts: Optional[Dict[int, LevelLayout]] = None
            ) -> BalanceStats:
    """Aggregate per-device cost over all levels under ``layouts``
    (default: the sim's current layouts; absent level == identity)."""
    if layouts is None:
        layouts = getattr(sim, "layouts", {})
    ndev = int(getattr(sim, "ndev", 1))
    per = np.zeros(ndev, dtype=np.float64)
    for l in sim.levels():
        noct = sim.tree.noct(l)
        w = oct_costs(sim, l)
        lay = layouts.get(l)
        cap = (lay.noct_pad if lay is not None
               else sim._noct_pad(l, noct)) // ndev
        rows = lay.oct_row if lay is not None \
            else np.arange(noct, dtype=np.int64)
        per += np.bincount(rows // cap, weights=w, minlength=ndev)[:ndev]
    mean = float(per.sum()) / ndev
    mx = float(per.max()) if len(per) else 0.0
    imb = mx / mean if mean > 0 else 1.0
    return BalanceStats(per_dev=per, max_cost=mx, mean_cost=mean,
                        imbalance=imb)


def enabled(sim) -> bool:
    """Opt-in gate: ``&AMR_PARAMS load_balance`` plus the reference's
    ``cost_weighting`` run flag, restricted to the state layers the
    layout transform covers (hydro + gravity + PM particles).  Layers
    carrying extra per-cell/side-channel state keep the identity layout."""
    p = sim.params
    if not bool(getattr(p.amr, "load_balance", False)):
        return False
    if not bool(getattr(p.run, "cost_weighting", True)):
        return False
    if getattr(sim.cfg, "physics", "hydro") != "hydro":
        return False                      # MHD face fields / SR state
    if getattr(sim, "_needs_mig_log", False):
        return False                      # subclass-owned per-cell state
    if getattr(sim, "rt_amr", None) is not None:
        return False
    if getattr(sim, "tracer_x", None) is not None:
        return False
    if getattr(sim, "sinks", None) is not None:
        return False
    if getattr(sim, "movie", None) is not None:
        return False
    sf = getattr(sim, "sf_spec", None)
    if sf is not None and getattr(sf, "enabled", False):
        return False
    return True


# ------------------------------------------------------- layout application
#
# Value-remap conventions (ttd = 2^ndim):
#   oct value v at level L      ->  oct_row_L[v]           (v < noct)
#   flat cell value v at L      ->  oct_row_L[v//ttd]*ttd + v%ttd
# Sentinels (trash rows, ghost slots, -1, noct_pad) pass through unchanged.
# Row permutation of an oct-indexed [noct_pad, ...] array scatters the
# first ``noct`` rows to ``oct_row`` slots and fills pads.

def remap_octs(v: np.ndarray, lay: LevelLayout) -> np.ndarray:
    """Remap oct-index values through ``lay``; anything outside
    ``[0, noct)`` (sentinels like ``noct_pad``, -1) passes through."""
    v64 = np.asarray(v).astype(np.int64)
    mapped = lay.oct_row[np.clip(v64, 0, lay.noct - 1)]
    return np.where((v64 >= 0) & (v64 < lay.noct), mapped,
                    v64).astype(np.asarray(v).dtype)


def remap_cells(v: np.ndarray, lay: LevelLayout, ttd: int) -> np.ndarray:
    """Remap flat-cell values through ``lay``; anything outside
    ``[0, noct*ttd)`` (pad cells, ghost slots, trash rows, the PM
    ``ncell_pad`` sentinel, -1) passes through."""
    v64 = np.asarray(v).astype(np.int64)
    ncell = lay.noct * ttd
    mapped = (lay.oct_row[np.clip(v64, 0, ncell - 1) // ttd] * ttd
              + np.where(v64 >= 0, v64 % ttd, 0))
    return np.where((v64 >= 0) & (v64 < ncell), mapped,
                    v64).astype(np.asarray(v).dtype)


def _perm_oct_rows(a: np.ndarray, lay: LevelLayout, fill) -> np.ndarray:
    out = np.full_like(a, fill)
    out[lay.oct_row] = a[:lay.noct]
    return out


def _perm_cell_rows(a: np.ndarray, lay: LevelLayout, ttd: int,
                    fill) -> np.ndarray:
    rows = (lay.oct_row[:, None] * ttd
            + np.arange(ttd, dtype=np.int64)).reshape(-1)
    out = np.full_like(a, fill)
    out[rows] = a[:lay.noct * ttd]
    return out


def remap_son_oct(m, lay_p1: LevelLayout):
    """Remap ``son_oct`` values (oct indices at l+1) through the l+1
    layout.  Pad entries hold 0 and land on ``oct_row[0]`` — harmless,
    their ``ref_cell`` is -1."""
    from dataclasses import replace
    return replace(m, son_oct=remap_octs(m.son_oct, lay_p1))


def apply_layout_level(m, lay_m1: Optional[LevelLayout],
                       lay: Optional[LevelLayout],
                       lay_p1: Optional[LevelLayout]):
    """Transform tree-order ``LevelMaps`` into layout order.

    Rows of oct-indexed arrays are permuted by ``lay``; stored index
    values are remapped through the layout of the level they point at
    (cells of l: ``lay``; cells of l-1: ``lay_m1``; octs of l+1:
    ``lay_p1``)."""
    from dataclasses import replace
    if m.complete:
        assert lay is None and lay_m1 is None, \
            "complete levels keep the identity layout"
        return remap_son_oct(m, lay_p1) if lay_p1 is not None else m

    ttd = 1 << m.ndim
    kw = {}
    if lay is not None:
        assert lay.noct == m.noct and lay.noct_pad == m.noct_pad, \
            f"layout/maps mismatch at lvl {m.lvl}"
        if m.has_stencil:
            trash = m.ncell_pad + m.ni_pad
            # stencil values: cells of l (< ncell_pad) remap; interp slots
            # (>= ncell_pad) and the trash row pass through remap_cells
            src = remap_cells(m.stencil_src, lay, ttd)
            kw["stencil_src"] = _perm_oct_rows(src, lay, trash)
            if m.vsgn is not None:
                kw["vsgn"] = _perm_oct_rows(m.vsgn, lay, 0)
            kw["ok_ref"] = _perm_oct_rows(m.ok_ref, lay, False)
        kw["valid_oct"] = _perm_oct_rows(m.valid_oct, lay, False)
        corr = _perm_oct_rows(m.corr_idx, lay, -1)
        kw["ref_cell"] = remap_cells(m.ref_cell, lay, ttd)
    else:
        corr = m.corr_idx
        kw["ref_cell"] = m.ref_cell
    if lay_m1 is not None:
        if m.has_stencil:
            kw["interp_cell"] = remap_cells(m.interp_cell, lay_m1, ttd)
            kw["interp_nb"] = remap_cells(m.interp_nb, lay_m1, ttd)
        corr = remap_cells(corr, lay_m1, ttd)
    kw["corr_idx"] = corr
    son = m.son_oct
    if lay_p1 is not None:
        son = remap_octs(son, lay_p1)
    kw["son_oct"] = son
    return replace(m, **kw)


def apply_layout_blocks(b, lay_m1: Optional[LevelLayout],
                        lay: Optional[LevelLayout]):
    """Transform tree-order ``BlockMaps`` into layout order.

    Tile-indexed arrays (``tile_src``/``tile_ok``/``tile_vsgn`` rows and
    the incremental-rebuild geometry) keep tree/Morton row order — tiles
    are a pure function of the Morton prefix set, independent of where
    the layout placed each oct's flat row.  Only the *values* that point
    at flat cell rows remap: ``tile_src`` entries (cells of l; interp
    slots and the trash row pass through), ``interp_cell``/``interp_nb``
    (cells of l-1), and the scatter-back maps ``cell_tile``/``cell_slot``
    / ``oct_tile``/``oct_slot``, whose ROWS are flat-cell/oct rows and so
    permute with the layout.  Pad rows keep the zero-output sentinels
    (``cell_slot = c^ndim`` gathers the appended zero column; pad-oct
    corr garbage is dropped by the layout-transformed ``corr_idx = -1``).
    """
    from dataclasses import replace
    if lay is None and lay_m1 is None:
        return b
    ttd = 1 << b.ndim
    kw = {}
    if lay is not None:
        assert lay.noct == b.noct and lay.noct_pad == b.noct_pad, \
            f"layout/blocks mismatch at lvl {b.lvl}"
        c = 1 << (b.shift + 1)
        kw["tile_src"] = remap_cells(b.tile_src, lay, ttd)
        kw["cell_tile"] = _perm_cell_rows(b.cell_tile, lay, ttd, 0)
        kw["cell_slot"] = _perm_cell_rows(b.cell_slot, lay, ttd,
                                          c ** b.ndim)
        kw["oct_tile"] = _perm_oct_rows(b.oct_tile, lay, 0)
        kw["oct_slot"] = _perm_oct_rows(b.oct_slot, lay, 0)
    if lay_m1 is not None:
        kw["interp_cell"] = remap_cells(b.interp_cell, lay_m1, ttd)
        kw["interp_nb"] = remap_cells(b.interp_nb, lay_m1, ttd)
    return replace(b, **kw)


def apply_layout_gravity(g, lay_m1: Optional[LevelLayout],
                         lay: Optional[LevelLayout]):
    """Transform tree-order ``GravityMaps`` into layout order."""
    from dataclasses import replace
    if lay is None and lay_m1 is None:
        return g
    ndim = g.nb.shape[1]
    ttd = 1 << ndim
    kw = {}
    if lay is not None:
        # nb values index concat(cells, ghosts, zero): only cells
        # (< ncell_pad) remap; pad rows point at zero_row = ncell_pad+ng_pad
        zrow = g.ncell_pad + g.ng_pad
        kw["nb"] = _perm_cell_rows(remap_cells(g.nb, lay, ttd),
                                   lay, ttd, zrow)
        kw["valid_cell"] = _perm_cell_rows(g.valid_cell, lay, ttd, False)
        if g.oct_nb is not None:
            noct_pad = g.oct_nb.shape[0]
            kw["oct_nb"] = _perm_oct_rows(remap_octs(g.oct_nb, lay),
                                          lay, noct_pad)
        if g.mg:
            nb0, par0, n0 = g.mg[0]
            par0p = _perm_oct_rows(par0, lay, int(nb0.shape[0]))
            kw["mg"] = ((nb0, par0p, n0),) + tuple(g.mg[1:])
    if lay_m1 is not None:
        kw["g_cell"] = remap_cells(g.g_cell, lay_m1, ttd)
        kw["g_nb"] = remap_cells(g.g_nb, lay_m1, ttd)
    return replace(g, **kw)
