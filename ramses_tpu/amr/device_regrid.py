"""Device-resident regrid migration.

The host reference path (``maps.build_prolong_maps`` +
``hierarchy._migrate_level``) rebuilds per-level numpy row tables on
every changed-tree regrid — the r04-instrumented trace showed that host
work (migrate 9.3 s of a 94 s run) dominating once the sweep itself went
fast.  This module derives the same survivor-copy and new-oct
prolongation maps *on device* with one jitted kernel per level, straight
from the (already sorted) Morton key arrays:

* survivors: a binary search of the new level's keys in the old level's
  sorted keys (``Octree.lookup_keys`` is exactly this on host);
* father cells: a level-l oct key IS its father cell's level-(l-1)
  Morton key, and the covering oct key is ``key >> ndim`` — no
  coordinate decode needed;
* child offsets within the father oct: the bit-reversed low ``ndim``
  bits of the key (the host ``f_off = f_off*2 + (coords[:, d] & 1)``
  fold, since coordinate parities are the low interleaved key bits);
* father neighbours: a jnp port of ``keys.decode``/``encode`` and
  ``tree.map_coords`` (same mask ladders, same reflect/clip semantics),
  then the same binary search.

Selection is by ``where`` over values the host path would gather from
identical rows, and ``kernels.interp_cells`` is elementwise per request
row, so the migrated ``u`` is bitwise identical to the host path (pinned
by tests/test_oct_blocking.py).

Integer width: with jax x64 enabled the port mirrors the host 64-bit
mask ladders (coords to 21 bits/dim in 3D); without it the kernel runs
the standard 32-bit ladders, valid while ``ndim * coord_bits`` fits an
int32 — :func:`keys_fit` gates, and the hierarchy falls back to the
host path beyond.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ramses_tpu.amr import kernels as K
from ramses_tpu.amr.tree import cell_offsets

# spread-mask ladders keyed (ndim, wide): premask + (shift, mask) steps,
# mirroring amr/keys.py bit-for-bit in the 64-bit case and the standard
# 32-bit Morton ladders otherwise; compact runs the same table in
# reverse (see _compact)
_TABS = {
    (2, True): (0xFFFFFFFF,
                ((16, 0x0000FFFF0000FFFF), (8, 0x00FF00FF00FF00FF),
                 (4, 0x0F0F0F0F0F0F0F0F), (2, 0x3333333333333333),
                 (1, 0x5555555555555555))),
    (3, True): (0x1FFFFF,
                ((32, 0x1F00000000FFFF), (16, 0x1F0000FF0000FF),
                 (8, 0x100F00F00F00F00F), (4, 0x10C30C30C30C30C3),
                 (2, 0x1249249249249249))),
    (2, False): (0xFFFF,
                 ((8, 0x00FF00FF), (4, 0x0F0F0F0F),
                  (2, 0x33333333), (1, 0x55555555))),
    (3, False): (0x3FF,
                 ((16, 0xFF0000FF), (8, 0x0F00F00F),
                  (4, 0xC30C30C3), (2, 0x49249249))),
}


def _x64() -> bool:
    return bool(jax.config.jax_enable_x64)


def key_dtype():
    """Device integer dtype for Morton keys (int64 under x64)."""
    return jnp.int64 if _x64() else jnp.int32


def keys_fit(ndim: int, lvl: int, root=None) -> bool:
    """Can every key/coord this level needs fit the device key dtype?"""
    root = tuple(root or ()) or (1,) * ndim
    n = max(root[:ndim]) << max(lvl - 1, 0)    # cells/dim at lvl-1
    bits = max(int(n - 1).bit_length(), 1)
    if _x64():
        return bits <= {1: 62, 2: 31, 3: 20}[ndim]
    return bits <= {1: 30, 2: 15, 3: 10}[ndim]


def _sent(dtype) -> int:
    return int(np.iinfo(np.dtype(dtype.name if hasattr(dtype, "name")
                                 else dtype)).max)


def upload_keys(keys: np.ndarray, pad: int):
    """Sorted level keys padded to ``pad`` with the max-int sentinel
    (keeps the array sorted; sentinel never equals a real key under
    :func:`keys_fit`)."""
    dt = np.int64 if _x64() else np.int32
    out = np.full(pad, np.iinfo(dt).max, dtype=dt)
    n = min(len(keys), pad)
    out[:n] = keys[:n]
    return jnp.asarray(out)


def _spread(x, ndim: int, wide: bool):
    pre, tab = _TABS[(ndim, wide)]
    x = x & jnp.asarray(pre, x.dtype)
    for s, m in tab:
        x = (x | (x << s)) & jnp.asarray(m, x.dtype)
    return x


def _compact(x, ndim: int, wide: bool):
    pre, tab = _TABS[(ndim, wide)]
    x = x & jnp.asarray(tab[-1][1], x.dtype)
    for i in range(len(tab) - 1, 0, -1):
        x = (x | (x >> tab[i][0])) & jnp.asarray(tab[i - 1][1], x.dtype)
    return (x | (x >> tab[0][0])) & jnp.asarray(pre, x.dtype)


def _encode(c, ndim: int):
    """jnp port of keys.encode: coords [n, ndim] → keys [n]."""
    if ndim == 1:
        return c[:, 0]
    sdt = c.dtype
    udt = jnp.uint64 if sdt == jnp.int64 else jnp.uint32
    k = _spread(c[:, 0].astype(udt), ndim, sdt == jnp.int64)
    for d in range(1, ndim):
        k = k | (_spread(c[:, d].astype(udt), ndim,
                         sdt == jnp.int64) << d)
    return k.astype(sdt)


def _decode(k, ndim: int):
    """jnp port of keys.decode: keys [n] → coords [n, ndim]."""
    if ndim == 1:
        return k[:, None]
    sdt = k.dtype
    udt = jnp.uint64 if sdt == jnp.int64 else jnp.uint32
    ku = k.astype(udt)
    return jnp.stack([_compact(ku >> d, ndim,
                               sdt == jnp.int64).astype(sdt)
                      for d in range(ndim)], axis=1)


def _bitrev_low(k, ndim: int):
    """Child slot within the father oct: the host ``f_off*2 +
    (coords[:, d] & 1)`` fold over ascending d, read straight off the
    low interleaved key bits."""
    off = jnp.zeros_like(k)
    for d in range(ndim):
        off = off * 2 + ((k >> d) & 1)
    return off


def _map_coords(cc, bc_kinds, dims, ndim: int):
    """jnp port of tree.map_coords (static bc kinds / dims): mapped
    coords plus the per-dim 'crossed a reflecting face' flags."""
    outs, refls = [], []
    for d in range(ndim):
        n = int(dims[d])
        lo, hi = bc_kinds[d]
        x = cc[:, d]
        if lo == 0 and hi == 0:
            outs.append(jnp.mod(x, n))
            refls.append(jnp.zeros(x.shape, bool))
            continue
        below, above = x < 0, x >= n
        r = jnp.zeros(x.shape, bool)
        if lo == 1:
            x = jnp.where(below, -1 - x, x)
            r = r | below
        elif lo != 0:
            x = jnp.where(below, 0, x)
        if hi == 1:
            x = jnp.where(above, 2 * n - 1 - x, x)
            r = r | above
        elif hi != 0:
            x = jnp.where(above, n - 1, x)
        outs.append(jnp.clip(x, 0, n - 1))
        refls.append(r)
    return jnp.stack(outs, axis=1), jnp.stack(refls, axis=1)


def _find(sorted_keys, ks):
    """(clipped position, exact-hit) of ``ks`` in a sorted key array —
    the device half of ``Octree.lookup_keys``."""
    pos = jnp.searchsorted(sorted_keys, ks)
    pos = jnp.clip(pos, 0, sorted_keys.shape[0] - 1)
    return pos, sorted_keys[pos] == ks


def migrate_level(old_u, u_coarse, new_keys, old_keys, coarse_keys,
                  ncell_pad: int, ndim: int, bc_kinds: tuple,
                  dims: tuple, cfg, itype: int):
    """One level's regrid migration with maps derived on device.

    ``new_keys``/``old_keys``/``coarse_keys`` are sentinel-padded sorted
    key arrays (:func:`upload_keys`) of the new level, the old level and
    the new coarser level; ``dims`` are the lvl-1 cell counts per dim.
    Returns the migrated [ncell_pad, nvar] batch, bitwise identical to
    ``build_prolong_maps`` + ``_migrate_level``.

    Host-parked state (``offload.HostBuffer``, &AMR_PARAMS offload)
    composes: parked operands are fetched here, outside the jit, so the
    traced program always sees device arrays.
    """
    from ramses_tpu.amr.offload import as_device
    return _migrate_level_jit(as_device(old_u), as_device(u_coarse),
                              new_keys, old_keys, coarse_keys, ncell_pad,
                              ndim, bc_kinds, dims, cfg, itype)


@partial(jax.jit, static_argnames=("ncell_pad", "ndim", "bc_kinds",
                                   "dims", "cfg", "itype"))
def _migrate_level_jit(old_u, u_coarse, new_keys, old_keys, coarse_keys,
                       ncell_pad: int, ndim: int, bc_kinds: tuple,
                       dims: tuple, cfg, itype: int):
    ttd = 1 << ndim
    sent = _sent(new_keys.dtype)
    valid = new_keys < sent                       # real (non-pad) octs
    pos, kept = _find(old_keys, new_keys)
    kept = kept & valid
    f_pos, _ = _find(coarse_keys, new_keys >> ndim)
    f_cell = f_pos * ttd + _bitrev_low(new_keys, ndim)
    og = _decode(new_keys, ndim)                  # cell coords at lvl-1
    nb = []
    for d in range(ndim):
        cols = []
        for s in (-1, +1):
            nc = og.at[:, d].add(s)
            ncm, nrefl = _map_coords(nc, bc_kinds, dims, ndim)
            nkey = _encode(ncm, ndim)
            n_pos, found = _find(coarse_keys, nkey >> ndim)
            bad = ~found | nrefl.any(axis=1)
            cols.append(jnp.where(bad, f_cell,
                                  n_pos * ttd + _bitrev_low(nkey, ndim)))
        nb.append(jnp.stack(cols, axis=1))
    nb = jnp.stack(nb, axis=1)                    # [noct_pad, ndim, 2]

    rows = jnp.arange(ncell_pad)
    oi, j = rows // ttd, rows % ttd
    sgn_tab = jnp.asarray((cell_offsets(ndim) * 2 - 1).astype(np.float64),
                          dtype=u_coarse.dtype)   # [2^d, ndim]
    # father rows gathered once per oct; ncell_pad == noct_pad * 2^ndim
    vals = K.interp_octs(u_coarse, f_cell, nb, sgn_tab, cfg, itype=itype)
    copied = old_u[pos[oi] * ttd + j]
    return jnp.where(kept[oi][:, None], copied.astype(old_u.dtype),
                     jnp.where(valid[oi][:, None],
                               vals.astype(old_u.dtype), 0))
