"""Staged bit-permutation (``amr/bitperm.py``) against the one-shot form.

The module applies the flat↔dense permutation of a complete level one
Morton group at a time (a TPU compile-time repair, PR 22); the one-shot
``(2,)*ndim*lvl`` reshape + single transpose it replaced is kept here
as the plain reference.  Pure data movement ⇒ bitwise equality, for
full boxes and for every shard-local slab cut, with and without
trailing axes (state rows ``[n, nvar]`` and bare refinement masks).
Also pins ``kernels.interp_octs`` (per-oct gathers) bitwise to
``interp_cells`` on the ``2^ndim``-fold repeated indices it replaced
in the device regrid.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ramses_tpu.amr import bitperm


def _one_shot(rows, lvl, ndim, mbits):
    seq = bitperm._bit_seq(lvl, ndim)
    pos = {bit: p - mbits for p, bit in enumerate(seq) if p >= mbits}
    ax = tuple(pos[(d, i)] for d in range(ndim)
               for i in range(lvl - 1, -1, -1) if (d, i) in pos)
    nb = ndim * lvl - mbits
    tr = rows.shape[1:]
    x = rows.reshape((2,) * nb + tr)
    x = jnp.transpose(x, ax + tuple(range(nb, nb + len(tr))))
    return x.reshape(bitperm.slab_shape(lvl, ndim, mbits) + tr)


@pytest.mark.smoke
@pytest.mark.parametrize("trailing", [(), (5,), (3, 2)])
@pytest.mark.parametrize("ndim,lvl", [(1, 4), (2, 3), (3, 1), (3, 3)])
def test_staged_equals_one_shot(ndim, lvl, trailing):
    rng = np.random.default_rng(ndim * 10 + lvl)
    for mbits in range(ndim * (lvl - 1) + 1):
        ncell = 1 << (ndim * lvl - mbits)
        rows = jnp.asarray(rng.standard_normal((ncell,) + trailing))
        want = _one_shot(rows, lvl, ndim, mbits)
        got = bitperm.flat_to_dense_slab(rows, lvl, ndim, mbits)
        assert got.shape == want.shape
        assert np.array_equal(got, want), mbits
        back = bitperm.dense_to_flat_slab(got, lvl, ndim, mbits)
        assert np.array_equal(back, rows), mbits


@pytest.mark.smoke
def test_bool_mask_round_trip_matches_flat_index():
    """A bare mask (no trailing axis) lands where ``flat_index_np``
    says each dense cell lives."""
    lvl, ndim = 3, 3
    n = 1 << lvl
    dense = np.zeros((n, n, n), bool)
    cells = np.array([[0, 0, 0], [1, 2, 3], [7, 7, 7], [4, 0, 5]])
    dense[tuple(cells.T)] = True
    flat = np.asarray(bitperm.dense_to_flat(jnp.asarray(dense), lvl, ndim))
    want = np.zeros(n ** 3, bool)
    want[bitperm.flat_index_np(cells, lvl, ndim)] = True
    assert np.array_equal(flat, want)


@pytest.mark.smoke
@pytest.mark.parametrize("itype", [0, 1, 2, 3])
def test_interp_octs_equals_repeated_interp_cells(itype):
    from ramses_tpu.amr import kernels as K
    from ramses_tpu.amr.tree import cell_offsets
    from ramses_tpu.config import Params
    from ramses_tpu.hydro.core import HydroStatic

    cfg = HydroStatic.from_params(Params(ndim=3))
    rng = np.random.default_rng(itype)
    u = jnp.asarray(rng.standard_normal((64, cfg.nvar)), jnp.float32)
    cell = jnp.asarray(rng.integers(0, 64, 11), jnp.int32)
    nb = jnp.asarray(rng.integers(0, 64, (11, 3, 2)), jnp.int32)
    sgn_tab = jnp.asarray(cell_offsets(3) * 2 - 1, jnp.float32)
    got = K.interp_octs(u, cell, nb, sgn_tab, cfg, itype=itype)
    oi, j = np.repeat(np.arange(11), 8), np.tile(np.arange(8), 11)
    want = K.interp_cells(u, cell[oi], nb[oi], sgn_tab[j], cfg,
                          itype=itype)
    assert np.array_equal(got, want)
