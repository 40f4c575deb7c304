"""``--seed`` → where the blast goes.

Every seed is an exact translation of every other through the periodic
box: the point region's centre is a corner of a cell of the seed grid
(``2**level`` cells a side) whose index, per dimension, is a multiple of
``pitch`` cells and at least one pitch from the box faces.  ``pitch`` is
the coarsest Morton-tile pitch of the blocked AMR levels, so oct and tile
counts per level, and with them the padded shapes and the compiled
programs, are the same for every seed; the data, the Morton order and the
gather indices differ.

Where the pitch comes from (each configuration states its own as
``seed.pitch_cells``; both Sedov configurations share the AMR one, so the
same seed puts the blast at the same physical place in both):
``amr/maps.py`` ``build_block_maps`` tiles a blocked level in Morton-aligned
tiles of ``2**oct_block_shift`` = 4 octs a side (``oct_block_shift=2``, the
default and the only shift the Pallas gate admits).  A level-8 tile is 4
level-8 octs = 4 level-7 cells a side; a level-9 tile is 2 level-7 cells.
A translation by a multiple of 4 level-7 cells maps tiles onto tiles at
both levels.

The seed also draws WHEN in the window the slice that ``correct`` follows
is taken (``check_fraction``): the first slice that starts once that share
of ``--seconds`` has passed.
"""

import numpy as np


def blast_centre(seed: int, level: int, pitch: int, boxlen: float):
    """Three coordinates in ``[pitch, n - pitch] * boxlen / n``."""
    n = 1 << level
    slots = n // pitch - 1            # multiples of pitch in [pitch, n-pitch]
    if slots < 1:
        raise ValueError(f"seed grid 2**{level} too small for pitch {pitch}")
    rng = np.random.default_rng(int(seed))
    k = pitch * (1 + rng.integers(0, slots, size=3))
    return [float(i) * boxlen / n for i in k], [int(i) for i in k]


def check_fraction(seed: int) -> float:
    """Share of the window after which the checked slice starts, in
    [0, 1): a stream of its own, so the blast's place is not moved."""
    return float(np.random.default_rng([int(seed), 1]).random())


def place_blast(params, centre, region: int):
    """Write the centre into ``&INIT_PARAMS`` of an already-loaded
    namelist (no per-seed namelist file is written)."""
    init = params.init
    for axis, key in enumerate(("x_center", "y_center", "z_center")):
        vals = list(getattr(init, key))
        vals[region] = centre[axis]
        setattr(init, key, vals)
