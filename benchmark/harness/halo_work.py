"""What the slab decomposition of a complete level sends over the ICI,
counted the same way for every PR: bytes ONE device sends in ONE
direction-pair exchange, from the configuration's ``slab`` group alone.

Origin of each number (``ramses_tpu/parallel/dense_slab.py``):

* ``halo_extend`` walks the spatial axes in order.  An axis the device
  grid cuts sends two slabs (its low and its high ``ng`` cells) of the
  block AS EXTENDED SO FAR, so corner ghosts carry true values; an uncut
  axis wraps locally and only makes the later slabs larger.
* The hydro sweep (``dense_sweep_slab``, ``ng`` = ``ghost_sweep`` = the
  MUSCL-Hancock stencil's 2 ghost cells, ``hydro/muscl.NGHOST``) extends
  the state (``nvar`` values a cell) and, where the level has refined
  cells (``masked``), the refined-cell mask in the state's dtype (one
  value a cell).  When the per-shard fused kernel runs
  (``kernel_axes``), its lane axis - the last of ``kernel_axes``, always
  uncut - is left bare: the kernel wraps it itself.
* The refinement flags (``dense_flags_slab``, ``ng`` = ``ghost_flags``
  = 1: a gradient reads one neighbour) extend the state on every axis.
* A complete level ``l`` is swept ``2**(l - lmin)`` times a coarse step
  (``sweeps_per_coarse_step``; 1 for the base level) and flagged once a
  regrid (``flags_per_regrid``).

``dma_halo.traffic_snapshot()`` counts the same slabs while a program is
traced (bytes and slabs a device, one direction each): the entry holds
these functions against it once after warm-up, ``benchmark/tests`` at a
rehearsal size.  On the DMA backend the two slabs of a cut axis ride one
kernel call (``exchange_pair``), so kernel calls = slabs / 2.
"""


def local_box(level: int, grid) -> tuple:
    """Cells a device holds per axis: the level's ``2**level`` over the
    device grid."""
    return tuple((1 << level) // int(g) for g in grid)


def extend_slabs(loc, grid, ng: int, bare_axes=()) -> list:
    """Cells of each slab one device sends in one ``halo_extend``."""
    ext, out = [int(n) for n in loc], []
    for d in range(len(ext)):
        if d in bare_axes:
            continue
        if int(grid[d]) > 1:
            cells = ng
            for e, n in enumerate(ext):
                if e != d:
                    cells *= n
            out += [cells, cells]
        ext[d] += 2 * ng
    return out


def sweep_traffic(slab: dict, kernel: bool = True) -> dict:
    """{bytes, slabs} one device sends for ONE sweep of the level.
    ``kernel``: the per-shard fused kernel runs (its lane axis bare)."""
    loc = local_box(slab["level"], slab["grid"])
    bare = (int(slab["kernel_axes"][-1]),) if kernel else ()
    cells = extend_slabs(loc, slab["grid"], int(slab["ghost_sweep"]), bare)
    values = int(slab["nvar"]) + (1 if slab["masked"] else 0)
    return {"bytes": sum(cells) * values * int(slab["itemsize"]),
            "slabs": len(cells) * (2 if slab["masked"] else 1)}


def flags_traffic(slab: dict) -> dict:
    """{bytes, slabs} one device sends for ONE flags pass of the level."""
    loc = local_box(slab["level"], slab["grid"])
    cells = extend_slabs(loc, slab["grid"], int(slab["ghost_flags"]))
    return {"bytes": sum(cells) * int(slab["nvar"]) * int(slab["itemsize"]),
            "slabs": len(cells)}


def bytes_sent(slab: dict, steps: int, regrids: int,
               kernel: bool = True) -> int:
    """Bytes ONE device sends through the halo exchange over ``steps``
    coarse steps and ``regrids`` regrids."""
    return (steps * int(slab["sweeps_per_coarse_step"])
            * sweep_traffic(slab, kernel)["bytes"]
            + regrids * int(slab["flags_per_regrid"])
            * flags_traffic(slab)["bytes"])
