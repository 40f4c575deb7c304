"""What a window's work is, counted the same way for every PR.

* Cell updates: the reference's ``mus/pt`` definition
  (``amr/adaptive_loop.f90:204-212``), copied from ``bench.py:413-416``
  (the original is listed for deletion in PERF.md): one update per cell
  per level substep, a level ``l`` being swept ``2**(l - lmin)`` times a
  coarse step.
* Least time of a cell update on a chip, from ``peaks.json``.
"""

# 2 * nvar * itemsize: every conserved variable read once and written once.
# Origin: the algorithm's minimum; a stencil re-reads neighbours from VMEM,
# not from HBM.
def bytes_per_cell_update(nvar: int, itemsize: int) -> int:
    return 2 * nvar * itemsize


# Origin: jax.jit(muscl.unsplit + apply_fluxes).lower().compile()
# .cost_analysis()["flops"] of the plain XLA formulation, 32^3 cells, f32,
# ndim=3, nvar=5, minmod, LLF, on the sandbox CPU (PR 24): 78,970,880 flops
# / 32768 cells.  A count, not a speed.
FLOPS_PER_CELL_UPDATE = 2410


def amr_cell_updates(noct_by_level: dict, lmin: int, ndim: int = 3) -> int:
    """Cell updates of ONE coarse step of a tree."""
    return sum(noct * (1 << ndim) * (1 << (lvl - lmin))
               for lvl, noct in noct_by_level.items())


def least_time_s(cell_updates: float, peak: dict, nvar: int = 5,
                 itemsize: int = 4):
    """(seconds, which peak bounds) for ``cell_updates`` on one chip."""
    by_bytes = cell_updates * bytes_per_cell_update(nvar, itemsize) \
        / peak["hbm_bytes_per_s"]
    by_flops = cell_updates * FLOPS_PER_CELL_UPDATE / peak["flops_bf16"]
    if by_bytes >= by_flops:
        return by_bytes, "bytes"
    return by_flops, "flops"
