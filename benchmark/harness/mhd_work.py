"""What an MHD cell update is, counted the same way for every PR.

``harness/work.least_time_s`` counts the hydro step (5 variables, 40 B,
2 410 flops); the constrained-transport step moves and computes more, so the
MHD readers count with this file and the MHD cell is listed under no reader
that counts with the other.

* Bytes: every cell variable (8) and every staggered face (3) read once and
  written once, ``2 * 11 * itemsize`` = 88 B in float32.  The algorithm's
  minimum: a stencil re-reads neighbours from VMEM, not from HBM.  (The
  centred field among the 8 is derived from the faces; a program that does
  not store it moves less and reads over its share of THIS count - the
  yardstick then needs a ``benchmark`` issue, not an edit here.)
* Flops: ``jax.jit(mhd_plain.step).lower(...).cost_analysis()["flops"]``
  of the plain reference's one step, 32^3 cells, f32, BEFORE optimisation
  (PR 34): 109 707 280 flops / 32 768 cells = 3 348 (+ 42 transcendentals),
  every operation of the scheme as written, once.  A count, not a speed.
  The same step COMPILED reads 32 899 on the sandbox CPU and 9 074 for the
  described v5e: both count what the compiler recomputes when it copies a
  producer into each consumer's fusion, which is not work the algorithm
  needs.  (XLA's count of the PROGRAM's like formulation compiled for the
  described v5e: 7 560 at 128^3, 8 285 at 64^3 - ISSUE 34.)

At 819 GB/s and 197 TFLOP/s that is 1.07e-10 s by bytes and 1.7e-11 s by
flops a cell update (4.6e-11 s at the compiled 9 074): bytes-bound on paper, so the shares are of the HBM
roofline.
"""

NVAR_CELL = 8
NVAR_FACE = 3
FLOPS_PER_CELL_UPDATE = 3348


def bytes_per_cell_update(itemsize: int = 4) -> int:
    return 2 * (NVAR_CELL + NVAR_FACE) * itemsize


def least_time_s(cell_updates: float, peak: dict, itemsize: int = 4):
    """(seconds, which peak bounds) for ``cell_updates`` on one chip."""
    by_bytes = cell_updates * bytes_per_cell_update(itemsize) \
        / peak["hbm_bytes_per_s"]
    by_flops = cell_updates * FLOPS_PER_CELL_UPDATE / peak["flops_bf16"]
    if by_bytes >= by_flops:
        return by_bytes, "bytes"
    return by_flops, "flops"
