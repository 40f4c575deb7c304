"""Cells the tiled CT kernel loads and computes per cell it writes: what
``ct_kernel_roofline_pct`` is a share OF.  Every grid step of
``mhd/pallas_ct.ct_step_tiled`` reads a ``(bx+6) x 16 x nz`` window (3 halo
rows a side: CT reaches 2 cells and 3 faces) to write ``bx x 8 x nz``
cells; the program records the pick per shape at trace time
(``pallas_ct.block_stats()``), read here in process as
``sweep_window_ratio`` reads the hydro kernel's.  Over one sweep of every
shape traced.  A program without the record (the parent of the PR that
added it) or in which the kernel was not traced reads as nothing."""


def block_records():
    try:
        from ramses_tpu.mhd import pallas_ct
        return pallas_ct.block_stats()
    except (ImportError, AttributeError):
        return []


def read(reduced, spans, counts, ctx):
    loaded = written = 0
    for b in block_records():
        cells = b["shape"][0] * b["shape"][1] * b["shape"][2]
        loaded += cells // b["written_cells"] * b["window_cells"]
        written += cells
    return loaded / written if written else None
