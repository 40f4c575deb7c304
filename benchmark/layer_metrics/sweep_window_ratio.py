"""Cells the fused sweep kernel loads and computes per cell it writes: what
``sweep_kernel_roofline_pct`` is a share OF.  Every grid step of
``hydro/pallas_muscl`` reads a ``(bx+4) x 16 x nz`` window to write
``bx x 8 x nz`` cells; the program records the pick per call signature at
trace time (``pallas_muscl.block_stats()``), read here in process as
``_program_spans`` reads the span records.  Over one sweep of every
signature traced.  A program without the record (the parent of the PR that
added it) or in which no kernel was traced reads as nothing."""


def block_records():
    try:
        from ramses_tpu.hydro import pallas_muscl
        return pallas_muscl.block_stats()
    except (ImportError, AttributeError):
        return []


def read(reduced, spans, counts, ctx):
    loaded = written = 0
    for b in block_records():
        cells = b["shape"][0] * b["shape"][1] * b["shape"][2]
        loaded += cells // b["written_cells"] * b["window_cells"]
        written += cells
    return loaded / written if written else None
