"""The tiled CT kernel's share of its roofline: least time of the cell
updates it swept in the traced window (``harness/mhd_work``: 88 B a cell
update at the chip's HBM peak) over the self time of the device ops named
``mhd/pallas_ct.KERNEL_NAME``, picked from ``op_s`` by name as
``slab_kernel_roofline_pct`` picks its kernel.  The name is repeated here
because a program without the kernel (the parent of the PR that added it)
must read as nothing, not raise.  No such op: nothing, never 0."""

from benchmark.harness import mhd_work
from benchmark.layer_metrics import _mesh_ops

CT_KERNEL = "%ct_step_tiled"


def kernel_seconds(reduced):
    return _mesh_ops.seconds(
        reduced, lambda s: _mesh_ops.is_kernel(s, CT_KERNEL))


def read(reduced, spans, counts, ctx):
    sec = kernel_seconds(reduced)
    n = counts.get("kernel_cell_updates", 0)
    if sec <= 0 or not n:
        return None
    least, _ = mhd_work.least_time_s(n, ctx["peak"])
    return 100.0 * least / sec
