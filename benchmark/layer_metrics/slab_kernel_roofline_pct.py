"""The per-shard fused kernel's share of its roofline: least time of the
cell updates it swept in the traced window (the complete level, counted
by the entry only while the per-shard kernel runs) at ONE chip's HBM peak,
over the kernel's self time SUMMED over the device planes - each chip
sweeps its share, so the sum is what one chip would need for all of it.
The kernel's ops are picked from ``op_s`` by name, so the halo DMA
kernel (also a ``tpu_custom_call``) is not among them.  No such op:
nothing."""

from benchmark.harness import work
from benchmark.layer_metrics import _mesh_ops


def read(reduced, spans, counts, ctx):
    sec = _mesh_ops.seconds(
        reduced, lambda s: _mesh_ops.is_kernel(s, _mesh_ops.SHARD_KERNEL))
    n = counts.get("kernel_cell_updates", 0)
    if sec <= 0 or not n:
        return None
    least, _ = work.least_time_s(n, ctx["peak"])
    return 100.0 * least / sec
