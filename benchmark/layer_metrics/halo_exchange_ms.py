"""Device time the mesh spends exchanging, a coarse step and a chip: self
time of the halo DMA kernel plus XLA's own collectives (all-reduce,
all-gather, collective-permute, all-to-all, reduce-scatter; async pairs
by their -start and -done ops) in EVERY module of the traced window - the
step, the flags, the migration, the Courant pass - summed over the device
planes, over the planes and the coarse steps traced.  Self time of an
exchange is also the time a chip waits for its neighbour in it.  Neither
kind of op in the trace: nothing."""

from benchmark.layer_metrics import _mesh_ops


def read(reduced, spans, counts, ctx):
    sec = _mesh_ops.seconds(
        reduced, lambda s: _mesh_ops.is_kernel(s, _mesh_ops.DMA_KERNEL)
        or _mesh_ops.is_collective(s))
    steps = counts.get("steps_done", 0)
    if sec <= 0 or not steps:
        return None
    return 1e3 * sec / _mesh_ops.mesh_size(reduced) / steps
