"""The halo DMA kernel's share of the ICI's peak: the bytes one chip
sends through it over the traced window (``harness/halo_work.py`` from
the configuration's ``slab`` group: sweeps a coarse step, flags a regrid)
at the chip's published interconnect peak (``peaks_ici.json``, by
``device_kind``), over the kernel's self time a chip (summed over the
device planes, over the planes).  The kernel's time holds its neighbour
barrier and its waits, so the share is small while exchanges are few and
short; over 100 it is a wrong byte count.  No such kernel in the trace,
or no ``slab`` group: nothing."""

import json
import os

from benchmark.harness import halo_work
from benchmark.layer_metrics import _mesh_ops


def ici_peak():
    """Bytes/s of the chip this process runs on; a chip that is not in
    the table is an error, never a default."""
    import jax
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks_ici.json")) as f:
        return float(json.load(f)[jax.devices()[0].device_kind]
                     ["ici_bytes_per_s"])


def read(reduced, spans, counts, ctx):
    sec = _mesh_ops.seconds(
        reduced, lambda s: _mesh_ops.is_kernel(s, _mesh_ops.DMA_KERNEL))
    slab = ctx["config"].get("slab")
    if sec <= 0 or not slab or not counts.get("steps_done"):
        return None
    sent = halo_work.bytes_sent(slab, counts["steps_done"],
                                counts.get("regrids", 0))
    least = sent / ici_peak()
    return 100.0 * least / (sec / _mesh_ops.mesh_size(reduced))
