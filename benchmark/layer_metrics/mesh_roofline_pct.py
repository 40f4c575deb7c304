"""The whole step's share of the MESH's peak: least time of ALL cell
updates of the traced window at ``n_devices`` x one chip's HBM peak
(``work.py`` from ``peaks.json``), over the mean BUSY time of the device
planes there.  The mesh twin of ``hydro_roofline_pct`` (which divides by
one chip's peak and would read ``n_devices`` times too high): it reads
the same work whatever implements it, so it bounds a later claim in a
cell that runs on several chips.  A trace of one device plane has no
mesh: nothing."""

from benchmark.harness import work
from benchmark.layer_metrics import _mesh_ops


def read(reduced, spans, counts, ctx):
    n = _mesh_ops.mesh_size(reduced)
    if n < 2 or reduced["busy_s"] <= 0 or not counts.get("cell_updates"):
        return None
    least, _ = work.least_time_s(counts["cell_updates"], ctx["peak"])
    return 100.0 * least / n / reduced["busy_s"]
