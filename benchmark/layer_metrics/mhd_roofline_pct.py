"""The whole MHD step's share of the chip's peak: least time for ALL cell
updates of the traced window (``harness/mhd_work``) over the device's BUSY
time there (union of op intervals).  It reads the same work whatever
implements it - what ``hydro_roofline_pct`` is to the hydro cells - so it
still bounds a claim after a later PR takes the kernel out or in."""

from benchmark.harness import mhd_work


def read(reduced, spans, counts, ctx):
    if reduced["busy_s"] <= 0 or not counts.get("cell_updates"):
        return None
    least, _ = mhd_work.least_time_s(counts["cell_updates"], ctx["peak"])
    return 100.0 * least / reduced["busy_s"]
