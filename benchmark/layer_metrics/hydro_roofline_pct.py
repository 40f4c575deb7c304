"""The whole step's share of the chip's peak: least time for ALL cell
updates of the traced window over the device's BUSY time there (union of
op intervals).  It reads the same work whatever implements it, so it still
bounds a claim after a later PR takes a kernel out or in.  (This system
runs no model; this is the number a model's ``step_mfu`` would be.)"""

from benchmark.harness import work


def read(reduced, spans, counts, ctx):
    if reduced["busy_s"] <= 0 or not counts.get("cell_updates"):
        return None
    least, _ = work.least_time_s(counts["cell_updates"], ctx["peak"])
    return 100.0 * least / reduced["busy_s"]
