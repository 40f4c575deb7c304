"""Moving the level state onto the new tree (``regrid: migrate``: survivor
copy and prolongation of new octs, dispatched per level), per regrid."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid: migrate")
