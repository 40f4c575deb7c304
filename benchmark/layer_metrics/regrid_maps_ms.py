"""Index tables of the new tree, per regrid: the ``regrid: maps`` span
(``AmrSim._rebuild_maps``: the tables in numpy and, inside it as ``regrid:
maps upload``, their upload)."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid: maps")
