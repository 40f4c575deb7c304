"""The restriction sweep that ends a regrid (``regrid: restrict``:
``AmrSim._restrict_all``), per regrid."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid: restrict")
