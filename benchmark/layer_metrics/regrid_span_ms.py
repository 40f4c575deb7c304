"""Host time of one regrid, from inside: the program's own ``regrid`` span
(``AmrSim.regrid``, early returns included) per regrid traced.  The inside
twin of ``regrid_host_ms``, which reads the ``bench/regrid`` span the entry
sets around the same call from outside."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid")
