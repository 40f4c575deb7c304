"""The Courant pass on the host, per regrid: the program's ``courant``
spans (``AmrSim.coarse_dt``: dispatch of the Courant program and, in its
``courant: fetch`` child, the wait for it and for what the device still
owed) over the regrids traced — after a regrid the cached step is stale,
so every slice of a regrid-every-step window pays one."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "courant")
