"""Uploads of the new tree's index tables, per regrid: the ``regrid: maps
upload`` spans inside ``regrid: maps`` (``AmrSim._rebuild_maps``: one
placement a table on one chip, four on the mesh).  A program without the
span reads as nothing, never as 0."""

from benchmark.layer_metrics import _program_spans

SPAN = "regrid: maps upload"


def read(reduced, spans, counts, ctx):
    if not any(r["name"] == SPAN for r in _program_spans.traced_records()):
        return None
    return _program_spans.per_root_ms(counts, "regrid", SPAN)
