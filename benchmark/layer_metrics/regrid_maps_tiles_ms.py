"""Tile tables of the partial levels, per regrid: the ``regrid: maps tiles``
span (``maps.build_block_maps`` inside ``AmrSim._rebuild_maps``, once a
blocked level).  A program without that span (the parent of the PR that
added it) reads as nothing, never as 0."""

from benchmark.layer_metrics import _program_spans

SPAN = "regrid: maps tiles"


def read(reduced, spans, counts, ctx):
    if not any(r["name"] == SPAN for r in _program_spans.traced_records()):
        return None
    return _program_spans.per_root_ms(counts, "regrid", SPAN)
