"""Flagging, per regrid: the ``regrid: flag`` span less its ``regrid: tree
build`` child — dispatch of the flags program, the blocking fetch of the
packed flags (``regrid: flag fetch``: it also waits out what the device
still owes, the previous coarse step included) and their unpacking."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid: flag",
                                      less=("regrid: tree build",))
