"""Building the new tree from the flags on the host (``regrid: tree build``:
``flagmod.compute_new_tree``), per regrid."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "regrid", "regrid: tree build")
