"""Share of the device's idle time, in the traced window, that lies under
NO program span: what is left of ``breakdown.idle_gaps``' ``other`` once
every idle interval (all of them, not the five longest) is cut against
the innermost program span open at the time.  Prints the idle ms a coarse
step (AMR) or a slice (uniform, MHD) by span.  Needs the clocks joined
(``_span_clock``)."""

from benchmark.layer_metrics import _span_clock


def read(reduced, spans, counts, ctx):
    att = _span_clock.attribution(reduced, counts)
    if att is None:
        return None
    idle = att["idle"]
    total = sum(idle.values())
    if not total:
        return None
    amr = att["join"]["root"] == "regrid"
    per = (counts.get("steps_done") if amr else counts.get("slices")) or 1
    _span_clock.table("device idle", idle, per,
                      "coarse step" if amr else "slice")
    return 100.0 * idle.get((_span_clock.NO_SPAN, False), 0.0) / total
