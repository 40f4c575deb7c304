"""Share of the device's busy time during which the host works: busy
intervals of the first device plane that lie under a program span which is
not a ``wait`` (the host is building tables, dispatching, decoding — not
blocked on the device, and not outside the program's spans).  Near 0 when
host and device take turns; the number an overlap of the regrid with the
step moves.  Needs the clocks joined (``_span_clock``)."""

from benchmark.layer_metrics import _span_clock


def read(reduced, spans, counts, ctx):
    att = _span_clock.attribution(reduced, counts)
    if att is None:
        return None
    busy = att["busy"]
    total = sum(busy.values())
    if not total:
        return None
    per = counts.get("steps_done") or counts.get("slices") or 1
    _span_clock.table("device busy", busy, per, "coarse step")
    working = sum(sec for (label, wait), sec in busy.items()
                  if not wait and label != _span_clock.NO_SPAN)
    return 100.0 * working / total
