"""Host time a coarse step spent blocked on the device: the program's
spans whose records say ``wait`` (``regrid: flag fetch``, ``courant:
fetch``, ``evolve: wait``; the set is the program's,
``utils/timers.WAIT_LABELS``) over the coarse steps traced.  Nothing
unless the traced ``regrid`` roots are as many as counted."""

from benchmark.layer_metrics import _program_spans, _span_clock


def read(reduced, spans, counts, ctx):
    recs = _program_spans.traced_records()
    n = sum(r["name"] == "regrid" and r["depth"] == 0 for r in recs)
    steps = counts.get("steps_done")
    if not n or n != counts.get("regrids") or not steps \
            or any("wait" not in r for r in recs):
        return None
    acc = {}
    for r in recs:
        if r["wait"]:
            acc[r["name"]] = acc.get(r["name"], 0.0) \
                + (r["t1_ns"] - r["t0_ns"]) * 1e-9
    _span_clock.say("[host wait] ms a coarse step by span: " + ", ".join(
        f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(acc.items())))
    return 1e3 * sum(acc.values()) / steps
