"""Host time of one regrid: total duration of the ``bench/regrid`` spans
inside the traced window over the regrids counted there."""


def read(reduced, spans, counts, ctx):
    ivs = spans.get("bench/regrid", [])
    w0, w1 = reduced["window"]
    ivs = [(s, e) for s, e in ivs if s >= w0 and e <= w1]
    if not ivs or not counts.get("regrids"):
        return None
    return 1e3 * sum(e - s for s, e in ivs) / len(ivs)
