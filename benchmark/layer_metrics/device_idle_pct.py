"""Share of the traced window in which no op ran on the device."""


def read(reduced, spans, counts, ctx):
    if reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
