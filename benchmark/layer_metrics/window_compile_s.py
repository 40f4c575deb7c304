"""Seconds the compile timer (``platform.compile_cache_stats``: compile or
cache load) ran inside the window.  0 in a sound run."""


def read(reduced, spans, counts, ctx):
    return ctx["window_compile_s"]
