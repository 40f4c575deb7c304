"""Host time of one slice that the device cannot overlap: the ``evolve`` span
(one pass of ``driver.Simulation.evolve``'s loop) less its ``evolve: wait``
child (the blocking fetches), per slice.  What is left is the dispatch
(``evolve: dispatch``) and the bookkeeping around it."""

from benchmark.layer_metrics import _program_spans


def read(reduced, spans, counts, ctx):
    return _program_spans.per_root_ms(counts, "evolve", "evolve",
                                      less=("evolve: wait",))
