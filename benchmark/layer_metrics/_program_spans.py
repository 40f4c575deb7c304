"""Shared by the readers of the PROGRAM's own spans.

``ramses_tpu/utils/timers.span`` puts every phase section of the program on
the profiler's clock (a ``TraceAnnotation``) and, while a profiler session
is on, keeps a closed record of it in a ring in the process:
``{name, parent, depth, t0_ns, t1_ns, compiles, compile_s, traced}``.  The
reducer keeps only ``bench/*`` host events and ``run.py`` deletes the trace
before the readers run, so these readers take the records from the program,
in process, as ``window_compile_s`` takes the compile timer.

A phase is divided by the number of its ROOT spans (``regrid``: one a
regrid; ``evolve``: one a pass of ``driver.Simulation.evolve``, which is one
a slice), and only when that number is what the window counted in its traced
part: anything else (a program without the ring, as the parent of the PR
that added it; a ring that overflowed; a session that is not the window's)
reads as nothing, never as a wrong quotient.
"""

# root span -> the window's count of it (``harness/window.Counts``)
ROOTS = {"regrid": "regrids", "evolve": "slices"}


def traced_records():
    """The records opened under a profiler session, oldest first; none
    where the program has no such ring."""
    try:
        from ramses_tpu.utils.timers import span_records
    except ImportError:
        return []
    return [r for r in span_records() if r["traced"]]


def per_root_ms(counts, root, name, less=()):
    """Milliseconds of the spans called ``name`` per ``root`` span, less
    those of its children called ``less``; None unless the traced ``root``
    records are as many as the window counted (and more than none)."""
    recs = traced_records()
    n = sum(r["name"] == root for r in recs)
    if not n or n != counts.get(ROOTS[root]):
        return None

    def ns(keep):
        return sum(r["t1_ns"] - r["t0_ns"] for r in recs if keep(r))

    own = ns(lambda r: r["name"] == name)
    kids = ns(lambda r: r["name"] in less and r["parent"] == name)
    return (own - kids) / n / 1e6
