"""Label-based wallclock timers (``timer_m``, ``amr/update_time.f90:38-56``)
whose sections are spans on the profiler's clock.

Same zero-overhead design as the reference: exactly one label is active;
switching to a new label accumulates the elapsed time on the previous
one.  ``output_timer`` prints the per-label breakdown and the fraction of
total — the reference's per-dump report (``:77-180``).

A ``section`` is also a :func:`span`: a ``jax.profiler.TraceAnnotation``
of the same label, so any profiler session (``profile_trace``, the
on-demand captures of ``obs/profile.py``, the benchmark's traced runs)
shows the host phase beside the device's ops on one clock, and — while
such a session is on, or the section belongs to a real :class:`Timers` —
one closed record in a process-wide ring (:func:`span_records`).  With
no session and no telemetry a section reads no clock and stores nothing.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ramses_tpu import platform

# closed spans, oldest first (children close, and so land, before their
# parents); bounded: a long instrumented run keeps the newest (~1000
# AMR coarse steps, a few MB)
_RING: collections.deque = collections.deque(maxlen=1 << 14)
# labels of the recorded spans open on this thread, outermost first
_OPEN = threading.local()
# the spans in which the host blocks on the device (a fetch that waits
# out what was dispatched): their records say ``wait``.  Decided here,
# by label, so no call site says it
WAIT_LABELS = frozenset({"regrid: flag fetch", "evolve: wait",
                         "courant: fetch"})


def span(label: str, timers: Optional["Timers"] = None):
    """One host phase, as a context manager: a ``TraceAnnotation`` and,
    only while a profiler session is on (asked here, at entry) or
    ``timers`` is a live :class:`Timers`, a closed record in the ring.
    Off is the bare annotation: no clock read, nothing stored."""
    traced = TraceAnnotation.is_enabled()
    if not traced and timers is None:
        return TraceAnnotation(label)
    return _recorded(label, timers, traced)


@contextlib.contextmanager
def _recorded(label: str, timers: Optional["Timers"], traced: bool):
    """The recording half of :func:`span`: ``{name, parent, depth, t0_ns,
    t1_ns, compiles, compile_s, traced, wait}`` on
    ``time.perf_counter_ns`` — ``parent`` the enclosing recorded span's
    label, ``wait`` whether the host blocks on the device in it
    (:data:`WAIT_LABELS`), ``compiles`` /
    ``compile_s`` what the compile timer (``platform._CACHE_STATS``:
    compile or cache load) counted across it, ``traced`` whether it
    opened under a session.  ``timers`` also gets its label switched
    for the span's life (self time per label)."""
    stack = _OPEN.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    depth = len(stack)
    stats = platform._CACHE_STATS
    ncomp, comp_s = stats["compiles"], stats["compile_s"]
    with TraceAnnotation(label):
        stack.append(label)
        if timers is not None:
            prev = timers._label
            timers.timer(label)
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            if timers is not None:
                timers.timer(prev if prev is not None else "stop")
            t1 = time.perf_counter_ns()
            stack.pop()
            _RING.append({
                "name": label, "parent": parent, "depth": depth,
                "t0_ns": t0, "t1_ns": t1,
                "compiles": stats["compiles"] - ncomp,
                "compile_s": stats["compile_s"] - comp_s,
                "traced": traced, "wait": label in WAIT_LABELS})


def span_records() -> List[dict]:
    """The ring's closed spans, oldest first; ``traced`` marks those
    opened under a profiler session."""
    return list(_RING)


def clear_span_records():
    _RING.clear()


class Timers:
    def __init__(self, sync=None):
        self.acc: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self._label: Optional[str] = None
        self._t0 = 0.0
        # Optional device-drain callable invoked at every label switch.
        # Async dispatch misattributes device time to whichever section
        # happens to block next; with ``sync`` set, each section pays for
        # exactly the work it enqueued (use for instrumented runs only —
        # draining costs a device round-trip per switch).
        self.sync = sync

    def timer(self, label: str):
        """Switch the active label (accumulates the previous one)."""
        if self.sync is not None and self._label is not None:
            self.sync()
        now = time.perf_counter()
        if self._label is not None:
            self.acc[self._label] = self.acc.get(self._label, 0.0) \
                + (now - self._t0)
            self.count[self._label] = self.count.get(self._label, 0) + 1
        self._label = label if label != "stop" else None
        self._t0 = now

    def stop(self):
        self.timer("stop")

    def snapshot(self) -> Dict[str, float]:
        """Accumulated seconds per label, including the still-running
        portion of the active label, without switching labels.  The
        telemetry recorder diffs consecutive snapshots to attribute
        wallclock to phases per record."""
        out = dict(self.acc)
        if self._label is not None:
            out[self._label] = out.get(self._label, 0.0) \
                + (time.perf_counter() - self._t0)
        return out

    def section(self, label: str):
        return span(label, self)

    def output_timer(self, file=None) -> str:
        """Per-label breakdown (``output_timer``, min/avg/max collapse to
        one host here; the sharded runs are single-controller)."""
        self.stop()
        total = sum(self.acc.values()) or 1.0
        lines = ["   --------------------------------------------------",
                 "   TIMER      %        time     calls   label",
                 "   --------------------------------------------------"]
        for lbl, t in sorted(self.acc.items(), key=lambda kv: -kv[1]):
            lines.append(f"   {100 * t / total:6.1f}   {t:10.3f}  "
                         f"{self.count.get(lbl, 0):8d}   {lbl}")
        lines.append(f"   total: {total:.3f} s")
        out = "\n".join(lines)
        if file is not None:
            print(out, file=file)
        return out


class NullTimers(Timers):
    """Zero-cost stand-in for un-instrumented runs.

    The reference's timers are compiled in unconditionally; here a run
    without telemetry must pay NOTHING — no ``perf_counter`` calls, no
    label switches (the telemetry subsystem's zero-overhead-off
    contract): a section is a bare :func:`span`, which records only
    while a profiler session is on.  Drivers swap in a real
    :class:`Timers` only when telemetry (or an explicit instrumentation
    pass, e.g. bench.py's ``Timers(sync=sim.drain)``) asks for it.
    """

    def timer(self, label: str):
        pass

    def section(self, label: str):
        return span(label)

    def snapshot(self) -> Dict[str, float]:
        return {}


@contextlib.contextmanager
def profile_trace(logdir: str):
    """jax.profiler wrapper: structured device traces (the observability
    the reference lacks, SURVEY.md §5.1)."""
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
