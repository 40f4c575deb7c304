"""Device time by phase: the trace's op self times (``reduced["op_s"]``)
put to the scope — level and phase — that owns each op.

The chip's trace carries no scope (an op event is its HLO text and its
times), so the op -> phase map comes from the program, in process:
``ramses_tpu/telemetry/hlo.device_phases()`` compiles (a cache load) what
``AmrSim.step_coarse`` and ``_criteria_flags`` dispatched under the
profiler session and reads, from the compiled text, which
``jax.named_scope`` owns each instruction — its own ``op_name``, else what
reads it, else what it reads.  A program without that function (the parent
of the PR that added it), or that noted nothing, reads as nothing.
"""

import time

from benchmark.harness.trace_reduce import _no_hash as module_of
from benchmark.layer_metrics._span_clock import say

UNATTRIBUTED = "unattributed"
# an op of the trace whose name the program's table does not hold: the
# executable that ran came from a compile-cache entry written by a
# program with other metadata (the cache's key leaves metadata out, and
# XLA numbers a few reshape chains by it), the table from this one's
NOT_IN_TABLE = "unattributed (not in the table)"
_MEMO = {}


def tables():
    """``{module name: {instruction: (scope path, kind)}}``, asked once."""
    if "tables" not in _MEMO:
        try:
            from ramses_tpu.telemetry import hlo
            t0 = time.perf_counter()
            _MEMO["tables"] = hlo.device_phases()
            say(f"[device phases] device_phases() took "
                f"{time.perf_counter() - t0:.2f} s for "
                f"{sorted(_MEMO['tables'])}")
        except (ImportError, AttributeError):
            _MEMO["tables"] = {}
    return _MEMO["tables"]


def op_phases(reduced):
    """``[(module, op, hlo kind, scope path, kind, self seconds)]`` of the
    ops of the modules the program has a table for; an op the table does
    not know is ``unattributed`` too, under its own path.  None without
    tables or such ops."""
    tabs = tables()
    out = []
    for (mod, short), sec in reduced["op_s"].items():
        tab = tabs.get(module_of(mod))
        if tab is None:
            continue
        op, _, hlo_kind = short.partition(" ")
        path, kind = tab.get(op.lstrip("%"), (NOT_IN_TABLE, UNATTRIBUTED))
        out.append((module_of(mod), op, hlo_kind, path, kind, sec))
    return out or None


def by_path(ops):
    acc = {}
    for _, _, _, path, kind, sec in ops:
        acc[(path, kind)] = acc.get((path, kind), 0.0) + sec
    return acc
