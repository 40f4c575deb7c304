"""``.xplane.pb`` → the few numbers the per-layer metrics read.

Device planes are the planes whose name starts with ``/device:``; on each,
the line ``XLA Modules`` holds one event per program execution and the
line ``XLA Ops`` one event per HLO op.  Host spans are the events named
``bench/...`` (``jax.profiler.TraceAnnotation`` set by the harness) on any
line of a host plane.  All times are seconds on the trace's own clock.
"""

import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def read_planes(path: str):
    """({device plane name: {line name: [(name, start_s, end_s)]}},
    {span name: [(start_s, end_s)]})."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
            if lines.get(OP_LINE):
                devices[plane.name] = lines
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return devices, {k: sorted(v) for k, v in spans.items()}


def short_name(full: str) -> str:
    """``%fusion.17 fusion`` from the trace's full HLO text of an op; a
    Pallas kernel keeps its ``tpu_custom_call`` mark."""
    head, _, rest = full.partition(" = ")
    if not rest:
        return full[:120]
    m = re.search(r"[\s)]([a-z][a-z0-9\-]*)\(", " " + rest)
    kind = m.group(1) if m else ""
    if KERNEL_MARK in full:
        kind = "custom-call:" + KERNEL_MARK
    return f"{head} {kind}".strip()[:120]


def self_times(ops):
    """[(name, start, end, self seconds)]: an op that holds others (a
    ``while`` and its body) keeps only the time its children leave."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in stack)
    return out


def module_of(op_start, modules):
    """Name of the module execution whose interval holds ``op_start``
    (``modules`` sorted by start), or ``""``."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= op_start:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] <= op_start < modules[lo - 1][2]:
        return modules[lo - 1][0]
    return ""


def covering_span(t0, t1, spans):
    """The harness span (the window span excepted) that overlaps the gap
    [t0, t1] longest, the shorter span on a tie (a slice holds its regrid
    and its step); ``other`` when none covers half of it."""
    best, best_key = "other", None
    for name, ivs in spans.items():
        if name == WINDOW_SPAN:
            continue
        for s, e in ivs:
            over = min(e, t1) - max(s, t0)
            if over >= 0.5 * (t1 - t0):
                key = (over, -(e - s))
                if best_key is None or key > best_key:
                    best, best_key = name, key
    return best


def reduce_trace(path: str) -> dict:
    """The reduction every PR shares.

    ``window_s``: length of the ``bench/window`` span (or, without one,
    first device event to last).  ``busy_s``: union of the op intervals
    inside the window, averaged over the device planes.  ``module_s`` /
    ``module_n``: summed time and executions per module name.  ``op_s``:
    summed SELF time per (module, short op name); ``kernel_s``: per module,
    the self time of the ops that are Pallas kernels.  ``gaps``: idle intervals of the first
    device plane inside the window, longest first, each with the harness
    span that covers it.  ``spans``: the host spans."""
    devices, spans = read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with an '{OP_LINE}' line")
    if spans.get(WINDOW_SPAN):
        w0, w1 = spans[WINDOW_SPAN][0][0], spans[WINDOW_SPAN][-1][1]
    else:
        w0 = min(ev[1] for d in devices.values() for ev in d[OP_LINE])
        w1 = max(ev[2] for d in devices.values() for ev in d[OP_LINE])
    busy, module_s, module_n = [], defaultdict(float), defaultdict(int)
    op_s, kernel_s = defaultdict(float), defaultdict(float)
    gaps = []
    for i, (_, lines) in enumerate(sorted(devices.items())):
        ops = lines[OP_LINE]
        mods = sorted(lines.get(MODULE_LINE, []), key=lambda ev: ev[1])
        merged = _union(_clip([(s, e) for _, s, e in ops], w0, w1))
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in mods:
            if e > w0 and s < w1:
                module_s[name] += min(e, w1) - max(s, w0)
                module_n[name] += 1
        for name, s, e, own in self_times(ops):
            if s >= w0 and e <= w1:
                mod = module_of(s, mods)
                op_s[(mod, short_name(name))] += own
                if KERNEL_MARK in name:
                    kernel_s[mod] += own
        if i == 0:
            edge = [w0] + [t for iv in merged for t in iv] + [w1]
            for g0, g1 in zip(edge[0::2], edge[1::2]):
                if g1 > g0:
                    gaps.append((g1 - g0, g0, g1))
    gaps.sort(reverse=True)
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy),
        "n_devices": len(devices),
        "module_s": dict(module_s),
        "module_n": dict(module_n),
        "op_s": dict(op_s),
        "kernel_s": dict(kernel_s),
        "gaps": [(covering_span(g0, g1, spans), dur, g0 - w0)
                 for dur, g0, g1 in gaps],
        "spans": spans,
        "window": (w0, w1),
    }


def _no_hash(module: str) -> str:
    """``jit_run_steps`` from ``jit_run_steps(3778617071719131941)``."""
    return re.sub(r"\(\d+\)$", "", module)


def breakdown(red: dict, n_ops: int = 10, n_gaps: int = 5) -> dict:
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:n_ops]
    return {
        "device_ops": [[f"{_no_hash(mod)}/{op}" if mod else op, sec]
                       for (mod, op), sec in ops],
        "idle_gaps": [[name, dur] for name, dur, _ in red["gaps"][:n_gaps]],
    }


def dump(path: str, limit: int = 12) -> str:
    """Plain-text look at a trace: planes, lines, first events.  For the
    by-hand reading that has to come before trusting the reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:limit]:
                stats = {k: v for k, v in list(ev.stats)[:6]}
                out.append(f"    {ev.name!r} start={ev.start_ns:.0f}ns "
                           f"dur={ev.duration_ns:.0f}ns {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12))
