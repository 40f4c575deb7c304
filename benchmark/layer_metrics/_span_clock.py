"""The program's spans on the device's clock.

``utils/timers.span`` records are on ``time.perf_counter_ns``; the device
trace (``harness/trace_reduce``) is on the profiler session's clock.  The
two are joined here, from what ``reduced`` already holds: the harness wraps
the call that opens a ROOT span of the program in a ``bench/*`` annotation
(``bench/regrid`` round ``AmrSim.regrid`` -> the ``regrid`` root;
``bench/slice`` round a slice -> the one ``evolve`` root of a uniform or
MHD slice), so the k-th ``bench/*`` interval opens a few microseconds
before the k-th root record opens and closes a few after it closes.  Every
pair bounds the offset between the clocks from both sides,

    bench_start_k - t0_k  <=  offset  <=  bench_end_k - t1_k,

and the tightest pairs give ``offset = max_k(bench_start_k - t0_k)``.  The
self-check: the roots are as many as the window counted AND as the trace
has ``bench/*`` intervals, and the interval of offsets that every pair
allows is neither empty (two pairs that contradict each other: clocks that
do not join) nor wider than 0.2 ms.  Anything else reads as nothing.

With the offset every idle and busy interval of the first device plane is
cut against the INNERMOST program span open at the time.
"""

import bisect
import sys

from benchmark.layer_metrics import _program_spans

# root span of the program -> (the harness span round it, the window's
# count of it)
ROOTS = {"regrid": ("bench/regrid", "regrids"),
         "evolve": ("bench/slice", "slices")}
AGREE_S = 0.2e-3          # the pairs agree within this
NEGATIVE_S = 0.02e-3      # ... and contradict each other by no more
NO_SPAN = "(no program span)"

_MEMO = {}


def say(msg):
    print(msg, file=sys.stderr, flush=True)


def join(reduced, counts, recs=None):
    """``{offset_s, width_s, spread_s, root, n, recs}`` or None.
    ``recs``: the traced records (default: the program's ring); records
    without ``wait`` (a program older than the flag) read as nothing."""
    if recs is None:
        recs = _program_spans.traced_records()
    if not recs or any("wait" not in r for r in recs):
        return None
    for root, (bench, counted) in ROOTS.items():
        roots = [r for r in recs if r["name"] == root and r["depth"] == 0]
        if roots:
            break
    else:
        return None
    ivs = sorted(reduced["spans"].get(bench, ()))
    n = len(roots)
    if n != counts.get(counted) or n != len(ivs):
        say(f"[span clock] {n} traced {root!r} roots, the window counted "
            f"{counts.get(counted)}, the trace has {len(ivs)} {bench}: "
            f"no join")
        return None
    lows = [bs - r["t0_ns"] * 1e-9 for (bs, _), r in zip(ivs, roots)]
    highs = [be - r["t1_ns"] * 1e-9 for (_, be), r in zip(ivs, roots)]
    low, high = max(lows), min(highs)
    out = {"offset_s": low, "width_s": high - low,
           "spread_s": low - min(lows), "root": root, "n": n, "recs": recs}
    say(f"[span clock] {n} pairs {bench} / {root!r}: offset "
        f"{low:.9f} s; the pairs allow an interval {1e6 * out['width_s']:.1f}"
        f" us wide; spread of bench_start - t0 over the pairs "
        f"{1e6 * out['spread_s']:.1f} us (median "
        f"{1e6 * (low - sorted(lows)[n // 2]):.1f})")
    if not -NEGATIVE_S <= out["width_s"] <= AGREE_S:
        say("[span clock] the pairs do not agree within 0.2 ms: no join")
        return None
    return out


def segments(recs, offset_s):
    """The records flattened to ``[(start_s, end_s, label, wait)]`` on the
    trace's clock: sorted, disjoint, each under its INNERMOST span."""
    evs = sorted(((r["t0_ns"] * 1e-9 + offset_s, r["t1_ns"] * 1e-9 + offset_s,
                   r["depth"], r["name"], bool(r["wait"])) for r in recs),
                 key=lambda e: (e[0], e[2]))
    out, stack = [], []           # stack of [end, label, wait]

    def emit(a, b):
        if stack and b > a:
            out.append((a, b, stack[-1][1], stack[-1][2]))

    t = None
    for s, e, _, label, wait in evs:
        while stack and stack[-1][0] <= s:
            emit(t, stack[-1][0])
            t = stack.pop()[0]
        if stack:
            emit(t, s)
        t = s
        stack.append([e, label, wait])
    while stack:
        emit(t, stack[-1][0])
        t = stack.pop()[0]
    return out


def cut(intervals, segs):
    """``{(label, wait): seconds}`` of ``intervals`` under each innermost
    span; what lies under none goes to ``(NO_SPAN, False)``."""
    starts = [s[0] for s in segs]
    acc = {}
    for a, b in intervals:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            s, e, label, wait = segs[i]
            over = min(e, b) - max(s, a)
            if over > 0:
                acc[(label, wait)] = acc.get((label, wait), 0.0) + over
                covered += over
            i += 1
        rest = (b - a) - covered
        if rest > 0:
            acc[(NO_SPAN, False)] = acc.get((NO_SPAN, False), 0.0) + rest
    return acc


def idle_and_busy(reduced):
    """(idle, busy) intervals of the first device plane inside the traced
    window, on the trace's clock: ALL the gaps, and what lies between."""
    w0, w1 = reduced["window"]
    idle = sorted((w0 + rel, w0 + rel + dur)
                  for _, dur, rel in reduced["gaps"])
    edge = [w0] + [t for iv in idle for t in iv] + [w1]
    busy = [(a, b) for a, b in zip(edge[0::2], edge[1::2]) if b > a]
    return idle, busy


def attribution(reduced, counts):
    """``{idle, busy: {(label, wait): seconds}, join}`` or None; computed
    once a ``reduced``."""
    key = id(reduced)
    if key not in _MEMO:
        _MEMO.clear()
        j = join(reduced, counts)
        if j is None:
            _MEMO[key] = None
        else:
            segs = segments(j["recs"], j["offset_s"])
            idle, busy = idle_and_busy(reduced)
            _MEMO[key] = {"idle": cut(idle, segs), "busy": cut(busy, segs),
                          "join": j}
    return _MEMO[key]


def table(title, acc, per, unit):
    """One stderr table: ms a ``unit`` by innermost span, longest first."""
    total = sum(acc.values())
    say(f"[{title}] ms a {unit} by innermost program span "
        f"(total {1e3 * total / per:.3f}):")
    for (label, wait), sec in sorted(acc.items(), key=lambda kv: -kv[1]):
        say(f"    {1e3 * sec / per:9.3f}  {100 * sec / total:5.1f} %  "
            f"{label}{'  [wait]' if wait else ''}")
