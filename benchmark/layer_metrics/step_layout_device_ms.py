"""Device time a coarse step that moves bytes and does no physics: self
time of the ops of the step and flags programs whose phase ``kind`` is
``layout`` (gathers, pads, transposes, read-backs and the copies the
compiler inserts for them), over the coarse steps traced.  Prints the
device ms a coarse step by scope path (level x phase) with its kind."""

from benchmark.layer_metrics import _device_phases


def read(reduced, spans, counts, ctx):
    ops = _device_phases.op_phases(reduced)
    steps = counts.get("steps_done")
    if not ops or not steps:
        return None
    acc = _device_phases.by_path(ops)
    total = sum(acc.values())
    _device_phases.say(
        f"[device phases] ms a coarse step by scope path, "
        f"{len(ops)} ops of {sorted({o[0] for o in ops})} "
        f"(total {1e3 * total / steps:.3f}; on a mesh summed over chips):")
    for (path, kind), sec in sorted(acc.items(), key=lambda kv: -kv[1]):
        _device_phases.say(f"    {1e3 * sec / steps:9.3f}  "
                           f"{100 * sec / total:5.1f} %  {kind:12s} {path}")
    kinds = {}
    for (_, kind), sec in acc.items():
        kinds[kind] = kinds.get(kind, 0.0) + sec
    _device_phases.say("[device phases] by kind: " + ", ".join(
        f"{k} {1e3 * s / steps:.3f} ms ({100 * s / total:.1f} %)"
        for k, s in sorted(kinds.items(), key=lambda kv: -kv[1])))
    return 1e3 * kinds.get("layout", 0.0) / steps
