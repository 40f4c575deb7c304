"""The honesty number of the op -> phase table: share of the self time of
the step and flags programs' ops whose phase is ``unattributed`` (no scope
of its own, no user and no producer that has one, or an op the table does
not know).  Prints the ten longest such ops with their HLO kind."""

from benchmark.layer_metrics import _device_phases


def read(reduced, spans, counts, ctx):
    ops = _device_phases.op_phases(reduced)
    if not ops:
        return None
    total = sum(o[5] for o in ops)
    lost = sorted((o for o in ops if o[4] == _device_phases.UNATTRIBUTED),
                  key=lambda o: -o[5])
    if not total:
        return None
    steps = counts.get("steps_done") or 1
    _device_phases.say(
        f"[device phases] unattributed: {len(lost)} of {len(ops)} ops, "
        f"{1e3 * sum(o[5] for o in lost) / steps:.4f} ms a coarse step; "
        f"the longest:")
    for mod, op, hlo_kind, path, _, sec in lost[:10]:
        _device_phases.say(f"    {1e3 * sec / steps:9.4f} ms  {mod}/{op} "
                           f"{hlo_kind}  [{path}]")
    return 100.0 * sum(o[5] for o in lost) / total
