"""The comparison that decides ``correct``.

The configuration names its plain reference (``reference/<name>.py``); the
reference advances the input of the window's held slice, and
``measure`` turns program-vs-reference into a few gaps.  Each number has a
limit of its own in the configuration file.  ``control`` puts the reference
computed in a lower precision in the program's place: it has to come out
not correct (run by ``benchmark/tests`` and by hand on the chip, never by a
benchmark run)."""

import importlib
import math


def load_reference(config):
    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def compare(config, snap, control=None):
    """({name: (value, limit)}, correct)."""
    ref_mod = load_reference(config)
    ref = ref_mod.advance(snap, config, "float32")
    got = ref_mod.program_output(snap) if control is None \
        else ref_mod.advance(snap, config, control)
    numbers = ref_mod.measure(got, ref, snap, config)
    limits = config["limits"]
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"the reference gave no {sorted(missing)}")
    out = {k: (float(numbers[k]), float(limits[k])) for k in limits}
    ok = all(math.isfinite(v) and v <= lim for v, lim in out.values())
    return out, ok
