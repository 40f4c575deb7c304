"""Device time of the flags program per regrid: summed time of the
XLA-module events of ``hierarchy._fused_flags`` (the name taken from the
program) over the regrids of the traced window.  The program no
configuration's ``step_programs`` lists: ``step_device_ms`` does not see
it.  On a mesh: chip-ms summed over the planes, as ``step_device_ms``."""


def read(reduced, spans, counts, ctx):
    try:
        from ramses_tpu.amr import hierarchy
        name = hierarchy._fused_flags.__name__
    except (ImportError, AttributeError):
        return None
    mods = [m for m in reduced["module_s"] if name in m]
    if not mods or not counts.get("regrids"):
        return None
    return 1e3 * sum(reduced["module_s"][m] for m in mods) \
        / counts["regrids"]
