"""Device time of one coarse step: summed time of the XLA-module events
whose name contains one of the configuration's ``step_programs``, over the
coarse steps of the traced window."""


def step_module_names(reduced, ctx):
    keys = ctx["config"]["step_programs"]
    return [m for m in reduced["module_s"] if any(k in m for k in keys)]


def read(reduced, spans, counts, ctx):
    names = step_module_names(reduced, ctx)
    if not names or not counts.get("steps_done"):
        return None
    return 1e3 * sum(reduced["module_s"][m] for m in names) \
        / counts["steps_done"]
