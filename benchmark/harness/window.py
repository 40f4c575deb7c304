"""Set-up, develop, the timed window and its clock.

The window is the same loop for every entry: one slice after another
through the entry's own ``evolve`` until ``seconds`` have passed, then a
device sync, then the clock stops.  Every rate is all the work of the
window over all of that wall time.  A mix with ``lap_steps`` makes the
entry go back, inside the window, to the state set-up marked each time
that many steps are done: the same steps at the same padded shapes again,
so the window lasts ``seconds`` although the stretch of steps in which no
padded shape changes is short.  One slice, the first to start once the
seed's share of ``seconds`` has passed, is held for the comparison.  A
traced run wraps the first ``trace_seconds`` of the window in
``jax.profiler`` and keeps running to the same end.
"""

import gc
import logging
import re
import time

import jax


def compile_stats():
    from ramses_tpu.platform import compile_cache_stats
    return compile_cache_stats()


class Counts:
    """What the window counted; a traced run also keeps the counts of its
    traced part, which the per-layer metrics divide by."""

    def __init__(self):
        self.slices = 0
        self.steps_asked = 0
        self.steps_done = 0
        self.cell_updates = 0
        self.kernel_cell_updates = 0
        self.regrids = 0
        self.laps_off = 0
        self.sim_time = 0.0

    def add(self, r):
        self.slices += 1
        self.steps_asked += r["asked"]
        self.steps_done += r["done"]
        self.cell_updates += r["cell_updates"]
        self.kernel_cell_updates += r.get("kernel_cell_updates", 0)
        self.regrids += r.get("regrids", 0)
        self.laps_off += r.get("laps_off", 0)
        self.sim_time += r["sim_time"]

    def as_dict(self):
        return dict(vars(self))


class CompileLog(logging.Handler):
    """Names of the programs JAX compiles (or loads from its cache) while
    it is attached: a compile inside the window is a finding to print."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (\S+)", record.getMessage())
        if m:
            self.names.append(m.group(1))

    def __enter__(self):
        self.was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self.logger = logging.getLogger("jax._src.interpreters.pxla")
        self.level, self.propagate = self.logger.level, self.logger.propagate
        self.logger.addHandler(self)
        self.logger.setLevel(logging.DEBUG)
        self.logger.propagate = False
        return self

    def __exit__(self, *exc):
        self.logger.removeHandler(self)
        self.logger.setLevel(self.level)
        self.logger.propagate = self.propagate
        jax.config.update("jax_log_compiles", self.was)


def warm_up(entry, traffic):
    """Develop the flow, then run the warm-up slices: every program of the
    window is compiled (or loaded) before the clock starts."""
    t0 = time.perf_counter()
    if traffic.get("develop_steps", 0):
        entry.develop(int(traffic["develop_steps"]),
                      traffic.get("develop_regrid_every"))
        entry.sync()
    t1 = time.perf_counter()
    for _ in range(int(traffic.get("warm_slices", 1))):
        entry.run_slice()
    entry.sync()
    entry.mark()
    return {"develop_s": t1 - t0, "warm_s": time.perf_counter() - t1}


def timed_window(entry, traffic, seconds: float, trace_dir=None,
                 check_at: float = 0.0):
    """One slice after another until ``seconds``.  Returns the window's
    wall, its Counts, the Counts of its traced part (or None), the wall at
    the end of each slice, the compile seconds and the names of the
    programs compiled or loaded inside it."""
    total, traced = Counts(), None
    c0 = compile_stats()["compile_s"]
    tracing = held = False
    ends = []

    def stop_tracing():
        entry.sync()
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing, traced = True, Counts()
        span = jax.profiler.TraceAnnotation("bench/window")
        span.__enter__()
    gc.collect()
    gc.freeze()       # no collector pause over set-up's objects in the window
    with CompileLog() as log:
        t0 = time.perf_counter()
        now = 0.0
        while True:
            hold = not held and now >= check_at * seconds
            held = held or hold
            with jax.profiler.TraceAnnotation("bench/slice"):
                r = entry.run_slice(hold=hold)
            total.add(r)
            now = time.perf_counter() - t0
            ends.append(now)
            if tracing:
                traced.add(r)
                if now >= float(traffic.get("trace_seconds", 3.0)):
                    stop_tracing()
                    tracing = False
                    now = time.perf_counter() - t0
            if now >= seconds or r["done"] == 0:
                break
        if tracing:
            stop_tracing()
        entry.sync()
        wall = time.perf_counter() - t0
    return {
        "compiled": log.names,
        "wall_s": wall,
        "counts": total,
        "traced": traced,
        "slice_ends": ends,
        "window_compile_s": compile_stats()["compile_s"] - c0,
    }
