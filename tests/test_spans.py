"""The phase timers' sections are spans (``utils/timers.span``): one
``TraceAnnotation`` each, and closed records in a process-wide ring only
while a profiler session is on or the owner is a live ``Timers``."""

import glob
import os
import types

import jax
import jax.numpy as jnp
import pytest

from ramses_tpu import platform
from ramses_tpu.config import params_from_dict, params_from_string
from ramses_tpu.utils import timers
from ramses_tpu.utils.timers import NullTimers, Timers

pytestmark = pytest.mark.smoke

SEDOV2D = """
&RUN_PARAMS
hydro=.true.
nstepmax=64
/
&AMR_PARAMS
levelmin=4
levelmax=5
boxlen=1.0
/
&INIT_PARAMS
nregion=2
region_type(1)='square'
region_type(2)='point'
x_center=0.5,0.5
y_center=0.5,0.5
length_x=10.0,1.0
length_y=10.0,1.0
exp_region=10.0,10.0
d_region=1.0,0.0
p_region=1e-5,0.1
/
&OUTPUT_PARAMS
tend=1.0
/
&HYDRO_PARAMS
gamma=1.4
courant_factor=0.8
/
&REFINE_PARAMS
err_grad_p=0.1
/
"""

REGRID_LABELS = {
    "regrid", "regrid: flag", "regrid: flag fetch", "regrid: tree build",
    "regrid: balance", "regrid: maps", "regrid: maps tiles",
    "regrid: maps upload", "regrid: migrate", "regrid: restrict"}
REGRID_PHASES = REGRID_LABELS - {"regrid", "regrid: flag fetch",
                                 "regrid: tree build", "regrid: maps tiles",
                                 "regrid: maps upload"}


class FakeClock:
    """Both clocks of ``utils/timers`` on one hand-wound counter."""

    def __init__(self):
        self.ns = 0

    def advance(self, seconds):
        self.ns += int(seconds * 1e9)

    def perf_counter(self):
        return self.ns / 1e9

    def perf_counter_ns(self):
        return self.ns


@pytest.fixture
def ring():
    timers.clear_span_records()
    yield timers.span_records
    timers.clear_span_records()


def _amr_sim():
    from ramses_tpu.amr.hierarchy import AmrSim
    sim = AmrSim(params_from_string(SEDOV2D, ndim=2))
    sim.regrid_interval = 0
    return sim


def _dur(r):
    return r["t1_ns"] - r["t0_ns"]


def test_nesting_parent_and_self_time_under_a_fake_clock(monkeypatch, ring):
    clock = FakeClock()
    monkeypatch.setattr(timers, "time", clock)
    tm = Timers()
    with tm.section("a"):
        clock.advance(1.0)
        with tm.section("b"):
            clock.advance(2.0)
            with tm.section("c"):
                clock.advance(0.25)
        clock.advance(0.5)
    with tm.section("a"):
        clock.advance(4.0)
    # self time per label, as before: what phases_s and output_timer read
    assert tm.acc == {"a": 5.5, "b": 2.0, "c": 0.25}
    assert tm.count == {"a": 3, "b": 2, "c": 1}
    assert tm.snapshot() == tm.acc and tm._label is None
    recs = ring()
    # children close, and so land, before their parents
    assert [(r["name"], r["parent"], r["depth"], _dur(r)) for r in recs] == [
        ("c", "b", 2, 250_000_000), ("b", "a", 1, 2_250_000_000),
        ("a", None, 0, 3_750_000_000), ("a", None, 0, 4_000_000_000)]
    assert not any(r["traced"] or r["wait"] for r in recs)
    assert all(r["compiles"] == 0 and r["compile_s"] == 0.0 for r in recs)


def test_an_exception_closes_the_span_and_unwinds_the_stack(ring):
    tm = Timers()
    with pytest.raises(KeyError):
        with tm.section("outer"):
            with tm.section("inner"):
                raise KeyError("x")
    with tm.section("next"):
        pass
    assert [(r["name"], r["parent"]) for r in ring()] == [
        ("inner", "outer"), ("outer", None), ("next", None)]
    assert tm._label is None


def test_off_is_off_no_record_no_clock_no_fetch(monkeypatch, ring):
    """No profiler session and ``NullTimers``: a section stores nothing,
    reads no clock and adds no device fetch — through a whole regrid."""
    sim = _amr_sim()
    assert isinstance(sim.timers, NullTimers)
    sim.evolve(1e9, nstepmax=4)
    sim.regrid()
    sim.evolve(1e9, nstepmax=8)

    def boom():
        raise AssertionError("a clock was read with tracing off")

    monkeypatch.setattr(timers, "time", types.SimpleNamespace(
        perf_counter=boom, perf_counter_ns=boom))
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with sim.timers.section("anything"):
        pass
    assert calls == []
    octs = sim.tree.noct(sim.lmax)
    sim.regrid()
    assert sim.tree.noct(sim.lmax) != octs      # the full path, not the
    assert len(calls) == 1                      # early return; ONE trip
    # the Courant pass after it (its ``courant: fetch`` child is a
    # section like the others) and a coarse step: still no clock, and
    # nothing noted for ``telemetry/hlo.device_phases``
    from ramses_tpu.telemetry import hlo
    assert sim._dt_cache is None
    sim.step_coarse(sim.coarse_dt())
    assert hlo.dispatch_records() == []
    assert ring() == []
    assert sim.timers.snapshot() == {}


def test_compile_deltas_count_what_compiled_inside(ring):
    platform._install_cache_listener()
    tm = Timers()

    @jax.jit
    def fresh(x):
        return x * 3.0 + 1.0

    with tm.section("outer"):
        with tm.section("compiles"):
            fresh(jnp.ones(7)).block_until_ready()
        with tm.section("cached"):
            fresh(jnp.ones(7)).block_until_ready()
    by = {r["name"]: r for r in ring()}
    assert by["compiles"]["compiles"] >= 1 and by["compiles"]["compile_s"] > 0
    assert by["cached"]["compiles"] == 0 and by["cached"]["compile_s"] == 0.0
    assert by["outer"]["compiles"] == by["compiles"]["compiles"]


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    data = ProfileData.from_file(xplane)
    return {ev.name for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


def test_regrid_under_a_profiler_session(tmp_path, ring):
    """A tiny two-level ``AmrSim`` (``NullTimers``) regridding twice under
    a real ``jax.profiler`` session: the label set of the regrid, its
    nesting, its coverage, and the same names in the written trace."""
    sim = _amr_sim()
    sim.evolve(1e9, nstepmax=4)
    sim.regrid()
    assert ring() == []                         # no session yet: nothing
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            sim.evolve(1e9, nstepmax=sim.nstep + 4)
            sim.regrid()
        sim.drain()
    finally:
        jax.profiler.stop_trace()
    with sim.timers.section("after the session"):
        pass
    recs = ring()
    assert all(r["traced"] for r in recs)
    assert {r["name"] for r in recs} == REGRID_LABELS | {
        "hydro - godunov", "evolve: wait"}
    # the spans in which the host blocks on the device say so
    assert {r["name"] for r in recs if r["wait"]} == {
        "regrid: flag fetch", "evolve: wait"}
    regrids = [r for r in recs if r["name"] == "regrid"]
    assert len(regrids) == 2
    assert all(r["parent"] is None and r["depth"] == 0 for r in regrids)
    parents = {r["name"]: r["parent"] for r in recs}
    assert {n: parents[n] for n in REGRID_LABELS} == {
        "regrid": None, "regrid: flag": "regrid",
        "regrid: flag fetch": "regrid: flag",
        "regrid: tree build": "regrid: flag", "regrid: balance": "regrid",
        "regrid: maps": "regrid", "regrid: maps tiles": "regrid: maps",
        "regrid: maps upload": "regrid: maps",
        "regrid: migrate": "regrid", "regrid: restrict": "regrid"}
    # each phase once a regrid, inside it, and together nearly all of it
    for g in regrids:
        kids = [r for r in recs if r["parent"] == "regrid"
                and g["t0_ns"] <= r["t0_ns"] and r["t1_ns"] <= g["t1_ns"]]
        assert sorted(r["name"] for r in kids) == sorted(REGRID_PHASES)
    covered = sum(_dur(r) for r in recs if r["parent"] == "regrid")
    assert covered >= 0.95 * sum(_dur(g) for g in regrids)
    assert all(isinstance(r["compiles"], int)
               and isinstance(r["compile_s"], float) for r in recs)
    names = _host_event_names(str(tmp_path))
    assert REGRID_LABELS | {"hydro - godunov", "evolve: wait"} <= names


def test_live_timers_keep_self_time_per_label(ring):
    """Telemetry's view (``phases_s``, ``output_timer``): the old labels
    with their old meaning, the renamed one, no bare timer/stop pair."""
    sim = _amr_sim()
    sim.evolve(1e9, nstepmax=4)
    sim.timers = Timers()
    sim.regrid()
    snap = sim.timers.snapshot()
    assert set(snap) == REGRID_LABELS           # and no other
    assert sim.timers._label is None
    assert "regrid: migrate" in sim.timers.output_timer()
    recs = {r["name"]: r for r in ring()}
    assert not any(r["traced"] for r in recs.values())
    # a label's seconds are its span less its children's
    flag = _dur(recs["regrid: flag"]) - _dur(recs["regrid: flag fetch"]) \
        - _dur(recs["regrid: tree build"])
    assert snap["regrid: flag"] == pytest.approx(flag / 1e9, abs=2e-3)
    assert sum(snap.values()) == pytest.approx(_dur(recs["regrid"]) / 1e9,
                                               abs=2e-3)


def test_uniform_evolve_spans(tmp_path, ring):
    from ramses_tpu.driver import Simulation
    groups = {
        "run_params": {"hydro": True, "nstepmax": 8},
        "amr_params": {"levelmin": 4, "levelmax": 4, "boxlen": 1.0},
        "init_params": {"nregion": 2,
                        "region_type": ["square", "square"],
                        "x_center": [0.25, 0.75], "y_center": [0.5, 0.5],
                        "length_x": [0.5, 0.5], "length_y": [10.0, 10.0],
                        "exp_region": [10.0, 10.0],
                        "d_region": [1.0, 0.125],
                        "p_region": [1.0, 0.1]},
        "hydro_params": {"riemann": "hllc"},
        "output_params": {"noutput": 1, "tout": [0.5], "tend": 0.5},
    }
    sim = Simulation(params_from_dict(groups, ndim=2), dtype=jnp.float64)
    assert isinstance(sim.timers, NullTimers)
    sim.evolve(chunk=4)                         # two passes, untraced
    assert sim.state.nstep == 8 and ring() == []
    sim.params.run.nstepmax = 20
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.evolve(chunk=4)                     # three passes
    finally:
        jax.profiler.stop_trace()
    recs = ring()
    assert sim.state.nstep == 20
    assert [(r["name"], r["parent"]) for r in recs] == 3 * [
        ("evolve: dispatch", "evolve"), ("evolve: wait", "evolve"),
        ("evolve", None)]
    assert all(r["traced"] for r in recs)
    assert {"evolve", "evolve: dispatch", "evolve: wait"} \
        <= _host_event_names(str(tmp_path))
