"""The readers that put the program's spans on the device's clock
(``layer_metrics/_span_clock``) and device time to a phase
(``layer_metrics/_device_phases``), on synthetic ``reduced`` + records:
CPU, run by hand with the rest of ``benchmark/tests``.  Nothing here is a
speed.  (A rehearsal through ``run.py`` has no device plane, so no reader
is called there; the chip shows the real chain.)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

import run  # noqa: E402
from benchmark.layer_metrics import (_device_phases,  # noqa: E402
                                     _program_spans, _span_clock)

AMR = "sedov3d-amr-7to9.regrid-every-step"
MESH = "sedov3d-amr-7to9-sharded.regrid-every-step"
UNIFORM = ("sedov3d-uniform-256.steady", "sedov3d-uniform-512.steady",
           "mhd-blast3d-uniform-256.steady")
NEW = {"flags_device_ms": "ms", "step_layout_device_ms": "ms",
       "step_unattributed_pct": "%", "courant_host_ms": "ms",
       "host_wait_ms": "ms", "host_device_overlap_pct": "%",
       "device_idle_unnamed_pct": "%"}
MS = 1_000_000
# the program's clock reads 5 000 s when the profiler session starts
CLOCK = 5_000_000 * MS


def rec(name, parent, depth, t0_ms, t1_ms, wait=False):
    return {"name": name, "parent": parent, "depth": depth,
            "t0_ns": CLOCK + int(t0_ms * MS), "t1_ns": CLOCK + int(t1_ms * MS),
            "compiles": 0, "compile_s": 0.0, "traced": True, "wait": wait}


def one_slice(at, skew_ms=0.0):
    """A 100 ms AMR slice starting ``at`` ms: regrid 0-60 (flag 0-30 with
    its fetch 2-20, maps 30-60), courant 61-70 (fetch 62-70), the step's
    dispatch 71-73.  ``skew_ms`` moves the records against the trace."""
    a = at + skew_ms
    return [rec("regrid: flag fetch", "regrid: flag", 2, a + 2, a + 20, True),
            rec("regrid: flag", "regrid", 1, a, a + 30),
            rec("regrid: maps", "regrid", 1, a + 30, a + 60),
            rec("regrid", None, 0, a, a + 60),
            rec("courant: fetch", "courant", 1, a + 62, a + 70, True),
            rec("courant", None, 0, a + 61, a + 70),
            rec("hydro - godunov", None, 0, a + 71, a + 73)]


def reduced_of(n, busy_of_slice):
    """``reduced`` of ``n`` slices of 100 ms in a window that starts at
    10 ms of the trace; the device is busy at ``busy_of_slice`` (ms within
    a slice); ``bench/regrid`` opens 2 us before the root, closes 3 after."""
    w0, w1 = 0.010, 0.010 + 0.1 * n
    busy = [(w0 + 0.1 * k + a * 1e-3, w0 + 0.1 * k + b * 1e-3)
            for k in range(n) for a, b in busy_of_slice]
    edge = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = sorted(((g1 - g0, g0) for g0, g1 in zip(edge[0::2], edge[1::2])
                   if g1 > g0), reverse=True)
    return {"window": (w0, w1), "window_s": w1 - w0,
            "busy_s": sum(b - a for a, b in busy),
            "gaps": [("other", dur, g0 - w0) for dur, g0 in gaps],
            "spans": {"bench/regrid": [
                (w0 + 0.1 * k - 2e-6, w0 + 0.1 * k + 0.060 + 3e-6)
                for k in range(n)]},
            "module_s": {"jit__fused_flags(77)": 0.015 * n,
                         "jit__fused_coarse_step(99)": 0.040 * n},
            "op_s": {}}


@pytest.fixture
def program(monkeypatch):
    """Synthetic records and phase tables in the program's place."""
    def put(recs, tables=None):
        monkeypatch.setattr(_program_spans, "traced_records", lambda: recs)
        _span_clock._MEMO.clear()
        _device_phases._MEMO.clear()
        if tables is not None:
            _device_phases._MEMO["tables"] = tables
    yield put
    _span_clock._MEMO.clear()
    _device_phases._MEMO.clear()


def read(metric, reduced, counts):
    return run.layer_reader(metric).read(reduced, reduced["spans"], counts,
                                         {})


def records(n, skew=()):
    """``n`` slices' records; the window starts 10 ms into the trace."""
    skew = dict(skew)
    return [r for k in range(n)
            for r in one_slice(10 + 100 * k, skew.get(k, 0.0))]


# device busy 5-20 (flags, under the flag fetch), 31-36 (migration, under
# maps: the host works meanwhile), 62-69 (courant, under its fetch) and
# 74-99 (the step, under no span)
BUSY = [(5, 20), (31, 36), (62, 69), (74, 99)]
COUNTS = {"regrids": 3, "steps_done": 3, "slices": 3}


def test_declared_in_their_cells_with_a_reader_each():
    bench = run.load_cell(AMR)[0]
    for cell in (AMR, MESH):
        have = {m["name"] for m in run.metrics_of(bench, "per_layer", cell)}
        assert {n + ".host_bound" for n in NEW} <= have
        assert "device_idle_unnamed_pct" not in have
    for cell in UNIFORM:
        have = {m["name"] for m in run.metrics_of(bench, "per_layer", cell)}
        assert "device_idle_unnamed_pct" in have
        assert not {n + ".host_bound" for n in NEW} & have
    for m in bench["per_layer"]:
        base = run.base_name(m["name"])
        if base in NEW:
            assert m["unit"] == NEW[base]
            assert callable(run.layer_reader(m["name"]).read)
            assert m["moves"] == ("cell_updates_per_s.host_bound"
                                  if m["name"].endswith(".host_bound")
                                  else "cell_updates_per_s")


def test_clocks_join_from_matched_roots(program, capsys):
    program(records(3))
    red = reduced_of(3, BUSY)
    j = _span_clock.join(red, COUNTS)
    # trace = program clock - 5000 s, less the 2 us the harness span
    # opens before the root
    assert j["offset_s"] == pytest.approx(-5000.0 - 2e-6, abs=1e-9)
    assert j["width_s"] == pytest.approx(5e-6, abs=1e-9)
    assert (j["root"], j["n"]) == ("regrid", 3)
    assert "3 pairs bench/regrid" in capsys.readouterr().err
    # one pair that opened late (the host was descheduled between the
    # harness span and the root) loosens its own bound only
    program(records(3, skew={1: 0.7}))
    red["spans"]["bench/regrid"][1] = (
        red["spans"]["bench/regrid"][1][0],
        red["spans"]["bench/regrid"][1][1] + 0.7e-3)
    j2 = _span_clock.join(red, COUNTS)
    assert j2["offset_s"] == pytest.approx(j["offset_s"], abs=1e-9)
    assert j2["spread_s"] == pytest.approx(0.7e-3, abs=1e-9)


@pytest.mark.parametrize("skew_ms", [1.0, -1.0])
def test_a_one_ms_disagreement_gives_none(program, capsys, skew_ms):
    """Records that sit 1 ms off their harness span in ONE pair: no offset
    satisfies every pair, so every reader on the joined clock is silent."""
    program(records(3, skew={1: skew_ms}))
    red = reduced_of(3, BUSY)
    assert _span_clock.join(red, COUNTS) is None
    assert "do not agree" in capsys.readouterr().err
    for m in ("host_device_overlap_pct", "device_idle_unnamed_pct"):
        _span_clock._MEMO.clear()
        assert read(m, red, COUNTS) is None


def test_a_root_count_off_by_one_gives_none(program):
    program(records(3))
    red = reduced_of(3, BUSY)
    off = dict(COUNTS, regrids=4)
    for m in ("host_device_overlap_pct", "device_idle_unnamed_pct",
              "host_wait_ms", "courant_host_ms"):
        _span_clock._MEMO.clear()
        assert read(m, red, off) is None
    # the trace has one harness span fewer than roots
    red["spans"]["bench/regrid"].pop()
    _span_clock._MEMO.clear()
    assert read("device_idle_unnamed_pct", red, COUNTS) is None
    # records of a program older than the wait flag: nothing, no raise
    old = [{k: v for k, v in r.items() if k != "wait"} for r in records(3)]
    program(old)
    for m in ("host_device_overlap_pct", "device_idle_unnamed_pct",
              "host_wait_ms"):
        assert read(m, reduced_of(3, BUSY), COUNTS) is None
    program([])
    assert read("device_idle_unnamed_pct", reduced_of(3, BUSY),
                COUNTS) is None


def test_a_gap_under_two_nested_spans_goes_to_the_inner(program, capsys):
    program(records(3))
    red = reduced_of(3, BUSY)
    att = _span_clock.attribution(red, COUNTS)
    idle = {k: v / 3 for k, v in att["idle"].items()}
    # idle a slice (ms): 0-5 = 0-2 flag + 2-5 fetch; 20-31 = 20-30 flag +
    # 30-31 maps; 36-62 = 36-60 maps + 60-61 none + 61-62 courant; 69-74
    # = 69-70 fetch + 70-71 none + 71-73 godunov + 73-74 none; 99-100 none
    want = {("regrid: flag", False): 12e-3, ("regrid: flag fetch", True): 3e-3,
            ("regrid: maps", False): 25e-3, ("courant", False): 1e-3,
            ("courant: fetch", True): 1e-3, ("hydro - godunov", False): 2e-3,
            (_span_clock.NO_SPAN, False): 4e-3}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v, abs=2e-5), k
    assert ("regrid", False) not in idle       # never the outer span
    got = read("device_idle_unnamed_pct", red, COUNTS)
    assert got == pytest.approx(100 * 4 / 48, abs=0.1)
    err = capsys.readouterr().err
    assert "[device idle] ms a coarse step by innermost program span" in err
    assert "regrid: flag fetch  [wait]" in err


def test_busy_under_a_wait_is_not_overlap(program):
    program(records(3))
    red = reduced_of(3, BUSY)
    # busy a slice: 15 under the flag fetch and 7 under the courant fetch
    # (waits: the host is idle), 5 under maps (overlap), 25 under no span
    got = read("host_device_overlap_pct", red, COUNTS)
    assert got == pytest.approx(100 * 5 / 52, abs=0.1)
    busy = {k: v / 3 for k, v in
            _span_clock.attribution(red, COUNTS)["busy"].items()}
    assert busy[("regrid: flag fetch", True)] == pytest.approx(15e-3,
                                                               abs=2e-5)
    assert busy[(_span_clock.NO_SPAN, False)] == pytest.approx(25e-3,
                                                               abs=2e-5)
    # all of it under waits: no overlap but the 2 us of the join's slack
    _span_clock._MEMO.clear()
    assert read("host_device_overlap_pct", reduced_of(
        3, [(5, 20), (62, 69)]), COUNTS) == pytest.approx(0.0, abs=0.02)


def test_wait_and_courant_per_step(program, capsys):
    program(records(3))
    red = reduced_of(3, BUSY)
    assert read("host_wait_ms", red, COUNTS) == pytest.approx(18 + 8)
    assert read("courant_host_ms", red, COUNTS) == pytest.approx(9.0)
    assert "courant: fetch 8.000, regrid: flag fetch 18.000" \
        in capsys.readouterr().err
    assert read("flags_device_ms", red, COUNTS) == pytest.approx(15.0)
    assert read("flags_device_ms", dict(red, module_s={}), COUNTS) is None


def test_uniform_slices_join_on_evolve_roots(program):
    """Three 160 ms slices: ``bench/slice`` round one ``evolve`` root each
    (dispatch 1-2, wait 2-159), device busy 3-158."""
    recs = []
    for k in range(3):
        a = 20 + 160 * k
        recs += [rec("evolve: dispatch", "evolve", 1, a + 1, a + 2),
                 rec("evolve: wait", "evolve", 1, a + 2, a + 159, True),
                 rec("evolve", None, 0, a + 0.03, a + 159.5)]
    program(recs)
    w0, w1 = 0.020, 0.500
    busy = [(w0 + 0.16 * k + 0.003, w0 + 0.16 * k + 0.158)
            for k in range(3)]
    edge = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [("other", g1 - g0, g0 - w0)
            for g0, g1 in zip(edge[0::2], edge[1::2])]
    red = {"window": (w0, w1), "gaps": gaps, "op_s": {}, "module_s": {},
           "spans": {"bench/slice": [(w0 + 0.16 * k, w0 + 0.16 * k + 0.1596)
                                     for k in range(3)]}}
    counts = {"slices": 3, "steps_done": 48}
    j = _span_clock.join(red, counts)
    assert j["root"] == "evolve" and j["width_s"] == pytest.approx(
        0.13e-3, abs=1e-6)
    # idle a slice: 0.03 before the root (none), 0.97 evolve, 1 dispatch,
    # 1 wait, then 158-159 wait, 159-159.5 evolve, 0.5 none
    got = read("device_idle_unnamed_pct", red, counts)
    assert got == pytest.approx(100 * 0.53 / 5.0, abs=0.2)


def test_device_time_goes_to_its_phase(program, capsys):
    tables = {
        "jit__fused_coarse_step": {
            "copy.614": ("sweep l7/gather", "layout"),
            "fused_step_padded.3": ("sweep l7/kernel", "kernel"),
            "fusion.9": ("sweep l8/ghost", "physics"),
            "fusion.11": ("unattributed", "unattributed")},
        "jit__fused_flags": {"copy.260": ("flags l7/gather", "layout")}}
    program(records(3), tables)
    red = reduced_of(3, BUSY)
    step, flags = "jit__fused_coarse_step(99)", "jit__fused_flags(77)"
    red["op_s"] = {
        (step, "%copy.614 copy"): 0.030,
        (step, "%fused_step_padded.3 custom-call:tpu_custom_call"): 0.012,
        (step, "%fusion.9 fusion"): 0.009,
        (step, "%fusion.11 fusion"): 0.003,
        (step, "%fusion.77 fusion"): 0.003,      # not in the table
        (flags, "%copy.260 copy"): 0.015,
        ("jit_other(5)", "%copy.1 copy"): 0.5}   # no table: not counted
    assert read("step_layout_device_ms", red, COUNTS) == pytest.approx(15.0)
    assert read("step_unattributed_pct", red, COUNTS) == pytest.approx(
        100 * 0.006 / 0.072)
    err = capsys.readouterr().err
    assert "layout       sweep l7/gather" in err
    assert "jit__fused_coarse_step/%fusion.77 fusion  [unattributed (not " \
        "in the table)]" in err
    assert "jit__fused_coarse_step/%fusion.11 fusion  [unattributed]" in err
    # a program that noted nothing: silent
    program(records(3), {})
    assert read("step_layout_device_ms", red, COUNTS) is None
    assert read("step_unattributed_pct", red, COUNTS) is None
