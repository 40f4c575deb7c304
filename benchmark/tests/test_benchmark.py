"""The benchmark's own checks: CPU, tiny levels, run by hand with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Not under ``tests/``, not part of tier-1.  Nothing here is a speed.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

UNIFORM = "sedov3d-uniform-256.steady"
AMR = "sedov3d-amr-7to9.regrid-every-step"
TRACE = os.path.join(HERE, "data", "uniform256_3s.xplane.pb")
CPU = dict(os.environ, JAX_PLATFORMS="cpu")


# ------------------------------------------------------------- the reducer
@pytest.fixture(scope="module")
def reduced():
    from benchmark.harness import trace_reduce
    return trace_reduce.reduce_trace(TRACE)


def test_reducer_on_the_recorded_trace(reduced):
    """``uniform256_3s.xplane.pb``: 19 slices of the uniform cell on one
    v5e (chip call 2 of PR 24), read by hand from its dump: 19 executions
    of ``jit_run_steps`` of 158.7 ms each, a ``bench/window`` span of
    3.0806 s, ~3.7 ms of idle device between slices."""
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(3.08063, abs=1e-4)
    assert reduced["busy_s"] == pytest.approx(3.01510, abs=1e-4)
    assert reduced["busy_s"] <= reduced["window_s"]
    step = [m for m in reduced["module_s"] if "run_steps" in m]
    assert len(step) == 1 and reduced["module_n"][step[0]] == 19
    assert reduced["module_s"][step[0]] == pytest.approx(19 * 0.15869,
                                                         rel=1e-3)
    # the kernel's self time is inside its module's, the while loop that
    # holds it is not counted twice
    assert 0.5 < reduced["kernel_s"][step[0]] / reduced["module_s"][step[0]] < 1
    assert sum(reduced["op_s"].values()) <= reduced["busy_s"] * 1.001
    # gaps: longest first, inside the window, none longer than a slice
    durs = [g[1] for g in reduced["gaps"]]
    assert durs == sorted(durs, reverse=True) and 0 < durs[0] < 0.16
    assert len(reduced["spans"]["bench/slice"]) == 19


def test_union_self_time_and_gap_attribution():
    from benchmark.harness import trace_reduce as tr
    assert tr._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    ops = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 4.0, 9.0),
           ("c", 11.0, 12.0)]
    own = {n: s for n, _, _, s in tr.self_times(ops)}
    assert own == {"while": 2.0, "a": 3.0, "b": 5.0, "c": 1.0}
    spans = {"bench/window": [(0, 100)], "bench/slice": [(10, 30)],
             "bench/regrid": [(10, 20)], "bench/step": [(20, 30)]}
    assert tr.covering_span(11, 19, spans) == "bench/regrid"
    assert tr.covering_span(21, 29, spans) == "bench/step"
    assert tr.covering_span(40, 50, spans) == "other"
    assert tr.module_of(5.0, [("m", 0.0, 4.0), ("n", 4.5, 6.0)]) == "n"
    assert tr.module_of(4.2, [("m", 0.0, 4.0), ("n", 4.5, 6.0)]) == ""


def test_layer_readers_on_the_recorded_trace(reduced):
    """Every reader of BENCHMARK.json gives a number or nothing; a share of
    a roofline stays under 100 %; a reader with nothing to read gives None,
    never 0."""
    import run
    bench, cell, config, traffic, peaks = run.load_cell(UNIFORM)
    peak = peaks["TPU v5 lite"]
    counts = {"steps_done": 19 * 16, "cell_updates": 19 * 16 * 256 ** 3,
              "kernel_cell_updates": 19 * 16 * 256 ** 3, "regrids": 0}
    ctx = {"config": config, "traffic": traffic, "peak": peak,
           "window_compile_s": 0.0, "cell": UNIFORM}
    got = {m["name"]: run.layer_reader(m["name"]).read(
        reduced, reduced["spans"], counts, ctx)
        for m in run.metrics_of(bench, "per_layer", UNIFORM)}
    assert len(got) == 5 and not any("." in k for k in got)
    # the other cell's names read the same quantities through the same
    # readers; one with nothing to read there gives nothing
    assert {run.base_name(m["name"])
            for m in run.metrics_of(bench, "per_layer", AMR)} \
        == set(got) | {"regrid_host_ms"}
    assert run.layer_reader("step_device_ms.host_bound") \
        is run.layer_reader("step_device_ms")
    assert run.layer_reader("regrid_host_ms").read(
        reduced, reduced["spans"], counts, ctx) is None   # no regrid span
    assert got["step_device_ms"] == pytest.approx(158.69 / 16, rel=1e-3)
    assert 0 < got["hydro_roofline_pct"] < got["sweep_kernel_roofline_pct"] \
        < 100
    assert got["device_idle_pct"] == pytest.approx(
        100 * (1 - 3.01510 / 3.08063), abs=0.01)
    assert got["window_compile_s"] == 0.0
    empty = dict(counts, kernel_cell_updates=0, cell_updates=0)
    assert run.layer_reader("sweep_kernel_roofline_pct").read(
        reduced, reduced["spans"], empty, ctx) is None
    assert run.layer_reader("hydro_roofline_pct").read(
        reduced, reduced["spans"], empty, ctx) is None


# ---------------------------------------------------------------- the work
def test_cell_update_count_on_a_hand_made_tree():
    """Three levels: 8 octs of level 3 (complete 4^3 ... here just counts),
    3 of level 4, 5 of level 5; a level l is swept 2**(l-lmin) times."""
    from benchmark.harness import work
    per = {3: 8, 4: 3, 5: 5}
    assert work.amr_cell_updates(per, 3) == 8 * 8 * 1 + 3 * 8 * 2 + 5 * 8 * 4
    assert work.amr_cell_updates({3: 8}, 3) == 64
    assert work.amr_cell_updates(per, 3, ndim=2) == 8 * 4 + 3 * 4 * 2 \
        + 5 * 4 * 4
    peak = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    sec, which = work.least_time_s(1e9, peak)
    assert which == "bytes" and sec == pytest.approx(1e9 * 40 / 819e9)
    assert work.bytes_per_cell_update(5, 4) == 40


# ---------------------------------------------------------------- the seed
def test_seed_rule_places_the_blast_on_the_pitch():
    from benchmark.harness import seed
    seen = set()
    for s in (0, 1, 2, 2 ** 31 + 7, 4000000011):
        centre, index = seed.blast_centre(s, 7, 4, 0.5)
        assert all(i % 4 == 0 and 4 <= i <= 124 for i in index)
        assert centre == [i * 0.5 / 128 for i in index]
        assert seed.blast_centre(s, 7, 4, 0.5) == (centre, index)
        seen.add(tuple(index))
    assert len(seen) > 1
    # the held slice's place in the window: drawn too, blast not moved
    shares = {seed.check_fraction(s) for s in (0, 1, 2, 2 ** 31 + 7)}
    assert len(shares) == 4 and all(0 <= x < 1 for x in shares)


def test_seeds_are_translations_equal_counts_per_level():
    """Tiny levels (4→6), 12 coarse steps with a regrid before each: three
    seeds give the same oct, padded-oct and tile counts per level at every
    step, so one set of compiled programs serves every seed."""
    import jax.numpy as jnp
    import run
    from ramses_tpu.amr.hierarchy import AmrSim
    _, _, config, traffic, _ = run.load_cell(AMR)
    config = dict(config, rehearse={"levelmin": 4, "levelmax": 6,
                                    "seed_level": 4})
    tables = []
    for s in (11, 12, 4000000013):
        sim = AmrSim(run.build_params(config, traffic, s, True),
                     dtype=jnp.float32)
        rows = []
        for _ in range(12):
            sim.evolve(1e9, nstepmax=sim.nstep + 1)
            rows.append([(l, sim.tree.noct(l), int(sim.maps[l].noct_pad),
                          int(sim.blocks[l].ntile) if l in sim.blocks else 0)
                         for l in sim.levels()])
        tables.append(rows)
    assert tables[0] == tables[1] == tables[2]
    assert max(n for _, n, _, _ in tables[0][-1][1:]) > 0   # levels refined


# ------------------------------------------ files found by name, none edited
def test_a_dummy_cell_and_metric_are_found_by_name(tmp_path):
    """A later PR's cell = new files + entries in BENCHMARK.json: a copy of
    the benchmark with a dummy configuration, mix and per-layer metric
    dropped in loads them with no existing file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "sedov3d-uniform-256.json")))
    cfg["name"] = "dummy-config"
    (root / "benchmark/configs/dummy-config.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(BENCH, "traffic", "steady.json")))
    mix.update(name="dummy-mix", slice_steps=4)
    (root / "benchmark/traffic/dummy-mix.json").write_text(json.dumps(mix))
    (root / "benchmark/layer_metrics/dummy_metric.py").write_text(
        "def read(reduced, spans, counts, ctx):\n"
        "    return 1e3 * reduced['busy_s'] / counts['slices']\n")
    bench["configs"].append({"name": "dummy-config", "source": "none",
                             "file": "benchmark/configs/dummy-config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-config.dummy-mix",
                               "config": "dummy-config",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device",
                               "moves": "cell_updates_per_s",
                               "workloads": ["dummy-config.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = (
        "import sys, json\n"
        f"sys.path.insert(0, {str(root)!r})\n"
        f"sys.path.insert(0, {str(root / 'benchmark')!r})\n"
        "import run\n"
        "from benchmark.harness import trace_reduce\n"
        "bench, cell, config, traffic, peaks = "
        "run.load_cell('dummy-config.dummy-mix')\n"
        "names = [m['name'] for m in run.metrics_of(bench, 'per_layer', "
        "cell['name'])]\n"
        f"red = trace_reduce.reduce_trace({TRACE!r})\n"
        "val = run.layer_reader('dummy_metric').read(red, red['spans'], "
        "{'slices': 19}, {})\n"
        "print(json.dumps([config['name'], traffic['slice_steps'], names, "
        "val, run.ROOT]))\n")
    out = subprocess.run([sys.executable, "-c", script], env=CPU,
                         capture_output=True, text=True, check=True)
    name, steps, names, val, used_root = json.loads(
        out.stdout.strip().splitlines()[-1])
    assert (name, steps, names) == ("dummy-config", 4, ["dummy_metric"])
    assert used_root == str(root)
    assert val == pytest.approx(1e3 * 3.01510 / 19, rel=1e-3)
    for p, data in before.items():
        assert p.read_bytes() == data


# --------------------------------------------------- no chip, no result line
def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", UNIFORM,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=CPU, capture_output=True, text=True, cwd=ROOT)
    assert out.returncode != 0
    assert "correct" not in out.stdout and "{" not in out.stdout
    assert "not a TPU" in out.stderr


def test_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in CPU.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", UNIFORM,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, cwd=tmp_path)
    assert out.returncode != 0 and "{" not in out.stdout
    assert "not in this checkout" in out.stderr


# ------------------------- correct: sound run, control, planted faults (CPU)
def _judge(cellname, entry, control=None):
    """One held slice from the marked state (seconds=0: the first slice
    is the held one)."""
    import run
    bench, cell, config, traffic, peaks = run.load_cell(cellname)
    if hasattr(entry, "rewind"):
        entry.rewind()
        entry.first_lap.clear()
    return run.window_and_judge(
        bench, cell, config, traffic, peaks["TPU v5 lite"], entry,
        seconds=0.0, control=control, rehearse=True, free=False)


@pytest.fixture(scope="module")
def uniform_entry():
    import run
    _, _, config, traffic, _ = run.load_cell(UNIFORM)
    entry, _ = run.set_up(config, traffic, 4000000041, rehearse=True)
    return entry


@pytest.fixture(scope="module")
def amr_entry():
    import run
    _, _, config, traffic, _ = run.load_cell(AMR)
    config = dict(config, rehearse={"levelmin": 4, "levelmax": 6,
                                    "seed_level": 4})
    entry, _ = run.set_up(config, traffic, 4000000042, rehearse=True)
    return entry


def _uniform_fault(kind):
    """Plants the fault UNDER the harness: in the program's ``evolve``."""
    def plant(entry):
        sim = entry.sim
        evolve0 = sim.evolve

        def evolve():
            u0 = sim.state.u
            evolve0()
            if kind == "state unchanged":
                sim.state.u = u0
            elif kind == "one cell altered":
                sim.state.u = sim.state.u.at[0, 3, 4, 5].multiply(1.05)
        sim.evolve = evolve
        return lambda: setattr(sim, "evolve", evolve0)
    return plant


def _amr_fault(kind):
    """Plants the fault in the program's ``step_coarse`` / ``regrid``."""
    def plant(entry):
        sim = entry.sim
        step0, regrid0 = entry.step0, entry.regrid0

        def step(dt):
            import jax.numpy as jnp
            u0 = {l: jnp.copy(a) for l, a in sim.u.items()}   # step donates
            step0(dt)
            if kind == "state unchanged":
                sim.u = u0
            elif kind == "one cell altered":
                l = max(sim.levels())
                sim.u = dict(sim.u)
                sim.u[l] = sim.u[l].at[7, 0].multiply(1.05)

        def regrid():
            if kind == "regrid skipped":
                return
            regrid0()
            if kind == "migrated state altered":
                l = max(sim.levels())
                sim.u = dict(sim.u)
                sim.u[l] = sim.u[l].at[:64].multiply(1.01)
        entry.step0, entry.regrid0 = step, regrid
        return lambda: (setattr(entry, "step0", step0),
                        setattr(entry, "regrid0", regrid0))
    return plant


def test_laps_repeat_the_first_bit_for_bit(amr_entry):
    """Nine slices from the mark go round the 7-step lap: the same step
    numbers, trees and times again; a lap whose step differs is counted."""
    e = amr_entry
    e.rewind()
    e.first_lap.clear()
    n0 = e.sim.nstep
    rows = [e.run_slice() for _ in range(9)]
    assert [r["laps_off"] for r in rows] == [0] * 9
    assert e.sim.nstep == n0 + 2 and len(e.first_lap) == e.lap_steps == 7
    assert rows[7]["cell_updates"] == rows[0]["cell_updates"]
    assert rows[8]["sim_time"] == rows[1]["sim_time"]
    step0 = e.step0
    e.step0 = lambda dt: step0(0.5 * dt)
    try:
        assert e.run_slice()["laps_off"] == 1
    finally:
        e.step0 = step0


CASES = [
    ("sound", None, None, True),
    ("control", "bfloat16", None, False),
    ("state unchanged", None, "state unchanged", False),
    ("one cell altered", None, "one cell altered", False),
]


@pytest.mark.parametrize("label,control,fault,want", CASES,
                         ids=[c[0] for c in CASES])
def test_uniform_correct(uniform_entry, label, control, fault, want):
    undo = _uniform_fault(fault)(uniform_entry) if fault else (lambda: None)
    try:
        result = _judge(UNIFORM, uniform_entry, control)
    finally:
        undo()
    assert result["correct"] is want, result["compared"]
    if fault == "state unchanged":
        assert result["compared"]["state_gap"]["value"] == pytest.approx(1.0)
    if fault == "regrid skipped":
        assert result["compared"]["tree_missing"]["value"] > 0


@pytest.mark.parametrize(
    "label,control,fault,want",
    CASES + [(k, None, k, False)
             for k in ("migrated state altered", "regrid skipped")],
    ids=[c[0] for c in CASES] + ["migrated state altered", "regrid skipped"])
def test_amr_correct(amr_entry, label, control, fault, want):
    undo = _amr_fault(fault)(amr_entry) if fault else (lambda: None)
    try:
        result = _judge(AMR, amr_entry, control)
    finally:
        undo()
    assert result["correct"] is want, result["compared"]
    if fault == "state unchanged":
        assert result["compared"]["state_gap"]["value"] == pytest.approx(1.0)
    if fault == "regrid skipped":
        assert result["compared"]["tree_missing"]["value"] > 0
