"""The MHD blast cell's own checks (CPU, by hand, not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mhd_cell.py -q -p no:cacheprovider

The reference in slabs against the whole box, the configuration's limits
against seeded blasts, the bfloat16 control and the planted faults through
the cell's own entry are held in tier-1 (``tests/test_mhd_blast_cell.py``);
here, what belongs to the harness: the cell and its files are found by name,
the whole command rehearses (control included), the three new readers read
the program's records and give nothing without them, and a fault planted in
the entry's output fails the run.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

CELL = "mhd-blast3d-uniform-256.steady"
HYDRO = "sedov3d-uniform-256.steady"
CPU = dict(os.environ, JAX_PLATFORMS="cpu")
NEW = ["ct_kernel_roofline_pct", "mhd_roofline_pct", "ct_window_ratio"]


def test_cell_is_found_by_name_with_its_own_readers():
    import run
    bench, cell, config, traffic, peaks = run.load_cell(CELL)
    _, _, _, hydro_traffic, _ = run.load_cell(HYDRO)
    assert (cell["chips"], cell["traffic"]) == (1, "steady")
    assert traffic == hydro_traffic                 # the mix, unedited
    assert (config["entry"], config["reference"]) == ("mhd_simulation",
                                                      "uniform_mhd")
    assert config["reduced"] == [] and set(config["assumed"]) >= {
        "box", "riemann", "riemann2d", "blast_position", "tend", "dtype"}
    assert set(config["limits"]) == {
        "state_gap", "cell_gap", "face_gap", "time_gap",
        "mass_drift_per_step", "energy_drift", "divb_max"}
    for name in ("entries." + config["entry"],
                 "reference." + config["reference"]):
        __import__("benchmark." + name)
    # the end-to-end metrics of the device-bound cells, and no reader that
    # counts hydro work
    e2e = [m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == [m["name"] for m in
                   run.metrics_of(bench, "end_to_end", HYDRO)]
    layer = [m["name"] for m in run.metrics_of(bench, "per_layer", CELL)]
    assert layer == ["step_device_ms", "device_idle_pct", "window_compile_s",
                     "evolve_host_ms"] + NEW
    for name in layer:
        assert callable(run.layer_reader(name).read)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "cell_updates_per_s"


def _rehearse(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload",
         CELL, "--seed", "4000000061", "--seconds", "1", *extra],
        env=CPU, capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    return json.loads(last[last.index("{"):]), out.stdout


def test_cell_rehearses_on_the_cpu():
    result, text = _rehearse("--trace", "1")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == {
        "state_gap", "cell_gap", "face_gap", "time_gap",
        "mass_drift_per_step", "energy_drift", "divb_max"}
    assert "[formulation] grid: XLA formulation" in text


def test_control_comes_out_not_correct():
    result, _ = _rehearse("--trace", "0", "--control", "bfloat16")
    assert result["correct"] is False and result["failed"] == 0
    assert all(v["value"] > v["limit"] for v in result["compared"].values())


def test_a_fault_in_the_entrys_output_fails_the_run(monkeypatch):
    """Through ``run.window_and_judge`` with the entry's snapshot spoiled:
    one face off by 1e-3 (``face_gap``, ``divb_max``), then the state
    returned unchanged (``state_gap`` = 1)."""
    import jax
    import numpy as np
    import run
    bench, cell, config, traffic, peaks = run.load_cell(CELL)
    faults = {
        "face": lambda s: dict(s, bf_out=_poked(np, s["bf_out"])),
        "unchanged": lambda s: dict(s, u_out=s["u_in"], bf_out=s["bf_in"]),
    }
    for fault, over in (("face", {"face_gap", "divb_max"}),
                        ("unchanged", {"state_gap", "cell_gap", "face_gap",
                                       "time_gap"})):
        with jax.enable_x64(False):
            entry, _ = run.set_up(config, traffic, 11, rehearse=True)
            real = entry.snapshot
            monkeypatch.setattr(entry, "snapshot",
                                lambda real=real, f=faults[fault]: f(real()))
            result = run.window_and_judge(
                bench, cell, config, traffic, None, entry, seconds=0.5,
                seed=11, rehearse=True)
        assert result["correct"] is False and result["failed"] == 0
        got = {k for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
        assert got >= over - {"time_gap"}, (fault, result["compared"])


def _poked(np, bf):
    bf = np.array(bf)
    bf[0, 3, 4, 5] += 1e-3
    return bf


@pytest.mark.parametrize("n,want", [(256, 3.5), (128, 2.75)])
def test_ct_window_ratio_reads_the_programs_record(monkeypatch, n, want):
    import run
    from ramses_tpu.mhd import pallas_ct as pc
    reader = run.layer_reader("ct_window_ratio")
    monkeypatch.setattr(pc, "_BLOCKS", {})
    assert reader.read(None, None, {}, {}) is None
    monkeypatch.setattr(pc, "_BLOCKS",
                        {(n, n, n): pc._block_record((n, n, n))})
    assert reader.read(None, None, {}, {}) == want
    # a program without the record (the parent of the PR that added it)
    monkeypatch.delattr(pc, "block_stats")
    assert reader.read(None, None, {}, {}) is None


def test_rooflines_pick_the_ct_kernel_by_name():
    """On a reduction shaped as ``trace_reduce`` gives it: the CT kernel's
    ops among another kernel's and the ghost pass; no such op (the parent:
    an XLA formulation, were it to fit) reads as nothing."""
    import run
    from benchmark.harness import mhd_work
    peak = run.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    n = 96 * 256 ** 3
    mod = "jit_run_steps(7)"
    red = {"busy_s": 3.308, "op_s": {
        (mod, "%ct_step_tiled.3 custom-call:tpu_custom_call"): 2.994,
        (mod, "%fusion.36 fusion"): 0.107,
        ("jit_other(1)", "%fused_step_padded.3 custom-call:tpu_custom_call"):
            1.0}}
    counts = {"cell_updates": n, "kernel_cell_updates": n}
    ctx = {"peak": peak}
    least, bound = mhd_work.least_time_s(n, peak)
    assert bound == "bytes"
    kern = run.layer_reader("ct_kernel_roofline_pct").read(red, {}, counts,
                                                           ctx)
    whole = run.layer_reader("mhd_roofline_pct").read(red, {}, counts, ctx)
    assert kern == pytest.approx(100 * least / 2.994)
    assert whole == pytest.approx(100 * least / 3.308)
    assert 0 < whole < kern < 100
    del red["op_s"][(mod, "%ct_step_tiled.3 custom-call:tpu_custom_call")]
    assert run.layer_reader("ct_kernel_roofline_pct").read(
        red, {}, counts, ctx) is None
    assert run.layer_reader("mhd_roofline_pct").read(
        red, {}, {"cell_updates": 0}, ctx) is None
