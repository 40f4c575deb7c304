"""The 512^3 uniform cell's own checks (CPU, by hand, not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_uniform512_harness.py -q -p no:cacheprovider

The slab reference against the whole-box one, the configuration's limits
against seeded blasts and the bfloat16 control are held in tier-1
(``tests/test_uniform512_cell.py``); here, what belongs to the harness: the
cell and its files are found by name, the whole command rehearses, and the
new per-layer reader reads the program's record.
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

CELL = "sedov3d-uniform-512.steady"
SMALL = "sedov3d-uniform-256.steady"
CPU = dict(os.environ, JAX_PLATFORMS="cpu")


def test_cell_is_found_by_name_and_is_the_256_cell_at_level_9():
    import run
    bench, cell, config, traffic, peaks = run.load_cell(CELL)
    _, _, small, small_traffic, _ = run.load_cell(SMALL)
    assert (cell["chips"], cell["traffic"]) == (1, "steady")
    assert traffic == small_traffic
    assert config["reference"] == "uniform_hydro_slabs"
    assert config["reduced"] == [] and config["entry"] == small["entry"]
    for key in ("physics", "initial_condition", "seed", "step_programs",
                "dtype", "point_region"):
        assert config[key] == small[key], key
    assert {k: config["rehearse"][k] for k in small["rehearse"]} \
        == small["rehearse"]
    # limits from this size's own readings: tighter than the 256^3 file's
    assert all(config["limits"][k] < small["limits"][k]
               for k in small["limits"])
    assert config["guarantees"] == {
        k: v.replace("256^3", "512^3") for k, v in small["guarantees"].items()}
    nml = [open(os.path.join(BENCH, "configs", c["namelist"])).read()
           for c in (small, config)]
    assert nml[0].replace("levelmin=8", "levelmin=9").replace(
        "levelmax=8", "levelmax=9") == nml[1]
    # the cell reports what the 256 cell reports, and the new ratio
    names = [[m["name"] for m in run.metrics_of(bench, kind, c)]
             for kind in ("end_to_end", "per_layer") for c in (SMALL, CELL)]
    assert names[0] == names[1] and names[2] == names[3]
    assert names[3][-1] == "sweep_window_ratio"


def test_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload",
         CELL, "--seed", "4000000061", "--seconds", "1", "--trace", "1"],
        env=CPU, capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last[last.index("{"):])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == {
        "state_gap", "cell_gap", "time_gap", "mass_drift_per_step",
        "energy_drift"}


@pytest.mark.parametrize("n,want", [(256, 3.0), (512, 4.0)])
def test_sweep_window_ratio_reads_the_programs_record(monkeypatch, n, want):
    import run
    from ramses_tpu.hydro import pallas_muscl as pk
    reader = run.layer_reader("sweep_window_ratio")
    monkeypatch.setattr(pk, "_BLOCKS", {})
    assert reader.read(None, None, {}, {}) is None
    monkeypatch.setattr(pk, "_BLOCKS", {
        ((n, n, n), False): pk._block_record((n, n, n), False)})
    assert reader.read(None, None, {}, {}) == want
    # a program without the record (the parent of the PR that added it)
    monkeypatch.delattr(pk, "block_stats")
    assert reader.read(None, None, {}, {}) is None
