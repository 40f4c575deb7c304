"""The two readers of the parts of ``regrid: maps`` (PR 37):
``regrid_maps_tiles_ms`` (span ``regrid: maps tiles``) and
``regrid_maps_upload_ms`` (span ``regrid: maps upload``).  CPU, by hand
with the rest of ``benchmark/tests``; nothing here is a speed."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from benchmark.layer_metrics import _program_spans  # noqa: E402
from test_program_spans import SEDOV2D, one_regrid, read, rec  # noqa: E402

AMR = ("sedov3d-amr-7to9.regrid-every-step",
       "sedov3d-amr-7to9-sharded.regrid-every-step")
PARTS = ("regrid_maps_tiles_ms", "regrid_maps_upload_ms")


@pytest.fixture
def records(monkeypatch):
    def put(recs):
        monkeypatch.setattr(_program_spans, "traced_records", lambda: recs)
    return put


def test_declared_in_the_amr_cells_only():
    bench = run.load_cell(AMR[0])[0]
    for name in PARTS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == list(AMR) and m["layer"] == "regrid"
        assert (m["source"], m["unit"], m["better"], m["moves"]) == (
            "program_span", "ms", "lower", "cell_updates_per_s.host_bound")
        assert callable(run.layer_reader(name).read)


def test_the_quotient_and_the_parent(records):
    # the parent: ``regrid: maps upload`` (5 ms a regrid), no tiles span
    records(one_regrid(0) + one_regrid(1000, scale=2))
    counts = {"regrids": 2, "slices": 2}
    assert read("regrid_maps_upload_ms", counts) == 7.5
    assert read("regrid_maps_tiles_ms", counts) is None      # never 0
    # the change: two blocked levels a regrid, 3 + 4 ms
    tiles = [rec("regrid: maps tiles", "regrid: maps", a, b)
             for a, b in ((32, 35), (36, 40), (1032, 1035), (1036, 1040))]
    records(one_regrid(0) + one_regrid(1000) + tiles)
    assert read("regrid_maps_tiles_ms", counts) == 7.0
    assert read("regrid_maps_tiles_ms", {"regrids": 3}) is None
    records([])
    assert all(read(m, {"regrids": 0}) is None for m in PARTS)


def test_the_program_leaves_both_spans(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_string
    from ramses_tpu.utils import timers
    sim = AmrSim(params_from_string(SEDOV2D, ndim=2))
    sim.regrid_interval = 1
    sim.evolve(1e9, nstepmax=3)
    timers.clear_span_records()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            sim.evolve(1e9, nstepmax=sim.nstep + 1)
        sim.drain()
    finally:
        jax.profiler.stop_trace()
    counts = {"regrids": 3, "slices": 3}
    tiles, upload, maps = (read(m, counts) for m in PARTS
                           + ("regrid_maps_ms",))
    assert 0 < tiles < maps and 0 < upload < maps and tiles + upload < maps
    timers.clear_span_records()
