"""The readers of the program's own spans (``layer_metrics/_program_spans``
and the seven metrics on it): CPU, run by hand with the rest of
``benchmark/tests``.  Nothing here is a speed.

A rehearsal through ``run.py`` cannot show these readers: without a device
plane ``reduced`` is ``None`` and no reader is called.  So the chain is run
here once by hand: a tiny ``AmrSim`` regridding under a ``jax.profiler``
session, its records through the helper and each reader.
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
from benchmark.layer_metrics import _program_spans  # noqa: E402

UNIFORM = "sedov3d-uniform-256.steady"
AMR = "sedov3d-amr-7to9.regrid-every-step"
REGRID = ("regrid_span_ms", "regrid_flag_ms", "regrid_tree_ms",
          "regrid_maps_ms", "regrid_migrate_ms", "regrid_restrict_ms")
MS = 1_000_000


def rec(name, parent, t0_ms, t1_ms, traced=True):
    return {"name": name, "parent": parent, "depth": 0 if parent is None
            else 1, "t0_ns": t0_ms * MS, "t1_ns": t1_ms * MS, "compiles": 0,
            "compile_s": 0.0, "traced": traced}


def one_regrid(at, scale=1):
    """A regrid of 100·scale ms: flag 30 (fetch 12, tree 10), balance 1,
    maps 25 (upload 5), migrate 20, restrict 22; 2 ms in no phase."""
    flag, maps = "regrid: flag", "regrid: maps"
    spans = [("regrid: flag fetch", flag, 2, 14),
             ("regrid: tree build", flag, 20, 30), (flag, "regrid", 0, 30),
             ("regrid: balance", "regrid", 30, 31),
             ("regrid: maps upload", maps, 40, 45), (maps, "regrid", 31, 56),
             ("regrid: migrate", "regrid", 56, 76),
             ("regrid: restrict", "regrid", 76, 98), ("regrid", None, 0, 100)]
    return [rec(name, parent, at + a * scale, at + b * scale)
            for name, parent, a, b in spans]


def one_slice(at):
    """A uniform slice of 162 ms: dispatch 1.5, wait 159, 1.5 around."""
    return [rec("evolve: dispatch", "evolve", at + 1, at + 2.5),
            rec("evolve: wait", "evolve", at + 2.5, at + 161.5),
            rec("evolve", None, at, at + 162)]


def read(metric, counts):
    return run.layer_reader(metric).read(None, {}, counts, {})


@pytest.fixture
def records(monkeypatch):
    """Put synthetic records in the program's place."""
    def put(recs):
        monkeypatch.setattr(_program_spans, "traced_records",
                            lambda: [r for r in recs if r["traced"]])
    return put


def test_the_declared_metrics_have_readers_in_their_cells_only():
    bench = run.load_cell(AMR)[0]
    amr = {m["name"] for m in run.metrics_of(bench, "per_layer", AMR)}
    uni = {m["name"] for m in run.metrics_of(bench, "per_layer", UNIFORM)}
    assert set(REGRID) <= amr and "evolve_host_ms" not in amr
    assert "evolve_host_ms" in uni and not set(REGRID) & uni
    for name in REGRID + ("evolve_host_ms",):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["source"], m["unit"], m["better"]) == (
            "program_span", "ms", "lower")
        assert callable(run.layer_reader(name).read)


def test_the_quotient(records):
    # two regrids, the second twice as long: the mean of 100 and 200
    records(one_regrid(0) + one_regrid(1000, scale=2))
    counts = {"regrids": 2, "slices": 2}
    got = {m: read(m, counts) for m in REGRID}
    assert got == {"regrid_span_ms": 150.0, "regrid_flag_ms": 30.0,
                   "regrid_tree_ms": 15.0, "regrid_maps_ms": 37.5,
                   "regrid_migrate_ms": 30.0, "regrid_restrict_ms": 33.0}
    phases = sum(v for k, v in got.items() if k != "regrid_span_ms")
    assert phases == pytest.approx(0.97 * got["regrid_span_ms"])
    # a child of another name's parent is not taken off
    stray = [rec("regrid: tree build", None, 5000, 5400)]
    records(one_regrid(0) + stray)
    assert read("regrid_flag_ms", {"regrids": 1}) == 20.0
    assert read("regrid_tree_ms", {"regrids": 1}) == 410.0
    records(one_slice(0) + one_slice(162) + one_slice(324))
    assert read("evolve_host_ms", {"slices": 3, "regrids": 0}) \
        == pytest.approx(3.0)


def test_nothing_rather_than_a_wrong_quotient(records):
    records(one_regrid(0) + one_regrid(1000))
    for m in REGRID:
        assert read(m, {"regrids": 3, "slices": 3}) is None   # one missing
        assert read(m, {"regrids": 1, "slices": 1}) is None   # one too many
        assert read(m, {"slices": 2}) is None                 # not counted
    # the uniform cell's reader in an AMR cell: no ``evolve`` span there
    assert read("evolve_host_ms", {"regrids": 2, "slices": 2}) is None
    # the AMR cell's readers in the uniform cell: 0 regrids, 0 records
    records(one_slice(0))
    for m in REGRID:
        assert read(m, {"regrids": 0, "slices": 1}) is None
    # records, but none opened under a profiler session
    records([dict(r, traced=False) for r in one_regrid(0) + one_slice(0)])
    for m in REGRID + ("evolve_host_ms",):
        assert read(m, {"regrids": 1, "slices": 1}) is None


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    """The parent of the PR that added the ring: the import fails, the
    readers give nothing and do not raise."""
    import ramses_tpu.utils.timers as timers
    monkeypatch.delattr(timers, "span_records")
    assert _program_spans.traced_records() == []
    for m in REGRID + ("evolve_host_ms",):
        assert read(m, {"regrids": 1, "slices": 1}) is None


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


def test_the_whole_chain_on_a_tiny_amr_sim(tmp_path):
    """The program's records, as it leaves them, through every reader."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_string
    from ramses_tpu.utils import timers
    sim = AmrSim(params_from_string(SEDOV2D, ndim=2))
    sim.regrid_interval = 1           # the cell's cadence: evolve regrids
    sim.evolve(1e9, nstepmax=3)       # untraced: compiled, nothing kept
    timers.clear_span_records()
    regrids = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            sim.evolve(1e9, nstepmax=sim.nstep + 1)
            regrids += 1
        sim.drain()
    finally:
        jax.profiler.stop_trace()
    sim.evolve(1e9, nstepmax=sim.nstep + 1)   # after the session: not kept
    counts = {"regrids": regrids, "slices": regrids}
    got = {m: read(m, counts) for m in REGRID}
    assert all(v is not None and v >= 0 for v in got.values()), got
    phases = sum(v for k, v in got.items() if k != "regrid_span_ms")
    assert 0.95 * got["regrid_span_ms"] <= phases <= got["regrid_span_ms"]
    assert read("evolve_host_ms", counts) is None
    assert all(read(m, dict(counts, regrids=regrids + 1)) is None
               for m in REGRID)
    timers.clear_span_records()
