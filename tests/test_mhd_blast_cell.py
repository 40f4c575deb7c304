"""The MHD blast configuration of the benchmark, at CPU sizes.

``benchmark/configs/mhd-blast3d-uniform-256.json`` names an entry
(``MhdSimulation``), a plain reference written from the published scheme
(``benchmark/reference/mhd_plain.py``, in x-slabs) and limits.  Held here:
the slab reference IS the whole-box reference (to the bit); the program
(through the cell's own entry, at the configuration's ``rehearse`` levels,
three seeds) agrees with it inside the file's limits and the bfloat16
control does not; planted faults are caught (state unchanged, one face
altered, a divergent face); div B stays at round-off; ``MhdSimulation``
has the ``evolve`` spans, the ``[kernel]`` line and
``run_header.sweep_block``; the three MHD readers read their records and
give nothing without them.

The suite's x64 is off around the program and the comparison, as it is on
the chip (``tests/test_mesh_main.py`` says why).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mhd-blast3d-uniform-256.steady"
NML = os.path.join(ROOT, "benchmark", "configs",
                   "mhd-blast3d-uniform-256.nml")
SEEDS = [4000000051, 7, 2 ** 31 + 11]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mhd-blast3d-uniform-256.json")) as f:
        return json.load(f)


def _blast_snap(shape=(12, 8, 8), nsteps=3):
    """The cell's initial condition in kind (uniform oblique field, an
    over-pressured ball), as a held slice's input."""
    x = np.meshgrid(*[(np.arange(n) + 0.5) / n for n in shape],
                    indexing="ij")
    r2 = sum((xi - 0.5) ** 2 for xi in x)
    b = np.array([0.70710678, 0.70710678, 0.0], np.float32)
    bf = np.broadcast_to(b[:, None, None, None], (3,) + shape).copy()
    u = np.zeros((8,) + shape, np.float32)
    u[0] = 1.0
    u[4] = np.where(r2 < 0.06, 10.0, 0.1) / (5 / 3 - 1) + 0.5
    u[5:8] = bf
    return {"u_in": u, "bf_in": bf, "t_in": 0.0, "tend": 1.0,
            "nsteps": nsteps, "dx": 1.0 / shape[0]}


def _slabs_against_whole(config, nslab, dtype):
    """'' when ``nslab`` x-slabs give the whole-box step's cells, faces and
    time to the bit over 3 steps of a 12 x 8 x 8 box, else what differs."""
    from benchmark.reference import uniform_mhd
    snap = _blast_snap()
    with jax.enable_x64(False):
        whole = uniform_mhd.whole_box(snap, config, dtype)
        slabs = uniform_mhd.advance(snap, config, dtype, nslab=nslab)
    if not slabs["t"] == whole["t"] > 0:
        return f"t {slabs['t']} vs {whole['t']}"
    if np.array_equal(np.asarray(whole["u"]), snap["u_in"]):
        return "nothing moved"
    return "; ".join(
        f"{k} differs by {np.abs(np.asarray(slabs[k]) - np.asarray(whole[k])).max():.2e}"
        for k in ("u", "bf")
        if not np.array_equal(np.asarray(slabs[k]), np.asarray(whole[k])))


@pytest.fixture(scope="module")
def no_fma_slabs():
    """The float32 cases in a child without FMA (``tests/no_fma_child.py``:
    the slab's and the box's loops vectorise differently, and with them
    which ``a * b + c`` become one instruction)."""
    import no_fma_child
    return no_fma_child.result(no_fma_child.start(__file__))


@pytest.mark.parametrize("nslab", [2, 4])
def test_slab_reference_is_the_whole_box_reference(no_fma_slabs, nslab):
    """A cell's update is the same operations on the same numbers whether
    its neighbours came through a roll of the box or of a slab with a
    3-plane margin: to the bit.  4 slabs of three planes: as much margin
    as slab."""
    assert no_fma_slabs[str(nslab)] == ""


def test_slab_reference_in_bfloat16(config):
    """The control's dtype, on the suite's own backend (bfloat16 rounds
    away what an FMA would keep)."""
    assert _slabs_against_whole(config, 4, "bfloat16") == ""


def test_slab_count_fits_the_state_to_the_chip():
    """256^3 f32: 16 slabs of 16 planes (63 MB with margins); a small box
    still takes two."""
    from benchmark.reference.uniform_mhd import SLAB_BYTES, slab_count
    assert slab_count((256, 256, 256), 4) == 16
    assert 11 * (16 + 6) * 256 * 256 * 4 <= SLAB_BYTES
    assert slab_count((32, 32, 32), 4) == 2


def _condinit(centre, n=32):
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.core import MhdStatic
    from ramses_tpu.mhd.driver import mhd_condinit
    params = load_params(NML, ndim=3)
    for key, c in zip(("x_center", "y_center", "z_center"), centre):
        getattr(params.init, key)[1] = c
    return mhd_condinit((n,) * 3, 1.0 / n, params,
                        MhdStatic.from_params(params))


# the second: one pitch (4 level-7 cells) from the low x and the high z
# face, inside the blast's radius - the sphere crosses both faces
@pytest.mark.parametrize("centre", [(0.375, 0.5, 0.375),
                                    (0.03125, 0.5, 0.96875)],
                         ids=["inside", "across_two_faces"])
def test_initial_totals_count_the_blast(config, centre):
    """Energy of the initial condition from the configuration's numbers:
    the ambient gas and field over the box, the over-pressure over the
    cells whose centres lie in the sphere - what ``mhd_condinit`` builds,
    wherever the seed puts the sphere: a region that crosses a periodic
    face continues on the other side, so every placement is an exact
    translation of the centred one."""
    from benchmark.reference import uniform_mhd
    n = 32
    u, bf = _condinit(centre)
    mass, energy = uniform_mhd.initial_totals(config, 1.0 / n)
    assert mass == pytest.approx(u[0].sum() / n ** 3, rel=1e-12)
    assert energy == pytest.approx(u[4].sum() / n ** 3, rel=1e-6)
    assert uniform_mhd.blast_cells(0.1, 1.0 / n) == int(
        (u[4] > u[4].min() * 1.5).sum())
    u0, bf0 = _condinit((0.5, 0.5, 0.5))
    shift = [round((c - 0.5) * n) for c in centre]
    assert np.array_equal(u, np.roll(u0, shift, axis=(1, 2, 3)))
    assert np.array_equal(bf, np.roll(bf0, shift, axis=(1, 2, 3)))


def test_region_does_not_cross_an_outflow_face():
    """Only a periodic face lets a region through: the same sphere in a
    box with outflow walls in x is clipped at the wall."""
    from ramses_tpu.mhd.driver import _region_mask
    n = 32
    x = np.meshgrid(*[(np.arange(n) + 0.5) / n] * 3, indexing="ij")

    class Init:
        x_center, y_center, z_center = [0.03125], [0.5], [0.5]
        length_x = length_y = length_z = [0.2]
        exp_region = [2.0]

    clipped = _region_mask(x, 0, Init, 3, [None, 1.0, 1.0])
    whole = _region_mask(x, 0, Init, 3, [1.0, 1.0, 1.0])
    assert np.array_equal(clipped, _region_mask(x, 0, Init, 3))
    assert not clipped[n // 2:].any() and whole[n // 2:].any()
    assert np.array_equal(whole[:n // 2], clipped[:n // 2])


@pytest.fixture(scope="module", params=SEEDS, ids=[str(s) for s in SEEDS])
def held_slice(request, config):
    """Two 16-step slices of the seeded blast through the cell's own entry
    at the ``rehearse`` levels, the second held."""
    from benchmark import run
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "steady.json")) as f:
        traffic = json.load(f)
    with jax.enable_x64(False):
        entry, _ = run.set_up(config, traffic, request.param, rehearse=True)
        row = entry.run_slice(hold=True)
        entry.sync()
        snap = entry.snapshot()
        forms = entry.formulations()
    assert row["done"] == traffic["slice_steps"] == snap["nsteps"]
    assert row["kernel_cell_updates"] == 0         # the CPU: XLA scan
    assert forms == [("grid", "XLA formulation", False)]
    return snap


def _limits(config):
    return dict(config, limits={**config["limits"],
                                **config["rehearse"].get("limits", {})})


CONTROLS = [("float32 program", None, True),
            ("bfloat16 in the program's place", "bfloat16", False)]


@pytest.mark.parametrize("label,control,want", CONTROLS,
                         ids=[c[0] for c in CONTROLS])
def test_program_agrees_with_the_plain_reference(config, held_slice, label,
                                                 control, want):
    """The file's own limits (each set between the chip's sound readings
    and the bfloat16 control's, PERF.md section 2) with its rehearsal's
    changes (reasons in the file's ``rehearse.why_limits``): the program
    passes every one; the control fails every one that has an upper
    reading."""
    from benchmark.harness import check
    cfg = _limits(config)
    with jax.enable_x64(False):
        compared, ok = check.compare(cfg, held_slice, control)
    over = {k for k, (v, lim) in compared.items() if not v <= lim}
    assert ok is want, json.dumps(compared)
    assert over == (set() if want else set(cfg["limits"])), compared


FAULTS = {
    "state returned unchanged": (
        lambda s: dict(s, u_out=s["u_in"], bf_out=s["bf_in"]),
        {"state_gap": 1.0, "face_gap": 1.0}),
    "one face altered": (
        lambda s: dict(s, bf_out=_poke(s["bf_out"], (1, 5, 6, 7), 1e-3)),
        {"face_gap": None, "divb_max": None}),
    "one cell altered": (
        lambda s: dict(s, u_out=_poke(s["u_out"], (0, 9, 3, 20), 1e-2)),
        {"cell_gap": None}),
}


def _poke(a, index, by):
    a = np.array(a)
    a[index] += by
    return a


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_faults_are_caught(config, held_slice, fault):
    from benchmark.harness import check
    plant, want = FAULTS[fault]
    with jax.enable_x64(False):
        compared, ok = check.compare(_limits(config), plant(held_slice))
    assert not ok
    for name, reads in want.items():
        value, limit = compared[name]
        assert value > limit
        if reads is not None:
            assert value == pytest.approx(reads)


def test_divb_stays_at_round_off(config, held_slice):
    """32 steps from the initial condition leave max |div B| dx / max |B|
    of the faces at float32 round-off (5e-6: the per-step rounding of a
    face, 6e-8, random-walks; the chip reads 5.7e-6 after 400 steps at
    256^3), far under the 1e-3 a single wrong face gives."""
    from benchmark.reference import uniform_mhd
    assert held_slice["nstep_out"] == 32
    assert uniform_mhd.divb_max(held_slice["bf_out"], 1.0) < 5e-6
    assert uniform_mhd.divb_max(
        _poke(held_slice["bf_out"], (2, 1, 1, 1), 1e-3), 1.0) > 5e-4


def _mhd_sim(tmp_path=None):
    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.driver import MhdSimulation
    params = load_params(NML, ndim=3)
    params.amr.levelmin = params.amr.levelmax = 4
    if tmp_path is not None:
        params.output.telemetry = str(tmp_path / "run.jsonl")
    return params, MhdSimulation(params, dtype=jnp.float32)


def test_evolve_spans(tmp_path):
    """``evolve`` holds ``evolve: dispatch`` and ``evolve: wait``, one of
    each a pass of the loop, as ``driver.Simulation`` has them: what
    ``evolve_host_ms`` reads."""
    from ramses_tpu.utils import timers
    _, sim = _mhd_sim()
    with jax.enable_x64(False):
        sim.evolve(nstepmax=2)                      # compile outside
        timers.clear_span_records()
        jax.profiler.start_trace(str(tmp_path))
        try:
            sim.evolve(nstepmax=6, chunk=2)
        finally:
            jax.profiler.stop_trace()
    recs = [r for r in timers.span_records() if r["traced"]][-6:]
    assert [r["name"] for r in recs if r["parent"] is None] \
        == ["evolve", "evolve"]
    kids = [(r["name"], r["parent"]) for r in recs if r["parent"]]
    assert sorted(kids) == sorted([("evolve: dispatch", "evolve"),
                                   ("evolve: wait", "evolve")] * 2)
    assert sim.nstep == 6


def test_kernel_line_and_run_header(tmp_path):
    """``[kernel]`` names ``pallas_ct`` with each traced shape's pick, or
    says the run kept the XLA formulation (the CPU does); a run with
    telemetry on carries the CT kernel's ``sweep_block`` in its
    ``run_header`` and ``divb`` in its step records."""
    from ramses_tpu.mhd import pallas_ct
    from ramses_tpu.telemetry import screen
    rec = pallas_ct._block_record((256, 256, 256))
    assert rec == {"kernel": "pallas_ct", "shape": [256] * 3, "bx": 8,
                   "by": 8, "halo": 3, "window_cells": 14 * 16 * 256,
                   "written_cells": 8 * 8 * 256}
    assert screen.kernel_line([rec], kernel="pallas_ct") == (
        "[kernel] pallas_ct: 256x256x256 bx=8 by=8 window/written=3.50")
    params, sim = _mhd_sim(tmp_path)
    with jax.enable_x64(False):
        sim.evolve(nstepmax=2)
    sim.telemetry.close(sim)
    assert screen.sweep_kernel(sim) == "pallas_ct"
    assert screen.sweep_blocks("pallas_ct") == pallas_ct.block_stats()
    assert screen.kernel_line([], "pallas_ct") == \
        "[kernel] pallas_ct: not traced (XLA formulation)"
    with open(params.output.telemetry) as f:
        header, step = json.loads(f.readline()), json.loads(f.readline())
    assert header["kind"] == "run_header"
    assert header["run_info"]["driver"] == "MhdSimulation"
    assert header["run_info"]["sweep_kernel"] == "pallas_ct"
    assert header["run_info"]["sweep_block"] == pallas_ct.block_stats()
    assert step["kind"] == "step" and 0 <= step["divb"] < 1e-6


@pytest.mark.parametrize("shape,want", [
    ((256, 256, 256), 3.5),              # bx 8: (8+6)*16 / (8*8)
    ((128, 128, 128), 2.75),             # bx 16
    (None, None)])                       # kernel not traced: nothing
def test_ct_window_ratio_reads_block_stats(monkeypatch, shape, want):
    from benchmark.layer_metrics import ct_window_ratio
    from ramses_tpu.mhd import pallas_ct
    blocks = {} if shape is None else {shape: pallas_ct._block_record(shape)}
    monkeypatch.setattr(pallas_ct, "_BLOCKS", blocks)
    assert ct_window_ratio.read(None, None, {}, {}) == want


def test_mhd_rooflines_read_a_trace_and_nothing_without():
    """The two shares on a made-up reduction: 88 B a cell update at the
    HBM peak over the CT kernel's self time (picked by name among other
    custom calls) and over the busy time; no such op, no updates: None."""
    from benchmark.harness import mhd_work
    from benchmark.layer_metrics import ct_kernel_roofline_pct as kern
    from benchmark.layer_metrics import mhd_roofline_pct as whole
    peak = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    n = 16 * 256 ** 3
    least, bound = mhd_work.least_time_s(n, peak)
    assert bound == "bytes" and least == pytest.approx(n * 88 / 819e9)
    mod = "jit_run_steps(1)"
    red = {"busy_s": 0.6, "op_s": {
        (mod, "%ct_step_tiled.3 custom-call:tpu_custom_call"): 0.5,
        (mod, "%fused_step_padded.3 custom-call:tpu_custom_call"): 9.0,
        (mod, "%fusion.36 fusion"): 0.1}}
    counts = {"cell_updates": n, "kernel_cell_updates": n}
    ctx = {"peak": peak}
    assert kern.read(red, None, counts, ctx) \
        == pytest.approx(100 * least / 0.5)
    assert whole.read(red, None, counts, ctx) \
        == pytest.approx(100 * least / 0.6)
    assert 0 < kern.read(red, None, counts, ctx) < 100
    no_kernel = dict(red, op_s={(mod, "%fusion.36 fusion"): 0.1})
    assert kern.read(no_kernel, None, counts, ctx) is None
    assert kern.read(red, None, {"cell_updates": n}, ctx) is None
    assert whole.read(dict(red, busy_s=0.0), None, counts, ctx) is None
    assert whole.read(red, None, {}, ctx) is None


if __name__ == "__main__":
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mhd-blast3d-uniform-256.json")) as f:
        cfg = json.load(f)
    print("RESULT " + json.dumps(
        {str(n): _slabs_against_whole(cfg, n, "float32") for n in (2, 4)}))
