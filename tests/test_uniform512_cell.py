"""The 512^3 uniform configuration of the benchmark, at CPU sizes.

``benchmark/configs/sedov3d-uniform-512.json`` names a reference that
advances the box in x-slabs (the plain whole-box step needs tens of GB
at 512^3).  Held here: the slab reference IS the whole-box reference
(to the bit), the program (``driver.Simulation`` through the cell's own
entry, at the configuration's ``rehearse`` levels) agrees with it inside
the file's limits on seeded blasts, the bfloat16 control does not, a
state returned unchanged reads ``state_gap`` = 1, and the benchmark's
``sweep_window_ratio`` reads the kernel's ``block_stats()`` records.

The suite's x64 is off around the program and the comparison, as it is
on the chip (``tests/test_mesh_main.py`` says why).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sedov3d-uniform-512.steady"
SEEDS = [4000000051, 7, 2 ** 31 + 11]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sedov3d-uniform-512.json")) as f:
        return json.load(f)


def _blast_snap(n=32, nsteps=16):
    """A one-cell blast in a cold ambient box: the cell's own initial
    condition in kind, as a held slice's input."""
    u = np.zeros((5, n, n, n), np.float32)
    u[0] = 1.0
    u[4] = 1e-5 / 0.4
    u[4, 9, 13, 21] += 1.0 / (0.5 / n) ** 3
    return {"u_in": u, "t_in": 0.0, "tend": 1.0, "nsteps": nsteps,
            "dx": 0.5 / n}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("nslab", [2, 4, 16])
def test_slab_reference_is_the_whole_box_reference(config, nslab, dtype):
    """16 steps at 32^3 in ``nslab`` x-slabs against
    ``uniform_hydro.advance``: to the bit, state and time — a cell's
    update is the same operations on the same numbers whether its
    neighbours came through a roll of the box or of a slab with a
    2-cell margin.  16 slabs of two planes: as much margin as slab."""
    from benchmark.reference import uniform_hydro, uniform_hydro_slabs
    snap = _blast_snap()
    with jax.enable_x64(False):
        whole = uniform_hydro.advance(snap, config, dtype)
        slabs = uniform_hydro_slabs.advance(snap, config, dtype,
                                            nslab=nslab)
    assert slabs["t"] == whole["t"] > 0
    assert np.array_equal(np.asarray(slabs["u"]), np.asarray(whole["u"]))
    assert not np.array_equal(np.asarray(whole["u"]), snap["u_in"])


def test_slab_count_fits_the_state_to_the_chip():
    """512^3 f32: 32 slabs of 16 planes (105 MB with margins); the
    bfloat16 control half as many; a small box still takes two."""
    from benchmark.reference.uniform_hydro_slabs import (SLAB_BYTES,
                                                         slab_count)
    assert slab_count((5, 512, 512, 512), 4) == 32
    assert 5 * (16 + 4) * 512 * 512 * 4 <= SLAB_BYTES
    assert slab_count((5, 512, 512, 512), 2) == 16
    assert slab_count((5, 32, 32, 32), 4) == 2


@pytest.fixture(scope="module", params=SEEDS, ids=[str(s) for s in SEEDS])
def held_slice(request, config):
    """Two 16-step slices of the seeded blast through the cell's own
    entry at the ``rehearse`` levels, the second held."""
    from benchmark import run
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "steady.json")) as f:
        traffic = json.load(f)
    with jax.enable_x64(False):
        entry, _ = run.set_up(config, traffic, request.param, rehearse=True)
        row = entry.run_slice(hold=True)
        entry.sync()
        snap = entry.snapshot()
    assert row["done"] == traffic["slice_steps"] == snap["nsteps"]
    return snap


CONTROLS = [("float32 program", None, True),
            ("bfloat16 in the program's place", "bfloat16", False)]


@pytest.mark.parametrize("label,control,want", CONTROLS,
                         ids=[c[0] for c in CONTROLS])
def test_program_agrees_with_the_slab_reference(config, held_slice, label,
                                                control, want):
    """The file's own limits (each set between the chip's sound readings
    and the bfloat16 control's, PERF.md section 2) with its rehearsal's
    one change (``mass_drift_per_step`` 1e-8: on a rehearsal's 32^3
    cells a sound run's drift a step reads up to 3.7e-10, the control's
    from 6.9e-6; reason in the file's ``rehearse.why_limits``): the
    program passes every one, the control fails at least one."""
    from benchmark.harness import check
    config = dict(config, limits={**config["limits"],
                                  **config["rehearse"]["limits"]})
    with jax.enable_x64(False):
        compared, ok = check.compare(config, held_slice, control)
    over = {k: v for k, (v, lim) in compared.items() if not v <= lim}
    assert ok is want, json.dumps(compared)
    assert bool(over) is not want, over


def test_unchanged_state_reads_one(config, held_slice):
    from benchmark.harness import check
    snap = dict(held_slice, u_out=held_slice["u_in"])
    with jax.enable_x64(False):
        compared, ok = check.compare(config, snap)
    assert not ok
    assert compared["state_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("shape,masked,want", [
    ((256, 256, 256), False, 1.5625),    # (16, 32): (16+4)*(32+8) / (16*32)
    ((512, 512, 512), False, 1.875),     # (8, 32): (8+4)*(32+8) / (8*32)
    ((128, 128, 128), True, 1.875),      # masked (8, 32)
    (None, False, None)])                # no kernel traced: nothing
def test_sweep_window_ratio_reads_block_stats(monkeypatch, shape, masked,
                                              want):
    """The reader gives cells loaded per cell written from the records
    the kernel leaves at trace time, and nothing when there are
    none."""
    from benchmark.layer_metrics import sweep_window_ratio
    from ramses_tpu.hydro import pallas_muscl as pk
    blocks = {}
    if shape is not None:
        blocks[(shape, masked)] = pk._block_record(shape, masked)
    monkeypatch.setattr(pk, "_BLOCKS", blocks)
    assert pk.block_stats() == list(blocks.values())
    got = sweep_window_ratio.read(None, None, {}, {})
    assert got == want
    if shape == (512, 512, 512):          # three signatures: cell-weighted
        blocks[((128,) * 3, True)] = pk._block_record((128,) * 3, True)
        blocks[((256,) * 3, False)] = pk._block_record((256,) * 3, False)
        want2 = (1.875 * 512 ** 3 + 1.875 * 128 ** 3 + 1.5625 * 256 ** 3) / (
            512 ** 3 + 128 ** 3 + 256 ** 3)
        assert sweep_window_ratio.read(None, None, {}, {}) \
            == pytest.approx(want2)


def test_kernel_line_and_run_header(tmp_path):
    """``[kernel]`` names each traced signature's pick and ratio; a run
    with telemetry on carries ``sweep_block`` in its ``run_header``
    (on the CPU the run itself traces no kernel: the XLA formulation)."""
    from ramses_tpu.config import load_params
    from ramses_tpu.driver import Simulation
    from ramses_tpu.hydro import pallas_muscl as pk
    from ramses_tpu.telemetry import screen
    line = screen.kernel_line([pk._block_record((512,) * 3, False)])
    assert line == ("[kernel] pallas_muscl: 512x512x512 bx=8 by=32 "
                    "window/written=1.88")
    assert "not traced" in screen.kernel_line([])
    params = load_params(os.path.join(ROOT, "benchmark", "configs",
                                      "sedov3d-uniform-512.nml"), ndim=3)
    params.amr.levelmin = params.amr.levelmax = 4
    params.run.nstepmax = 2
    params.output.telemetry = str(tmp_path / "run.jsonl")
    sim = Simulation(params, dtype=jnp.float32)
    sim.evolve()
    sim.telemetry.close(sim)
    with open(params.output.telemetry) as f:
        header = json.loads(f.readline())
    assert header["kind"] == "run_header"
    assert header["run_info"]["sweep_block"] == pk.block_stats()
