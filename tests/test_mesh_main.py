"""The command line's choice of AMR class on a host with several devices
(``ramses_tpu.__main__.build_amr_sim``), the Pallas gates that ask what
the SIMULATION spans (not the host), and the four-device run against the
benchmark's plain reference and against one device.

CPU, forced host devices (``conftest.py``: 8), tiny levels.  Forced CPU
devices are one host's cores: with no devices named the command line
builds ``AmrSim`` here, whatever their number.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ramses_tpu import __main__ as cli
from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.config import load_params
from ramses_tpu.parallel.amr_sharded import ShardedAmrSim

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NML = os.path.join(ROOT, "namelists", "sedov3d_amr.nml")
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "sedov3d-amr-7to9-sharded.json")
SEED = 4000000043          # the blast's place (benchmark/harness/seed.py)


def _params(lmin=4, lmax=5):
    p = load_params(NML, ndim=3)
    p.amr.levelmin, p.amr.levelmax = lmin, lmax
    p.run.nstepmax = 0
    return p


# ------------------------------------------------- (a) which class is built
@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    sim = AmrSim(_params(), dtype=jnp.float32)
    sim.evolve(1e9, nstepmax=2)
    return sim.dump(1, str(tmp_path_factory.mktemp("ckpt"))), sim


def _accelerator_with_four(monkeypatch):
    """The default backend says it is the accelerator and shows four
    devices (the first four forced host devices stand for the chips)."""
    four = jax.devices()[:4]
    monkeypatch.setattr(cli, "_on_accelerator", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a: four)


WHO = [
    ("four named", lambda mp: jax.devices()[:4], ShardedAmrSim, 4),
    ("one named", lambda mp: jax.devices()[:1], AmrSim, 1),
    ("none named, CPU backend", lambda mp: None, AmrSim, 1),
    ("none named, accelerator with four",
     lambda mp: _accelerator_with_four(mp), ShardedAmrSim, 4),
]


@pytest.mark.parametrize("start", ["fresh", "checkpoint"])
@pytest.mark.parametrize("label,devices,cls,ndev", WHO,
                         ids=[w[0] for w in WHO])
def test_build_amr_sim_picks_the_class(monkeypatch, checkpoint, label,
                                       devices, cls, ndev, start):
    said = []
    outdir, src = checkpoint
    sim = cli.build_amr_sim(
        _params(), jnp.float32, devices=devices(monkeypatch),
        restart=outdir if start == "checkpoint" else None, log=said.append)
    assert type(sim) is cls and sim.ndev == ndev
    assert sim._fused_spec().ndev == ndev
    for l in sim.levels():
        assert len(sim.u[l].sharding.device_set) == ndev, (l, start)
    # one line: the class, the devices, each level's formulation
    assert len(said) == 1 and said[0].startswith(
        f"amr: {cls.__name__} over {ndev} device(s)")
    assert all(f"level {l}: " in said[0] for l in sim.levels())
    assert ("slab-sharded" in said[0]) is (ndev > 1)
    if start == "checkpoint":
        assert sim.nstep == src.nstep and sim.t == pytest.approx(src.t)
        assert [sim.tree.noct(l) for l in sim.levels()] == \
            [src.tree.noct(l) for l in src.levels()]


def test_run_on_forced_cpu_devices_builds_amr_sim(tmp_path, monkeypatch,
                                                  capsys):
    """``python -m ramses_tpu`` itself, 8 forced CPU devices visible."""
    assert jax.device_count() >= 4 and jax.default_backend() == "cpu"
    nml = tmp_path / "amr.nml"
    txt = open(NML).read().replace("levelmin=7", "levelmin=4") \
        .replace("levelmax=9", "levelmax=5").replace("nstepmax=12",
                                                     "nstepmax=1")
    assert "levelmin=4" in txt and "nstepmax=1" in txt
    nml.write_text(txt)
    monkeypatch.chdir(tmp_path)
    sim = cli.run(cli.build_parser().parse_args([str(nml), "--ndim", "3"]))
    assert type(sim) is AmrSim and sim.nstep == 1
    assert "amr: AmrSim over 1 device(s) [cpu]" in capsys.readouterr().out


# ------------------------------------- B3: the gates ask the simulation
def test_pallas_gates_ask_what_the_simulation_spans(monkeypatch):
    """A one-device simulation on a host with 8 devices keeps its
    kernels; one that spans four does not.  Both sides, every gate."""
    from ramses_tpu.grid.uniform import _pallas_ok
    from ramses_tpu.hydro import pallas_muscl as pk
    from ramses_tpu.hydro import pallas_oct as po
    from ramses_tpu.parallel.sharded import ShardedSim
    assert jax.device_count() == 8
    cfg = AmrSim._make_cfg(_params())
    f32 = jnp.float32
    monkeypatch.setattr(po, "FORCE_INTERPRET", True)
    assert po.available(cfg, 128, f32) and po.available(cfg, 128, f32, 1)
    assert not po.available(cfg, 128, f32, 4)
    assert po.tile_available(cfg, 64, f32, 2)
    # the dense kernel has no interpret hook: ask its gate as the chip would
    sim = AmrSim(_params(), dtype=f32)
    faces = sim.bspec.faces
    uni = load_params(os.path.join(ROOT, "namelists", "sedov3d.nml"), ndim=3)
    uni.amr.levelmin = uni.amr.levelmax = 4
    sh = ShardedSim(uni, devices=jax.devices()[:4], dtype=f32)
    assert sh.grid.ndev == 4 and sh.inner.grid.ndev == 4
    one = dataclasses.replace(sh.grid, shape=(128,) * 3, ndev=1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk.kernel_available(cfg, (128,) * 3, faces, f32)
    assert pk.kernel_available(cfg, (128,) * 3, faces, f32, 1)
    assert not pk.kernel_available(cfg, (128,) * 3, faces, f32, 4)
    assert _pallas_ok(one, f32)
    assert not _pallas_ok(dataclasses.replace(one, ndev=4), f32)
    monkeypatch.undo()
    # what each class tells its step programs, and what they then run
    monkeypatch.setattr(po, "FORCE_INTERPRET", True)
    assert sim._fused_spec().ndev == 1 and sim._fused_spec().pallas_tiles
    forms = sim.level_formulations()
    assert [k for l, _, k in forms if l > sim.lmin] == [True], forms
    sh4 = ShardedAmrSim(_params(), devices=jax.devices()[:4], dtype=f32)
    spec = sh4._fused_spec()
    assert spec.ndev == 4 and not spec.pallas_tiles
    forms = sh4.level_formulations()
    assert "slab-sharded" in forms[0][1] and "XLA tiles" in forms[1][1]
    assert not any(k for _, _, k in forms)


# --------------- (b), (c): four devices, the reference, and one device
@pytest.fixture(scope="module")
def four_device_run():
    """Seven coarse steps of the seeded blast on four devices through the
    cell's own entry (a regrid before each, as ``nremap=0`` says), the
    last one held for the comparison.  The suite's x64 is off here and
    in the tests that read this run, as it is on the chip: with it on,
    weakly typed scalars of the Courant step are computed in float64
    (``(sqrt(1 + 2e-4 cf) - 1) / 1e-4`` loses 1e-4 of itself in float32)
    and program and reference part by 9e-5 in ``dt``, over the cell's
    2e-5."""
    with jax.enable_x64(False):
        return _four_device_run()


def _four_device_run():
    from benchmark import run
    from benchmark.entries import sharded_amr_sim
    config = json.load(open(CONFIG))
    config["rehearse"] = dict(config["rehearse"], levelmin=4, levelmax=6,
                              seed_level=4)
    traffic = {"slice_steps": 1}
    params = run.build_params(config, traffic, SEED, rehearse=True)
    entry = sharded_amr_sim.Entry(config, traffic, params)
    assert type(entry.sim) is ShardedAmrSim and entry.sim.ndev == 4
    assert entry.sim.regrid_interval == 1
    rows = [entry.run_slice(hold=(i == 6)) for i in range(7)]
    assert [r["done"] for r in rows] == [1] * 7
    assert [r["regrids"] for r in rows] == [1] * 7
    return config, params, entry


CONTROLS = [("float32 program", None, True),
            ("bfloat16 in the program's place", "bfloat16", False)]


@pytest.mark.parametrize("label,control,want", CONTROLS,
                         ids=[c[0] for c in CONTROLS])
def test_four_devices_agree_with_the_plain_reference(four_device_run,
                                                     label, control, want):
    """The configuration's own limits, with its rehearsal's one change
    (``mass_drift`` 1e-7: a sound run's drift on a rehearsal's few cells
    reads up to 1.1e-8, the control's from 9.6e-7; reason in the
    configuration's ``rehearse.why_limits``).  Each limit lies between
    the f32 program's reading and the bfloat16 control's (PERF.md section
    2), so the control has to fail at least one."""
    from benchmark.harness import check
    config, _, entry = four_device_run
    config = dict(config, limits={**config["limits"],
                                  **config["rehearse"]["limits"]})
    snap = entry.snapshot()
    assert "pre" in snap and snap["nsteps"] == 1
    with jax.enable_x64(False):
        compared, ok = check.compare(config, snap, control)
    over = {k: v for k, (v, lim) in compared.items() if not v <= lim}
    assert ok is want, json.dumps(compared)
    assert bool(over) is not want, over


def test_four_devices_against_one(four_device_run):
    """Same seed through the same function over one named device
    (``AmrSim``): the same trees, the state within f32 rounding.  The two
    differ in reduction order only (the partitioner's fusions, the
    slab-local sweep); L1(diff)/L1(ref) over seven steps stays under
    1e-5 (``chip_smoke.py`` SHARD_L1_RTOL; a wrong halo or a dropped
    shard is O(1))."""
    _, params, entry = four_device_run
    four = entry.sim
    with jax.enable_x64(False):
        one = cli.build_amr_sim(params, jnp.float32,
                                devices=jax.devices()[:1], log=None)
        assert type(one) is AmrSim
        one.evolve(1e9, nstepmax=four.nstep)
    assert one.nstep == four.nstep == 7
    assert one.t == pytest.approx(four.t, rel=1e-6)
    for l in one.levels():
        assert np.array_equal(one.tree.cell_coords(l),
                              four.tree.cell_coords(l)), l
        a = np.asarray(four.tree_order_cells(four.u[l], l), np.float64)
        b = np.asarray(one.tree_order_cells(one.u[l], l), np.float64)
        n = one.tree.noct(l) * 8
        d = np.abs(a[:n] - b[:n]).sum() / np.abs(b[:n]).sum()
        assert d < 1e-5, (l, d)
