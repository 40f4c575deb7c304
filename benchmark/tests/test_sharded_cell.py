"""Checks of the four-chip cell's own files: CPU, four forced host
devices, tiny levels, by hand like the rest of ``benchmark/tests``:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Nothing here is a speed.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

from ramses_tpu.platform import force_cpu_mesh  # noqa: E402

force_cpu_mesh(4)           # before any backend exists in this process

SHARDED = "sedov3d-amr-7to9-sharded.regrid-every-step"
KERNEL = ('%fused_step_shard.3 = f32[5,64,64,128]{3,2,1,0} custom-call('
          'f32[5,68,72,128]{3,2,1,0} %p), '
          'custom_call_target="tpu_custom_call"')
DMA = ('%halo_dma_exchange.7 = (f32[5,128,2,64]{3,2,1,0}, '
       'f32[5,128,2,64]{3,2,1,0}) custom-call(s32[2]{0} %d, %a, %b), '
       'custom_call_target="tpu_custom_call"')
ALLRED = '%all-reduce.2 = f32[]{:T(128)} all-reduce(f32[] %x), to_apply=%m'
PERM = ('%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) '
        'collective-permute-start(f32[8]{0} %y)')
FUSION = '%fusion.5 = f32[64]{0} fusion(f32[64]{0} %z), kind=kLoop'


# ----------------------------------------------- readers, two device planes
@pytest.fixture()
def reduced(monkeypatch):
    """The real reduction over a hand-made trace of TWO device planes:
    one coarse step (a per-shard kernel call of 0.3 s, a halo DMA call of
    50 ms, an all-reduce of 20 ms, a collective-permute start of 10 ms,
    a fusion of 0.2 s) and one flags program (a halo DMA call of 50 ms),
    the second plane 0.1 s behind the first, in a 2 s window."""
    from benchmark.harness import trace_reduce

    def plane(t):
        return {
            trace_reduce.MODULE_LINE: [
                ("jit__fused_coarse_step(11)", t, t + 1.0),
                ("jit__fused_flags(12)", t + 1.2, t + 1.4)],
            trace_reduce.OP_LINE: [
                (KERNEL, t + 0.1, t + 0.4), (DMA, t + 0.4, t + 0.45),
                (ALLRED, t + 0.5, t + 0.52), (PERM, t + 0.6, t + 0.61),
                (FUSION, t + 0.7, t + 0.9),
                (DMA.replace(".7", ".9"), t + 1.2, t + 1.25)]}

    monkeypatch.setattr(
        trace_reduce, "read_planes",
        lambda path: ({"/device:TPU:0": plane(0.0),
                       "/device:TPU:1": plane(0.1)},
                      {trace_reduce.WINDOW_SPAN: [(0.0, 2.0)]}))
    return trace_reduce.reduce_trace("hand-made")


def test_mesh_readers_on_two_planes(reduced, monkeypatch):
    import run
    from benchmark.layer_metrics import halo_dma_roofline_pct
    bench, cell, config, traffic, peaks = run.load_cell(SHARDED)
    peak = peaks["TPU v5 lite"]
    assert reduced["n_devices"] == 2
    assert reduced["busy_s"] == pytest.approx(0.63)
    n7 = 128 ** 3
    counts = {"steps_done": 1, "regrids": 1, "cell_updates": 3 * n7,
              "kernel_cell_updates": n7}
    ctx = {"config": config, "traffic": traffic, "peak": peak,
           "window_compile_s": 0.0, "cell": SHARDED}
    monkeypatch.setattr(halo_dma_roofline_pct, "ici_peak", lambda: 200e9)
    new = ["mesh_roofline_pct", "slab_kernel_roofline_pct",
           "halo_exchange_ms", "halo_dma_roofline_pct"]
    listed = {run.base_name(m["name"]): m
              for m in run.metrics_of(bench, "per_layer", SHARDED)}
    assert set(new) <= set(listed)
    assert all(listed[n]["workloads"] == [SHARDED] for n in new)
    assert not {"hydro_roofline_pct", "sweep_kernel_roofline_pct"} \
        & set(listed)
    got = {n: run.layer_reader(n).read(reduced, reduced["spans"], counts,
                                       ctx) for n in new}
    least = 40 / 819e9                   # seconds a cell update, one chip
    assert got["mesh_roofline_pct"] == pytest.approx(
        100 * 3 * n7 * least / 2 / 0.63)
    assert got["slab_kernel_roofline_pct"] == pytest.approx(
        100 * n7 * least / (2 * 0.3))
    # DMA 2 x 50 ms + all-reduce 20 + permute 10, a plane and a step
    assert got["halo_exchange_ms"] == pytest.approx(130.0)
    sent = 1622016 + 676000              # halo_work, by hand: its docstring
    assert got["halo_dma_roofline_pct"] == pytest.approx(
        100 * sent / 200e9 / 0.1)
    assert all(0 < got[n] < 100 for n in new if n.endswith("_pct"))
    # the same quantity the accepted readers see: kernel_s lumps the two
    # kinds of custom call, which is why the new cell does not list them
    step = [m for m in reduced["kernel_s"] if "coarse_step" in m][0]
    assert reduced["kernel_s"][step] == pytest.approx(2 * 0.35)


def test_mesh_readers_give_nothing_where_there_is_nothing(reduced):
    """One plane, no named kernel, no collective, no ``slab`` group: each
    reader returns None, never 0 and never an error (what the parent of
    the PR that named the kernels reads)."""
    import run
    _, _, config, traffic, peaks = run.load_cell(SHARDED)
    bare = dict(reduced, n_devices=1, op_s={
        k: v for k, v in reduced["op_s"].items() if "fusion" in k[1]})
    counts = {"steps_done": 1, "regrids": 1, "cell_updates": 10,
              "kernel_cell_updates": 10}
    ctx = {"config": config, "traffic": traffic,
           "peak": peaks["TPU v5 lite"], "window_compile_s": 0.0,
           "cell": SHARDED}
    for n in ("mesh_roofline_pct", "slab_kernel_roofline_pct",
              "halo_exchange_ms", "halo_dma_roofline_pct"):
        assert run.layer_reader(n).read(bare, {}, counts, ctx) is None, n
    plain = dict(ctx, config={k: v for k, v in config.items()
                              if k != "slab"})
    assert run.layer_reader("halo_dma_roofline_pct").read(
        reduced, {}, counts, plain) is None
    none = dict(counts, steps_done=0, cell_updates=0, kernel_cell_updates=0)
    for n in ("mesh_roofline_pct", "slab_kernel_roofline_pct",
              "halo_exchange_ms", "halo_dma_roofline_pct"):
        assert run.layer_reader(n).read(reduced, {}, none, ctx) is None, n


def test_ici_peak_table_names_the_chip_and_its_source():
    table = json.load(open(os.path.join(BENCH, "peaks_ici.json")))
    hbm = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert set(table) == set(hbm)
    for kind, row in table.items():
        assert row["ici_bytes_per_s"] == 1600e9 / 8 and row["source"]


# ------------------------------------------------ the byte count, by hand
def test_halo_work_by_hand():
    from benchmark.harness import halo_work as hw
    slab = json.load(open(os.path.join(
        BENCH, "configs", "sedov3d-amr-7to9-sharded.json")))["slab"]
    assert hw.local_box(7, [1, 2, 2]) == (128, 64, 64)
    # kernel on: x bare; y slabs 2 x 128.2.64, z slabs 2 x 128.68.2 cells,
    # five variables and the mask, four bytes
    assert hw.sweep_traffic(slab) == {
        "bytes": 2 * (128 * 2 * 64 + 128 * 68 * 2) * 6 * 4, "slabs": 8}
    # kernel off: x wraps to 132 first
    assert hw.sweep_traffic(slab, kernel=False)["bytes"] \
        == 2 * (132 * 2 * 64 + 132 * 68 * 2) * 6 * 4
    # flags: one ghost, x wraps to 130, five variables
    assert hw.flags_traffic(slab) == {
        "bytes": 2 * (130 * 64 + 130 * 66) * 5 * 4, "slabs": 4}
    assert hw.bytes_sent(slab, 3, 2) == 3 * 1622016 + 2 * 676000
    # uncut everywhere: nothing is sent
    assert hw.extend_slabs((8, 8, 8), (1, 1, 1), 2) == []


def test_halo_work_against_the_traced_kernel_path(monkeypatch):
    """``dma_halo.traffic_snapshot()`` of ONE level-7 sweep traced at the
    cell's real shapes with the per-shard kernel's gate answering as on
    the chip (shapes only, nothing runs), and of one flags pass."""
    import jax
    import jax.numpy as jnp
    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import load_params
    from ramses_tpu.hydro import pallas_muscl as pk
    from ramses_tpu.parallel import dense_slab, dma_halo
    from ramses_tpu.parallel.mesh import oct_mesh
    from benchmark.harness import halo_work as hw
    config = json.load(open(os.path.join(
        BENCH, "configs", "sedov3d-amr-7to9-sharded.json")))
    slab = config["slab"]
    params = load_params(os.path.join(BENCH, "configs", config["namelist"]),
                         ndim=3)
    cfg = AmrSim._make_cfg(params)
    sl = dense_slab.build_slab_spec(
        oct_mesh(jax.devices()[:4]), 7, 3, (128,) * 3, 128 ** 3,
        ((0, 0),) * 3, halo_backend="ppermute")
    assert list(sl.grid) == slab["grid"] and sl.loc == (128, 64, 64)
    u = jax.ShapeDtypeStruct((128 ** 3, 5), jnp.float32)
    ok = jax.ShapeDtypeStruct((128 ** 3,), jnp.bool_)
    dt = jax.ShapeDtypeStruct((), jnp.float32)
    for kernel in (True, False):
        with monkeypatch.context() as mp:
            spec = sl
            if kernel:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                assert list(pk.shard_axes(
                    cfg, sl.loc, (False, True, True), jnp.float32)) \
                    == slab["kernel_axes"]
                spec = sl._replace(backend="dma")     # as on the chip
            dma_halo.reset_traffic()
            jax.eval_shape(
                lambda a, b, c: dense_slab.dense_sweep_slab(
                    a, b, c, 1.0 / 128, spec, cfg), u, ok, dt)
        got = dma_halo.traffic_snapshot()
        want = hw.sweep_traffic(slab, kernel)
        assert (got["halo_bytes"], got["halo_exchanges"]) \
            == (want["bytes"], want["slabs"]), kernel
    dma_halo.reset_traffic()
    jax.eval_shape(lambda a: dense_slab.dense_flags_slab(
        a, sl, lambda ext: jnp.ones(ext.shape[1:], jnp.bool_), 8), u)
    got = dma_halo.traffic_snapshot()
    want = hw.flags_traffic(slab)
    assert (got["halo_bytes"], got["halo_exchanges"]) \
        == (want["bytes"], want["slabs"])


# ----------------------------------------------------- the entry, four devices
@pytest.fixture(scope="module")
def sharded_entry():
    import run
    _, _, config, traffic, _ = run.load_cell(SHARDED)
    config = dict(config, rehearse=dict(config["rehearse"], levelmin=4,
                                        levelmax=6, seed_level=4))
    entry, _ = run.set_up(config, traffic, 4000000044, rehearse=True)
    return entry


def test_entry_builds_the_sharded_class_and_counts_its_halos(sharded_entry):
    e = sharded_entry
    assert type(e.sim).__name__ == "ShardedAmrSim" and e.sim.ndev == 4
    assert e.halo_count_ok                  # traffic_snapshot == halo_work
    forms = e.formulations(count_calls=True)
    assert "slab-sharded sweep (grid (1, 2, 2)" in forms[0][1]
    assert all("XLA tiles" in text for _, text, _ in forms[1:-1])
    assert forms[-1][0] == "coarse-step program" and forms[-1][2]
    # on the CPU no level is on a Pallas kernel: nothing is counted as swept
    # by one
    assert e.kernel_levels() == set()


def test_rewind_keeps_every_levels_sharding(sharded_entry, monkeypatch):
    """The marked state goes back over the mesh; a bare ``jnp.asarray``
    (the one-chip entry's way: one device) is caught at once."""
    import jax.numpy as jnp
    e = sharded_entry
    e.rewind()
    for l in e.sim.levels():
        assert len(e.sim.u[l].sharding.device_set) == 4
        assert e.sim.u[l].sharding == e.sim._row2_sharding
    e.assert_spans()
    # on the class: rewind drops what the mark did not see on the instance
    monkeypatch.setattr(type(e.sim), "_place",
                        lambda self, a, kind: jnp.asarray(a))
    with pytest.raises(AssertionError, match="spans 1 device"):
        e.rewind()
    monkeypatch.undo()
    e.rewind()


def test_sharded_laps_repeat_the_first_bit_for_bit(sharded_entry):
    e = sharded_entry
    e.rewind()
    e.first_lap.clear()
    n0 = e.sim.nstep
    rows = [e.run_slice() for _ in range(9)]
    assert [r["laps_off"] for r in rows] == [0] * 9
    assert e.sim.nstep == n0 + 2 and len(e.first_lap) == e.lap_steps == 7
    assert rows[7]["cell_updates"] == rows[0]["cell_updates"]
    assert rows[8]["sim_time"] == rows[1]["sim_time"]


def test_a_traced_window_always_has_a_held_slice(sharded_entry, tmp_path):
    """Under a profiler session the first slice after the mark is held
    although the window did not ask; the slice the window asks for later
    replaces it; without a session nothing is held unasked."""
    import jax
    e = sharded_entry
    e.rewind()
    e.pre = e.mid = e.out = None
    e.run_slice()
    assert e.out is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        e.run_slice()
        first = e.out["nstep"]
        assert e.mid is not None and e.pre is not None
        e.run_slice()
        assert e.out["nstep"] == first          # only the first
        e.run_slice(hold=True)
        assert e.out["nstep"] == first + 2      # the window's own choice
    finally:
        jax.profiler.stop_trace()
    snap = e.snapshot()
    assert snap["nsteps"] == 1 and "pre" in snap
    e.pre = e.mid = e.out = None


@pytest.mark.parametrize("control,want", [(None, True), ("bfloat16", False)],
                         ids=["sound", "control"])
def test_sharded_correct_through_the_harness(sharded_entry, control, want):
    from test_benchmark import _judge
    result = _judge(SHARDED, sharded_entry, control)
    assert result["correct"] is want, result["compared"]
    assert result["failed"] == 0
