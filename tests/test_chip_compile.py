"""Compile the Sedov main-path kernels for a DESCRIBED TPU v5e.

The sandbox has no chip, but the chip's compiler is installed and
compiles for a described topology (``v5e:2x2``).  These cases hold the
three Pallas hydro kernels of the default main path to what Mosaic
accepts, at the real widths the Sedov runs use — every shape the f32
hydro gates (``pallas_muscl.kernel_available``, ``pallas_oct.available``
/ ``tile_available``) admit must have a passing case here, or the gate
must not admit it.  A compile is not a run: nothing here says anything
about results or speed.

This is the only file that describes the chip: only one process may
load the TPU library, so the topology is described inside a
module-scoped fixture (never at import), the compiles run in the
test's own process, and the persistent compile cache is off around
them (a described-device entry cannot be read back without a chip).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ramses_tpu.config import load_params
from ramses_tpu.hydro import pallas_muscl as pk
from ramses_tpu.hydro import pallas_oct as po
from ramses_tpu.hydro.core import HydroStatic

NML = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "namelists", "sedov3d.nml")
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return HydroStatic.from_params(load_params(NML, ndim=3))


@pytest.fixture()
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, *shapes):
    """Lower+compile ``fn`` for the described chip with x64 off (the
    suite turns it on; index maps must stay i32 for Mosaic)."""
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _fits_one_chip(compiled):
    """What the program keeps resident in HBM (args + outputs + temps)
    fits one chip; the kernels ask for ``pk.VMEM_LIMIT_BYTES`` of
    scoped VMEM, which Mosaic has already held them to by compiling."""
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < 16 * 2 ** 30


PERIODIC = ((0, 0),) * 3


# lane extent -> (the plain signature's pick, the masked one's)
PICKS = {128: ((32, 32), (8, 32)), 256: ((16, 32), (4, 16)),
         384: ((8, 32), (4, 8)), 512: ((8, 32), (4, 8))}


@pytest.mark.parametrize("n", [128, 256, 384, 512, 640, 768, 1024, 192])
def test_block_rule_pinned(cfg, n):
    """The block rule's picks, pinned for both signatures: the plain
    one's is the fastest tile of the by-hand grid on the chip, the
    masked one's the fastest whose window stays at the 256 KiB a
    variable its code size in HBM allows (PERF.md section 6, PR 35), so
    a change to a budget or the cost that moves one re-compiles an
    existing benchmark cell's program and is a perf issue with a claim,
    not a side effect.
    What has no compile case below the gate declines.  The mesh's
    level-7 slabs relabel an uncut 128-cell axis to the lane: they take
    the masked 128 pick (``by`` divides their 64 rows)."""
    plain, masked = PICKS.get(n, ((None, None),) * 2)
    assert pk._pick_block((n, n, n)) == plain
    assert pk._pick_block((n, n, n), masked=True) == masked
    assert pk.supports(cfg, (n, n, n), PERIODIC, F32) is (n in PICKS)
    if n == 128:
        assert pk._pick_block((64, 64, 128), masked=True) == masked
        assert pk._pick_block((128, 64, 128), masked=True) == masked


@pytest.mark.parametrize("n,mask,want_flux", [
    (256, False, False),      # uniform sedov3d.nml step (courant fused)
    (128, True, False),       # AMR complete base level 7
    (128, True, True),        # ... with the MC-tracer flux capture
    (256, True, False),       # complete level 8
    (384, False, False),      # the third lane extent the budget admits
    (512, False, False),      # uniform levelmin=9 (the 512^3 cell)
    (512, True, False),       # complete level 9
    (512, True, True),        # ... with the flux capture
])
def test_fused_step_padded_compiles(one_chip, cfg, no_cache, n, mask,
                                    want_flux):
    shape = (n, n, n)
    assert pk.supports(cfg, shape, PERIODIC, F32)
    bx, by = PICKS[n][mask]
    assert pk._pick_block(shape, mask) == (bx, by)
    pad = (n + 2 * pk.NG, n + pk._wy(by) - by, n)
    assert pad == (n + 4, n + 8, n)       # whatever the pick
    u = jax.ShapeDtypeStruct((5,) + pad, F32, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    dx = 0.5 / n
    if mask:
        ok = jax.ShapeDtypeStruct(pad, F32, sharding=one_chip)
        compiled = _compile(lambda u, ok, dt: pk.fused_step_padded(
            u, dt, cfg, dx, shape, ok_pad=ok, want_flux=want_flux),
            u, ok, dt)
    else:
        compiled = _compile(lambda u, dt: pk.fused_step_padded(
            u, dt, cfg, dx, shape, courant=True), u, dt)
    _fits_one_chip(compiled)
    rec = [b for b in pk.block_stats()
           if b["shape"] == list(shape) and b["masked"] is mask]
    assert rec == [{"shape": list(shape), "masked": mask, "bx": bx, "by": by,
                    "window_cells": (bx + 4) * (by + 8) * n,
                    "written_cells": bx * by * n}]


@pytest.mark.parametrize("loc", [(128, 64, 64), (128, 128, 64)])
def test_fused_step_shard_compiles(one_chip, cfg, no_cache, loc):
    """The mesh's level-7 slabs (the four-chip cell: 128^3 cut (1, 2, 2);
    cut once: (1, 1, 2)): ``fused_step_shard`` relabels the uncut
    128-cell axis to the lane and pads the pick's junk rows itself, so
    the per-shard call compiles at the masked 128 pick too."""
    axes = (1, 2, 0)
    rel = tuple(loc[a] for a in axes)
    bx, by = PICKS[128][True]
    assert pk._pick_block(rel, True) == (bx, by)
    ext = (loc[0], loc[1] + 2 * pk.NG, loc[2] + 2 * pk.NG)
    up = jax.ShapeDtypeStruct((5,) + ext, F32, sharding=one_chip)
    okp = jax.ShapeDtypeStruct(ext, F32, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = _compile(lambda up, okp, dt: pk.fused_step_shard(
        up, okp, dt, cfg, 1.0 / 128, loc, axes, want_flux=True),
        up, okp, dt)
    assert pk.SHARD_KERNEL_NAME in compiled.as_text()
    _fits_one_chip(compiled)


def _computation(hlo: str, name: str) -> str:
    """The text of one named computation of a compiled module."""
    m = re.search(r"^(?:ENTRY )?%s \(.*?^}" % re.escape(name), hlo,
                  re.M | re.S)
    assert m, name
    return m.group(0)


def _state_ops(comp: str, n: int) -> list:
    """Opcodes of a computation's instructions whose result is ONE
    array of the state's shape ``f32[5,n,n,n]`` (the kernel's result is
    a tuple with the Courant scalar: it is not among them)."""
    return re.findall(r"^\s*(?:ROOT )?%%\S+ = f32\[5,%d,%d,%d\]\S* ([\w-]+)\("
                      % (n, n, n), comp, re.M)


@pytest.mark.parametrize("n,trace", [(256, False), (256, True),
                                     (512, False)])
def test_run_steps_loop_body_is_pad_and_kernel(one_chip, cfg, no_cache, n,
                                               trace):
    """The whole 16-step program of a uniform run (what
    ``driver.Simulation.evolve`` dispatches on the chip; both uniform
    benchmark cells): ONE kernel, and inside the while body no pass
    over the state but ``pad_xy`` and that kernel — no ``select``
    masking a step out, no ``copy`` of the carry (the kernel's output
    IS the next carry).  At entry the not-donated argument is copied
    into the carry once.  The temporaries are then the padded state and
    little else, and state + output + temporaries fit one chip."""
    from ramses_tpu.grid import boundary as bmod
    from ramses_tpu.grid import uniform
    grid = uniform.UniformGrid(cfg=cfg, shape=(n, n, n), dx=0.5 / n,
                               bc=bmod.BoundarySpec.periodic(3))
    u = jax.ShapeDtypeStruct((5, n, n, n), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = _compile(
        lambda u, t, tend: uniform._run_steps_pallas(grid, u, t, tend, 16,
                                                     trace=trace),
        u, t, t)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    entry = _computation(hlo, re.search(r"^ENTRY (%\S+)", hlo, re.M)[1])
    whiles = re.findall(r" while\(.*?body=(%[\w.-]+)", entry)
    assert len(whiles) == 1
    body = _computation(hlo, whiles[0])
    assert "tpu_custom_call" in body
    assert set(_state_ops(body, n)) <= {"get-tuple-element"}
    entry_ops = _state_ops(entry, n)
    assert entry_ops.count("copy") <= 1
    assert set(entry_ops) <= {"parameter", "copy", "get-tuple-element"}
    by = pk._pick_block((n, n, n))[1]
    padded = 5 * (n + 2 * pk.NG) * (n + pk._wy(by) - by) * n * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.1 * padded + 16 * 2 ** 20
    _fits_one_chip(compiled)


@pytest.mark.parametrize("noct", [128, 256, 512, 4096])
def test_oct_sweep_compiles(one_chip, cfg, no_cache, noct):
    """128/256/512 are the three lane tiles ``pallas_oct._tile`` picks;
    4096 is many grid steps of the widest."""
    assert po._tile(noct) == min(noct, 512)
    u = jax.ShapeDtypeStruct((5, 6, 6, 6, noct), F32, sharding=one_chip)
    ok = jax.ShapeDtypeStruct((6, 6, 6, noct), F32, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    _compile(lambda u, ok, dt: po.oct_sweep(u, ok, dt, cfg, 1.0 / 512),
             u, ok, dt)


@pytest.mark.parametrize("ntile", [8, 64, 128, 256, 512, 1024])
def test_tile_sweep_compiles(one_chip, cfg, no_cache, ntile):
    """Default ``oct_block_shift``; the tile buckets are powers of two
    >= 8, so 8/64 cover the whole-axis lane tile and 128/1024 the
    128-lane tile (one and many grid steps); 256 and 512 are the
    padded tile counts of levels 8 and 9 in the benchmark's AMR window
    (PERF.md section 4)."""
    from ramses_tpu.config import AmrParams
    shift = AmrParams().oct_block_shift
    assert po.tile_shape_ok(ntile, shift)
    td = (1 << (shift + 1)) + 2 * po._NG
    u = jax.ShapeDtypeStruct((5, td, td, td, ntile), F32,
                             sharding=one_chip)
    ok = jax.ShapeDtypeStruct((td, td, td, ntile), F32, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = _compile(lambda u, ok, dt: po.tile_sweep(
        u, ok, dt, cfg, 1.0 / 512, shift), u, ok, dt)
    _fits_one_chip(compiled)


# ----------------------------------------------------------------------
# the tiled constrained-transport MHD kernel (mhd/pallas_ct) and the
# uniform MHD run's 16-step program (the benchmark's
# mhd-blast3d-uniform-256 cell): first Mosaic compiles of any MHD code
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def mhd_cfg():
    from ramses_tpu.mhd.core import MhdStatic
    nml = os.path.join(os.path.dirname(NML), os.pardir, "benchmark",
                       "configs", "mhd-blast3d-uniform-256.nml")
    return MhdStatic.from_params(load_params(nml, ndim=3))


@pytest.mark.parametrize("n,bx", [(128, 16), (256, 8), (512, None),
                                  (384, None), (64, None)])
def test_ct_kernel_compiles_where_the_gate_admits(one_chip, mhd_cfg,
                                                  no_cache, n, bx):
    """Every lane extent ``pallas_ct.supports`` admits (128 and 256)
    compiles under the 100 MiB of scoped VMEM the call asks for and
    leaves its ``block_stats()`` record; what has no case here the gate
    declines (512^3 is 5.9 GB of state: a cell of its own, with its
    case, when it comes)."""
    from ramses_tpu.mhd import pallas_ct as pc
    shape = (n, n, n)
    assert pc._pick_block(shape) == (bx, pc.BY if bx else None)
    assert pc.supports(mhd_cfg, shape, PERIODIC, F32) is (bx is not None)
    if bx is None:
        return
    pad = (n + 2 * pc.HALO, n + pc.WY - pc.BY, n)
    up = jax.ShapeDtypeStruct((pc.NHYDRO,) + pad, F32, sharding=one_chip)
    bfp = jax.ShapeDtypeStruct((3,) + pad, F32, sharding=one_chip)
    dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = _compile(lambda up, bfp, dt: pc.ct_step_tiled(
        up, bfp, dt, mhd_cfg, 1.0 / n, shape), up, bfp, dt)
    assert pc.KERNEL_NAME in compiled.as_text()
    _fits_one_chip(compiled)
    rec = [b for b in pc.block_stats() if b["shape"] == list(shape)]
    assert rec == [{"kernel": "pallas_ct", "shape": list(shape), "bx": bx,
                    "by": 8, "halo": 3,
                    "window_cells": (bx + 6) * 16 * n,
                    "written_cells": bx * 8 * n}]


def test_mhd_run_steps_loop_body_is_ghost_pass_and_kernel(one_chip, mhd_cfg,
                                                          no_cache):
    """The whole 16-step program of the uniform MHD run at 256^3 (what
    ``MhdSimulation.evolve`` dispatches on the chip): ONE kernel, and
    inside the while body nothing of the state's size but the two
    ghost-pass fusions (hydro rows, faces) and that kernel — no
    ``select`` masking a step out, no ``slice`` or ``copy`` of the
    carry.  The temporaries are the padded copies (567 MB) and a third
    of that again (750 MB read; the XLA scan's: 28.32 GB, no program),
    and state + output + temporaries fit one chip."""
    from ramses_tpu.mhd import pallas_ct as pc
    from ramses_tpu.mhd import uniform as mu
    n = 256
    grid = mu.MhdGrid(cfg=mhd_cfg, shape=(n, n, n), dx=1.0 / n,
                      bc_kinds=PERIODIC)
    u = jax.ShapeDtypeStruct((8, n, n, n), F32, sharding=one_chip)
    bf = jax.ShapeDtypeStruct((3, n, n, n), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = _compile(
        lambda u, bf, t, tend: mu._run_steps_kernel(grid, u, bf, t, tend,
                                                    16), u, bf, t, t)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    entry = _computation(hlo, re.search(r"^ENTRY (%\S+)", hlo, re.M)[1])
    whiles = re.findall(r" while\(.*?body=(%[\w.-]+)", entry)
    assert len(whiles) == 1
    body = _computation(hlo, whiles[0])
    assert "tpu_custom_call" in body

    def ops(rows, extents):
        return re.findall(
            r"^\s*(?:ROOT )?%%\S+ = f32\[%s,%d,%d,%d\]\S* ([\w-]+)\("
            % ((rows,) + extents), body, re.M)

    for rows in (3, 5, 8):
        assert set(ops(rows, (n, n, n))) <= {"get-tuple-element"}
    padded = (n + 2 * pc.HALO, n + pc.WY - pc.BY, n)
    assert ops(5, padded) == ["fusion"] and ops(3, padded) == ["fusion"]
    _fits_one_chip(compiled)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1.4 * 8 * padded[0] * padded[1] * n * 4


def test_mhd_batched_run_steps_compiles(one_chip, mhd_cfg, no_cache):
    """``mu.run_steps_batch`` vmaps ``run_steps``, so on the chip every
    member of an ensemble of admitted boxes takes the kernel path: the
    batched 16-step program (3 members of 128^3) compiles too — ONE
    kernel, the batch a grid axis of it, each member its own ``dt`` word
    and Courant accumulator in SMEM — and fits one chip."""
    from ramses_tpu.mhd import pallas_ct as pc
    from ramses_tpu.mhd import uniform as mu
    n, nb = 128, 3
    grid = mu.MhdGrid(cfg=mhd_cfg, shape=(n, n, n), dx=1.0 / n,
                      bc_kinds=PERIODIC)
    assert pc.supports(mhd_cfg, grid.shape, PERIODIC, F32)
    u = jax.ShapeDtypeStruct((nb, 8, n, n, n), F32, sharding=one_chip)
    bf = jax.ShapeDtypeStruct((nb, 3, n, n, n), F32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((nb,), F32, sharding=one_chip)
    compiled = _compile(jax.vmap(
        lambda u, bf, t, tend: mu._run_steps_kernel(grid, u, bf, t, tend,
                                                    16)), u, bf, t, t)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert pc.KERNEL_NAME in hlo
    _fits_one_chip(compiled)



# ----------------------------------------------------------------------
# the op -> phase table on the chip's own layout copies
# ----------------------------------------------------------------------
@pytest.mark.parametrize("program", ["step", "flags"])
def test_amr_programs_phase_table(one_chip, no_cache, monkeypatch, program):
    """The coarse step and the flags program of a small 3-level
    hierarchy (complete level 4 on the XLA dense sweep: the fused
    kernel starts at 128 lanes; levels 5 and 6 on the Pallas tile
    kernel), compiled for the described v5e: every instruction parses,
    the tile kernels carry their ``name=`` and sit under ``kernel``,
    and every ``copy`` / ``reshape`` / ``transpose`` the TPU compiler
    left in the entry computation owns a phase — by its own ``op_name``
    or by what reads it — or is listed here."""
    import warnings

    from ramses_tpu.amr import hierarchy as H
    from ramses_tpu.config import params_from_string
    from ramses_tpu.telemetry import hlo
    from tests.test_oct_blocking import SEDOV3D
    with jax.enable_x64(False):
        sim = H.AmrSim(params_from_string(SEDOV3D.format(
            lmin=4, lmax=6, blk=".true.", riemann="llf"), ndim=3), dtype=F32)
        # the gates ask the backend: steered here, not by an option
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        sim._spec = None
        spec = sim._fused_spec()
        u, dev = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (sim.u, sim.dev))
        if program == "step":
            dt = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
            compiled = H._fused_coarse_step.lower(
                u, dev, {}, dt, spec, None).compile()
        else:
            r = sim.params.refine
            compiled = H._fused_flags.lower(
                u, dev, spec,
                (float(r.err_grad_d), float(r.err_grad_u),
                 float(r.err_grad_p)),
                (float(r.floor_d), float(r.floor_u), float(r.floor_p)),
                int(r.interpol_type)).compile()
    text = compiled.as_text()
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # parsed == present
        ins = hlo.parse_instructions(text)
        tab = hlo.phase_table(text)
    assert hlo.module_name(text) == ("jit__fused_coarse_step"
                                     if program == "step"
                                     else "jit__fused_flags")
    kernels = [n for n, i in ins.items() if i["opcode"] == "custom-call"
               and n.startswith(po.TILE_KERNEL_NAME)]
    if program == "step":
        # one call a sweep: level 5 twice, level 6 four times
        assert text.count('custom_call_target="tpu_custom_call"') == 6
        assert len(kernels) == 6
        assert {tab[n] for n in kernels} == {
            ("sweep l5/kernel", "kernel"), ("sweep l6/kernel", "kernel")}
    else:
        assert not kernels
    work = [n for n, i in ins.items() if i["opcode"] not in (
        "parameter", "constant", "tuple", "get-tuple-element", "bitcast")]
    lost = [n for n in work if tab[n][0] == hlo.UNATTRIBUTED]
    assert len(lost) <= 0.02 * len(work), lost
    layout_ops = [n for n, i in ins.items() if i["caller"] == ""
                  and i["opcode"] in ("copy", "reshape", "transpose")]
    assert len(layout_ops) > 20
    listed = ()             # none today: every one owns a phase
    assert [n for n in layout_ops if tab[n][0] == hlo.UNATTRIBUTED
            and n not in listed] == []
    outer = "sweep" if program == "step" else "flags"
    paths = {tab[n][0] for n in work}
    assert {f"{outer} l{l}/gather" for l in (4, 5, 6)} <= paths
    assert {f"{outer} l{l}/ghost" for l in (5, 6)} <= paths
