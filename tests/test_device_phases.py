"""Device-phase scopes of the AMR programs and the op -> phase table
(``telemetry/hlo.phase_table``): which ``jax.named_scope`` — level and
phase — owns each instruction of a compiled program, the layout copies
the compiler inserts included; the record of what was dispatched under a
profiler session; the ``wait`` flag of the host spans."""

import re
import types
import warnings

import jax
import jax.numpy as jnp
import pytest

from ramses_tpu import platform
from ramses_tpu.amr import hierarchy as H
from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.config import params_from_string
from ramses_tpu.telemetry import hlo
from ramses_tpu.utils import timers
from ramses_tpu.utils.timers import NullTimers

from tests.test_oct_blocking import SEDOV3D

pytestmark = pytest.mark.smoke


@pytest.fixture(scope="module")
def sim():
    """A small 3-level 3D tree: complete level 4, partial 5 and 6 on
    the Morton-tile batch."""
    p = params_from_string(SEDOV3D.format(lmin=4, lmax=6, blk=".true.",
                                          riemann="llf"), ndim=3)
    s = AmrSim(p, dtype=jnp.float32)
    assert s.levels() == [4, 5, 6] and s.blocks
    return s


def _flag_args(sim):
    r = sim.params.refine
    return (sim.u, sim.dev, sim._fused_spec(),
            (float(r.err_grad_d), float(r.err_grad_u), float(r.err_grad_p)),
            (float(r.floor_d), float(r.floor_u), float(r.floor_p)),
            int(r.interpol_type))


def _step_args(sim):
    return (sim.u, sim.dev, {}, jnp.asarray(1e-4, sim.dtype),
            sim._fused_spec(), sim._cool_bundle())


# what each program must carry on this tree.  The lowered module keeps
# a nested jit (``tile_sweep``, ``dense_sweep`` ...) as a function of
# its own: the outer scope sits on the call, the inner ones on the ops
# inside, and XLA joins them when it inlines (the compiled paths are
# held by test_phase_table_of_the_compiled_programs)
STEP_SCOPES = ({f"sweep l{l}" for l in (4, 5, 6)}
               | {"fluxcorr l5", "fluxcorr l6", "restrict l4",
                  "restrict l5", "courant"}
               | {"ghost", "gather", "pad", "kernel", "scatter"})
FLAG_SCOPES = ({f"flags l{l}" for l in (4, 5, 6)}
               | {"ghost", "gather", "pad", "criteria", "scatter"})


def _lowered_scopes(lowered):
    """Every declared scope met in the locations of a lowered module."""
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {c for n in names for c in hlo.scope_path(n).split("/")
            if c}


@pytest.mark.parametrize("program", ["step", "flags"])
def test_every_declared_scope_is_in_the_lowered_program(sim, program):
    if program == "step":
        low, want = H._fused_coarse_step.lower(*_step_args(sim)), STEP_SCOPES
    else:
        low, want = H._fused_flags.lower(*_flag_args(sim)), FLAG_SCOPES
    assert _lowered_scopes(low) == want


def test_migrate_level_carries_its_scopes():
    cfg = H.HydroStatic.from_params(params_from_string(
        SEDOV3D.format(lmin=4, lmax=5, blk=".true.", riemann="llf"), ndim=3))
    i32 = jnp.zeros(8, jnp.int32)
    u = jnp.zeros((64, cfg.nvar), jnp.float32)
    text = H._migrate_level.lower(
        u, u, i32, i32, i32, jnp.zeros((8, 3, 2), jnp.int32),
        jnp.ones((8, 3), jnp.float32), i32, 64, cfg, 1
    )
    assert _lowered_scopes(text) == {"migrate: copy", "migrate: interp"}
    assert hlo.phase_kind("migrate: copy") == "layout"


def test_the_table_of_scopes():
    assert set(hlo.PHASE_KINDS.values()) == {"kernel", "layout", "physics"}
    with pytest.raises(KeyError):
        hlo.phase("not a phase")
    # the last component of an op_name is the primitive, never a scope
    assert hlo.scope_path("jit(f)/jit(main)/sweep l8/jit(tile_sweep)/"
                          "gather/gather") == "sweep l8/gather"
    assert hlo.scope_path("jit(f)/jit(main)/gather") == ""
    assert hlo.scope_path("") == ""
    assert hlo.phase_kind("sweep l8/gather") == "layout"
    assert hlo.phase_kind("sweep l12") == "physics"
    assert hlo.phase_kind("flags l7/criteria") == "physics"
    assert hlo.phase_kind("sweep l7/kernel") == "kernel"
    assert hlo.phase_kind("") == hlo.UNATTRIBUTED


# a hand-written compiled module: one instruction per rule
HAND = """HloModule jit_hand, is_scheduled=true, entry_computation_layout={(f32[8,4]{1,0})->f32[8,4]{1,0}}

%fused_computation (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  ROOT %neg.1 = f32[8,4]{1,0} negate(%param_0.1), metadata={op_name="jit(hand)/sweep l8/kernel/neg"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body.1 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %copy.7 = f32[8,4]{0,1} copy(%gte.1)
  %fusion.3 = f32[8,4]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(hand)/sweep l8/kernel/neg"}
  ROOT %tuple.2 = (s32[], f32[8,4]{1,0}) tuple(%gte.0, %fusion.3)
}

%cond.1 (p.1: (s32[], f32[8,4])) -> pred[] {
  %p.1 = (s32[], f32[8,4]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%p.1), index=0
  %c.5 = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(%gte.2, %c.5), direction=LT
}

ENTRY %main.1 (x: f32[8,4]) -> f32[8,4] {
  %x = f32[8,4]{1,0:T(8,128)} parameter(0)
  %copy.1 = f32[8,4]{0,1:T(4,128)} copy(%x)
  %transpose.2 = f32[4,8]{1,0} transpose(%copy.1), dimensions={1,0}
  %gather.4 = f32[4,8]{1,0} gather(%transpose.2, %x), offset_dims={1}, metadata={op_name="jit(hand)/jit(main)/sweep l8/jit(tile_sweep)/gather/gather" stack_frame_id=7}
  %copy.5 = f32[4,8]{0,1} copy(%gather.4)
  %reduce.6 = f32[4]{0} reduce(%copy.5, %zero.1), dimensions={1}, to_apply=%region_0.1
  %zero.1 = f32[] constant(0)
  %c.0 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8,4]{1,0}) tuple(%c.0, %x)
  %while.1 = (s32[], f32[8,4]{1,0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(hand)/fluxcorr l9/while"}
  %iota.8 = s32[16]{0} iota(), iota_dimension=0
  ROOT %gte.9 = f32[8,4]{1,0} get-tuple-element(%while.1), index=1
}
"""


def test_phase_table_rules_on_hand_written_hlo():
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # parsed == present
        ins = hlo.parse_instructions(HAND)
        tab = hlo.phase_table(HAND)
    assert hlo.module_name(HAND) == "jit_hand"
    # entry, while body and condition; not the fusion's inside nor the
    # reduce's combiner
    assert {i["comp"] for i in ins.values()} == {"main.1", "body.1",
                                                 "cond.1"}
    assert "neg.1" not in tab and "add.9" not in tab
    # own scope
    assert tab["gather.4"] == ("sweep l8/gather", "layout")
    assert tab["fusion.3"] == ("sweep l8/kernel", "kernel")
    assert tab["while.1"] == ("fluxcorr l9", "physics")
    # a scope-less copy belongs to what reads it ...
    assert tab["copy.7"] == ("sweep l8/kernel", "kernel")
    # ... through a chain of scope-less users
    assert tab["transpose.2"] == tab["copy.1"] == ("sweep l8/gather",
                                                   "layout")
    assert tab["x"] == ("sweep l8/gather", "layout")
    # no user with a scope: its first operand's producer
    assert tab["copy.5"] == tab["reduce.6"] == ("sweep l8/gather", "layout")
    assert tab["gte.9"] == ("fluxcorr l9", "physics")
    # none of these, in a computation an instruction calls: the caller's
    assert ins["lt.1"]["caller"] == "while.1" and ins["x"]["caller"] == ""
    assert tab["lt.1"] == tab["c.5"] == ("fluxcorr l9", "physics")
    assert tab["gte.2"] == ("fluxcorr l9", "physics")
    # nothing: unattributed
    assert tab["iota.8"] == (hlo.UNATTRIBUTED, hlo.UNATTRIBUTED)
    assert tab["zero.1"] == (hlo.UNATTRIBUTED, hlo.UNATTRIBUTED)
    # bytes from the result shapes (tiling annotations ignored)
    assert ins["copy.1"]["bytes"] == 8 * 4 * 4
    assert ins["while.1"]["bytes"] == 4 + 8 * 4 * 4
    assert ins["lt.1"]["bytes"] == 1


def test_phase_table_warns_when_it_drops_an_instruction():
    broken = HAND.replace("%copy.5 = f32[4,8]{0,1} copy(",
                          "%copy.5 = ?? copy(")
    with pytest.warns(RuntimeWarning, match="parsed 21 of 22"):
        tab = hlo.phase_table(broken)
    assert "copy.5" not in tab


def test_phase_table_of_the_compiled_programs(sim):
    """On the backend at hand every instruction of both programs gets a
    phase: the table's names are the compiled text's, nothing is
    unattributed but what has neither scope, user nor producer."""
    for fn, args in ((H._fused_coarse_step, _step_args(sim)),
                     (H._fused_flags, _flag_args(sim))):
        text = fn.lower(*args).compile().as_text()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ins = hlo.parse_instructions(text)
            tab = hlo.phase_table(text)
        assert set(tab) == set(ins) and len(tab) > 50
        work = [n for n, i in ins.items() if i["opcode"] not in (
            "parameter", "constant", "tuple", "get-tuple-element")]
        lost = [n for n in work if tab[n][0] == hlo.UNATTRIBUTED]
        assert len(lost) <= 0.02 * len(work), lost
        assert {k for _, k in tab.values()} >= {"layout", "physics"}


@pytest.fixture
def records():
    hlo.clear_dispatch_records()
    timers.clear_span_records()
    yield
    hlo.clear_dispatch_records()
    timers.clear_span_records()


def test_signature_record_only_under_a_session(tmp_path, records):
    """No session: nothing recorded.  Under one: one entry a signature,
    arrays as ShapeDtypeStructs with their shardings; ``device_phases``
    compiles them when asked and leaves out a name with two
    signatures."""
    p = params_from_string(SEDOV3D.format(lmin=4, lmax=5, blk=".true.",
                                          riemann="llf"), ndim=3)
    sim = AmrSim(p, dtype=jnp.float32)
    assert isinstance(sim.timers, NullTimers)
    sim._criteria_flags(sim._fused_spec())
    sim.step_coarse(sim.coarse_dt())
    assert hlo.dispatch_records() == [] and hlo.device_phases() == {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):          # the same signatures twice
            sim._criteria_flags(sim._fused_spec())
            sim._dt_cache = None    # as after a regrid: a Courant pass
            sim.step_coarse(sim.coarse_dt())
        sim.drain()
    finally:
        jax.profiler.stop_trace()
    recs = hlo.dispatch_records()
    assert [fn.__name__ for fn, _ in recs] == ["_fused_flags",
                                               "_fused_coarse_step"]
    for fn, args in recs:
        leaves = jax.tree_util.tree_leaves(args)
        assert any(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
        assert not any(isinstance(a, jax.Array) for a in leaves)
    sim._criteria_flags(sim._fused_spec())      # session off: not noted
    assert len(hlo.dispatch_records()) == 2
    # asked after the window: the abstract arguments lower to the very
    # programs the calls ran (no new trace), and each is compiled once
    platform._install_cache_listener()
    compiled = platform._CACHE_STATS["compiles"]
    tables = hlo.device_phases()
    assert platform._CACHE_STATS["compiles"] == compiled + 2
    assert set(tables) == {"jit__fused_flags", "jit__fused_coarse_step"}
    assert all(len(t) > 20 for t in tables.values())
    # the spans of the same stretch: courant's fetch is its child, and
    # only the blocking spans say wait
    by = {r["name"]: r for r in timers.span_records()}
    assert by["courant: fetch"]["parent"] == "courant"
    assert by["courant: fetch"]["wait"] and not by["courant"]["wait"]
    assert not by["hydro - godunov"]["wait"]
    assert timers.WAIT_LABELS == {"regrid: flag fetch", "evolve: wait",
                                  "courant: fetch"}


def test_abstract_arguments_keep_a_committed_sharding_only():
    """What ``jit`` lowers for depends on whether an argument's placement
    is stated: an uncommitted array must come back as a bare shape."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    sh = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("x",)), P("x"))
    put = hlo._abstract(jax.device_put(jnp.ones(8), sh))
    free = hlo._abstract(jnp.ones(8))
    assert isinstance(put, jax.ShapeDtypeStruct) and put.sharding == sh
    assert free.sharding is None and free.shape == (8,)
    assert hlo._abstract(3.0) == 3.0


def test_a_stale_cache_entry_does_not_blind_the_table(tmp_path, records):
    """The compile cache's key leaves metadata out: a program compiled
    WITHOUT a scope (the parent commit) and the same program with it
    share an entry, and the one that comes second is handed the first
    one's text.  ``device_phases`` must read its own scopes all the
    same."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def make(scoped):
        def f(x):
            with hlo.phase("gather") if scoped else jax.named_scope("old"):
                return jnp.sin(x).T * 2.0
        return jax.jit(f)

    x = jnp.ones((64, 32), jnp.float32)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        make(False)(x).block_until_ready()      # the parent's entry
        mine = make(True)
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            hlo.note_dispatch(mine, x)
            mine(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        stale = mine.lower(x).compile().as_text()
        table = hlo.device_phases()["jit_f"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    if "gather" in stale:
        pytest.skip("this backend's cache did not hand back the other "
                    "program's text")
    assert ("gather", "layout") in set(table.values())


def test_two_signatures_of_one_name_are_left_out(tmp_path, records, capsys):
    @jax.jit
    def twice(x):
        return x * 2.0

    jax.profiler.start_trace(str(tmp_path))
    try:
        hlo.note_dispatch(twice, jnp.ones(4))
        hlo.note_dispatch(twice, jnp.ones(4))
        assert len(hlo.dispatch_records()) == 1
        hlo.note_dispatch(twice, jnp.ones(8))
    finally:
        jax.profiler.stop_trace()
    assert len(hlo.dispatch_records()) == 2
    assert hlo.device_phases() == {}
    assert "2 signatures of jit_twice" in capsys.readouterr().err


def test_off_notes_nothing_and_reads_no_clock(monkeypatch, records):
    def boom(*a, **k):
        raise AssertionError("read with tracing off")

    monkeypatch.setattr(timers, "time", types.SimpleNamespace(
        perf_counter=boom, perf_counter_ns=boom))
    monkeypatch.setattr(jax.tree_util, "tree_flatten", boom)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    hlo.note_dispatch(lambda x: x, jnp.ones(3))
    assert hlo.dispatch_records() == []
