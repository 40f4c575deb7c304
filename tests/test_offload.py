"""Out-of-core AMR offload (ramses_tpu/amr/offload.py).

Pins the engine's three contracts:

  * bitwise parity — ``offload=on`` equals ``off`` exactly through
    steps, regrids, and a checkpoint written WHILE levels were parked
    (the segmented per-level path runs the same kernels in the same
    order on the same inputs, so there is no tolerance to tune);
  * honest accounting — prefetches that land count as overlapped,
    prefetches that don't (and cold fetches) count as stalls, and the
    per-step device high-water tracks the managed residency;
  * zero overhead when off — the default path adds no device fetches
    and no engine at all (``sim._offload is None``).

Parity runs use ``nremap=1``: the chunked fast path accumulates ``t``
on device while engaged runs accumulate on host, so chunk==1 keeps both
sides on the per-step path where even ``t`` is bitwise equal.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.amr.offload import is_parked
from ramses_tpu.config import params_from_string

pytestmark = pytest.mark.smoke

SEDOV2D = """
&RUN_PARAMS
hydro=.true.
nstepmax={nstep}
nremap=1
/
&AMR_PARAMS
levelmin=4
levelmax={lmax}
boxlen=1.0
offload='{mode}'
offload_hbm_budget_mb={budget}
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


def _params(mode="off", budget=0.0, lmax=5, nstep=20):
    return params_from_string(
        SEDOV2D.format(mode=mode, budget=budget, lmax=lmax,
                       nstep=nstep), ndim=2)


def _assert_state_equal(a, b):
    assert list(a.levels()) == list(b.levels())
    for l in a.levels():
        np.testing.assert_array_equal(np.asarray(a.u[l]),
                                      np.asarray(b.u[l]))


# ---------------------------------------------------------------------
# bitwise parity: steps + regrids + checkpoint-while-parked + restore
# ---------------------------------------------------------------------
@pytest.mark.slow          # ~38s; nightly tier on the 1-core box
def test_bitwise_parity_through_steps_regrid_restart(tmp_path):
    s_off = AmrSim(_params("off", lmax=6))
    s_on = AmrSim(_params("on", lmax=6))
    s_off.evolve(1e9, nstepmax=4)
    s_on.evolve(1e9, nstepmax=4)
    eng = s_on._offload
    assert eng is not None and eng.engaged(s_on)
    assert eng.last_step_stats is not None
    assert eng.last_step_stats["fetches"] > 0
    # the engaged run really is out-of-core between steps
    assert any(is_parked(a) for a in s_on.u.values())
    _assert_state_equal(s_off, s_on)
    assert s_off.t == s_on.t

    # elastic checkpoint written while levels are parked: pario stages
    # the host buffer directly (no device round-trip), and the restored
    # sim continues bitwise with the never-offloaded reference
    out = s_on.dump_pario(1, str(tmp_path))
    assert any(is_parked(a) for a in s_on.u.values())   # dump didn't unpark
    s_res = AmrSim.from_checkpoint_dir(_params("off", lmax=6), out)
    assert s_res.t == s_off.t and s_res.nstep == s_off.nstep
    _assert_state_equal(s_off, s_res)

    s_off.evolve(1e9, nstepmax=6)
    s_on.evolve(1e9, nstepmax=6)
    s_res.evolve(1e9, nstepmax=6)
    _assert_state_equal(s_off, s_on)
    _assert_state_equal(s_off, s_res)
    assert s_off.t == s_on.t == s_res.t


class _StencilSim(AmrSim):
    """Partial levels on the per-oct 6^d stencil (the tile path's
    reference), as a solver family that opts out would run them."""
    _oct_blocked = False


@pytest.mark.parametrize("cls,kind", [(AmrSim, "tile"),
                                      (_StencilSim, "stencil")],
                         ids=["tile", "stencil"])
def test_bitwise_parity_both_partial_sweeps(cls, kind):
    """Both answers of the one dispatcher (``K.sweep_level`` /
    ``K.flags_level``) through both of its callers: the fused window
    (off) and the per-level segments (on) step and regrid bitwise
    alike, whichever formulation the partial levels take."""
    from ramses_tpu.amr import kernels as K
    s_off, s_on = cls(_params("off", lmax=6)), cls(_params("on", lmax=6))
    noct0 = [s_on.tree.noct(l) for l in s_on.levels()]
    # two steps, a regrid before each (nremap=1); from the third on the
    # two sides part by <= 1e-33 in momenta that are zero by symmetry,
    # on the tile path and on the stencil alike (ROADMAP D4)
    s_off.evolve(1e9, nstepmax=2)
    s_on.evolve(1e9, nstepmax=2)
    assert [s_on.tree.noct(l) for l in s_on.levels()] != noct0
    assert s_on._offload.engaged(s_on)
    assert any(is_parked(a) for a in s_on.u.values())
    spec = s_on._fused_spec()
    kinds = [K.level_kind(spec, i) for i in range(len(spec.levels))]
    assert kinds[0] == "dense" and set(kinds[1:]) == {kind}, kinds
    assert s_on.nstep == s_off.nstep == 2 and s_off.t == s_on.t
    _assert_state_equal(s_off, s_on)
    for l in s_off.levels():
        np.testing.assert_array_equal(s_off.tree.levels[l].keys,
                                      s_on.tree.levels[l].keys)


# ---------------------------------------------------------------------
# prefetch/stall accounting
# ---------------------------------------------------------------------
def test_prefetch_disabled_counts_stalls():
    sim = AmrSim(_params("on", lmax=6))
    sim._offload.prefetch_depth = 0        # every fetch is cold
    sim.evolve(1e9, nstepmax=2)
    st = sim._offload.last_step_stats
    assert st["prefetches"] == 0
    assert st["fetches"] > 0
    assert st["stalls"] == st["fetches"]
    assert st["overlap_frac"] == 0.0
    assert st["device_hwm_bytes"] > 0


def test_prefetch_overlap_accounted():
    sim = AmrSim(_params("on", lmax=6))
    sim.evolve(1e9, nstepmax=3)
    tot = sim._offload._tot
    assert tot["prefetches"] > 0
    assert tot["overlapped"] + tot["stalls"] == tot["fetches"]
    assert tot["bytes_parked"] > 0 and tot["bytes_fetched"] > 0


# ---------------------------------------------------------------------
# engagement modes
# ---------------------------------------------------------------------
def test_auto_mode_engagement_threshold():
    tiny = AmrSim(_params("auto", budget=1e-4))    # ~100 bytes: exceed
    assert tiny._offload is not None
    assert tiny._offload.engaged(tiny)
    huge = AmrSim(_params("auto", budget=1e6))     # 1 TB: never exceed
    assert huge._offload is not None
    assert not huge._offload.engaged(huge)
    # under the cap the fast path must hold device arrays only
    huge.step_coarse(huge.coarse_dt())
    assert not any(is_parked(a) for a in huge.u.values())


def test_on_mode_warns_and_declines_when_ineligible(recwarn):
    p = _params("on")
    p.run.fault_inject = "nan@999"         # fault injector present
    sim = AmrSim(p)
    assert sim._offload is not None
    assert not sim._offload.engaged(sim)
    assert any("offload=on ignored" in str(w.message) for w in recwarn)


def test_bad_mode_rejected():
    with pytest.raises(ValueError, match="offload"):
        AmrSim(_params("sometimes"))


# ---------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------
def test_zero_overhead_when_off(monkeypatch):
    import jax

    sim = AmrSim(_params("off"))
    assert sim._offload is None            # no engine on the default path
    sim.regrid_interval = 0
    sim.evolve(1e9, nstepmax=4)            # warm the fused chunk
    calls = {"n": 0}
    real = jax.device_get

    def counted(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counted)
    sim.evolve(1e9, nstepmax=sim.nstep + 8)
    assert calls["n"] == 0, \
        "offload=off must not add device fetches to evolve"


# ---------------------------------------------------------------------
# telemetry composition
# ---------------------------------------------------------------------
def test_telemetry_records_offload_stats(tmp_path):
    import json

    p = _params("on", lmax=6)
    p.output.telemetry = str(tmp_path / "run.jsonl")
    p.output.telemetry_interval = 1
    sim = AmrSim(p)
    sim.evolve(1e9, nstepmax=3)
    sim.telemetry.close(sim, print_timers=False)
    with open(tmp_path / "run.jsonl") as f:
        recs = [json.loads(line) for line in f]
    assert recs[0]["run_info"]["offload"] == "on"
    steps = [r for r in recs if r["kind"] == "step"]
    offs = [r["offload"] for r in steps if "offload" in r]
    assert offs, "engaged steps must carry the offload block"
    for o in offs:
        for k in ("stalls", "prefetches", "fetches", "overlap_frac",
                  "bytes_parked", "bytes_fetched", "device_hwm_bytes"):
            assert k in o
    foot = recs[-1]
    assert foot["kind"] == "run_footer"
    assert "offload_stalls" in foot
    assert foot["offload_bytes_parked"] > 0
    assert foot["offload_device_hwm_bytes"] > 0


# ---------------------------------------------------------------------
# schedule planner
# ---------------------------------------------------------------------
def test_plan_working_sets_cover_neighbors():
    from ramses_tpu.amr.offload import plan_schedule

    sim = AmrSim(_params("on", lmax=6))
    ops = plan_schedule(sim._fused_spec())
    lv = list(sim.levels())
    sweeps = [op for op in ops if op.kind == "sweep"]
    # factor-2 subcycling: level i sweeps 2^(i-lmin) times
    assert len(sweeps) == sum(1 << (i) for i in range(len(lv)))
    for op in ops:
        if op.kind == "sweep" and lv[op.i] > sim.lmin:
            assert lv[op.i] in op.ws and lv[op.i] - 1 in op.ws
        if op.kind == "restrict":
            assert set(op.ws) == {lv[op.i], lv[op.i + 1]}
    # every level is courant-scanned exactly once per coarse step
    assert sorted(op.i for op in ops if op.kind == "courant") \
        == list(range(len(lv)))
