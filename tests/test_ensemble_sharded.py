"""Two-level parallelism through the engine and the gang service
(``ensemble/meshplan.py`` + ``ensemble/batch.py`` + the cost-aware
serve loop).

Pins the composition contracts:

  * a mesh-of-8 PACKED run (member vmap sharded over per-device
    replicas) is BITWISE the solo per-member runs — the replica axis
    must be numerically invisible, exactly like the vmap axis;
  * a SLAB-mode member is bitwise the standalone sharded sim through
    ``parallel/halo.run_steps_halo``;
  * checkpoints round-trip ACROSS packings (packed -> single and
    single -> packed) bitwise — ensemble checkpoints are elastic over
    the device mesh, not just over host counts;
  * one stacked ``jax.device_get`` per chunk regardless of how many
    sub-batch groups a sweep splits into;
  * the cost-order serve loop gang-schedules small jobs concurrently
    and a shared-queue compile cache hands a second worker a zero-miss
    cold start.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax

from ramses_tpu.config import params_from_dict
from ramses_tpu.ensemble import queue as jq
from ramses_tpu.ensemble.batch import (EnsembleEngine, EnsembleSpec,
                                       build_member)
from ramses_tpu.ensemble.meshplan import MeshPlan
from ramses_tpu.ensemble.service import serve

pytestmark = pytest.mark.smoke

NDEV = min(8, len(jax.devices()))


def _hydro_params(nstepmax=6):
    """2D periodic Sedov-style base: nx=16 — slab-shardable over 8
    devices (2-cell shards == NGHOST) AND pack-shardable over any
    member count."""
    return params_from_dict({
        "run_params": {"hydro": True, "nstepmax": nstepmax},
        "amr_params": {"levelmin": 4, "levelmax": 4, "boxlen": 1.0},
        "init_params": {"nregion": 2,
                        "region_type": ["square", "point"],
                        "x_center": [0.5, 0.5], "y_center": [0.5, 0.5],
                        "length_x": [10.0, 1.0], "length_y": [10.0, 1.0],
                        "exp_region": [10.0, 10.0],
                        "d_region": [1.0, 0.0],
                        "p_region": [1e-5, 0.1]},
        "hydro_params": {"gamma": 1.4, "courant_factor": 0.8,
                         "riemann": "hllc"},
        "output_params": {"tend": 1e9},
    }, ndim=2)


def _solo_windows(spec, k, windows):
    """Replay the engine's exact fused-window sequence on one member."""
    from ramses_tpu.grid.uniform import run_steps

    grid, state, tend, _ = build_member(spec, k, dtype=jnp.float64)
    u, t = state[0], jnp.asarray(0.0, jnp.float64)
    te = jnp.asarray(tend, jnp.float64)
    for n in windows:
        u, t, _ = run_steps(grid, u, t, te, n)
    return u, float(t)


# ---------------------------------------------------------------------
# bitwise parity across packings
# ---------------------------------------------------------------------
@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices")
def test_packed_mesh_of_8_bitwise_vs_solo():
    """8 members packed over 8 per-device replicas == 8 solo runs,
    bitwise.  Members are data-parallel, so the GSPMD partition of the
    member axis must not change a single bit."""
    spec = EnsembleSpec(base=_hydro_params(nstepmax=6), nmember=8,
                        perturb_amp=0.01)
    eng = EnsembleEngine(spec, dtype=jnp.float64,
                         plan=MeshPlan.packed(tuple(range(8))))
    assert eng.groups[0].replicas == 8
    eng.run(chunk=4)
    assert eng.run_complete() and eng.nstep == 6
    info = eng.run_info()
    assert info["packing"]["mode"] == "packed"
    assert info["packing"]["group_replicas"] == [8]
    for k in range(8):
        solo_u, solo_t = _solo_windows(spec, k, (4, 2))
        ms = eng.member_state(k)
        assert np.asarray(ms["u"]).tobytes() == \
            np.asarray(solo_u).tobytes(), k
        assert ms["t"] == solo_t


@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices")
def test_slab_member_bitwise_vs_standalone_sharded():
    """A slab-mode member == the standalone sharded sim through
    ``run_steps_halo`` on the same mesh, window for window."""
    from ramses_tpu.parallel import halo

    p = _hydro_params(nstepmax=6)
    spec = EnsembleSpec(base=p, nmember=1, perturb_amp=0.01)
    eng = EnsembleEngine(spec, dtype=jnp.float64,
                         plan=MeshPlan.slab(tuple(range(8))))
    eng.run(chunk=4)
    assert eng.run_complete() and eng.nstep == 6
    assert eng.run_info()["packing"]["mode"] == "slab"

    grid, state, tend, _ = build_member(spec, 0, dtype=jnp.float64)
    mesh = halo.make_halo_mesh(jax.devices()[:8])
    u, t = state[0], jnp.asarray(0.0, jnp.float64)
    for n in (4, 2):
        u, t, _ = halo.run_steps_halo(grid, mesh, u, t, float(tend), n)
    ms = eng.member_state(0)
    assert np.asarray(ms["u"]).tobytes() == np.asarray(u).tobytes()
    assert ms["t"] == float(t)


@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices")
@pytest.mark.parametrize("first,second", [
    ("packed", "single"), ("single", "packed")])
def test_cross_packing_checkpoint_restore(tmp_path, first, second):
    """Save under one packing, restore under another, finish the run:
    bitwise identical to the uninterrupted solo windows."""
    plans = {"packed": MeshPlan.packed(tuple(range(8))),
             "single": MeshPlan.single()}
    spec = EnsembleSpec(base=_hydro_params(nstepmax=6), nmember=8,
                        perturb_amp=0.01)
    eng = EnsembleEngine(spec, dtype=jnp.float64, plan=plans[first])
    eng.run(chunk=4, nstepmax=4)          # first window only
    snap = eng.save(str(tmp_path))
    meta = json.load(open(os.path.join(snap, "ensemble.json")))
    assert meta["packing"]["mode"] == first

    eng2 = EnsembleEngine.from_checkpoint(spec, snap,
                                          dtype=jnp.float64,
                                          plan=plans[second])
    eng2.run(chunk=4)                     # remaining (2,) window
    assert eng2.run_complete() and eng2.nstep == 6
    for k in range(8):
        solo_u, solo_t = _solo_windows(spec, k, (4, 2))
        ms = eng2.member_state(k)
        assert np.asarray(ms["u"]).tobytes() == \
            np.asarray(solo_u).tobytes(), (first, second, k)
        assert ms["t"] == solo_t


# ---------------------------------------------------------------------
# one stacked fetch per chunk
# ---------------------------------------------------------------------
def test_multigroup_single_stacked_fetch_per_chunk(monkeypatch):
    """A static sweep that splits into TWO sub-batch groups still costs
    exactly ONE host round-trip per chunk: both groups' windows are
    dispatched async, then fetched in a single stacked device_get."""
    kw = dict(nmember=2, sweeps={"hydro.gamma": [1.4, 5.0 / 3.0]})
    # warm the compile caches so the counted run is pure dispatch
    EnsembleEngine(EnsembleSpec(base=_hydro_params(), **kw),
                   dtype=jnp.float64).run(chunk=4)
    eng = EnsembleEngine(EnsembleSpec(base=_hydro_params(), **kw),
                         dtype=jnp.float64)
    assert len(eng.groups) == 2
    calls = {"n": 0}
    real = jax.device_get

    def counted(x, _c=calls, _r=real):
        _c["n"] += 1
        return _r(x)

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", counted)
        eng.run(chunk=4)                  # windows (4, 2) -> 2 chunks
    assert eng.run_complete()
    assert calls["n"] == 2, calls


# ---------------------------------------------------------------------
# gang serve + shared compile cache
# ---------------------------------------------------------------------
_TINY_NML = """&RUN_PARAMS
hydro=.true.
nstepmax=2
/
&AMR_PARAMS
levelmin=2
levelmax=2
/
&OUTPUT_PARAMS
tend=1e9
/
&INIT_PARAMS
d_region=1.0
p_region=1e-5
/
&ENSEMBLE_PARAMS
nmember=2
perturb_amp=1e-3
perturb_seed=7
chunk_steps=2
/
"""


class _CapTel:
    closed = False

    def __init__(self):
        self.events = []

    def record_event(self, kind, **kw):
        self.events.append((kind, kw))

    def close(self, *a, **k):
        pass


@pytest.mark.skipif(NDEV < 2, reason="needs a multi-device mesh")
def test_gang_serve_overlaps_small_jobs(tmp_path):
    """Three packable small jobs gang onto disjoint submeshes in ONE
    claim round; every result records its packing and the gang's
    busy-device fraction."""
    qd = str(tmp_path / "q")
    for i in range(3):
        jq.submit(qd, _TINY_NML, job_id=f"small{i}")
    tel = _CapTel()
    serve(qd, idle_exit=True, max_attempts=1, telemetry=tel,
          log=lambda *a, **k: None)
    done = sorted(os.listdir(os.path.join(qd, "done")))
    assert done == [f"small{i}.json" for i in range(3)]
    gangs = [kw for kind, kw in tel.events if kind == "gang_schedule"]
    assert gangs and max(len(g["job_ids"]) for g in gangs) > 1
    for name in done:
        rec = json.load(open(os.path.join(qd, "done", name)))
        res = rec["result"]
        assert res["packing"]["mode"] in ("packed", "single")
        assert res["gang"]["jobs"] > 1
        assert 0.0 < res["gang"]["busy_frac"] <= 1.0
        assert res["queue_wait_s"] >= 0.0
        assert res["scenarios_per_device_s"] > 0.0


@pytest.mark.slow
def test_second_worker_zero_miss_cold_start(tmp_path):
    """The queue's shared persistent compile cache: worker 1 compiles a
    config cold, worker 2 (a fresh process) serves the SAME config with
    zero compile-cache misses."""
    qd = str(tmp_path / "q")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import sys; from ramses_tpu.ensemble.service import serve;"
            "serve(sys.argv[1], idle_exit=True, max_jobs=1,"
            "      max_attempts=1)")
    # sequential submits: each fresh worker process serves exactly one
    # job, so the second worker's cache stats are a true cold start
    for jid in ("first", "second"):
        jq.submit(qd, _TINY_NML, job_id=jid)
        subprocess.run([sys.executable, "-c", code, qd], env=env,
                       check=True, timeout=300)
    assert os.path.isdir(os.path.join(qd, "compile_cache"))
    recs = {name.split(".")[0]: json.load(
        open(os.path.join(qd, "done", name)))
        for name in os.listdir(os.path.join(qd, "done"))}
    assert set(recs) == {"first", "second"}
    first, second = recs["first"]["result"], recs["second"]["result"]
    assert first["compile_cache_misses"] > 0       # cold queue
    assert second["compile_cache_misses"] == 0, second
    assert second["compile_cache_hits"] > 0
