"""Canonical lowered step-chain programs the rule engine audits.

Generalizes ``telemetry/hlo.py lower_fused_step`` into an enumerator:
each driver family (uniform hydro, blocked/stencil AMR hydro, MHD CT,
RHD, RT-coupled, and — when the process has >1 device — the
row-sharded mesh) is built from a small canonical namelist on the CPU
backend and LOWERED only (trace, no compile, no execution past the
IC build), so the full enumeration costs seconds and the audited
StableHLO is exactly what a production run of that family would
compile.

Per-program ``meta`` carries the rule inputs: configured dtype bits
(``f64-leak``), donation expectation (``donation-miss``), partition
count (``nondeterministic-scatter``), and the gather budget
(``gather-blowup`` — budgets are the measured canonical-tree counts
with ~50% headroom, so a formulation regression trips the budget
while ordinary tree drift does not).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

# Canonical 2D Sedov used by the hydro AMR programs: two partial
# levels, small enough that the full build-and-lower is ~seconds on
# one CPU core.
SEDOV2D = """
&RUN_PARAMS
hydro=.true.
/
&AMR_PARAMS
levelmin=4
levelmax=5
boxlen=1.0
oct_blocking={blk}
/
&INIT_PARAMS
nregion=2
region_type(1)='square'
region_type(2)='point'
x_center=0.5,0.5
y_center=0.5,0.5
length_x=10.0,1.0
length_y=10.0,1.0
d_region=1.0,0.0
p_region=1e-5,0.1
/
&HYDRO_PARAMS
gamma=1.4
riemann='llf'
/
&REFINE_PARAMS
err_grad_p=0.1
/
"""

# gathered-element budgets of the canonical trees (measured on the
# seed lowering x ~1.5 headroom; a duplicated-batch regression is a
# >=2x jump, far past the headroom)
GATHER_BUDGETS = {
    "hydro_amr": 200_000,
    "mhd_amr": 800_000,
    "rhd_amr": 40_000,
    "rt_amr": 120_000,
    "hydro_amr_sharded": 400_000,
}


@dataclass
class Program:
    """One lowered program under audit."""
    name: str
    family: str                    # hydro | mhd | rhd | rt | uniform
    text: str
    meta: Dict[str, Any] = field(default_factory=dict)


def _dtype_bits(dtype) -> int:
    import jax.numpy as jnp
    return int(jnp.dtype(dtype).itemsize) * 8


def _from_sim(name: str, family: str, sim, text: Optional[str] = None,
              **meta) -> Program:
    from ramses_tpu.telemetry import hlo
    meta.setdefault("dtype_bits", _dtype_bits(sim.dtype))
    meta.setdefault("expect_donation", True)
    if name in GATHER_BUDGETS:
        meta.setdefault("gather_budget_elems", GATHER_BUDGETS[name])
    return Program(name=name, family=family,
                   text=text or hlo.lower_fused_step(sim), meta=meta)


def sim_program(sim, name: Optional[str] = None,
                text: Optional[str] = None) -> Program:
    """Audit-ready :class:`Program` for an already-built sim's fused
    step — the telemetry run-header hook (``analysis_findings``)
    audits the exact program the run measures through this.  Pass
    ``text`` when the caller already holds the lowering (the run
    header lowers once for the gather inventory and reuses it)."""
    family = "mhd" if hasattr(sim, "bfs") else "hydro"
    return _from_sim(name or type(sim).__name__, family, sim,
                     text=text)


# -- builders ---------------------------------------------------------
def _build_uniform() -> Program:
    import jax.numpy as jnp

    from ramses_tpu.config import params_from_string
    from ramses_tpu.driver import Simulation
    from ramses_tpu.grid.uniform import run_steps

    nml = "\n".join([
        "&RUN_PARAMS", "hydro=.true.", "/",
        "&AMR_PARAMS", "levelmin=5", "levelmax=5", "boxlen=1.0", "/",
        "&INIT_PARAMS", "nregion=1", "region_type(1)='square'",
        "d_region=1.0", "p_region=1.0", "/",
        "&OUTPUT_PARAMS", "tend=0.1", "/",
    ])
    sim = Simulation(params_from_string(nml, ndim=2),
                     dtype=jnp.float32)
    u = sim.state.u
    z = jnp.zeros((), u.dtype)
    text = run_steps.lower(sim.grid, u, z, z + 0.1, 4).as_text()
    # run_steps deliberately does NOT donate (the redo-step guard
    # retains the pre-window state) — expect_donation stays False
    return Program(name="hydro_uniform", family="uniform", text=text,
                   meta={"dtype_bits": 32, "expect_donation": False})


def _build_hydro_amr() -> Program:
    import jax.numpy as jnp

    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_string

    sim = AmrSim(params_from_string(SEDOV2D.format(blk=".true."),
                                    ndim=2), dtype=jnp.float32)
    # no ratio gate here: on the tiny 2D canonical tree the blocked
    # formulation gathers ~1.1x MORE than the stencil one (thin tiles,
    # low occupancy) — blocking pays off on deep 3D trees, which is
    # where the >=2x ratio gate lives (test_hlo_inventory slow tier,
    # through check_gather_ratio).  The budget is the gate here.
    return _from_sim("hydro_amr", "hydro", sim)


def _repo_path(rel: str) -> str:
    import os

    import ramses_tpu
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(ramses_tpu.__file__)))
    return os.path.join(root, rel)


def _build_mhd_amr() -> Program:
    import jax.numpy as jnp

    from ramses_tpu.config import load_params
    from ramses_tpu.mhd.amr import MhdAmrSim

    p = load_params(_repo_path("namelists/tube_mhd.nml"), ndim=2)
    p.amr.levelmin, p.amr.levelmax = 4, 5
    p.refine.err_grad_d = 0.05
    p.refine.err_grad_p = 0.05
    sim = MhdAmrSim(p, dtype=jnp.float32)
    return _from_sim("mhd_amr", "mhd", sim)


def _build_rhd_amr() -> Program:
    import jax.numpy as jnp

    from ramses_tpu.config import params_from_dict
    from ramses_tpu.rhd.amr import RhdAmrSim

    groups = {
        "run_params": {"hydro": True},
        "amr_params": {"levelmin": 4, "levelmax": 5, "boxlen": 1.0},
        "boundary_params": {"nboundary": 2,
                            "ibound_min": [-1, 1],
                            "ibound_max": [-1, 1],
                            "bound_type": [2, 2]},
        "init_params": {"nregion": 2,
                        "region_type": ["square", "square"],
                        "x_center": [0.25, 0.75],
                        "length_x": [0.5, 0.5],
                        "exp_region": [10.0, 10.0],
                        "d_region": [10.0, 1.0],
                        "p_region": [13.33, 1e-2]},
        "hydro_params": {"gamma": 5.0 / 3.0, "slope_type": 1},
        "refine_params": {"err_grad_d": 0.05, "err_grad_p": 0.05},
        "output_params": {"tend": 0.35},
    }
    sim = RhdAmrSim(params_from_dict(groups, ndim=1),
                    dtype=jnp.float32)
    return _from_sim("rhd_amr", "rhd", sim)


def _build_rt_amr() -> Program:
    import jax.numpy as jnp

    from ramses_tpu.amr.hierarchy import AmrSim
    from ramses_tpu.config import params_from_dict

    groups = {
        "run_params": {"hydro": True, "rt": True},
        "amr_params": {"levelmin": 3, "levelmax": 4, "boxlen": 1.0},
        "init_params": {"nregion": 1, "region_type": ["square"],
                        "x_center": [0.5], "y_center": [0.5],
                        "z_center": [0.5],
                        "length_x": [10.0], "length_y": [10.0],
                        "length_z": [10.0], "exp_region": [10.0],
                        "d_region": [1.0], "p_region": [1e-4]},
        "hydro_params": {"gamma": 5.0 / 3.0},
        "refine_params": {"err_grad_d": 0.05},
        "rt_params": {"rt_ndot": 1e48, "rt_c_fraction": 1e-4,
                      "rt_src_pos": [0.5, 0.5, 0.5],
                      "rt_otsa": True},
        "units_params": {"units_density": 1.66e-24,
                         "units_time": 3.15e13,
                         "units_length": 3.08e18},
        "output_params": {"tend": 0.01},
    }
    sim = AmrSim(params_from_dict(groups, ndim=3), dtype=jnp.float32)
    return _from_sim("rt_amr", "rt", sim)


def _build_hydro_amr_sharded() -> Optional[Program]:
    import jax
    import jax.numpy as jnp

    if jax.device_count() < 2:
        return None
    from ramses_tpu.config import params_from_string
    from ramses_tpu.parallel.amr_sharded import ShardedAmrSim

    # the shape a plain multi-device run compiles; it KEEPS one
    # accepted nondeterministic-scatter finding — the blocked tile
    # sweep folds the partial level's coarse corrections through a
    # scatter-add the partitioner may reassociate (it has repeated
    # bitwise wherever asked: tests/test_sharding.py, the four-chip
    # benchmark cell's laps).
    sim = ShardedAmrSim(
        params_from_string(SEDOV2D.format(blk=".true."), ndim=2),
        devices=jax.devices(), dtype=jnp.float32)
    return _from_sim("hydro_amr_sharded", "hydro", sim,
                     partitioned=True)


BUILDERS: Dict[str, Callable[[], Optional[Program]]] = {
    "hydro_uniform": _build_uniform,
    "hydro_amr": _build_hydro_amr,
    "mhd_amr": _build_mhd_amr,
    "rhd_amr": _build_rhd_amr,
    "rt_amr": _build_rt_amr,
    "hydro_amr_sharded": _build_hydro_amr_sharded,
}


def build_programs(names: Optional[List[str]] = None) -> List[Program]:
    """Build and lower the requested canonical programs (all by
    default; builders whose preconditions fail — e.g. the sharded
    program on a 1-device process — return None and are skipped).

    Builds run with x64 disabled regardless of the host config:
    production runs f32/i32, and the test suite's global
    ``jax_enable_x64`` would otherwise drag weak-typed python floats
    into the canonical lowerings as f64 select/multiply chains —
    exactly what ``f64-leak`` flags, but as a host-environment
    artifact rather than a program property."""
    import jax
    out: List[Program] = []
    with jax.enable_x64(False):
        for name, build in BUILDERS.items():
            if names is not None and name not in names:
                continue
            prog = build()
            if prog is not None:
                out.append(prog)
    return out
