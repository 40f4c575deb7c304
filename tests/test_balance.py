"""Cost-weighted Hilbert load balancing (``parallel/balance.py`` — the
``load_balance.f90``/``cost_weighting`` role on the sharded AMR path).

Oracles:
  * the capacity-constrained weighted cuts are feasible and balanced to
    one-oct granularity;
  * layouts are pure row permutations: a forced rebalance must leave
    the evolved physics identical to the identity-layout run (single
    device exercises every remap with zero communication effects);
  * the same with self-gravity + particles (gravity maps, PM deposit
    maps, migration under layouts);
  * a refinement ladder piled into one corner octant on the 8-device
    mesh triggers a natural rebalance, the per-device summed cost lands
    within the padding bound at every level, explicit ppermute halo
    schedules run on a >=4k-oct partial level, and mesh-of-8 ==
    mesh-of-1 on the evolved state;
  * the rebalance is observable: measured imbalance drops and the
    screen block reports it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.config import params_from_dict, params_from_string
from ramses_tpu.parallel import balance
from ramses_tpu.parallel.amr_sharded import ShardedAmrSim
from ramses_tpu.pm.particles import ParticleSet


# ---------------------------------------------------------------- unit

@pytest.mark.smoke
def test_balanced_cuts_uniform():
    w = np.ones(64)
    counts = balance.balanced_cuts(w, 8, 8)
    assert counts.sum() == 64 and (counts == 8).all()


@pytest.mark.smoke
def test_balanced_cuts_skewed_within_capacity():
    rng = np.random.default_rng(0)
    w = rng.uniform(0.5, 1.5, 100)
    w[:10] *= 50.0                       # heavy head
    counts = balance.balanced_cuts(w, 8, 16)
    assert counts.sum() == 100 and (counts <= 16).all() and (counts >= 0).all()
    # per-device cost within one max-weight of the ideal share wherever
    # the capacity clamp is not binding
    cuts = np.concatenate([[0], np.cumsum(counts)])
    per = np.array([w[a:b].sum() for a, b in zip(cuts[:-1], cuts[1:])])
    free = counts < 16
    assert (per[free] <= w.sum() / 8 + w.max() + 1e-12).all()


@pytest.mark.smoke
def test_balanced_cuts_exact_capacity_and_infeasible():
    counts = balance.balanced_cuts(np.ones(24), 3, 8)
    assert (counts == 8).all()
    with pytest.raises(ValueError):
        balance.balanced_cuts(np.ones(25), 3, 8)


@pytest.mark.smoke
def test_make_layout_roundtrip_and_remap_sentinels():
    rng = np.random.default_rng(1)
    order = rng.permutation(21).astype(np.int64)
    counts = balance.balanced_cuts(np.ones(21)[order], 4, 6)
    lay = balance.make_layout(order, counts, 24, 4)
    # inverse relation, per-segment placement
    assert (lay.row_oct[lay.oct_row] == np.arange(21)).all()
    for d in range(4):
        seg = lay.row_oct[d * 6:(d + 1) * 6]
        n = int(lay.counts[d])
        assert (seg[:n] >= 0).all() and (seg[n:] == -1).all()
    # value remaps: real indices move, sentinels pass through
    v = np.array([0, 20, -1, 21, 100], dtype=np.int32)
    r = balance.remap_octs(v, lay)
    assert r[0] == lay.oct_row[0] and r[1] == lay.oct_row[20]
    assert r[2] == -1 and r[3] == 21 and r[4] == 100
    ttd = 4
    c = np.array([0, 5, 21 * ttd - 1, 21 * ttd, -1], dtype=np.int32)
    rc = balance.remap_cells(c, lay, ttd)
    assert rc[0] == lay.oct_row[0] * ttd
    assert rc[1] == lay.oct_row[1] * ttd + 1
    assert rc[2] == lay.oct_row[20] * ttd + ttd - 1
    assert rc[3] == 21 * ttd and rc[4] == -1


# ------------------------------------------------------- invariance

def _sedov_groups(lb, lmin=3, lmax=5):
    g = {
        "run_params": {"hydro": True},
        "amr_params": {"levelmin": lmin, "levelmax": lmax, "boxlen": 1.0,
                       "load_balance": lb},
        "init_params": {"nregion": 2,
                        "region_type": ["square", "square"],
                        "x_center": [0.25, 0.75], "y_center": [0.5, 0.5],
                        "length_x": [0.5, 0.5], "length_y": [10.0, 10.0],
                        "exp_region": [10.0, 10.0],
                        "d_region": [1.0, 0.125],
                        "p_region": [1.0, 0.1]},
        "hydro_params": {"gamma": 1.4, "courant_factor": 0.8,
                         "riemann": "hllc", "slope_type": 1},
        "refine_params": {"err_grad_d": 0.05, "err_grad_p": 0.05},
        "output_params": {"tend": 0.05},
    }
    return {k: dict(v) for k, v in g.items()}


def _cmp_state(sim_a, sim_b, rtol, atol):
    for l in sim_a.levels():
        a = sim_a.tree_order_cells(np.asarray(sim_a.u[l]), l)
        b = sim_b.tree_order_cells(np.asarray(sim_b.u[l]), l)
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=f"lvl {l}")


@pytest.mark.slow
def test_forced_layout_single_device_invariance():
    """A forced Hilbert relayout is a pure row permutation: the evolved
    run must match the identity-layout run to roundoff, and the screen
    block must report the rebalance."""
    from ramses_tpu.utils.ops import OpsGuard

    sim0 = AmrSim(params_from_dict(_sedov_groups(False), ndim=2),
                  dtype=jnp.float64)
    sim1 = AmrSim(params_from_dict(_sedov_groups(True), ndim=2),
                  dtype=jnp.float64)
    sim0.evolve(0.015)
    sim1.evolve(0.015)
    sim1.request_rebalance()
    sim1.regrid()
    assert sim1.layouts, "forced rebalance adopted no layout"
    assert sim1._rebalance_count == 1
    # a layout level's real rows are scattered: [:noct] slicing invalid
    l = max(sim1.layouts)
    assert not np.array_equal(sim1.layouts[l].oct_row,
                              np.arange(sim1.layouts[l].noct))
    line = OpsGuard(sim1, install_signals=False).screen_block()
    assert " lb[" in line and "nreb=1" in line and "imb=" in line
    sim0.evolve(0.03)
    sim1.evolve(0.03)
    assert sim0.nstep == sim1.nstep
    for l in sim0.levels():
        assert sim0.tree.noct(l) == sim1.tree.noct(l)
    np.testing.assert_allclose(np.asarray(sim0.totals()),
                               np.asarray(sim1.totals()), rtol=1e-12)
    _cmp_state(sim0, sim1, rtol=1e-11, atol=1e-12)


@pytest.mark.slow          # ~13s; nightly tier on the 1-core box
def test_forced_layout_gravity_pm_invariance():
    """Layout transform correctness through the gravity maps (nb /
    ghost / mg ladder) and PM deposit maps: particles + CG self-gravity
    evolve identically under a forced relayout."""
    def _params(lb):
        txt = "\n".join([
            "&RUN_PARAMS", "hydro=.true.", "poisson=.true.",
            "pic=.true.", "/",
            "&AMR_PARAMS", "levelmin=3", "levelmax=5", "boxlen=1.0",
            f"load_balance={'.true.' if lb else '.false.'}",
            "load_balance_threshold=1.05", "cost_weight_part=0.5", "/",
            "&POISSON_PARAMS", "solver='cg'", "epsilon=1e-12", "/",
            "&INIT_PARAMS", "nregion=1", "region_type(1)='square'",
            "d_region=1.0", "p_region=1.0", "/",
            "&HYDRO_PARAMS", "riemann='hllc'", "courant_factor=0.5", "/",
            "&REFINE_PARAMS", "x_refine=0,0,0.25,0.25",
            "y_refine=0,0,0.25,0.25", "r_refine=-1,-1,0.2,0.2",
            "exp_refine=10,10,10,10", "/",
        ])
        return params_from_string(txt, ndim=2)

    # the new &AMR_PARAMS keys parse from namelist text
    p1 = _params(True)
    assert p1.amr.load_balance is True
    assert p1.amr.load_balance_threshold == 1.05
    assert p1.amr.cost_weight_part == 0.5

    rng = np.random.default_rng(7)
    x0 = np.concatenate([rng.uniform(0.05, 0.45, (48, 2)),
                         rng.uniform(0.0, 1.0, (16, 2))])
    v0 = rng.uniform(-0.05, 0.05, (64, 2))
    ps = ParticleSet.make(x0, v0, np.full(64, 1.0 / 64))
    sim0 = AmrSim(_params(False), dtype=jnp.float64,
                  particles=jax.device_put(ps))
    sim1 = AmrSim(p1, dtype=jnp.float64, particles=jax.device_put(ps))
    sim0.evolve(0.02, nstepmax=2)
    sim1.evolve(0.02, nstepmax=2)
    sim1.request_rebalance()
    sim1.regrid()
    sim0.regrid()
    assert sim1.layouts
    # equalize gravity warm-start state: the layout change cold-starts
    # sim1's solver (phi/fg pruned by design) — clear sim0's too so the
    # dt paths see the same inputs
    for s in (sim0, sim1):
        s.phi.clear()
        s.fg.clear()
        s._dt_cache = None
    for _ in range(4):
        sim0.step_coarse(sim0.coarse_dt())
        sim1.step_coarse(sim1.coarse_dt())
    np.testing.assert_allclose(np.asarray(sim0.totals()),
                               np.asarray(sim1.totals()),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(sim0.p.x),
                               np.asarray(sim1.p.x),
                               rtol=1e-9, atol=1e-11)
    _cmp_state(sim0, sim1, rtol=1e-8, atol=1e-10)


# -------------------------------------------------- sharded, skewed

def _skew_groups(lb, lmin=5, lmax=8):
    g = {
        "run_params": {"hydro": True},
        "amr_params": {"levelmin": lmin, "levelmax": lmax, "boxlen": 1.0,
                       "load_balance": lb},
        "init_params": {"nregion": 2,
                        "region_type": ["square", "square"],
                        "x_center": [0.3, 0.8], "y_center": [0.3, 0.8],
                        "length_x": [0.4, 0.6], "length_y": [0.4, 0.6],
                        "exp_region": [2.0, 2.0],
                        "d_region": [1.0, 0.25],
                        "p_region": [1.0, 0.2]},
        "hydro_params": {"gamma": 1.4, "courant_factor": 0.8,
                         "riemann": "hllc", "slope_type": 1},
        # geometric-only refinement: a sup-norm box in one corner at
        # every level -> a deterministic ladder piled into one octant
        "refine_params": {"r_refine": [-1.0] * (lmin - 1)
                          + [0.56] * (lmax - lmin),
                          "x_refine": [0.0] * (lmax - 1),
                          "y_refine": [0.0] * (lmax - 1),
                          "exp_refine": [10.0] * (lmax - 1)},
        "output_params": {"tend": 1.0},
    }
    return {k: dict(v) for k, v in g.items()}


@pytest.mark.slow
def test_skewed_tree_sharded_rebalances_and_matches_single_device():
    """The acceptance scenario: refinement piled into one corner octant
    on the 8-device mesh.  The natural (threshold) rebalance must fire,
    per-device summed cost must land within one-oct granularity of the
    ideal share at every level on a tree with a >=4k-oct partial
    level, and the evolved state must match the single-device run."""
    assert len(jax.devices()) >= 8
    LMIN, LMAX = 5, 8
    sim1 = AmrSim(params_from_dict(_skew_groups(False), ndim=2),
                  dtype=jnp.float64)
    sim8 = ShardedAmrSim(params_from_dict(_skew_groups(True), ndim=2),
                         devices=jax.devices()[:8], dtype=jnp.float64)
    for _ in range(LMAX - LMIN):
        sim1.regrid()
        sim8.regrid()
    assert ({l: sim1.tree.noct(l) for l in sim1.levels()}
            == {l: sim8.tree.noct(l) for l in sim8.levels()})
    # the finest level is partial and big enough to matter
    noct = sim8.tree.noct(LMAX)
    assert noct >= 4096
    assert noct < int(np.prod(sim8.tree.oct_dims(LMAX)))
    # the natural rebalance fired (blind row splits of a Morton-packed
    # corner put nearly everything on the first devices)
    assert sim8._rebalance_count >= 1 and sim8.layouts
    assert sim8.balance_stats is not None
    # per-device summed cost within one-oct granularity of the ideal
    # share at every level (the bucket-padding bound)
    for l in sim8.levels():
        w = balance.oct_costs(sim8, l)
        lay = sim8.layouts.get(l)
        cap = (lay.noct_pad if lay is not None
               else sim8._noct_pad(l, len(w))) // sim8.ndev
        rows = lay.oct_row if lay is not None else np.arange(len(w))
        per = np.bincount(rows // cap, weights=w, minlength=sim8.ndev)
        assert per.max() <= w.sum() / sim8.ndev + w.max() + 1e-9, l
    # observable: the adopted layouts beat the identity split
    imb_identity = balance.measure(sim8, {}).imbalance
    imb_balanced = balance.measure(sim8).imbalance
    assert imb_balanced < imb_identity
    assert sim8.balance_stats.imbalance == pytest.approx(imb_balanced)
    # mesh-of-8 == mesh-of-1 on the evolved state
    sim1.step_coarse(sim1.coarse_dt())
    sim8.step_coarse(sim8.coarse_dt())
    # total momentum is zero by symmetry (~1e-18 of round-off against a
    # mass of 0.18), and the mesh's scatter-add fold sums the coarse
    # corrections in another order than one device: absolute tolerance
    # at round-off of the largest total
    t1, t8 = np.asarray(sim1.totals()), np.asarray(sim8.totals())
    np.testing.assert_allclose(t1, t8, rtol=1e-12,
                               atol=1e-12 * np.abs(t1).max())
    _cmp_state(sim1, sim8, rtol=1e-11, atol=1e-12)
