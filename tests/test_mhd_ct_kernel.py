"""The tiled CT kernel (``mhd/pallas_ct.ct_step_tiled``) and the loop form
of the uniform MHD step program (``mhd/uniform._run_steps_kernel``).

The CPU suite never reaches them through ``run_steps`` (``kernel_ok`` is
false off the TPU), so they are driven directly, the kernel interpreted.

Bit for bit, and where.  The kernel re-spells ``mu.ct_core`` row by row:
the same operations on the same numbers in the same order.  XLA's CPU
backend contracts ``a * b + c`` into one fused multiply-add wherever both
land in one fusion ("always allow FMA fusion", its compiler options say),
so two spellings of ONE expression graph differ in the last bit there:
7e-7 absolute at most on these states.  The bitwise cases therefore run in
child processes whose backend has no FMA to contract with
(``tests/no_fma_child.py``): there kernel = ``mu.step`` and loop = scan
to the bit.  The same comparisons in this process hold to a few ulps
(tolerance: 2e-5 relative, 2e-6 absolute, the hydro kernel tests').
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

import no_fma_child  # noqa: E402  (tests/ is on the path: rootdir conftest)
from ramses_tpu.mhd import core, pallas_ct as pc, uniform as mu  # noqa: E402
from ramses_tpu.mhd.core import MhdStatic  # noqa: E402

CFG = MhdStatic(riemann="hlld", riemann2d="llf")
PERIODIC = ((0, 0),) * 3
F32 = jnp.float32


def _curl(a):
    return np.stack([
        (np.roll(a[2], -1, 1) - a[2]) - (np.roll(a[1], -1, 2) - a[1]),
        (np.roll(a[0], -1, 2) - a[0]) - (np.roll(a[2], -1, 0) - a[2]),
        (np.roll(a[1], -1, 0) - a[1]) - (np.roll(a[0], -1, 1) - a[0])])


def make_state(shape, kind, seed=3):
    """(u [8, *shape], bf [3, *shape]) float32, div B = 0 to round-off.
    ``blast``: the benchmark cell's initial condition in small (uniform
    oblique field, an over-pressured sphere); ``random``: every variable
    random, the field the curl of a random edge potential."""
    rng = np.random.default_rng(seed)
    q = np.zeros((8,) + shape)
    if kind == "blast":
        x = np.meshgrid(*[(np.arange(n) + 0.5) / n for n in shape],
                        indexing="ij")
        r2 = sum((xi - 0.5) ** 2 for xi in x)
        q[0] = 1.0
        q[4] = np.where(r2 < 0.09, 10.0, 0.1)
        bf = np.broadcast_to(
            np.array([0.70710678, 0.70710678, 0.0])[:, None, None, None],
            (3,) + shape).copy()
    else:
        bf = _curl(0.05 * rng.standard_normal((3,) + shape)) \
            + np.array([0.7, 0.7, 0.1])[:, None, None, None]
        q[0] = 1 + 0.3 * rng.random(shape)
        q[1:4] = 0.3 * rng.standard_normal((3,) + shape)
        q[4] = 0.5 + rng.random(shape)
    for c in range(3):
        q[5 + c] = 0.5 * (bf[c] + np.roll(bf[c], -1, c))
    u = core.prim_to_cons(jnp.asarray(q), CFG).astype(F32)
    return u, jnp.asarray(bf, F32)


def make_grid(shape):
    return mu.MhdGrid(cfg=CFG, shape=shape, dx=1.0 / shape[0],
                      bc_kinds=PERIODIC)


def kernel_step(grid, u, bf, dt):
    un, bcn, bfn, rate = pc.ct_step_tiled(
        pc.pad_xy(u[:pc.NHYDRO]), pc.pad_xy(bf), dt, grid.cfg, grid.dx,
        grid.shape, interpret=True)
    return (jnp.concatenate([un, bcn]), bfn,
            grid.cfg.courant_factor / rate[0, 0])


def _interpreting(real):
    return lambda *a, **kw: real(*a, **dict(kw, interpret=True))


@pytest.fixture()
def interpreted(monkeypatch):
    """The loop form's kernel runs interpreted."""
    monkeypatch.setattr(pc, "ct_step_tiled",
                        _interpreting(pc.ct_step_tiled))


# (id, shape, state): a 128-lane z everywhere.  ``wrap``: one tile whose
# every halo row is a periodic wrap of its own interior; ``tiles``: two x
# tiles and two y tiles, halos from neighbours and wraps.
STEP_CASES = [
    ("blast-wrap", (8, 8, 128), "blast"),
    ("random-wrap", (8, 8, 128), "random"),
    ("random-tiles", (32, 16, 128), "random"),
]
# (id, nsteps, tend in first dts or None = far away, steps expected,
#  dt_scale, dtype of the time axis)
LOOP_CASES = [
    ("all4", 4, None, 4, 1.0, "float32"),
    ("tend-after-3-of-4", 4, 2.5, 3, 1.0, "float32"),
    ("tend-not-after-t", 4, 0.0, 0, 1.0, "float32"),
    ("half-dt", 4, None, 4, 0.5, "float32"),
    ("f64-time-3-of-4", 4, 2.5, 3, 1.0, "float64"),
]
LOOP_SHAPE = (8, 8, 128)


def step_case(name):
    _, shape, kind = next(c for c in STEP_CASES if c[0] == name)
    grid = make_grid(shape)
    u, bf = make_state(shape, kind)
    dt = mu.cfl_dt(grid, u, bf)
    want = mu._jit_step(grid, u, bf, dt)
    want += (mu.cfl_dt(grid, *want),)
    return kernel_step(grid, u, bf, dt), want


def loop_case(name):
    _, nsteps, tend_dts, want_n, dt_scale, tdtype = next(
        c for c in LOOP_CASES if c[0] == name)
    grid = make_grid(LOOP_SHAPE)
    u, bf = make_state(LOOP_SHAPE, "random", seed=13)
    t0 = 0.25
    first = float(mu.cfl_dt(grid, u, bf)) * dt_scale
    tend = 1e9 if tend_dts is None else t0 + tend_dts * first
    t, tend = jnp.asarray(t0, tdtype), jnp.asarray(tend, tdtype)
    got = mu._run_steps_kernel(grid, u, bf, t, tend, nsteps,
                               dt_scale=dt_scale)
    want = mu.run_steps(grid, u, bf, t, tend, nsteps, dt_scale=dt_scale)
    return got, want, want_n, (u, bf, t, tend)


def batch_case():
    """``run_steps_batch``'s use: ``vmap`` of the loop over members with
    different ``tend``s, against the solo runs."""
    grid = make_grid(LOOP_SHAPE)
    states = [make_state(LOOP_SHAPE, "random", seed=s) for s in (31, 32, 33)]
    us = jnp.stack([s[0] for s in states])
    bfs = jnp.stack([s[1] for s in states])
    ts = jnp.asarray([0.0, 0.5, 0.25], F32)
    third = float(mu.cfl_dt(grid, *states[2]))
    tends = jnp.asarray([1e9, 0.5, 0.25 + 2.5 * third], F32)
    got = jax.vmap(lambda u, bf, t, te: mu._run_steps_kernel(
        grid, u, bf, t, te, 4))(us, bfs, ts, tends)
    solo = [mu._run_steps_kernel(grid, us[i], bfs[i], ts[i], tends[i], 4)
            for i in range(3)]
    return got, solo


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(got) != len(want):
        return "lengths differ"
    for i, (g, w) in enumerate(zip(got, want)):
        if g.dtype != w.dtype or g.shape != w.shape:
            return f"leaf {i}: {g.dtype}{g.shape} vs {w.dtype}{w.shape}"
        if not np.array_equal(np.asarray(g), np.asarray(w)):
            return (f"leaf {i}: max |diff| "
                    f"{float(jnp.max(jnp.abs(g - w))):.3e}")
    return ""


# The bitwise cases, grouped by what they compile (a group's cases share
# shapes and static arguments); each group is one child process.
CHILD_GROUPS = [
    ["step/blast-wrap", "step/random-wrap"],
    ["step/random-tiles"],
    ["loop/all4", "loop/tend-after-3-of-4", "loop/tend-not-after-t"],
    ["loop/half-dt"],
    ["batch/members"],
]


def child_main(cases):
    """The bitwise cases, in a process without FMA: one JSON object
    ``{case id: "" when the two sides agree to the bit, else why not}``."""
    jax.config.update("jax_enable_x64", True)
    pc.ct_step_tiled = _interpreting(pc.ct_step_tiled)
    out = {}
    for case in cases:
        kind, name = case.split("/")
        if kind == "step":
            out[case] = _same_bits(*step_case(name))
        elif kind == "batch":
            got, solo = batch_case()
            out[case] = "; ".join(filter(None, (
                _same_bits(tuple(g[i] for g in got), solo[i])
                for i in range(3)))) or (
                "" if list(np.asarray(got[3])) == [4, 0, 3] else "ndone")
        else:
            got, want, want_n, _ = loop_case(name)
            out[case] = _same_bits(got, want) or (
                "" if int(got[3]) == want_n else f"ndone {int(got[3])}")
    print("RESULT " + json.dumps(out))


class _Children:
    """Every group of :data:`CHILD_GROUPS` started at once, each in a child
    without FMA (``tests/no_fma_child.py``); a case's answer waits for its
    own group only."""

    def __init__(self):
        self.procs = {tuple(group): no_fma_child.start(__file__,
                                                       ",".join(group))
                      for group in CHILD_GROUPS}
        self.results = {}

    def __getitem__(self, case):
        if case not in self.results:
            group = next(g for g in self.procs if case in g)
            self.results.update(no_fma_child.result(self.procs[group]))
        return self.results[case]


@pytest.fixture(scope="module")
def no_fma_children():
    children = _Children()
    yield children
    for proc in children.procs.values():
        if proc.poll() is None:
            proc.kill()


@pytest.mark.parametrize("case", [c[0] for c in STEP_CASES])
def test_kernel_is_mu_step_bit_for_bit(no_fma_children, case):
    """Kernel (interpreted) = ``mu.step``: cells, faces and the next
    step's dt, to the bit, where the backend cannot contract FMAs."""
    assert no_fma_children["step/" + case] == ""


@pytest.mark.parametrize("case", [g for grp in CHILD_GROUPS[2:4] for g in grp])
def test_loop_form_is_scan_form_bit_for_bit(no_fma_children, case):
    """``_run_steps_kernel`` (a ``while_loop`` that stops when no step is
    owed) = the XLA scan ``run_steps``: ``(u, bf, t, ndone)`` to the bit."""
    assert no_fma_children[case] == ""


def test_batch_members_are_solo_runs_bit_for_bit(no_fma_children):
    """``run_steps_batch``'s ``vmap`` of the loop leaves each member what
    its solo run gives (members owing 4, 0 and 3 steps)."""
    assert no_fma_children["batch/members"] == ""


def _close(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)


def test_kernel_close_to_mu_step_in_process():
    """The same comparison on the suite's own backend (FMAs contracted per
    fusion): a few ulps, never a wrong halo or a dropped term."""
    got, want = step_case("random-tiles")
    _close(got, want)
    assert pc.block_stats()[-1]["halo"] == 3


def test_loop_close_to_scan_in_process(interpreted):
    """The f64 time axis (the suite's x64) included: the sweep stays in
    the state's dtype."""
    got, want, want_n, (u, bf, t, tend) = loop_case("f64-time-3-of-4")
    _close(got, want)
    assert int(got[3]) == want_n == int(want[3])
    assert got[2] == tend          # the clip lands on tend exactly
    got = mu._run_steps_kernel(make_grid(LOOP_SHAPE), u, bf, t, t, 4)
    assert int(got[3]) == 0 and got[2] == t     # no step: the input back
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(u))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(bf))


def test_divb_stays_at_round_off_through_the_kernel(interpreted):
    """32 steps of the kernel loop leave max |div B| dx / max |B| at float32
    round-off (random-walking up from the initial 1e-7: reason for the
    1e-5), and the check sees a face that is off by 1e-3."""
    grid = make_grid(LOOP_SHAPE)
    u, bf = make_state(LOOP_SHAPE, "random", seed=7)

    def divb(b):
        d = core.div_b([b[c] for c in range(3)], (grid.dx,) * 3, 3)
        return float(jnp.max(jnp.abs(d)) * grid.dx / jnp.max(jnp.abs(b)))

    t, tend = jnp.asarray(0.0, F32), jnp.asarray(1e9, F32)
    for _ in range(2):
        u, bf, t, nd = mu._run_steps_kernel(grid, u, bf, t, tend, 16)
        assert int(nd) == 16
    assert bool(jnp.isfinite(u).all() & jnp.isfinite(bf).all())
    assert divb(bf) < 1e-5
    assert divb(bf.at[1, 3, 4, 5].add(1e-3)) > 5e-4


def test_gate_and_block_rule():
    """What the gate admits has a compile case (``test_chip_compile``):
    cubes of 128 and 256 cells a side, f32, periodic, minmod + hlld + llf;
    and the kernel is not taken off the TPU."""
    assert pc._pick_block((256, 256, 256)) == (8, pc.BY)
    assert pc._pick_block((128, 128, 128)) == (16, pc.BY)
    assert pc._pick_block((512, 512, 512)) == (None, None)
    assert pc._pick_block((64, 64, 64)) == (None, None)
    assert pc._pick_block((64, 12, 128)) == (None, None)
    ok = (256,) * 3
    assert pc.supports(CFG, ok, PERIODIC, F32)
    assert pc.supports(CFG, (128,) * 3, PERIODIC, F32)
    # a box that is no cube has a block pick (the interpreted cases
    # above use such boxes) but no compile case: declined
    assert pc._pick_block((16, 8, 128))[0] and pc._pick_block(
        (128, 128, 256))[0]
    assert not pc.supports(CFG, (16, 8, 128), PERIODIC, F32)
    assert not pc.supports(CFG, (128, 128, 256), PERIODIC, F32)
    assert not pc.supports(CFG, ok, ((2, 2), (0, 0), (0, 0)), F32)
    assert not pc.supports(CFG, ok, ((0, 0), (0, 0), (2, 2)), F32)
    assert not pc.supports(CFG, ok, PERIODIC, jnp.float64)
    assert not pc.supports(CFG, ok, PERIODIC, jnp.bfloat16)
    import dataclasses
    for change in ({"riemann": "llf"}, {"riemann2d": "hlld"},
                   {"slope_type": 2}, {"npassive": 1}, {"ndim": 2}):
        assert not pc.supports(dataclasses.replace(CFG, **change), ok,
                               PERIODIC, F32)
    assert not pc.kernel_available(CFG, ok, PERIODIC, F32)   # CPU backend
    assert not mu.kernel_ok(make_grid(ok), F32)


if __name__ == "__main__":
    child_main(sys.argv[1].split(","))
