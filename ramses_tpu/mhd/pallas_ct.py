"""MHD constrained-transport kernels (Pallas).

The XLA spelling of the CT pipeline (:func:`ramses_tpu.mhd.uniform.
step_padded`) materializes every stage — primitives, slopes, Hancock
predictor, six Riemann faces, corner EMFs — as an HBM-resident grid
array: 33 KB of traffic a cell update at 128^3 and no program at all at
256^3 (28 GB of temporaries).  Two kernels keep the intermediates in
VMEM:

* :func:`ct_step_tiled` — the uniform run's kernel (``mhd/uniform.
  run_steps``).  The box is tiled over (x, y); each grid step sees the
  FULL z extent in the lanes (periodic z wraps inside the kernel) and
  reads overlapping ``(bx+6) x 16 x nz`` windows of the pre-padded cell
  state and staggered faces (:func:`pad_xy`: 3 ghost rows a side — CT
  reaches 2 cells and 3 faces), the window design of
  ``hydro/pallas_muscl.fused_step_padded`` whose window constants and
  VMEM limit are imported.  It also returns the largest Courant rate of
  the updated state, so the next step's dt costs no pass of its own.
  Scope (:func:`supports`): ndim 3, f32, minmod slopes,
  ``riemann='hlld'``, ``riemann2d='llf'``, no passives, a periodic
  cube of 128 or 256 cells a side.  Whatever the gate declines keeps the XLA formulation, and
  the run says which it took: the ``[kernel]`` line of ``python -m
  ramses_tpu`` and ``run_header.sweep_block`` (:func:`block_stats`).
* :func:`ct_step_slab` — the slab-sharded advance's single-block kernel
  (:func:`ramses_tpu.parallel.dense_slab.mhd_ct_slab`): the whole
  halo-complete local box in one VMEM block (boxes up to ~69^3), masks
  and EMF overrides included; its body CALLS ``mu.step_padded``.  A
  local box over the budget keeps the XLA path.

The tiled kernel re-spells ``mu.ct_core`` row by row (lists of 3D
windows; Mosaic has no 4D stack or scatter to give) from the same row
helpers (``core.*_rows``, ``riemann.hlld_rows``, ``riemann2d.
corner_emf``): on the CPU, interpreted, it is ``mu.step`` bit for bit
(``tests/test_mhd_ct_kernel.py``).

Test hook: :data:`FORCE_INTERPRET` (env ``RAMSES_PALLAS_CT_INTERPRET``
or monkeypatch) runs the slab kernel through the Pallas interpreter on
any backend, which is how CI exercises that path on CPU.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ramses_tpu.hydro.pallas_muscl import BY, VMEM_LIMIT_BYTES, WY, _slopes
from ramses_tpu.mhd import core, riemann as rsolve, riemann2d as r2d
from ramses_tpu.mhd import uniform as mu
from ramses_tpu.mhd.core import IBX, IP, MhdStatic, NCOMP

DISABLED = bool(os.environ.get("RAMSES_NO_PALLAS"))

# run the kernel through the Pallas interpreter on any backend (CI hook)
FORCE_INTERPRET = bool(os.environ.get("RAMSES_PALLAS_CT_INTERPRET"))

_VMEM_BUDGET = 100 * 1024 * 1024
_LIVE_ARRAYS = 60          # ≈ peak live grid-sized intermediates of ct_core


def interpret_mode() -> bool:
    return FORCE_INTERPRET or jax.default_backend() != "tpu"


def slab_available(cfg: MhdStatic, loc, dtype) -> bool:
    """True when the single-block kernel may run for a local box of
    shape ``loc``: pallas importable, a compiled TPU backend (or the
    explicit :data:`FORCE_INTERPRET` test hook — NOT just any CPU run:
    the interpreter is a correctness vehicle, not a fast path), and the
    padded box inside the VMEM budget (a larger slab keeps the XLA
    path).  Compiled runs additionally require float32 (the f64 VPU
    story is interpret-only)."""
    if DISABLED:
        return False
    dt = jnp.dtype(dtype)
    if not FORCE_INTERPRET:
        if jax.default_backend() != "tpu":
            return False
        if not interpret_mode() and dt != jnp.dtype(jnp.float32):
            return False
    ext = 1
    for s in loc:
        ext *= s + 2 * (mu.NGHOST + 1)
    return ext * dt.itemsize * _LIVE_ARRAYS <= _VMEM_BUDGET


def ct_step_slab(up, bfp_ext, dt, dx: Sequence[float], cfg: MhdStatic,
                 okp=None, ovr: Optional[dict] = None,
                 interpret: bool = False):
    """``mu.step_padded`` as a single-block VMEM kernel.

    ``up`` [nvar, \\*sp+2·ng] padded cells (raw B slots), ``bfp_ext``
    [NCOMP, \\*sp+2·(ng+1)] padded low faces, ``okp`` optional padded
    refined mask (bool or arithmetic), ``ovr`` optional dict
    (d1,d2) → (padded bool mask, padded values).  Returns the padded
    ``(un, bfn_stacked)`` exactly like ``step_padded`` (``bfn`` stacked
    on axis 0 — iterable per component)."""
    nd = cfg.ndim
    pairs = [(d1, d2) for d1 in range(nd) for d2 in range(d1 + 1, nd)]
    dtype = up.dtype
    has_ok = okp is not None
    has_ovr = ovr is not None

    inputs = [jnp.asarray(dt, dtype).reshape(1), up, bfp_ext]
    if has_ok:
        inputs.append(okp.astype(dtype))
    if has_ovr:
        inputs.append(jnp.stack([ovr[p][0].astype(dtype) for p in pairs]))
        inputs.append(jnp.stack([ovr[p][1] for p in pairs]))

    def kern(*refs):
        it = iter(refs)
        dt_ref, up_ref, bf_ref = next(it), next(it), next(it)
        okp_k = (next(it)[...] > 0.5) if has_ok else None
        ovr_k = None
        if has_ovr:
            om, ov = next(it)[...], next(it)[...]
            ovr_k = {p: (om[i] > 0.5, ov[i])
                     for i, p in enumerate(pairs)}
        un_ref, bfn_ref = next(it), next(it)
        un, bfn = mu.step_padded(cfg, tuple(dx), up_ref[...],
                                 bf_ref[...], dt_ref[0],
                                 okp=okp_k, ovr=ovr_k)
        un_ref[...] = un
        bfn_ref[...] = jnp.stack(bfn)

    def _full(shape):
        rank = len(shape)
        return pl.BlockSpec(shape, lambda: (0,) * rank)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET + 28 * 1024 * 1024)
    un, bfn = pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [_full(a.shape) for a in inputs[1:]],
        out_specs=(_full(up.shape),
                   _full((NCOMP,) + up.shape[1:])),
        out_shape=(jax.ShapeDtypeStruct(up.shape, dtype),
                   jax.ShapeDtypeStruct((NCOMP,) + up.shape[1:], dtype)),
        interpret=interpret,
        **kwargs)(*inputs)
    return un, bfn


# ----------------------------------------------------------------------
# the tiled kernel of the uniform run
# ----------------------------------------------------------------------
KERNEL_NAME = "ct_step_tiled"    # the device op's name in a trace
HALO = mu.NGHOST + 1             # ghost rows a side: 2 cells, 3 faces
NHYDRO = 5                       # cell rows the kernel reads (B rows of
#                                  ``u`` are derived from the faces)

# The block budget, stated once: a tile is admitted when LIVE_WINDOWS
# f32 arrays of its window ((bx+6) x WY x nz) fit the scoped VMEM the
# call asks Mosaic for (``pallas_muscl.VMEM_LIMIT_BYTES``).
# LIVE_WINDOWS is a calibration, not a count read from Mosaic: 400
# picks nz 128 -> bx 16 and nz 256 -> bx 8 (windows of 176-224 KiB a
# variable), each with a compile case in ``tests/test_chip_compile.py``.
# By hand on the chip at 256^3 (PERF.md, PR 34) bx 8 was the fastest
# tile: a 16-step slice 0.554 s, bx 16 0.610, bx 4 0.602; bx 32 does
# not fit Mosaic's VMEM.
LIVE_WINDOWS = 400
LANES = (128, 256)               # lane extents with a compile case


def _window_fits(bx: int, nz: int) -> bool:
    return (bx + 2 * HALO) * WY * nz * 4 * LIVE_WINDOWS <= VMEM_LIMIT_BYTES


def _pick_block(shape):
    """(bx, by) or (None, None): z whole in the lanes, the hydro
    kernel's 8-row y tile in its 16-row window (3 halo rows a side + 2
    junk), x the largest tile the budget admits."""
    nx, ny, nz = shape
    if nz not in LANES or ny % BY:
        return None, None
    for bx in (32, 16, 8, 4):
        if nx % bx == 0 and _window_fits(bx, nz):
            return bx, BY
    return None, None


def supports(cfg: MhdStatic, shape, bc_kinds, dtype) -> bool:
    """True when the tiled kernel covers this configuration."""
    if cfg.ndim != 3 or cfg.npassive != 0 or len(shape) != 3:
        return False
    if cfg.slope_type != 1 or cfg.riemann != "hlld" \
            or cfg.riemann2d != "llf":
        return False
    # z wraps inside the kernel; x/y ghosts are periodic copies (an
    # outflow edge would also need ``cfl_dt``'s wrapped centring of the
    # last cell reproduced in the kernel's Courant rate)
    if any(tuple(k) != (0, 0) for k in bc_kinds):
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    # cubes only: what ``MhdSimulation`` builds, and the shapes with a
    # Mosaic compile case (``tests/test_chip_compile.py``: 128^3 and
    # 256^3, solo and under ``run_steps_batch``'s vmap)
    if not shape[0] == shape[1] == shape[2]:
        return False
    return _pick_block(shape)[0] is not None


def kernel_available(cfg: MhdStatic, shape, bc_kinds, dtype) -> bool:
    """The uniform run's gate: kill switch, a TPU backend, coverage."""
    if DISABLED or jax.default_backend() != "tpu":
        return False
    return supports(cfg, shape, bc_kinds, dtype)


# Trace-time record of what the block rule picked, one per call
# signature; read by ``run_header.sweep_block``, the ``[kernel]`` line
# and the benchmark's ``ct_window_ratio``.
_BLOCKS: dict = {}


def _block_record(shape) -> dict:
    bx, by = _pick_block(shape)
    return {"kernel": "pallas_ct", "shape": list(shape), "bx": bx,
            "by": by, "halo": HALO,
            "window_cells": (bx + 2 * HALO) * WY * shape[2],
            "written_cells": bx * by * shape[2]}


def block_stats() -> list:
    """``[{kernel, shape, bx, by, halo, window_cells, written_cells}]``
    for every shape the tiled kernel was traced for in this process:
    each grid step loads and computes ``window_cells`` to write
    ``written_cells``."""
    return [dict(b) for b in _BLOCKS.values()]


def pad_xy(a):
    """Periodic ghost rows in x (3/3) and y (3 low / 5 high: 3 ghosts + 2
    junk rows of window slack) of ``[C, nx, ny, nz]`` — z wraps in the
    kernel."""
    nx, ny = a.shape[1:3]
    a = jnp.concatenate([a[:, nx - HALO:], a, a[:, :HALO]], axis=1)
    return jnp.concatenate([a[:, :, ny - HALO:], a,
                            a[:, :, :WY - BY - HALO]], axis=2)


def _ct_window(cfg: MhdStatic, dx: float, dt, u5, bf):
    """``mu.step_padded`` + ``mu.ct_core`` on one window, row by row.

    ``u5``: the five hydro rows, ``bf``: the three low-face rows, each
    ``[wx, wy, nz]``.  Returns ``(un [8 rows], bfn [3 rows])`` over the
    whole window; rows within 2 cells of a window edge in x or y hold
    wrapped junk the caller drops (``jnp.roll`` stencils, as
    ``ct_core``).  Same operations in the same order as the XLA
    spelling for ndim 3, no masks or overrides, ``riemann2d='llf'``."""
    roll = jnp.roll
    theta = float(cfg.slope_theta)
    # cell-centred field from the faces (step_padded)
    bc = [0.5 * (bf[c] + roll(bf[c], -1, c)) for c in range(NCOMP)]
    up = list(u5) + bc
    q = core.ctoprim_rows(up, cfg)
    dq = [[_slopes(roll(qk, 1, d), qk, roll(qk, -1, d), 1, theta)
           for qk in q] for d in range(3)]

    # conservative Hancock half-step: the cell's own reconstructed faces
    du_half = [jnp.zeros_like(x) for x in up]
    face_q = []
    for d in range(3):
        q_hi = [a + 0.5 * s for a, s in zip(q, dq[d])]
        q_lo = [a - 0.5 * s for a, s in zip(q, dq[d])]
        f_hi = core.flux_along_rows(q_hi, d, cfg)
        f_lo = core.flux_along_rows(q_lo, d, cfg)
        du_half = [h - (0.5 * dt / dx) * (a - b)
                   for h, a, b in zip(du_half, f_hi, f_lo)]
        face_q.append((q_lo, q_hi))

    # half-dt prediction of the staggered field
    bf_half = list(bf)
    for d1 in range(3):
        for d2 in range(d1 + 1, 3):
            sig = 1.0 if (d1, d2) in ((0, 1), (1, 2), (2, 0)) else -1.0
            v1, v2 = q[1 + d1], q[1 + d2]
            b1, b2 = q[IBX + d1], q[IBX + d2]
            e_c0 = sig * (v2 * b1 - v1 * b2)
            e_edge0 = 0.25 * (e_c0 + roll(e_c0, 1, d1) + roll(e_c0, 1, d2)
                              + roll(roll(e_c0, 1, d1), 1, d2))
            bf_half[d1] = bf_half[d1] - sig * (0.5 * dt / dx) * (
                roll(e_edge0, -1, d2) - e_edge0)
            bf_half[d2] = bf_half[d2] + sig * (0.5 * dt / dx) * (
                roll(e_edge0, -1, d1) - e_edge0)

    # three 1D Riemann solves; only the hydro rows are read afterwards
    # (with a 2D corner solver the EMFs do not come from these fluxes)
    fluxes = []
    for d in range(3):
        q_lo, q_hi = face_q[d]
        ul_c = [a + h for a, h in
                zip(core.prim_to_cons_rows(q_hi, cfg), du_half)]
        ur_c = [a + h for a, h in
                zip(core.prim_to_cons_rows(q_lo, cfg), du_half)]
        ql = core.ctoprim_rows([roll(a, 1, d) for a in ul_c], cfg)
        qr = core.ctoprim_rows(ur_c, cfg)
        perm = mu._rot_perm(cfg, d)
        fg = rsolve.hlld_rows([ql[i] for i in perm], [qr[i] for i in perm],
                              bf_half[d], cfg)
        t1, t2 = (d + 1) % 3, (d + 2) % 3
        out = [None] * NHYDRO
        out[0] = fg[0]
        out[1 + d], out[1 + t1], out[1 + t2] = fg[1], fg[2], fg[3]
        out[IP] = fg[4]
        fluxes.append(out)

    un = list(up[:NHYDRO])
    for d in range(3):
        un = [a + (dt / dx) * (f - roll(f, -1, d))
              for a, f in zip(un, fluxes[d])]
    q_half = core.ctoprim_rows([a + h for a, h in zip(up, du_half)], cfg)

    # CT induction from the 2D corner solver's EMFs
    pfloor = cfg.smallr * cfg.smallc ** 2
    bfn = list(bf)
    for d1 in range(3):
        for d2 in range(d1 + 1, 3):
            sig = 1.0 if (d1, d2) in ((0, 1), (1, 2), (2, 0)) else -1.0
            dorth = 3 - d1 - d2
            rows = (0, IP, 1 + d1, 1 + d2, 1 + dorth, IBX + dorth)

            def corner(s1, s2, *rolls, d1=d1, d2=d2, rows=rows):
                qc = []
                for k in rows:
                    a = q_half[k] + 0.5 * (s1 * dq[d1][k] + s2 * dq[d2][k])
                    if k == 0:
                        a = jnp.maximum(a, cfg.smallr)
                    elif k == IP:
                        a = jnp.maximum(a, pfloor)
                    for ax in rolls:
                        a = roll(a, 1, ax)
                    qc.append(a)
                return tuple(qc)

            states = {
                ("R", "T"): corner(-1.0, -1.0),
                ("L", "T"): corner(1.0, -1.0, d1),
                ("R", "B"): corner(-1.0, 1.0, d2),
                ("L", "B"): corner(1.0, 1.0, d1, d2),
            }
            eps = r2d.corner_emf(states, bf_half[d1],
                                 roll(bf_half[d1], 1, d2), bf_half[d2],
                                 roll(bf_half[d2], 1, d1), cfg)
            e_edge = -sig * eps
            bfn[d1] = bfn[d1] - sig * (dt / dx) * (
                roll(e_edge, -1, d2) - e_edge)
            bfn[d2] = bfn[d2] + sig * (dt / dx) * (
                roll(e_edge, -1, d1) - e_edge)

    # cell-centred field of the new faces
    un = un + [0.5 * (bfn[c] + roll(bfn[c], -1, c)) for c in range(NCOMP)]
    return un, bfn


def _courant_rate(cfg: MhdStatic, dx: float, un):
    """``mu.cfl_dt``'s rate, ``sum_d (|v_d| + c_fast,d) / dx``, of an
    updated state whose B rows are already centred."""
    q = core.ctoprim_rows(un, cfg)
    rate = 0.0
    for d in range(3):
        rate = rate + (jnp.abs(q[1 + d]) + core.fast_speed(q, d, cfg)) / dx
    return rate


def _make_kernel(cfg: MhdStatic, dx: float, bx: int, by: int):
    sx = slice(HALO, HALO + bx)
    sy = slice(HALO, HALO + by)

    def kernel(u_ref, bf_ref, dt_ref, un_ref, bcn_ref, bfn_ref, crt_ref):
        dt = dt_ref[0, 0]
        un, bfn = _ct_window(cfg, dx, dt,
                             [u_ref[k] for k in range(NHYDRO)],
                             [bf_ref[c] for c in range(NCOMP)])
        un = [a[sx, sy, :] for a in un]
        for k in range(NHYDRO):
            un_ref[k] = un[k]
        for c in range(NCOMP):
            bcn_ref[c] = un[IBX + c]
            bfn_ref[c] = bfn[c][sx, sy, :]
        # largest Courant rate of the UPDATED tile: the next step's dt
        # comes out of this launch.  Grid steps run one after another
        # on the core: accumulate into the one shared SMEM word.
        local = jnp.max(_courant_rate(cfg, dx, un))
        first = jnp.logical_and(pl.program_id(0) == 0,
                                pl.program_id(1) == 0)

        @pl.when(first)
        def _():
            crt_ref[0, 0] = local

        @pl.when(jnp.logical_not(first))
        def _():
            crt_ref[0, 0] = jnp.maximum(crt_ref[0, 0], local)

    return kernel


@partial(jax.jit, static_argnames=("cfg", "dx", "shape", "interpret"))
def ct_step_tiled(up, bfp, dt, cfg: MhdStatic, dx: float, shape,
                  interpret: bool = False):
    """One CT step of the whole box on pre-padded arrays.

    ``up`` [5, nx+6, ny+8, nz]: the hydro rows of the cell state,
    ``bfp`` [3, nx+6, ny+8, nz]: the low faces, both from
    :func:`pad_xy`.  Returns ``(un [5, nx, ny, nz], bcn [3, ...], bfn
    [3, ...], rate [1, 1])``: the updated hydro rows, the cell-centred
    field of the new faces (rows 5:8 of the state: apart, so the caller's
    loop carries the hydro rows as an array of their own and its ghost
    pass cuts no slice), the new faces and the largest Courant rate of
    the update (``courant_factor / rate`` is ``mu.cfl_dt`` of it)."""
    nx, ny, nz = shape
    bx, by = _pick_block(shape)
    _BLOCKS[tuple(shape)] = _block_record(shape)
    dtype = up.dtype
    dt2 = jnp.asarray(dt, dtype).reshape(1, 1)

    def window(nrow):
        return pl.BlockSpec(
            (pl.Element(nrow), pl.Element(bx + 2 * HALO), pl.Element(WY),
             pl.Element(nz)),
            lambda i, j: (0, i * bx, j * by, 0), memory_space=pltpu.VMEM)

    def tile(nrow):
        return pl.BlockSpec((nrow, bx, by, nz), lambda i, j: (0, i, j, 0),
                            memory_space=pltpu.VMEM)

    word = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(cfg, dx, bx, by),
        grid=(nx // bx, ny // by),
        in_specs=[window(NHYDRO), window(NCOMP), word],
        out_specs=(tile(NHYDRO), tile(NCOMP), tile(NCOMP), word),
        out_shape=(jax.ShapeDtypeStruct((NHYDRO, nx, ny, nz), dtype),
                   jax.ShapeDtypeStruct((NCOMP, nx, ny, nz), dtype),
                   jax.ShapeDtypeStruct((NCOMP, nx, ny, nz), dtype),
                   jax.ShapeDtypeStruct((1, 1), dtype)),
        interpret=interpret,
        name=KERNEL_NAME,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(up, bfp, dt2)
