"""Slab-local MHD constrained-transport kernel (Pallas).

The slab-sharded CT advance (:func:`ramses_tpu.parallel.dense_slab.
mhd_ct_slab`) hands each device a halo-complete local box.  The XLA
spelling of the CT pipeline (:func:`ramses_tpu.mhd.uniform.step_padded`)
materializes every stage — primitives, slopes, Hancock predictor, six
Riemann faces, four EMF edge averages — as an HBM-resident grid array;
at slab sizes that is pure bandwidth waste.  This module runs the SAME
pipeline as ONE single-block Pallas kernel: the padded state and faces
are read into VMEM once, every intermediate lives in VMEM, and HBM sees
exactly one write of the padded outputs.

No re-derivation: the kernel body CALLS ``mu.step_padded`` on the VMEM
refs, so the arithmetic is definitionally identical to the XLA fallback
(the bitwise contract the slab parity tests pin).  Availability is a
single-block question — the whole padded box plus ~60 live
intermediates must fit the VMEM budget — so the gate is a size check,
not a tiling search; oversized slabs silently keep the XLA path.

Test hook: :data:`FORCE_INTERPRET` (env ``RAMSES_PALLAS_CT_INTERPRET``
or monkeypatch) runs the kernel through the Pallas interpreter on any
backend, which is how CI exercises this path on CPU.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ramses_tpu.mhd import uniform as mu
from ramses_tpu.mhd.core import MhdStatic, NCOMP

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
    padded box inside the VMEM budget.  Compiled runs additionally
    require float32 (the f64 VPU story is interpret-only)."""
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
