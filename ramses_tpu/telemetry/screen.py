"""The RAMSES-style ``write_screen`` console sink.

One formatting module for every screen line the drivers print — the
per-``ncontrol`` control block (``amr/adaptive_loop.f90:199-214`` +
memory census, previously inlined in ``utils/ops.OpsGuard``) and the
per-step/per-chunk ``verbose`` line (previously ad-hoc ``print()``
calls in each driver).  Routing them here means ``verbose`` is pure
formatting: it no longer forces the per-step slow path — the chunked
fast path reports the same line from its chunk summary.

Everything here is host-side string building over values the caller
already holds; the only device fetch is the amortized conservation
audit the OpsGuard cadence explicitly requests (``audit=True``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def step_line(sim, dt: Optional[float] = None, chunk: int = 0,
              extra: str = "") -> str:
    """The per-step ``verbose`` line; with ``chunk=n`` it summarizes n
    fused coarse steps from one ``step_chunk`` dispatch."""
    nstep = getattr(sim, "nstep", None)
    t = getattr(sim, "t", None)
    if nstep is None and hasattr(sim, "state"):     # uniform driver
        nstep, t = sim.state.nstep, sim.state.t
    line = f"step {int(nstep):6d}  t={float(t):.6e}"
    if dt is None:
        dt = getattr(sim, "dt_old", None)
    if dt is not None:
        line += f" dt={float(dt):.3e}"
    if getattr(sim, "cell_updates", 0) and hasattr(
            sim, "mus_per_cell_update"):
        line += f" mus/pt={sim.mus_per_cell_update():.4f}"
    if hasattr(sim, "tree"):
        line += f" octs={[sim.tree.noct(l) for l in sim.levels()]}"
    if chunk > 1:
        line += f" chunk={chunk}"
    return line + ((" " + extra) if extra else "")


def sweep_kernel(sim) -> str:
    """The sweep kernel ``sim``'s driver runs on where its gate admits
    the run: the fused hydro kernel, unless the driver names its own
    (``MhdSimulation.sweep_kernel``).  The one place ``python -m
    ramses_tpu`` and ``run_header`` ask."""
    return getattr(sim, "sweep_kernel", "pallas_muscl")


def sweep_blocks(kernel: str) -> list:
    """``block_stats()`` of the kernel :func:`sweep_kernel` named: what
    its block rule picked for each signature traced in this process."""
    if kernel == "pallas_ct":
        from ramses_tpu.mhd import pallas_ct as mod
    else:
        from ramses_tpu.hydro import pallas_muscl as mod
    return mod.block_stats()


def kernel_line(blocks, kernel: str = "pallas_muscl") -> str:
    """The ``[kernel]`` line: what the block rule of the run's sweep
    kernel (``pallas_muscl``, or ``pallas_ct`` for the uniform MHD run)
    picked for each signature it was traced for (its ``block_stats()``),
    and the cells a grid step loads and computes per cell it writes.
    No record: the run kept the XLA formulation, and the line says so."""
    if not blocks:
        return f"[kernel] {kernel}: not traced (XLA formulation)"
    return f"[kernel] {kernel}: " + "; ".join(
        "{}{} bx={} by={} window/written={:.2f}".format(
            "x".join(str(n) for n in b["shape"]),
            " masked" if b.get("masked") else "", b["bx"], b["by"],
            b["window_cells"] / b["written_cells"]) for b in blocks)


def control_block(sim, max_rss: float = 0.0,
                  dev_mb: Optional[float] = None,
                  audit: bool = False, extra: str = "") -> str:
    """The reference's per-``ncontrol`` control line
    (``adaptive_loop.f90:199-214`` + ``amr/memory.f90`` census).

    ``audit=True`` adds the mcons/econs conservation line and the
    rt photon budget — both sync device state, so callers amortize
    (OpsGuard's ``cons_every``).  ``dev_mb``: pass a pre-sampled
    device-memory figure to keep this call fetch-free.
    """
    if dev_mb is None:
        from ramses_tpu.utils.ops import device_mb
        dev_mb = device_mb()
    octs = {l: sim.tree.noct(l) for l in sim.levels()} \
        if hasattr(sim, "tree") else {}
    line = (f" Main step={getattr(sim, 'nstep', 0):7d} "
            f"t={getattr(sim, 't', 0.0):13.6e} "
            f"dt={getattr(sim, 'dt_old', 0.0):11.4e} "
            f"mem={max_rss:8.1f}M/{dev_mb:8.1f}M")
    if hasattr(sim, "totals") and audit:
        # conservation audit line (the reference's mcons/econs print,
        # ``amr/update_time.f90`` output block) — amortized: totals()
        # syncs the full device state
        raw = sim.totals()
        if isinstance(raw, dict):          # uniform-grid totals() dicts
            line += f" mcons={float(raw.get('mass', 0.0)):.6e}"
            if "energy" in raw:
                line += f" econs={float(raw['energy']):.6e}"
        else:
            tot = np.asarray(raw)
            ie = getattr(getattr(sim, "cfg", None), "ienergy", None)
            line += f" mcons={tot[0]:.6e}"
            if ie is not None and ie < len(tot):
                line += f" econs={tot[ie]:.6e}"
    if hasattr(sim, "aexp_now") and getattr(sim, "cosmo", None) is not None:
        line += f" a={sim.aexp_now():8.5f}"
    bs = getattr(sim, "balance_stats", None)
    if bs is not None:
        # load-balance observability (the reference's load_balance
        # screen report): per-device cost extrema + rebalance count
        line += (f" lb[max/mean={bs.max_cost:.4g}/{bs.mean_cost:.4g}"
                 f" imb={bs.imbalance:.3f}"
                 f" nreb={getattr(sim, '_rebalance_count', 0)}]")
    rt = getattr(sim, "rt_amr", None) or getattr(sim, "rt", None)
    if rt is not None and hasattr(rt, "rt_stats") and audit:
        # photon budget line (the reference's output_rt_stats,
        # amr/amr_step.f90:467): total photons vs cumulative injected —
        # the conservation ratio drops as gas absorbs
        st = rt.rt_stats(sim)
        line += (f" rt[N={st['photons']:.4e}"
                 f" inj={st['injected']:.4e}"
                 f" ratio={st['ratio']:.4f}]")
    if octs:
        line += f" octs={octs}"
    return line + (" " + extra if extra else "")
