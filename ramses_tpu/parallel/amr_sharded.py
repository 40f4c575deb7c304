"""Multi-device AMR: level batches sharded over the device mesh.

Design (SURVEY.md §2.12 P1-P4): each level's dense cell batch
``[ncell_pad, nvar]`` is a global-view jax.Array sharded by rows over a
1D "oct" mesh axis.  Rows follow the Morton/Hilbert key order, so equal
row-splits are compact spatial domains (P1) that are balanced by
construction — the reference's cost-weighted ``cmp_new_cpu_map``
re-partition (P4) degenerates to "re-sort after refinement", which the
regrid pass already does.  Stencil gathers that cross shard boundaries
become compiler-inserted collectives (P2/P3); CFL min-reduction is a
``jnp.min`` → ``AllReduce`` (P7).

Cost weights (P4): the reference decomposes SPACE once — one Hilbert
interval per rank spanning all levels — so a rank owning more fine
octs does 2^(l-lmin)× more substep work, and ``load_balance`` must
weight the cuts by measured cost (``amr/load_balance.f90:285``).
Here every LEVEL is row-sharded independently, so equal splits already
balance the SWEEP work; what they do NOT balance is per-oct cost that
varies within a level (particles piled into a few octs) or the
trailing-pad remainder of skewed partial levels.  The opt-in
``&AMR_PARAMS load_balance`` path (:mod:`ramses_tpu.parallel.balance`)
closes that: at regrid time each partial level's rows are re-laid-out
as per-device contiguous Hilbert-key ranges whose summed cost
(solver sweeps + particle counts) is balanced within the
bucket-padding bound.

Partial levels run the global-view formulation: the Morton-tile sweep
over row-sharded tables, GSPMD inserting the collectives, the coarse
flux corrections folded by its scatter-add.  Complete levels
take the EXPLICIT slab-sharded dense path whenever the level is a
fully periodic unpadded power-of-two cube on a power-of-two device
count (:mod:`ramses_tpu.parallel.dense_slab`): shard-local bitperm +
ring halos, so the GSPMD partitioner never sees the bit-interleaved
transpose that previously degenerated to involuntary full
rematerialization (MULTICHIP_r05).  Levels outside that envelope keep
the global-view sweep with compiler-inserted collectives.

Every slab ring halo above rides the backend-dispatched exchange
engine (:mod:`ramses_tpu.parallel.dma_halo`): Pallas async
remote-copy DMA kernels with comm/compute overlap on TPU,
``lax.ppermute`` elsewhere, selected by the ``&AMR_PARAMS
halo_backend`` knob (``auto``/``dma``/``ppermute``) — the two agree
bitwise, so the choice is pure performance.

Fault tolerance is inherited from :class:`~ramses_tpu.amr.hierarchy.
AmrSim` unchanged: atomic manifest-validated dumps, the
``max_step_retries`` non-finite step guard (capture → probe → rollback
with halved dt), and supervised auto-resume all operate on the
host-side level dict, so the retained pre-step state re-shards exactly
like fresh init when a retry or restore replays it onto the mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ramses_tpu.amr.hierarchy import AmrSim
from ramses_tpu.config import Params
from ramses_tpu.parallel.mesh import oct_mesh


class ShardedAmrSim(AmrSim):
    """AmrSim with per-level state sharded over an ``oct`` mesh axis."""

    # row-sharded partial levels take the gather-fused blocked tile
    # sweep too: tile tables are row-sharded like the stencil ones and
    # FusedSpec.pallas_tiles=False forces the XLA tile formulation, so
    # GSPMD partitions the compact tile batch the same way it used to
    # partition the 6^d gather
    _oct_blocked = True

    def __init__(self, params: Params,
                 devices: Optional[Sequence[jax.Device]] = None,
                 dtype=jnp.float32, particles=None, init_tree=None,
                 init_dense_u=None, seed_tracers: bool = True):
        devices = list(devices if devices is not None else jax.devices())
        self.ndev = len(devices)
        self.mesh = oct_mesh(devices)
        self._row_sharding = NamedSharding(self.mesh, P("oct"))
        self._row2_sharding = NamedSharding(self.mesh, P("oct", None))
        self._rep_sharding = NamedSharding(self.mesh, P())
        self._warned_rep = set()
        if particles is not None:
            # particle rows shard over the mesh when the lane count
            # divides (deposit gathers/scatters stay global-view, so
            # GSPMD inserts the collectives either way); non-divisible
            # sets replicate — memory stops scaling, so warn at size
            import dataclasses as _dc

            def put(a):
                if (getattr(a, "ndim", 0) >= 1
                        and a.shape[0] % self.ndev == 0):
                    return jax.device_put(
                        a, self._row2_sharding if a.ndim > 1
                        else self._row_sharding)
                return jax.device_put(a, self._rep_sharding)

            n = particles.n
            if n % self.ndev and n > 1_000_000:
                import warnings
                warnings.warn(
                    f"particle count {n} not divisible by the "
                    f"{self.ndev}-device mesh: arrays REPLICATE on "
                    "every device (per-device memory stops scaling); "
                    "pad npartmax to a mesh multiple")
            particles = _dc.replace(
                particles, **{f.name: put(getattr(particles, f.name))
                              for f in _dc.fields(particles)})
        super().__init__(params, dtype=dtype, particles=particles,
                         init_tree=init_tree, init_dense_u=init_dense_u,
                         seed_tracers=seed_tracers)

    def dump(self, iout: int = 1, base_dir: str = ".",
             namelist_path=None, ncpu: Optional[int] = None) -> str:
        """Per-shard checkpoint files by default (one writer per domain,
        the pario/§2.10 role)."""
        return super().dump(iout, base_dir, namelist_path=namelist_path,
                            ncpu=self.ndev if ncpu is None else ncpu)

    # dump_pario: inherited from AmrSim — every host writes only its
    # addressable shard rows into its own validated shard dirs under
    # the two-phase global commit (io/pario.py format 2), io_group_size
    # bounding concurrent writers (the IOGROUPSIZE ring).  Restore onto
    # ANY device count via AmrSim.from_checkpoint_dir.

    def _slab_spec(self, lvl: int):
        """Explicit slab decomposition for a complete level, or None
        when the level falls outside the slab envelope (non-periodic,
        non-cubic root, padded rows, non-power-of-two mesh) and must
        keep the global-view sweep."""
        from ramses_tpu.parallel import dense_slab
        root = self.root or (1,) * self.cfg.ndim
        shape = tuple(r << lvl for r in root[:self.cfg.ndim])
        ncell_pad = self.maps[lvl].noct_pad * 2 ** self.cfg.ndim
        return dense_slab.build_slab_spec(
            self.mesh, lvl, self.cfg.ndim, shape, ncell_pad,
            self.bc_kinds,
            halo_backend=getattr(self.params.amr, "halo_backend",
                                 "auto"))

    def _noct_pad(self, lvl: int, noct: int) -> int:
        """Bucketed oct count (with the base class's hysteresis) rounded
        to a multiple of the device count (shardable rows; cells stay
        2^d-aligned automatically)."""
        b = super()._noct_pad(lvl, noct)
        if b % self.ndev:
            b += self.ndev - (b % self.ndev)
            self._pad_hist[lvl] = b
        return b

    def _place(self, arr, kind: str):
        if kind == "rep":
            return jax.device_put(arr, self._rep_sharding)
        if arr.shape[0] % self.ndev:
            # cells/octs rows must divide the mesh to shard; the
            # bucketed pads normally guarantee that, so a replicated
            # fallback at scale signals a padding bug — say so once
            if arr.shape[0] > 1_000_000 and kind not in self._warned_rep:
                import warnings
                self._warned_rep.add(kind)
                warnings.warn(
                    f"sharded-AMR: a {kind!r} array of {arr.shape[0]} "
                    f"rows is not divisible by the {self.ndev}-device "
                    "mesh and REPLICATES (memory/work stop scaling); "
                    "check the _noct_pad mesh alignment")
            return jax.device_put(arr, self._rep_sharding)
        return jax.device_put(arr, self._row_sharding if arr.ndim == 1
                              else self._row2_sharding)


from ramses_tpu.mhd.amr import MhdAmrSim as _MhdAmrSim  # noqa: E402


class ShardedMhdAmrSim(ShardedAmrSim, _MhdAmrSim):
    """MHD AMR on a device mesh: the sharded state layout / placement /
    slab machinery of :class:`ShardedAmrSim` composed with the CT
    physics of :class:`ramses_tpu.mhd.amr.MhdAmrSim` (cooperative MRO —
    both defer to :class:`~ramses_tpu.amr.hierarchy.AmrSim`).  Complete
    levels run the slab-sharded CT advance
    (:func:`ramses_tpu.parallel.dense_slab.mhd_ct_slab`) with the
    Morton-flat EMF override, so the multichip gate sees no global
    index scatter from the MHD path either."""
