"""Shared by the readers of what a device mesh adds to a trace.

``trace_reduce`` keeps, per device op, only its module and a short name
``%<hlo name> <kind>`` with its self time summed over the device planes
(``op_s``).  A Pallas kernel's HLO name is the ``name=`` its
``pallas_call`` carries, so the program names the two kernels the mesh
adds (``hydro/pallas_muscl.SHARD_KERNEL_NAME``,
``parallel/dma_halo.KERNEL_NAME``) and these readers pick them out by
that name; the names are repeated here because a program without them
(the parent of the PR that added them) must read as nothing, not raise.
"""

SHARD_KERNEL = "%fused_step_shard"     # per-shard fused MUSCL kernel
DMA_KERNEL = "%halo_dma_exchange"      # Pallas remote-copy halo exchange
# XLA's own collectives (GSPMD-partitioned tile sweeps, the Courant
# all-reduce, sharded gathers); async pairs end in -start / -done
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")


def is_kernel(short: str, name: str) -> bool:
    head = short.split(" ")[0]
    return head == name or head.startswith(name + ".")


def is_collective(short: str) -> bool:
    return short.split(" ")[-1].startswith(COLLECTIVES)


def seconds(reduced, keep) -> float:
    """Self seconds, summed over modules and device planes, of the ops
    whose short name ``keep`` accepts."""
    return sum(sec for (_, short), sec in reduced["op_s"].items()
               if keep(short))


def mesh_size(reduced) -> int:
    """Device planes in the trace; under two there is no mesh to read."""
    return int(reduced.get("n_devices", 1))
