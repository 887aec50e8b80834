"""Device mesh plumbing: the TPU-native replacement for src/network.

The reference builds an all-to-all TCP/MPI mesh with hand-written
Bruck/recursive-halving/ring collectives (reference src/network/
network.cpp:68-318).  On TPU the transport and algorithm selection belong to
XLA: we declare a `jax.sharding.Mesh` with axes

  * 'hosts'   — the process/DCN tier (parallel/topology.py)
  * 'data'    — row shards (the reference's data_parallel machines)
  * 'feature' — feature shards (the reference's feature_parallel machines)

and express the collectives through the axis-addressed vocabulary in
`parallel/topology.py`, inside shard_map'ped growers.  `num_machines`/
`machines` config maps to the mesh shape; ICI vs DCN placement follows
the hosts axis.  This module keeps the process-group plumbing
(rendezvous, global/local array placement) and the ring cost models the
psum-vs-scatter decision is priced with.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_distributed_initialized = False
# a timed-out rendezvous cannot be re-entered: the watchdog abandons a
# thread that may STILL complete jax.distributed.initialize later, and
# jax refuses a second initialize() in the same process — so a failed
# init is terminal for this process, recorded here to fail retries with
# a structured message instead of jax's confusing "only once" error
_distributed_init_failed: Optional[str] = None


def init_multihost(machines: str = "", local_listen_port: int = 0,
                   num_machines: int = 1) -> bool:
    """Map the reference's machine-list network config onto jax.distributed.

    The reference rendezvouses an all-to-all TCP mesh from `machines` =
    "ip1:port1,ip2:port2,..." (reference src/network/linkers_socket.cpp:
    165-220).  The TPU equivalent: every host runs the same program and
    calls `jax.distributed.initialize(coordinator, num_processes,
    process_id)`; afterwards jax.devices() spans all hosts and the SAME
    mesh/shard_map code runs globally — collectives ride ICI within a
    slice and DCN across slices, placed by XLA instead of hand-built
    Bruck/recursive-halving rings.

    The first machine-list entry is the coordinator; this host's position
    in the list (matched by LIGHTGBM_TPU_HOST_IP or the entry whose port
    matches local_listen_port when unambiguous) is its process id.
    Returns True if distributed init ran.  Single-process setups (CI, one
    host) skip it — the in-process virtual mesh covers them.
    """
    global _distributed_initialized, _distributed_init_failed
    if _distributed_initialized:
        return True
    if _distributed_init_failed is not None:
        raise RuntimeError(
            "a previous multi-host rendezvous failed in this process "
            f"({_distributed_init_failed}); jax.distributed cannot be "
            "re-initialized — restart the process to rejoin the group")
    entries = [m.strip() for m in str(machines).split(",") if m.strip()]
    if len(entries) <= 1 or num_machines <= 1:
        return False
    import os

    coordinator = entries[0]
    my_ip = os.environ.get("LIGHTGBM_TPU_HOST_IP", "")
    pid = None
    if my_ip:
        for i, e in enumerate(entries):
            if e.split(":")[0] == my_ip:
                pid = i
                break
    if pid is None:
        env_pid = os.environ.get("LIGHTGBM_TPU_PROCESS_ID", "")
        if env_pid:
            pid = int(env_pid)
    if pid is None:
        raise ValueError(
            "multi-host init: cannot determine this host's position in "
            "`machines`; set LIGHTGBM_TPU_HOST_IP or "
            "LIGHTGBM_TPU_PROCESS_ID")
    from .collective import guarded_collective

    # the rendezvous is the group's first collective: a host that never
    # shows up would otherwise hang every peer in initialize() forever.
    # retries=0 — a torn partial rendezvous cannot be re-entered (the
    # coordinator keeps half-joined state); the timeout surfaces it as
    # a structured failure instead, and the failure is recorded as
    # TERMINAL for this process (see _distributed_init_failed)
    try:
        guarded_collective(
            jax.distributed.initialize, name="init_multihost", retries=0,
            coordinator_address=coordinator, num_processes=len(entries),
            process_id=pid)
    except BaseException as exc:
        _distributed_init_failed = f"{type(exc).__name__}: {exc}"
        raise
    _distributed_initialized = True
    return True


def available_devices() -> int:
    return len(jax.devices())


def put_global(arr, sharding: NamedSharding):
    """device_put that also works when the mesh spans PROCESSES.

    Single-process: plain `jax.device_put`.  Multi-process (after
    `init_multihost`): `jax.device_put` rejects non-fully-addressable
    shardings, so build the global array from a callback — every process
    holds the same FULL host array (the reference's all-data-on-all-
    machines ingest; pre-partitioned loading shards earlier, at bin time)
    and contributes the shards its local devices own.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def put_local(local_arr, sharding: NamedSharding, global_shape) -> "jax.Array":
    """Build a global array from PER-PROCESS local shards.

    The pre-partitioned ingest (reference loader pre_partition: each
    machine holds only its own rows, dataset_loader.cpp row
    distribution): every process passes just the rows its devices own,
    laid out in its local order; jax maps them onto the process's
    addressable shards of the global array.  Complements `put_global`,
    whose contract is the opposite (every process holds the FULL host
    array)."""
    if jax.process_count() == 1:
        return jax.device_put(np.asarray(local_arr), sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_arr), global_shape)


def make_mesh(num_data_shards: int = 1, num_feature_shards: int = 1,
              devices: Optional[Sequence] = None,
              num_hosts: int = 0) -> Mesh:
    """The (hosts, data, feature) mesh — compatibility shim over
    `topology.make_topology`; new call sites should build the Topology
    directly and keep it (the mesh alone loses the shard counts)."""
    from .topology import make_topology

    return make_topology(num_data_shards=num_data_shards,
                         num_feature_shards=num_feature_shards,
                         num_hosts=num_hosts, devices=devices).mesh


def shard_rows(n: int, num_shards: int) -> int:
    """Rows per shard, padded so every shard is equal-size."""
    return (n + num_shards - 1) // num_shards


# --------------------------------------------------------------------------
# Elastic-resume placement (ISSUE 8): a checkpoint taken at P hosts holds
# per-host slices of the GLOBAL row axis; resuming at P' hosts needs (a)
# the global row offset of every checkpointed host to reassemble the
# global buffers, and (b) this process's offset in the NEW topology to
# slice its local rows back out.  Row order is process order in both
# directions (the put_local contract), so a reassemble+slice round trip
# is byte-exact.
# --------------------------------------------------------------------------

def row_offsets(rows_per_host: Sequence[int]) -> Tuple[np.ndarray, int]:
    """Per-host global row offsets (process order) and the total count."""
    rows = np.asarray(list(rows_per_host), np.int64)
    offsets = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)
    return offsets, int(rows.sum())


def local_row_offset(local_n: int) -> Tuple[int, int]:
    """(this process's global row offset, global total rows) in the LIVE
    topology — an allgather of the per-process local row counts, ridden
    through the collective watchdog.  Identity (0, local_n) when the
    process group is 1."""
    import jax

    if jax.process_count() == 1:
        return 0, int(local_n)
    from .topology import host_allgather

    lens = host_allgather(np.asarray([int(local_n)], np.int64),
                          name="row_offsets")[:, 0]
    offsets, total = row_offsets(lens)
    return int(offsets[jax.process_index()]), total


# --------------------------------------------------------------------------
# Aggregation cost model (tpu_hist_agg): predicted per-shard ICI receive
# bytes for the two histogram aggregation modes.  Bandwidth-optimal ring
# algorithms (the form XLA lowers to on ICI, and the reference's own
# Network::ReduceScatter / recursive-halving implementations,
# src/network/network.cpp:68-318) move:
#
#   all-reduce (psum)          2 * (P-1)/P * nbytes   per shard
#       = reduce-scatter + all-gather; every shard RECEIVES the whole
#       aggregated array again in the second phase
#   reduce-scatter (scatter)       (P-1)/P * nbytes   per shard
#       = the first phase alone; each shard keeps only its 1/P slice
#
# so scatter halves the wire traffic AND shrinks what lands in HBM by P.
# tools/perf_probe.py comm prints these next to measured wall times; the
# PERF_NOTES round-9 bytes-moved model cites them.
# --------------------------------------------------------------------------

def allreduce_recv_bytes(nbytes: int, shards: int) -> int:
    """Per-shard receive bytes of a ring all-reduce (psum) of `nbytes`."""
    if shards <= 1:
        return 0
    return 2 * (shards - 1) * nbytes // shards


def reduce_scatter_recv_bytes(nbytes: int, shards: int) -> int:
    """Per-shard receive bytes of a ring reduce-scatter (psum_scatter)."""
    if shards <= 1:
        return 0
    return (shards - 1) * nbytes // shards


def tree_hist_slots(num_leaves: int, split_batch: int, ramp: bool,
                    ramp_step: int) -> List[int]:
    """Leaf slots of each histogram call of one tree, the root's first,
    when every round splits all it can: the grower's own schedule
    (ops/grower.py: the root, the frontier ramp's pre-rounds at 1, s,
    s^2, ... slots, then the round loop at `split_batch`).  255 leaves at
    25 slots and s = 4 give 15 calls of 1, 1, 4, 16 and 11 x 25 slots."""
    k = max(1, min(int(split_batch), num_leaves - 1))
    widths, kr = [], 1
    while ramp and k > 1 and kr < k:
        widths.append(kr)
        kr *= int(ramp_step)
    slots, leaves = [1], 1
    while leaves < num_leaves:
        width = widths.pop(0) if widths else k
        slots.append(width)
        leaves += min(width, leaves, num_leaves - leaves)
    return slots


def exchange_bytes_per_tree(slots: Sequence[int], columns: int, bins: int,
                            hist_itemsize: int, scatter: bool,
                            cat_bins: int = 1) -> Dict[str, int]:
    """Bytes one row shard hands to each collective of the data axis while
    it grows one tree (operand bytes, before the ring's (P-1)/P): every
    call's [slots, columns, bins, 3] histograms into the reduce-scatter
    (scatter) or the all-reduce (psum), and under scatter the best-split
    sync of both children of every slot and of the root: three all-
    gathered words (gain, feature, threshold bin) and the winner's record
    (nine words and the categorical mask) through a masked all-reduce.
    The leaf totals' scalar all-reduces are left out."""
    hist = sum(slots) * columns * bins * 3 * hist_itemsize
    searched = 1 + 2 * sum(slots[1:])
    if not scatter:
        return {"reduce_scatter": 0, "all_gather": 0, "all_reduce": hist}
    return {"reduce_scatter": hist, "all_gather": searched * 3 * 4,
            "all_reduce": searched * (9 + cat_bins) * 4}


# --------------------------------------------------------------------------
# Tiered (ICI vs DCN) cost model: a reduction over ROW_AXES on an
# (hosts, data, feature) mesh lowers hierarchically — reduce-scatter
# inside each host's ICI ring, the cross-host leg over DCN on the 1/D
# partials, then an ICI all-gather to rebuild the full array where the
# op is an all-reduce.  Splitting the predicted receive bytes by tier
# prices the psum-vs-scatter decision per topology: DCN bandwidth is
# ~an order of magnitude below ICI, so the DCN leg dominates wall time
# even though it moves the fewest bytes.  perf_probe comm prints both
# legs next to measured walls.
# --------------------------------------------------------------------------

def tiered_allreduce_recv_bytes(nbytes: int, hosts: int,
                                devices_per_host: int) -> Tuple[int, int]:
    """(ICI, DCN) per-shard receive bytes of a hierarchical all-reduce:
    ICI reduce-scatter + DCN all-reduce of the 1/D partials + ICI
    all-gather.  Degenerates to the flat ring models at either tier=1."""
    d, h = max(devices_per_host, 1), max(hosts, 1)
    # ICI reduce-scatter (d-1)/d + ICI all-gather (d-1)/d = the flat
    # all-reduce ring's bytes; the DCN tier all-reduces the 1/d partials
    ici = allreduce_recv_bytes(nbytes, d)
    dcn = allreduce_recv_bytes(nbytes // d, h)
    return ici, dcn


def tiered_reduce_scatter_recv_bytes(nbytes: int, hosts: int,
                                     devices_per_host: int) -> Tuple[int, int]:
    """(ICI, DCN) per-shard receive bytes of a hierarchical
    reduce-scatter: the ICI phase, then the DCN reduce-scatter of each
    host's 1/D partials down to the final 1/(H*D) slices."""
    d, h = max(devices_per_host, 1), max(hosts, 1)
    ici = reduce_scatter_recv_bytes(nbytes, d)
    dcn = reduce_scatter_recv_bytes(nbytes // d, h)
    return ici, dcn
