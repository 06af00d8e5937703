"""Process-sharded DistEGNN data plane (DESIGN.md §11).

The GNN mesh (dist_egnn.make_gnn_mesh) lays the 'graph' axis out in
jax.devices() order, which enumerates devices process-by-process — so a
contiguous block of graph shards lives on each host's local devices.
These helpers are the host side of that layout: which shard rows a
process owns, and how its locally-built (D_local, B, ...) numpy fields
become one global sharded array without any host ever materialising
another host's shards.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def process_shard_range(n_shards: int, process_index: Optional[int] = None,
                        process_count: Optional[int] = None) -> tuple[int, int]:
    """Contiguous ``[lo, hi)`` of graph shards owned by this process.

    ``n_shards`` is the *global* D (= mesh size along the graph axis).
    Requires ``n_shards % process_count == 0`` — an uneven split would
    leave processes with different local array shapes, which
    ``jax.make_array_from_process_local_data`` cannot assemble.
    """
    pi = jax.process_index() if process_index is None else int(process_index)
    pc = jax.process_count() if process_count is None else int(process_count)
    if n_shards % pc:
        raise ValueError(
            f"process_shard_range: n_shards={n_shards} not divisible by "
            f"process_count={pc} — pick a shard count that is a multiple "
            f"of the host count")
    per = n_shards // pc
    return per * pi, per * (pi + 1)


def sharded_batch_from_process_local(mesh: Mesh, host: dict):
    """Process-local ``(D_local, B, ...)`` numpy fields → global ShardedBatch.

    Each field becomes a global ``(D, B, ...)`` array via
    ``jax.make_array_from_process_local_data`` under ``P('graph')``
    sharding: the local rows land on this process's devices, the global
    shape is inferred from the identical per-process local shape, and no
    cross-host copy of shard *data* ever happens — host memory and build
    time stay flat in the host count.  Single-process this is exactly
    ``sharded_batch_to_device(host, mesh)`` (one host owns every shard).
    """
    from repro.distributed.dist_egnn import GRAPH_AXIS, ShardedBatch

    sharding = NamedSharding(mesh, P(GRAPH_AXIS))
    return ShardedBatch(**{
        f: jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(host[f]))
        for f in ShardedBatch._fields})
