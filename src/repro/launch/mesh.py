"""Multi-host start-up (importing never touches jax device state)."""
from __future__ import annotations

from typing import Optional

import jax


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int,
                     cpu_collectives: Optional[str] = "gloo") -> None:
    """Join a multi-host jax run (the DistEGNN scale-out entry point).

    Must run before any other jax call touches the backend.  On the CPU
    backend cross-process collectives need an explicit implementation —
    without ``jax_cpu_collectives_implementation`` the first psum raises
    "Multiprocess computations aren't implemented on the CPU backend" —
    so ``cpu_collectives`` (default ``'gloo'``) is applied first (TPU/GPU
    runs ignore it; pass ``None`` to skip).  After this returns,
    ``jax.devices()`` spans every process and ``dist_egnn.make_gnn_mesh``
    builds the global graph mesh; each host then feeds only its own shards
    through the process-sharded stream (DESIGN.md §11).
    """
    if cpu_collectives is not None:
        jax.config.update("jax_cpu_collectives_implementation",
                          cpu_collectives)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=int(num_processes),
                               process_id=int(process_id))
