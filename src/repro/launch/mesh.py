"""Production mesh construction (pure function — importing never touches
jax device state; the dry-run sets XLA_FLAGS *before* calling this)."""
from __future__ import annotations

from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


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
