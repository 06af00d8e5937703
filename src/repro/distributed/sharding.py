"""pjit sharding rules for the transformer pool on the production mesh.

Scheme (DESIGN.md §4): batch → data-parallel over ('pod','data'); parameters
FSDP-sharded over 'data' and tensor-parallel over 'model' (heads / d_ff /
experts / vocab); KV caches shard batch over 'data' and heads (or head_dim
when the arch's kv count doesn't divide, e.g. granite's MQA) over 'model';
batch-1 long-context caches shard the *sequence* axis over 'data' instead
(context-parallel decode).

Rules are name-based over the param pytree paths — the same tree works for
Adam's m/v shadows.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FSDP = "data"
TP = "model"


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.shape and n % mesh.shape[axis] == 0


def _maybe(mesh: Mesh, axis: str, dim: int) -> Optional[str]:
    return axis if _div(dim, mesh, axis) else None


def dp_axes(mesh: Mesh):
    """Batch data-parallel axes: ('pod','data') on the multi-pod mesh."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def _spec_for(path: str, shape: tuple[int, ...], mesh: Mesh,
              tp_min_weight: int = 0, fsdp_min_weight: int = 0) -> P:
    """Name-based parameter partition rules.

    §Perf treatments (benchmarks/hillclimb.py):
    ``tp_min_weight``: weights with fewer elements are replicated instead of
    tensor-parallel-sharded.  REFUTED as a lone treatment for small models —
    it idles the fixed 'model' mesh axis entirely (per-chip flops ×|model|).
    ``fsdp_min_weight``: weights below the threshold skip the FSDP ('data')
    sharding but KEEP TP.  Rationale: GSPMD realises a data-sharded
    *contracting* dim as partial-sums + an all-reduce of the FULL activation
    tensor over 'data' — for a small weight that collective dwarfs the
    storage saved (the xlstm hillclimb found a 13 GB fp32 all-reduce per
    layer caused by FSDP on a 2.4 M-element weight)."""
    import numpy as _np
    n_elems = int(_np.prod(shape)) if shape else 0

    def fs(d):  # FSDP shard if divisible
        if fsdp_min_weight and n_elems < fsdp_min_weight:
            return None
        return _maybe(mesh, FSDP, d)

    def tp(d):
        if tp_min_weight and n_elems < tp_min_weight:
            return None
        return _maybe(mesh, TP, d)

    if len(shape) <= 1:
        return P()  # norms, biases, gates — replicate
    # MoE expert stacks: (E, d, ff) / (E, ff, d)
    if "experts" in path and len(shape) == 3:
        e, a, b = shape
        return P(tp(e), fs(a), None)
    if re.search(r"(embed|lm_head)$", path):
        v_or_d, d2 = shape
        if "embed" in path:  # (V, d)
            return P(tp(shape[0]), fs(shape[1]))
        return P(fs(shape[0]), tp(shape[1]))  # lm_head (d, V)
    # contraction-output projections: second dim is d_model
    if re.search(r"(wo|down|w_down|out_proj|ff_down|w_write)", path):
        return P(tp(shape[0]), fs(shape[1]))
    # default matmul weights (d_in, d_out): FSDP on in, TP on out
    return P(fs(shape[0]), tp(shape[1]))


def param_shardings(params_shape: Any, mesh: Mesh, *, tp_min_weight: int = 0,
                    fsdp_min_weight: int = 0):
    """ShapeDtypeStruct/array pytree → NamedSharding pytree (same structure)."""

    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
                        for k in path)
        return NamedSharding(mesh, _spec_for(pstr, tuple(leaf.shape), mesh,
                                             tp_min_weight, fsdp_min_weight))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_sharding(mesh: Mesh, batch: int, ndim: int) -> NamedSharding:
    """Tokens/labels (B, S, ...) — shard B over the dp axes when divisible."""
    axes = dp_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    lead = axes if batch % total == 0 else None
    return NamedSharding(mesh, P(lead, *([None] * (ndim - 1))))


def cache_shardings(cache_shape: Any, mesh: Mesh, batch: int):
    """Decode caches: batch over 'data' when divisible, else sequence over
    'data' (context-parallel); kv-heads (or head_dim) over 'model'."""

    def one(leaf):
        shp = tuple(leaf.shape)
        if len(shp) == 4:  # KV cache (B, T, KV, D) or ssm (B, H, P, N)
            b, t, kv, d = shp
            if _div(b, mesh, FSDP):
                return NamedSharding(mesh, P(FSDP, None, _maybe(mesh, TP, kv) or _maybe(mesh, TP, d) and None, _maybe(mesh, TP, d) if not _div(kv, mesh, TP) else None))
            return NamedSharding(mesh, P(None, _maybe(mesh, FSDP, t),
                                         _maybe(mesh, TP, kv),
                                         None if _div(kv, mesh, TP) else _maybe(mesh, TP, d)))
        if len(shp) == 3:  # MLA latent (B, T, L) / conv tail / vt state
            b, t, L = shp
            if _div(b, mesh, FSDP):
                return NamedSharding(mesh, P(FSDP, None, _maybe(mesh, TP, L)))
            return NamedSharding(mesh, P(None, _maybe(mesh, FSDP, t), _maybe(mesh, TP, L)))
        if len(shp) == 2:
            b, t = shp
            if _div(b, mesh, FSDP):
                return NamedSharding(mesh, P(FSDP, None))
            return NamedSharding(mesh, P(None, _maybe(mesh, FSDP, t)))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, cache_shape)


# --------------------------------------------------------------------------
# Process-sharded DistEGNN data plane (DESIGN.md §11).
#
# The GNN mesh (dist_egnn.make_gnn_mesh) lays the 'graph' axis out in
# jax.devices() order, which enumerates devices process-by-process — so a
# contiguous block of graph shards lives on each host's local devices.
# These helpers are the host side of that layout: which shard rows a
# process owns, and how its locally-built (D_local, B, ...) numpy fields
# become one global sharded array without any host ever materialising
# another host's shards.


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
