"""DistEGNN (Sec. VI): graph-partition parallelism via ``shard_map``.

One large geometric graph is split into D padded shards (data/partition.py);
each mesh slot along the ``graph`` axis processes its local subgraph while
the shared, ordered virtual nodes are re-synchronised with ``psum`` inside
every layer (Eqs. 16–17 — implemented by ``fast_egnn_apply(axis_name=...)``).
By default the layer schedule is comm/compute-*overlapped* (DESIGN.md §11.1):
each layer's virtual collectives are issued before/under the banded edge
pathway and consumed after it, bit-identical to the serialized schedule;
``overlap=`` on the builders below overrides ``cfg.overlap_sync``.

Gradient flow through the collective is automatic: ``jax.grad`` of a
``shard_map``-ed program produces the psum-of-cotangents backward rule that
the paper implements by hand for torch.distributed (DESIGN.md §6.1).

With ``cfg.use_kernel`` each shard's local edge pathway runs the banded
Pallas kernel, fed by the host-precomputed layouts that ``ShardedBatch``
carries alongside the edge arrays (zero trace-time regrouping —
DESIGN.md §6.6); shards failing the spec/VMEM eligibility check fall back
to the identical-math jnp path.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import GeometricGraph
from repro.core.message_passing import EDGE_KERNEL_BLOCK_E
from repro.core.mmd import mmd_loss
from repro.data.partition import repad_partition
from repro.kernels.edge_message import EdgeLayout, LayoutMeta, pick_windows
from repro.models.fast_egnn import FastEGNNConfig, fast_egnn_apply
from repro.training.losses import masked_mse
from repro.training.optim import Adam

Array = jax.Array
GRAPH_AXIS = "graph"

def make_gnn_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the graph-partition axis (data parallel handled by vmap
    inside each shard — every device owns shard d of *all* batch elements)."""
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), (GRAPH_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


class ShardedBatch(NamedTuple):
    """Batched, partitioned graph.  Leading dims (D, B, ...) — D is sharded.

    x/v/h/x_target: (D, B, n_cap, ·); senders/receivers/edge_mask: (D, B, e_cap);
    node_mask: (D, B, n_cap).  The ``lay_*`` fields mirror
    ``PartitionedGraph``'s host-precomputed banded layouts (D, B, ·): they
    ride the same ``graph``-axis sharding so each shard's fused edge kernel
    reads its own layout with zero trace-time regrouping (DESIGN.md §6.6).
    """

    x: Array
    v: Array
    h: Array
    senders: Array
    receivers: Array
    node_mask: Array
    edge_mask: Array
    x_target: Array
    lay_senders: Array
    lay_receivers: Array
    lay_edge_mask: Array
    lay_block_rwin: Array
    lay_block_swin: Array


# warn-once latch for stack_partitions re-padding (module-level: the
# pathology is a dataset property, repeating it per batch is noise)
_REPAD_WARNED = False


def stack_partitions_host(pgs, layout_cache=None) -> dict:
    """list[PartitionedGraph] → dict of stacked *numpy* ShardedBatch fields.

    The host (worker-thread-safe) half of :func:`stack_partitions` — the
    streaming data plane collates here and converts on the consumer side
    (``sharded_batch_to_device``) so device transfer can double-buffer
    (DESIGN.md §8).

    Per-sample node/edge capacities may differ — re-pad to the batch max so
    the stacked arrays are rectangular (host-precomputed banded layouts are
    rebuilt at the new capacities — ``data.partition.repad_partition``,
    through ``layout_cache`` when given).  Inflating a sample's capacity by
    more than 2× warns (once): that much padding usually means one outlier
    sample is dictating the whole batch's shapes — and compute.
    ``lay_window_offsets`` is a host-side diagnostic and deliberately *not*
    a ShardedBatch field — the kernel never reads it, so it would be dead
    payload on the graph axis.
    """
    global _REPAD_WARNED
    n_cap = max(p.x.shape[1] for p in pgs)
    e_cap = max(p.senders.shape[1] for p in pgs)

    stacked = []
    for p in pgs:
        n0, e0 = p.x.shape[1], p.senders.shape[1]
        if (n0, e0) == (n_cap, e_cap):
            stacked.append(p)
            continue
        if not _REPAD_WARNED and (n_cap > 2 * n0 or e_cap > 2 * e0):
            _REPAD_WARNED = True
            warnings.warn(
                f"stack_partitions: re-padding a sample from (n_cap={n0}, "
                f"e_cap={e0}) to the batch max (n_cap={n_cap}, e_cap={e_cap}) "
                f"— >2× inflation; one outlier sample is dictating the "
                f"batch's padded shapes (warned once)", stacklevel=2)
        stacked.append(repad_partition(p, n_cap, e_cap,
                                       layout_cache=layout_cache))

    return {f: np.stack([getattr(p, f) for p in stacked], axis=1)
            for f in ShardedBatch._fields}


def sharded_batch_to_device(host: dict, mesh: Optional[Mesh] = None
                            ) -> ShardedBatch:
    """Stacked numpy field dict → device ShardedBatch (async transfer).

    With a ``mesh`` each field is placed ``P('graph')``: shard d's rows go
    straight to device d, so no device stages another's shard and the
    jitted programs see the sharding they produce (no reshard, no retrace
    between the first call and later ones).  Without one the fields land
    on the default device (single-device reference callers)."""
    if mesh is None:
        return ShardedBatch(**{f: jnp.asarray(a) for f, a in host.items()})
    sharding = NamedSharding(mesh, P(GRAPH_AXIS))
    return ShardedBatch(**{f: jax.device_put(a, sharding)
                           for f, a in host.items()})


def stack_partitions(pgs, mesh: Optional[Mesh] = None) -> ShardedBatch:
    """list[PartitionedGraph] (one per batch element, each (D, ...)) →
    ShardedBatch, placed on ``mesh`` when given.  See
    :func:`stack_partitions_host` for the capacity re-padding semantics."""
    return sharded_batch_to_device(stack_partitions_host(pgs), mesh)


def _local_graph(sb: ShardedBatch) -> GeometricGraph:
    """Per-shard, per-batch-element local graph (no leading dims)."""
    e = sb.senders.shape[-1]
    return GeometricGraph(
        x=sb.x, v=sb.v, h=sb.h,
        senders=sb.senders, receivers=sb.receivers,
        edge_attr=jnp.zeros((e, 0), sb.x.dtype),
        node_mask=sb.node_mask, edge_mask=sb.edge_mask,
    )


def _edge_layout(sb: ShardedBatch) -> EdgeLayout:
    """This shard's host layout as kernel operands (no leading dims).

    The static band geometry is re-derived from the padded node capacity —
    the same derivation ``partition_sample`` used — so the kernel's meta
    check confirms layout and graph agree.
    """
    window, swindow, n_pad = pick_windows(sb.x.shape[-2])
    return EdgeLayout(
        senders=sb.lay_senders, receivers=sb.lay_receivers,
        edge_mask=sb.lay_edge_mask, block_rwin=sb.lay_block_rwin,
        block_swin=sb.lay_block_swin,
        meta=LayoutMeta(window, swindow, n_pad, EDGE_KERNEL_BLOCK_E))


def _resolve_overlap(cfg: FastEGNNConfig,
                     overlap: Optional[bool]) -> FastEGNNConfig:
    """Pin the layer schedule for a dist program build.

    ``overlap=None`` keeps ``cfg.overlap_sync`` (default: overlapped);
    an explicit bool overrides it — the parity harness builds both
    schedules from one config this way.  See DESIGN.md §11: the
    overlapped schedule issues each layer's virtual-node collectives
    before the banded edge pathway so the all-reduce runs under the edge
    compute; it is float-identical to the serialized one.
    """
    if overlap is None:
        return cfg
    return cfg._replace(overlap_sync=bool(overlap))


def build_dist_apply(cfg: FastEGNNConfig, mesh: Mesh,
                     overlap: Optional[bool] = None):
    """Jitted distributed forward: (params, ShardedBatch) → x_pred (D,B,n_cap,3).

    Params replicated; batch sharded on the graph axis.  With
    ``cfg.use_kernel`` each shard's local edge pathway runs the banded
    Pallas kernel, consuming the batch's host-precomputed layout (zero
    trace-time regrouping); shards whose spec/VMEM budget fails the
    eligibility check fall back to the identical-math jnp path.  With
    ``cfg.overlap_sync`` (or ``overlap=True``) every layer's virtual-node
    collectives are issued before its edge pathway and consumed after —
    the comm/compute overlap schedule of DESIGN.md §11, trace-counted as
    ``'collective_overlapped'`` vs ``'collective_serialized'`` in the
    dispatch telemetry.
    """
    cfg = _resolve_overlap(cfg, overlap)
    specs = ShardedBatch(*([P(GRAPH_AXIS)] * len(ShardedBatch._fields)))

    def shard_body(params, sb: ShardedBatch):
        sb = jax.tree.map(lambda a: a[0], sb)  # drop the size-1 local D dim

        def one(sbe):
            g = _local_graph(sbe)
            lay = _edge_layout(sbe) if cfg.use_kernel else None
            x, h, vs = fast_egnn_apply(params, cfg, g, axis_name=GRAPH_AXIS,
                                       edge_layout=lay)
            return x, vs

        x, vs = jax.vmap(one)(sb)
        return x[None], jax.tree.map(lambda a: a[None], vs)

    # replication checking off: vmap-over-psum inside shard_map needs the
    # unchecked collective batching rule
    mapped = jax.shard_map(shard_body, mesh=mesh, in_specs=(P(), specs),
                           out_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS)),
                           check_vma=False)
    return jax.jit(mapped)


def build_dist_train_step(cfg: FastEGNNConfig, mesh: Mesh, opt: Adam,
                          lam_mmd: float = 0.01, mmd_sigma: float = 1.5,
                          overlap: Optional[bool] = None):
    """Distributed train step implementing Eq. 18 + Alg. 1.

    The loss is the global masked MSE (psum across shards) plus λ × the mean
    over shards of the *local* MMD term — exactly Σ_d L_d / D.  ``jax.grad``
    through shard_map yields the synchronized gradients of Alg. 1 line 10.

    ``overlap`` pins the layer schedule (default: ``cfg.overlap_sync``,
    i.e. comm/compute-overlapped — DESIGN.md §11).  Both schedules produce
    identical losses and gradients; the overlapped one gives XLA a full
    edge pathway between each collective's launch and first use.
    """
    cfg = _resolve_overlap(cfg, overlap)
    specs = ShardedBatch(*([P(GRAPH_AXIS)] * len(ShardedBatch._fields)))

    def shard_loss(params, sb: ShardedBatch):
        sb = jax.tree.map(lambda a: a[0], sb)

        def one(sbe):
            g = _local_graph(sbe)
            lay = _edge_layout(sbe) if cfg.use_kernel else None
            x, h, vs = fast_egnn_apply(params, cfg, g, axis_name=GRAPH_AXIS,
                                       edge_layout=lay)
            mse = masked_mse(x, sbe.x_target, g.node_mask, axis_name=GRAPH_AXIS)
            # kernel-backed configs run the kernel-backed MMD cross term too
            mmd = mmd_loss(vs.z, sbe.x_target, g.node_mask, sigma=mmd_sigma,
                           use_kernel=cfg.use_kernel)
            return mse, mmd

        mse, mmd = jax.vmap(one)(sb)
        mmd_mean = jax.lax.pmean(jnp.mean(mmd), GRAPH_AXIS)  # Σ_d/D of Eq. 18
        loss = jnp.mean(mse) + lam_mmd * mmd_mean
        return loss[None]

    def loss_fn(params, sb):
        per_shard = jax.shard_map(shard_loss, mesh=mesh,
                                  in_specs=(P(), specs),
                                  out_specs=P(GRAPH_AXIS),
                                  check_vma=False)(params, sb)
        return jnp.mean(per_shard)  # identical on every shard already

    @jax.jit
    def train_step(params, opt_state, sb: ShardedBatch):
        loss, grads = jax.value_and_grad(loss_fn)(params, sb)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_step, jax.jit(loss_fn)
