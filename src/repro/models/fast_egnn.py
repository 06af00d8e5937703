"""FastEGNN (Sec. IV) — EGNN + ordered virtual nodes.

The *same* apply function implements DistEGNN (Sec. VI): passing
``axis_name='graph'`` while running under ``shard_map`` turns every
node-reduction (CoM, virtual aggregation Eqs. 16–17) into a cross-device
psum.  Single-device FastEGNN is the ``axis_name=None`` special case.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GeometricGraph
from repro.core.message_passing import clamp_vector_norm
from repro.core.mlp import init_mlp, mlp
from repro.core.virtual_nodes import (
    VirtualState,
    finish_virtual_aggregate,
    init_virtual_block,
    init_virtual_coords,
    launch_virtual_sums,
    masked_com,
    masked_com_sums,
    virtual_aggregate_from_sums,
    virtual_global_message,
    virtual_pathway,
)
from repro.models.egnn import EGNNConfig, real_real_pathway

Array = jax.Array


class FastEGNNConfig(NamedTuple):
    n_layers: int = 4
    hidden: int = 64
    h_in: int = 1
    edge_attr_dim: int = 0
    n_virtual: int = 3  # C
    s_dim: int = 64
    velocity: bool = True
    coord_clamp: float = 100.0
    # dispatch virtual AND real-real edge pathways to the Pallas kernels
    use_kernel: bool = False
    # Table II ablation: share one weight set across channels (unordered
    # "Global Nodes" variant — strictly weaker, kept for the benchmark)
    shared_virtual: bool = False
    # kernel compute precision ('f32' | 'bf16'); bf16 computes in bfloat16
    # with f32 accumulation inside the fused kernels (DESIGN.md §9)
    precision: str = "f32"
    # DistEGNN comm/compute overlap (DESIGN.md §11): issue each layer's
    # virtual-node collectives before the banded edge pathway and consume
    # them after it, so the all-reduce runs under the edge compute.  Only
    # takes effect with an axis_name (single-device has no collectives);
    # float-identical to the serialized schedule (same psums, same order).
    overlap_sync: bool = True

    def egnn(self) -> EGNNConfig:
        return EGNNConfig(
            n_layers=self.n_layers,
            hidden=self.hidden,
            h_in=self.h_in,
            edge_attr_dim=self.edge_attr_dim,
            velocity=self.velocity,
            coord_clamp=self.coord_clamp,
            precision=self.precision,
        )


def init_fast_egnn_layer(key, cfg: FastEGNNConfig):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    hid = cfg.hidden
    msg_in = 2 * hid + 1 + cfg.edge_attr_dim
    p = {
        "phi1": init_mlp(k1, [msg_in, hid, hid]),
        "phi_xr": init_mlp(k2, [hid, hid, 1], final_bias=False),
        # Eq. 7: h, real agg, virtual agg
        "phi_h": init_mlp(k3, [3 * hid, hid, hid]),
        "virtual": init_virtual_block(k5, cfg.n_virtual, hid, cfg.s_dim, hid,
                                      shared=cfg.shared_virtual),
    }
    if cfg.velocity:
        p["phi_v"] = init_mlp(k4, [hid, hid, 1])
    return p


def init_fast_egnn(key, cfg: FastEGNNConfig):
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {
        "embed": init_mlp(keys[0], [cfg.h_in, cfg.hidden]),
        # S ∈ R^{C×s_dim}: free learnable parameters (ordered set, Sec. IV-A)
        "s_init": 0.1 * jax.random.normal(keys[1], (cfg.n_virtual, cfg.s_dim)),
        "layers": [init_fast_egnn_layer(k, cfg) for k in keys[2:]],
    }


def fast_egnn_apply(
    params,
    cfg: FastEGNNConfig,
    g: GeometricGraph,
    *,
    axis_name: Optional[str] = None,
    edge_layout=None,
) -> tuple[Array, Array, VirtualState]:
    """Returns (coords (N,3), feats (N,hidden), final virtual state).

    ``axis_name`` ⇒ DistEGNN: node reductions become psums over that mesh
    axis (the caller must be inside shard_map over it).  ``edge_layout``
    (``kernels.edge_message.EdgeLayout``) is this shard's host-precomputed
    banded layout for the real-real pathway: with ``cfg.use_kernel`` the
    fused kernel consumes it directly instead of regrouping at trace time
    (DESIGN.md §6.6); ignored on the jnp path.

    Layer ``k`` runs under the name scope ``layer_<k>``, and inside it
    ``virtual_pathway`` (CoM, Eqs. 4-5, 8-9, and DistEGNN's psums),
    ``edge_pathway`` and ``node_update``: the compiled program's op names,
    and so a profile, attribute each operation to its layer and part.
    """
    h = mlp(params["embed"], g.h)
    x = g.x
    z0 = init_virtual_coords(x, g.node_mask, cfg.n_virtual, axis_name)
    vs = VirtualState(z=z0, s=params["s_init"])
    overlap = axis_name is not None and getattr(cfg, "overlap_sync", False)
    if overlap:
        return _apply_overlapped(params, cfg, g, h, x, vs, axis_name,
                                 edge_layout)

    from repro.core.message_passing import record_dispatch

    for k, lp in enumerate(params["layers"]):
        if axis_name is not None:
            # two serialized collective groups per layer: the CoM psum and
            # the Eqs. 16–17 aggregate psum both complete before any
            # dependent compute is issued (cf. 'collective_overlapped')
            record_dispatch("collective_serialized")
            record_dispatch("collective_serialized")
        with jax.named_scope(f"layer_{k}"):
            with jax.named_scope("virtual_pathway"):
                com = masked_com(x, g.node_mask, axis_name)  # Alg. 1 line 4
                mv = virtual_global_message(vs.z, com)  # Eq. 4
                dx_v, mh_v, dz_sum, ms_sum = virtual_pathway(
                    lp["virtual"], h, x, vs, mv, g.node_mask,
                    use_kernel=cfg.use_kernel,
                    precision=cfg.precision)  # Eq. 5
            dx_r, mh_r = _edge_pathway(lp, cfg, h, x, g, edge_layout)
            x_new, h = _node_update(lp, cfg, g, h, x, dx_r, mh_r, dx_v, mh_v)
            # Eqs. 8–9 / 16–17 use the pre-update coordinates x^{(l)}.
            with jax.named_scope("virtual_pathway"):
                vs = virtual_aggregate_from_sums(
                    lp["virtual"], vs, dz_sum, ms_sum, jnp.sum(g.node_mask),
                    axis_name)
        x = x_new
    return x, h, vs


def _edge_pathway(lp, cfg: FastEGNNConfig, h: Array, x: Array,
                  g: GeometricGraph, edge_layout) -> tuple[Array, Array]:
    """The real-real pathway (Eqs. 3, 6-7), under the scope
    ``edge_pathway``."""
    with jax.named_scope("edge_pathway"):
        return real_real_pathway(lp, h, x, g, cfg.coord_clamp,
                                 cfg.use_kernel, edge_layout=edge_layout,
                                 precision=cfg.precision)


def _node_update(lp, cfg: FastEGNNConfig, g: GeometricGraph, h: Array,
                 x: Array, dx_r: Array, mh_r: Array, dx_v: Array,
                 mh_v: Array) -> tuple[Array, Array]:
    """Eqs. 6-7 from both pathways' terms, under the scope
    ``node_update``; returns ``(x^{(l+1)}, h^{(l+1)})``."""
    with jax.named_scope("node_update"):
        # clamp the virtual term like the real-real term (official EGNN
        # practice): an unbounded gate feeds the |x|→|d²| runaway loop.
        # Norm rescale, not componentwise clip — the clip box is
        # axis-aligned and would break Prop. IV.1 when it binds.
        dx_v = clamp_vector_norm(dx_v, cfg.coord_clamp)
        dx = dx_r + dx_v
        if cfg.velocity:
            dx = dx + mlp(lp["phi_v"], h) * g.v
        x_new = x + dx * g.node_mask[:, None]  # Eq. 6
        h_new = h + mlp(lp["phi_h"],
                        jnp.concatenate([h, mh_r, mh_v], axis=-1))  # Eq. 7
    return x_new, h_new


def _apply_overlapped(params, cfg: FastEGNNConfig, g: GeometricGraph,
                      h: Array, x: Array, vs: VirtualState, axis_name: str,
                      edge_layout) -> tuple[Array, Array, VirtualState]:
    """The comm/compute-overlapped DistEGNN layer schedule (DESIGN.md §11).

    Software-pipelined over the layers: each layer's CoM psum is *issued*
    before its banded edge pathway, and the Eqs. 16–17 aggregate psum is
    issued at the end of layer ``l`` but only *consumed* (the tiny
    ``phi_s`` epilogue) after layer ``l+1``'s edge pathway has been
    issued.  The edge pathway depends on neither collective — it reads
    only ``(h^{(l)}, x^{(l)})`` — so in program order every all-reduce has
    a full edge kernel between launch and first use, which is exactly the
    window XLA's latency-hiding scheduler overlaps.  The psum operands,
    reduction order and epilogue math are unchanged, so the result is
    float-identical to the serialized schedule (the parity test in
    ``tests/test_multiprocess.py`` pins this).  Name scopes as in
    :func:`fast_egnn_apply`; layer ``l``'s aggregate epilogue is named
    where it runs, under layer ``l+1``'s ``virtual_pathway``.
    """
    from repro.core.message_passing import record_dispatch

    pending = None  # (layer_params, vs, dz, ms, n): psums in flight
    for k, lp in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{k}"):
            record_dispatch("collective_overlapped")  # CoM psum, issued early
            with jax.named_scope("virtual_pathway"):
                tot, cnt = masked_com_sums(x, g.node_mask, axis_name)
            dx_r, mh_r = _edge_pathway(lp, cfg, h, x, g, edge_layout)
            with jax.named_scope("virtual_pathway"):
                if pending is not None:  # consume layer l-1's aggregate psums
                    vs = finish_virtual_aggregate(*pending)
                    pending = None
                com = tot / jnp.maximum(cnt, 1.0)  # Alg. 1 line 4
                mv = virtual_global_message(vs.z, com)  # Eq. 4
                dx_v, mh_v, dz_sum, ms_sum = virtual_pathway(
                    lp["virtual"], h, x, vs, mv, g.node_mask,
                    use_kernel=cfg.use_kernel,
                    precision=cfg.precision)  # Eq. 5
            x_new, h = _node_update(lp, cfg, g, h, x, dx_r, mh_r, dx_v, mh_v)
            # Eqs. 16–17 collectives launched here (pre-update coordinates
            # x^{(l)} — same operands as the serialized path), finished
            # after the *next* layer's edge pathway
            record_dispatch("collective_overlapped")
            with jax.named_scope("virtual_pathway"):
                sums = launch_virtual_sums(dz_sum, ms_sum,
                                           jnp.sum(g.node_mask), axis_name)
            pending = (lp["virtual"], vs, *sums)
        x = x_new
    # drain the last layer's psums
    with jax.named_scope(f"layer_{k}"), jax.named_scope("virtual_pathway"):
        vs = finish_virtual_aggregate(*pending)
    return x, h, vs
