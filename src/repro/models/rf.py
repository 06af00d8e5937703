"""Radial Field (Köhler et al., 2019) + FastRF (Sec. V).

RF computes messages purely from inter-node distances — no node features.
FastRF therefore also drops ``h`` and the virtual features ``S`` from the
virtual pathway (zero-width arrays), keeping only geometry.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GeometricGraph
from repro.core.message_passing import EdgeSpec, edge_pathway
from repro.core.mlp import init_mlp
from repro.core.virtual_nodes import VirtualState, init_virtual_coords
from repro.models.plugin import init_plugin, virtual_plugin_step

Array = jax.Array


class RFConfig(NamedTuple):
    n_layers: int = 4
    hidden: int = 64
    n_virtual: int = 0  # 0 → plain RF
    velocity: bool = True
    coord_clamp: float = 100.0
    use_kernel: bool = False  # dispatch edge + virtual pathways to Pallas
    precision: str = "f32"  # kernel compute precision ('f32' | 'bf16')


def edge_spec(coord_clamp: float, precision: str = "f32") -> EdgeSpec:
    """Köhler-style normalised radial field: geometry-only φ (no node
    features), the width-1 message *is* the gate, and the pair direction is
    scaled by 1/(‖r‖+1) so far-apart pairs can't produce
    distance-proportional updates (raw rel·gate diverges on dense far-field
    graphs)."""
    return EdgeSpec(use_h=False, use_d2=True, gate="identity", rel="inv1p",
                    coord_clamp=coord_clamp, normalize=True,
                    precision=precision)


def init_rf(key, cfg: RFConfig):
    keys = jax.random.split(key, 2 * cfg.n_layers)
    layers = []
    for i in range(cfg.n_layers):
        p = {"phi": init_mlp(keys[2 * i], [1, cfg.hidden, 1], final_bias=False)}
        if cfg.n_virtual > 0:
            # h_dim = 0, s_dim = 0: geometry-only virtual pathway
            p["virtual"] = init_plugin(keys[2 * i + 1], cfg.n_virtual, 0, 0, cfg.hidden)
        layers.append(p)
    return {"layers": layers}


def rf_apply(params, cfg: RFConfig, g: GeometricGraph,
             axis_name: Optional[str] = None, edge_layout=None
             ) -> tuple[Array, Optional[VirtualState]]:
    """Returns (coords (N,3), final virtual state or None without the
    plug-in)."""
    x = g.x
    n = x.shape[0]
    vs = None
    if cfg.n_virtual > 0:
        z0 = init_virtual_coords(x, g.node_mask, cfg.n_virtual, axis_name)
        vs = VirtualState(z=z0, s=jnp.zeros((cfg.n_virtual, 0), x.dtype))
    h_empty = jnp.zeros((n, 0), x.dtype)

    spec = edge_spec(cfg.coord_clamp, cfg.precision)
    for lp in params["layers"]:
        dx, _ = edge_pathway({"phi1": lp["phi"]}, h_empty, x, g, spec,
                             use_kernel=cfg.use_kernel, layout=edge_layout)
        if cfg.n_virtual > 0:
            dx_v, _, vs = virtual_plugin_step(lp["virtual"], h_empty, x, vs,
                                              g.node_mask, axis_name,
                                              use_kernel=cfg.use_kernel,
                                              precision=cfg.precision)
            dx = dx + dx_v
        if cfg.velocity:
            dx = dx + g.v  # RF integrates the initial velocity directly
        x = x + dx * g.node_mask[:, None]
    return x, vs
