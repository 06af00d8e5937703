"""SchNet (Schütt et al., 2018) + FastSchNet (Sec. V, Eq. 13).

SchNet is invariant: continuous-filter convolutions update features from
RBF-expanded distances.  For position prediction we attach the equivariant
coordinate head of Eq. 13; FastSchNet additionally receives the virtual
pathway correction.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GeometricGraph
from repro.core.message_passing import (EdgeSpec, aggregate_edges,
                                        edge_pathway, edge_rel_d2)
from repro.core.mlp import init_linear, init_mlp, linear, mlp
from repro.core.virtual_nodes import VirtualState, init_virtual_coords
from repro.models.plugin import init_plugin, virtual_plugin_step

Array = jax.Array


class SchNetConfig(NamedTuple):
    n_layers: int = 4
    hidden: int = 64
    h_in: int = 1
    n_rbf: int = 32
    rbf_cutoff: float = 10.0
    n_virtual: int = 0
    s_dim: int = 64
    velocity: bool = True
    coord_clamp: float = 100.0
    use_kernel: bool = False  # dispatch coord head + virtual path to Pallas
    precision: str = "f32"  # kernel compute precision ('f32' | 'bf16')


def edge_spec(coord_clamp: float, precision: str = "f32") -> EdgeSpec:
    """Eq. 13 coordinate head: φ(h_i, h_j, d²) emits the scalar gate
    directly (identity gate), masked-mean aggregation."""
    return EdgeSpec(use_h=True, use_d2=True, gate="identity", rel="raw",
                    coord_clamp=coord_clamp, normalize=True,
                    precision=precision)


def ssp(x):
    """Shifted softplus, SchNet's activation."""
    return jax.nn.softplus(x) - jnp.log(2.0)


def rbf_expand(d: Array, n_rbf: int, cutoff: float) -> Array:
    """Gaussian RBF expansion of distances, (E,) → (E, n_rbf)."""
    centers = jnp.linspace(0.0, cutoff, n_rbf)
    gamma = n_rbf / cutoff
    return jnp.exp(-gamma * (d[:, None] - centers[None, :]) ** 2)


def init_schnet(key, cfg: SchNetConfig):
    keys = jax.random.split(key, 3 * cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        k_f, k_c, k_v = keys[3 * i], keys[3 * i + 1], keys[3 * i + 2]
        p = {
            # filter generator W(d): rbf → hidden
            "filter": init_mlp(k_f, [cfg.n_rbf, cfg.hidden, cfg.hidden]),
            "in_proj": init_linear(jax.random.fold_in(k_f, 1), cfg.hidden, cfg.hidden),
            "out": init_mlp(jax.random.fold_in(k_f, 2), [cfg.hidden, cfg.hidden, cfg.hidden]),
            # Eq. 13 coordinate head: φ(h_i, h_j) scalar gate
            "coord": init_mlp(k_c, [2 * cfg.hidden + 1, cfg.hidden, 1], final_bias=False),
            "phi_v": init_mlp(jax.random.fold_in(k_c, 1), [cfg.hidden, cfg.hidden, 1]),
        }
        if cfg.n_virtual > 0:
            p["virtual"] = init_plugin(k_v, cfg.n_virtual, cfg.hidden, cfg.s_dim, cfg.hidden)
        layers.append(p)
    out = {"embed": init_mlp(keys[-1], [cfg.h_in, cfg.hidden]), "layers": layers}
    if cfg.n_virtual > 0:
        out["s_init"] = 0.1 * jax.random.normal(jax.random.fold_in(keys[-1], 7),
                                                (cfg.n_virtual, cfg.s_dim))
    return out


def schnet_apply(params, cfg: SchNetConfig, g: GeometricGraph,
                 axis_name: Optional[str] = None,
                 edge_layout=None
                 ) -> tuple[Array, Array, Optional[VirtualState]]:
    """Returns (coords (N,3), feats (N,hidden), final virtual state or
    None without the plug-in)."""
    h = mlp(params["embed"], g.h)
    x = g.x
    vs = None
    if cfg.n_virtual > 0:
        z0 = init_virtual_coords(x, g.node_mask, cfg.n_virtual, axis_name)
        vs = VirtualState(z=z0, s=params["s_init"])

    spec = edge_spec(cfg.coord_clamp, cfg.precision)
    for lp in params["layers"]:
        _, d2 = edge_rel_d2(x, g)
        d = jnp.sqrt(d2[:, 0] + 1e-12)
        w = mlp(lp["filter"], rbf_expand(d, cfg.n_rbf, cfg.rbf_cutoff), act=ssp)
        # continuous-filter convolution (cfconv): the RBF-filter product
        # doesn't fit the φ1 form, so only the reduction is shared
        hj = linear(lp["in_proj"], h)[g.senders]
        agg = aggregate_edges(hj * w * g.edge_mask[:, None], g, normalize=False)
        h = h + mlp(lp["out"], agg, act=ssp)
        # Eq. 13: equivariant coordinate head + virtual pathway
        dx, _ = edge_pathway({"phi1": lp["coord"]}, h, x, g, spec,
                             use_kernel=cfg.use_kernel, layout=edge_layout)
        if cfg.n_virtual > 0:
            dx_v, _, vs = virtual_plugin_step(lp["virtual"], h, x, vs,
                                              g.node_mask, axis_name,
                                              use_kernel=cfg.use_kernel,
                                              precision=cfg.precision)
            dx = dx + dx_v
        if cfg.velocity:
            dx = dx + mlp(lp["phi_v"], h) * g.v
        x = x + dx * g.node_mask[:, None]
    return x, h, vs
