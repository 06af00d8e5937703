"""Single-channel TFN (Thomas et al., 2018) + FastTFN (Sec. V, Eq. 15).

TPU-native adaptation (DESIGN.md §6): instead of generic Clebsch–Gordan
machinery over complex spherical harmonics, we realise the paper's
*single-channel, type-1-output* TFN (ℓ ≤ 2) with explicit Cartesian tensor
algebra — mathematically the same CG paths:

  Y⁰ ⊗ v¹ → 1 :  w₀ · v_j
  Y¹ ⊗ h⁰ → 1 :  w₁ · r̂
  Y¹ ⊗ v¹ → 1 :  w₂ · (r̂ × v_j)                (antisymmetric path)
  Y² ⊗ v¹ → 1 :  w₃ · (r̂ r̂ᵀ − I/3) v_j        (symmetric-traceless path)

with per-path weights w_k generated by a radial MLP of ‖r‖ — exactly TFN's
radially-modulated equivariant kernels, restricted to degree ≤ 2 and one
channel (the paper itself reduces TFN to one channel for FastTFN).  The model
is SO(3)-equivariant (the cross-product path flips sign under reflection,
matching TFN's SO(3) group).  Type-0 features update from the invariant
paths Y⁰⊗h⁰→0 and Y¹⊗v¹→0 (dot product).

As in the paper, spherical-harmonic initialisation *requires edges*: TFN
variants do not support edge-dropping rate 1.00 (Table III footnote).

With ``use_kernel`` and a batch that carries its banded layout, the edge
pathway runs as one fused Pallas kernel per direction
(``kernels/tfn_edge.py``): the paths, the radial network and the
degree-mean reduction per block of edges in VMEM (DESIGN.md §6.5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GeometricGraph
from repro.core.message_passing import (aggregate_edges, edge_rel_d2,
                                        record_dispatch)
from repro.core.mlp import init_mlp, mlp
from repro.core.virtual_nodes import VirtualState, init_virtual_coords
from repro.models.plugin import init_plugin, virtual_plugin_step

Array = jax.Array


class TFNConfig(NamedTuple):
    n_layers: int = 4
    hidden: int = 64
    h_in: int = 1
    n_rbf: int = 16
    rbf_cutoff: float = 10.0
    n_virtual: int = 0
    s_dim: int = 64
    coord_clamp: float = 100.0
    # dispatch the edge pathway (where the batch carries a banded layout)
    # and the fast_* plug-in's virtual pathway to the Pallas kernels
    use_kernel: bool = False
    precision: str = "f32"  # kernel compute precision ('f32' | 'bf16')


def _rbf(d: Array, n: int, cutoff: float) -> Array:
    centers = jnp.linspace(0.0, cutoff, n)
    gamma = n / cutoff
    return jnp.exp(-gamma * (d[:, None] - centers[None, :]) ** 2)


def init_tfn(key, cfg: TFNConfig):
    keys = jax.random.split(key, 2 * cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        k_r, k_v = keys[2 * i], keys[2 * i + 1]
        p = {
            # radial net → 4 type-1 path weights + 2 type-0 path weights
            "radial": init_mlp(k_r, [cfg.n_rbf + cfg.hidden, cfg.hidden, 6]),
            "h_out": init_mlp(jax.random.fold_in(k_r, 1), [cfg.hidden + 2, cfg.hidden, cfg.hidden]),
        }
        if cfg.n_virtual > 0:
            p["virtual"] = init_plugin(k_v, cfg.n_virtual, cfg.hidden, cfg.s_dim, cfg.hidden)
        layers.append(p)
    out = {"embed": init_mlp(keys[-1], [cfg.h_in, cfg.hidden]), "layers": layers}
    if cfg.n_virtual > 0:
        out["s_init"] = 0.1 * jax.random.normal(jax.random.fold_in(keys[-1], 7),
                                                (cfg.n_virtual, cfg.s_dim))
    return out


def edge_kernel_supported(radial, cfg: TFNConfig, g: GeometricGraph,
                          layout) -> bool:
    """Dispatch rule of the fused TFN edge pathway (DESIGN.md §3.2): the
    kernel asked for, a banded layout to walk (the batch's host layout),
    the two-layer radial network, and the per-window VMEM budget."""
    if not cfg.use_kernel or layout is None or len(radial) != 2:
        return False
    from repro.kernels.edge_message import VMEM_LIMIT_BYTES
    from repro.kernels.tfn_edge import tfn_edge_vmem_bytes

    return tfn_edge_vmem_bytes(g.n_nodes, cfg.hidden, cfg.n_rbf,
                               precision=cfg.precision) <= VMEM_LIMIT_BYTES


def tfn_edge_pathway(radial, h: Array, x: Array, g: GeometricGraph,
                     cfg: TFNConfig, layout=None) -> tuple[Array, Array]:
    """The Cartesian CG paths on every edge j → i, degree-mean reduced onto
    the receivers: ``(dx (N, 3), h_agg (N, 2))``.  The fused kernel
    (``kernels.ops.tfn_edge_pathway``) where :func:`edge_kernel_supported`
    holds, the jnp composition below otherwise; recorded at trace time as
    ``'tfn_edge_kernel'`` / ``'tfn_edge_jnp'``."""
    if edge_kernel_supported(radial, cfg, g, layout):
        from repro.kernels import ops as kops

        record_dispatch("tfn_edge_kernel")
        return kops.tfn_edge_pathway(radial, h, x, g.v, layout,
                                     cutoff=cfg.rbf_cutoff,
                                     clamp=cfg.coord_clamp,
                                     precision=cfg.precision)
    record_dispatch("tfn_edge_jnp")
    rel, d2e = edge_rel_d2(x, g)  # (E,3), (E,1)
    d = jnp.sqrt(d2e[:, 0] + 1e-12)
    rhat = rel / d[:, None]
    vj = g.v[g.senders]
    with jax.named_scope("radial_mlp"):
        rad_in = jnp.concatenate([_rbf(d, cfg.n_rbf, cfg.rbf_cutoff),
                                  h[g.senders]], axis=-1)
        w = jnp.clip(mlp(radial, rad_in), -cfg.coord_clamp,
                     cfg.coord_clamp)  # (E,6)
    # type-1 output paths (Eq. 15's CG product restricted to type-1 outputs)
    quad = rhat * jnp.sum(rhat * vj, axis=-1, keepdims=True) - vj / 3.0  # (r̂r̂ᵀ−I/3)v
    dx_e = (
        w[:, 0:1] * vj
        + w[:, 1:2] * rhat
        + w[:, 2:3] * jnp.cross(rhat, vj)
        + w[:, 3:4] * quad
    ) * g.edge_mask[:, None]
    # type-0 invariant paths for the feature update
    s0 = jnp.stack([w[:, 4], w[:, 5] * jnp.sum(rhat * vj, axis=-1)], axis=-1)
    s0 = s0 * g.edge_mask[:, None]
    return aggregate_edges(dx_e, g), aggregate_edges(s0, g)


def tfn_apply(params, cfg: TFNConfig, g: GeometricGraph,
              axis_name: Optional[str] = None, edge_layout=None
              ) -> tuple[Array, Array, Optional[VirtualState]]:
    """Returns (coords (N,3), feats (N,hidden), final virtual state or None
    without the plug-in).  ``edge_layout``: the batch's banded layout,
    which the fused edge pathway walks.

    Layer ``k`` runs under the name scope ``layer_<k>``, and inside it
    ``edge_pathway`` (with the radial network under ``radial_mlp``),
    ``virtual_pathway`` and ``node_update``.
    """
    h = mlp(params["embed"], g.h)
    x = g.x
    vs = None
    if cfg.n_virtual > 0:
        z0 = init_virtual_coords(x, g.node_mask, cfg.n_virtual, axis_name)
        vs = VirtualState(z=z0, s=params["s_init"])

    for k, lp in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{k}"):
            with jax.named_scope("edge_pathway"):
                dx, h_agg = tfn_edge_pathway(lp["radial"], h, x, g, cfg,
                                             edge_layout)
            if cfg.n_virtual > 0:
                with jax.named_scope("virtual_pathway"):
                    dx_v, _, vs = virtual_plugin_step(
                        lp["virtual"], h, x, vs, g.node_mask, axis_name,
                        use_kernel=cfg.use_kernel, precision=cfg.precision)
                dx = dx + dx_v
            with jax.named_scope("node_update"):
                x = x + dx * g.node_mask[:, None]
                h = h + mlp(lp["h_out"], jnp.concatenate([h, h_agg], axis=-1))
    return x, h, vs
