"""Jit'd dispatch layer over the Pallas kernels.

* unpacks the model's per-channel MLP parameter stacks into the kernels' flat
  weight layout (and precomputes the node-independent φ2 layer-1 constant);
* attaches ``jax.custom_vjp`` backward passes that call the **fused Pallas
  backward kernels** (DESIGN.md §9) — flash-attention-style recompute in
  VMEM, so neither direction materialises an (E, hidden) or (N, C, hidden)
  tensor; the pure-jnp oracles in ``kernels.ref`` remain the parity ground
  truth for both directions but are no longer on the compute path;
* threads the static precision contract (``kernels.runtime.Precision``)
  into every kernel pair.

Differentiability contract: coordinates, features, virtual state and all
weights carry real gradients; integer edge endpoints get float0
cotangents; **masks are not differentiated** — the edge mask, node mask and
a threaded ``EdgeLayout`` (a host-built copy of the edge data) all receive
zero cotangents, and the forward's ``deg`` output is constant w.r.t. every
differentiable input.  Nothing in the repo differentiates a mask; the zero
keeps the backward kernels free of the per-edge/per-node mask-gradient
scatters the oracle's vjp would imply.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.dtypes import float0

from repro.core.mlp import dot_precision
from repro.kernels.edge_message import (edge_pathway_bwd_fused,
                                        edge_pathway_fused)
from repro.kernels.mmd_rbf import mmd_cross_grads, mmd_cross_sum
from repro.kernels.runtime import resolve_precision
from repro.kernels.tfn_edge import tfn_edge_bwd_fused, tfn_edge_fused
from repro.kernels.virtual_message import (virtual_pathway_bwd_fused,
                                           virtual_pathway_fused)

Array = jax.Array


# ------------------------------------------------------------------- edge MP
@functools.lru_cache(maxsize=None)
def _edge_custom(gate_mode: str, rel_mode: str, clamp: float,
                 with_layout: bool = False, precision=None):
    """Per-variant custom_vjp wrapper (cached so jit caches stay warm).

    Forward: fused Pallas kernel — banded-CSR tiled, so any graph size the
    VMEM-budget check admits dispatches here; the banded regrouping runs
    inside the fused forward at trace time, or is skipped entirely when
    ``with_layout`` threads a host-precomputed ``EdgeLayout`` through as an
    extra (non-differentiable) operand.  Backward: the fused two-pass
    Pallas backward (``edge_pathway_bwd_fused``) over the same banded
    blocks — the only residual is the forward's ``deg`` column; messages
    and gates are recomputed in VMEM.  Integer edge indices get float0
    cotangents; the edge mask and the layout get zeros (module docstring).
    """
    prec = resolve_precision(precision)
    kw = dict(gate_mode=gate_mode, rel_mode=rel_mode, clamp=clamp,
              precision=prec)

    if with_layout:

        @jax.custom_vjp
        def f(x, h, snd, rcv, em, lay, *ws):
            return edge_pathway_fused(x, h, snd, rcv, em, *ws, layout=lay,
                                      **kw)

    else:

        @jax.custom_vjp
        def f(x, h, snd, rcv, em, *ws):
            return edge_pathway_fused(x, h, snd, rcv, em, *ws, **kw)

    def fwd(*args):
        out = f(*args)
        return out, (args, out[2])  # deg: the only non-primal residual

    def bwd(res, cots):
        args, deg = res
        if with_layout:
            x, h, snd, rcv, em, lay, *ws = args
        else:
            x, h, snd, rcv, em, *ws = args
            lay = None
        g_dx, g_mh, _g_deg = cots  # deg is constant w.r.t. x/h/weights
        grads = edge_pathway_bwd_fused(x, h, snd, rcv, em, *ws, deg,
                                       g_dx, g_mh, layout=lay, **kw)
        gx, gh, *gws = (g.astype(p.dtype)
                        for g, p in zip(grads, (x, h, *ws)))
        if with_layout:
            return (gx, gh, _zint(snd), _zint(rcv), jnp.zeros_like(em),
                    _layout_cotangent(lay), *gws)
        return (gx, gh, _zint(snd), _zint(rcv), jnp.zeros_like(em), *gws)

    f.defvjp(fwd, bwd)
    return f


def _zint(a):
    return np.zeros(a.shape, dtype=float0)


def _layout_cotangent(lay):
    """The zero cotangent of a threaded ``EdgeLayout`` (module docstring)."""
    return type(lay)(_zint(lay.senders), _zint(lay.receivers),
                     jnp.zeros_like(lay.edge_mask), _zint(lay.block_rwin),
                     _zint(lay.block_swin), meta=lay.meta)


def unpack_edge_params(lp, h: Array, spec) -> tuple[Array, tuple[Array, ...]]:
    """Model param pytree → the kernel's flat weight layout.

    φ1 layer-1 weight rows are ordered [h_r | h_s | d² | e_ij] (the
    concatenation order in ``core.message_passing._phi1_features``); the
    matrix is pre-split per input slice so optional inputs become
    zero-width or zero-weight slices.  Returns (h_for_kernel, weights).
    """
    n = h.shape[0]
    phi1 = lp["phi1"]
    w1, b1 = phi1[0]["w"], phi1[0]["b"]
    h1 = w1.shape[1]
    dh = h.shape[-1] if spec.use_h else 0
    if dh > 0:
        hk = h
        w1r, w1s = w1[:dh], w1[dh : 2 * dh]
    else:  # geometry-only models (RF): a zero feature column keeps shapes ≥1
        hk = jnp.zeros((n, 1), w1.dtype)
        w1r = w1s = jnp.zeros((1, h1), w1.dtype)
    off = 2 * dh
    if spec.use_d2:
        w1d = w1[off : off + 1]
    else:
        w1d = jnp.zeros((1, h1), w1.dtype)
    w2 = phi1[1]["w"]
    m = w2.shape[1]
    b2 = phi1[1]["b"][None, :] if "b" in phi1[1] else jnp.zeros((1, m), w2.dtype)
    if spec.gate == "mlp":
        gp = lp["gate"]
        wg1, bg1, wg2 = gp[0]["w"], gp[0]["b"][None, :], gp[1]["w"]
    else:  # unused by the 'identity'/'none' static branches
        wg1 = bg1 = wg2 = jnp.zeros((1, 1), w2.dtype)
    return hk, (w1r, w1s, w1d, b1[None, :], w2, b2, wg1, bg1, wg2)


def edge_pathway(lp, h: Array, x: Array, g, spec,
                 layout=None) -> tuple[Array, Array]:
    """Kernel-backed replacement for the jnp edge pathway.

    Returns (dx (N,3), mh (N,M)); eligibility is checked by the caller
    (``core.message_passing.kernel_supported`` — a per-window VMEM budget,
    constant in graph size, so Water-3D 8K and Fluid113K-scale graphs
    dispatch here rather than falling back to jnp).

    ``layout`` threads a host-precomputed ``EdgeLayout`` into the fused
    forward *and* backward (zero trace-time regrouping in either
    direction).  ``spec.precision`` selects the compute/accumulate pair.
    """
    hk, ws = unpack_edge_params(lp, h, spec)
    prec = resolve_precision(getattr(spec, "precision", None))
    if layout is not None:
        f = _edge_custom(spec.gate, spec.rel, float(spec.coord_clamp), True,
                         prec)
        dx, mh, _deg = f(x, hk, g.senders, g.receivers, g.edge_mask,
                         layout, *ws)
    else:
        f = _edge_custom(spec.gate, spec.rel, float(spec.coord_clamp), False,
                         prec)
        dx, mh, _deg = f(x, hk, g.senders, g.receivers, g.edge_mask, *ws)
    return dx, mh


# ------------------------------------------------------------------- TFN MP
@functools.lru_cache(maxsize=None)
def _tfn_edge_custom(cutoff: float, clamp: float, precision=None):
    """Per-variant custom_vjp wrapper of the fused TFN edge pathway
    (``kernels/tfn_edge.py``).  Backward: its two fused passes; the only
    residual is the forward's ``deg`` column.  The layout and the rbf
    centres get zero cotangents."""
    kw = dict(cutoff=cutoff, clamp=clamp, precision=resolve_precision(precision))

    @jax.custom_vjp
    def f(x, a, v, lay, centers, w1r, w2, b2):
        return tfn_edge_fused(x, a, v, lay, centers, w1r, w2, b2, **kw)

    def fwd(*args):
        out = f(*args)
        return out, (args, out[2])

    def bwd(res, cots):
        args, deg = res
        x, a, v, lay, centers, w1r, w2, b2 = args
        g_dx, g_h, _g_deg = cots  # deg is constant w.r.t. every input
        grads = tfn_edge_bwd_fused(*args, deg, g_dx, g_h, **kw)
        gx, ga, gv, gw1r, gw2, gb2 = (
            g.astype(p.dtype) for g, p in zip(grads, (x, a, v, w1r, w2, b2)))
        return (gx, ga, gv, _layout_cotangent(lay), jnp.zeros_like(centers),
                gw1r, gw2, gb2)

    f.defvjp(fwd, bwd)
    return f


def tfn_edge_pathway(radial, h: Array, x: Array, v: Array, layout, *,
                     cutoff: float, clamp: float,
                     precision=None) -> tuple[Array, Array]:
    """Kernel-backed TFN edge pathway: ``(dx (N, 3), h_agg (N, 2))``.

    The radial network's first-layer weight rows are ordered ``[rbf | h]``
    (the concatenation order of ``models.tfn.tfn_edge_pathway``'s jnp
    path); the ``h`` rows and the bias are applied per node here, in XLA
    (under the scope ``radial_mlp``), and the kernel gathers the product.
    Eligibility is the caller's (``models.tfn.edge_kernel_supported``).
    """
    w1, b1 = radial[0]["w"], radial[0]["b"]
    n_rbf = w1.shape[0] - h.shape[-1]
    with jax.named_scope("radial_mlp"):
        a = jnp.matmul(h, w1[n_rbf:], precision=dot_precision(h, w1)) + b1
    w2 = radial[1]["w"]
    b2 = radial[1]["b"][None, :]
    centers = jnp.linspace(0.0, cutoff, n_rbf, dtype=w1.dtype)[None, :]
    f = _tfn_edge_custom(float(cutoff), float(clamp),
                         resolve_precision(precision))
    dx, h_agg, _deg = f(x, a, v, layout, centers, w1[:n_rbf], w2, b2)
    return dx, h_agg


# ---------------------------------------------------------------- virtual MP
@functools.lru_cache(maxsize=None)
def _virtual_custom(precision=None):
    """Per-precision custom_vjp wrapper for the fused virtual pathway.

    Backward: the fused node-blocked Pallas backward
    (``virtual_pathway_bwd_fused``) — per-channel activations are
    recomputed in VMEM, dL/dz and every per-channel weight gradient
    accumulate across the sequential grid.  The node mask gets a zero
    cotangent (module docstring); the const1 cotangent flows back to
    s/m^v/b1 through the traced :func:`unpack_virtual_block`.
    """
    prec = resolve_precision(precision)

    @jax.custom_vjp
    def f(x, h, z, mask, *ws):  # ws: the 11 per-channel weight stacks
        return virtual_pathway_fused(x, h, z, mask, *ws, precision=prec)

    def fwd(*args):
        return f(*args), args

    def bwd(res, cots):
        x, h, z, mask, *ws = res
        grads = virtual_pathway_bwd_fused(x, h, z, mask, *ws, *cots,
                                          precision=prec)
        gx, gh, gz, *gws = (g.astype(p.dtype)
                            for g, p in zip(grads, (x, h, z, *ws)))
        return (gx, gh, gz, jnp.zeros_like(mask), *gws)

    f.defvjp(fwd, bwd)
    return f


def unpack_virtual_block(vb, s: Array, mv: Array, h_dim: int):
    """Per-channel stacks → kernel weight layout + the layer-1 constant.

    φ2 layer-1 weight rows are ordered [h | s | d² | m^v-column] (the
    concatenation order in ``core.virtual_nodes.virtual_messages``).
    """
    w1 = vb["phi2"][0]["w"]  # (C, msg_in, hid)
    b1 = vb["phi2"][0]["b"]  # (C, hid)
    c = w1.shape[0]
    s_dim = s.shape[-1]
    w1h = w1[:, :h_dim, :]
    w1s = w1[:, h_dim : h_dim + s_dim, :]
    w1d = w1[:, h_dim + s_dim, :]
    w1mv = w1[:, h_dim + s_dim + 1 :, :]  # (C, C, hid)
    const1 = (
        jnp.einsum("cs,csh->ch", s, w1s, precision=dot_precision(s, w1s))
        + jnp.einsum("ck,ckh->ch", mv.T, w1mv,
                     precision=dot_precision(mv, w1mv))
        + b1
    )
    return dict(
        w1h=w1h, w1d=w1d, const1=const1,
        w2=vb["phi2"][1]["w"], b2=vb["phi2"][1]["b"],
        wg1=vb["phi_xv"][0]["w"], bg1=vb["phi_xv"][0]["b"], wg2=vb["phi_xv"][1]["w"],
        wz1=vb["phi_z"][0]["w"], bz1=vb["phi_z"][0]["b"], wz2=vb["phi_z"][1]["w"],
    )


def virtual_pathway(vb, h: Array, x: Array, vs, mv: Array, node_mask: Array,
                    precision=None):
    """Kernel-backed replacement for the jnp virtual pathway in FastEGNN.

    Returns (dx (N,3), mh (N,hid), dz_sum (C,3), ms_sum (C,hid)); fused
    Pallas on both directions.  ``precision`` must be static (a string or
    ``runtime.Precision``).
    """
    w = unpack_virtual_block(vb, vs.s, mv, h.shape[-1])
    f = _virtual_custom(resolve_precision(precision))
    return f(
        x, h, vs.z, node_mask,
        w["w1h"], w["w1d"], w["const1"], w["w2"], w["b2"],
        w["wg1"], w["bg1"], w["wg2"], w["wz1"], w["bz1"], w["wz2"],
    )


# --------------------------------------------------------------------- MMD
@functools.lru_cache(maxsize=None)
def _mmd_cross_custom(sigma: float):
    """Per-sigma custom_vjp wrapper (sigma must stay *static* — a traced
    operand would break ``float(sigma)`` inside the jitted kernel under
    vmap/grad; cached like ``_edge_custom`` so jit caches stay warm).
    Backward: the fused ``mmd_cross_grads`` kernel (the (N, C) kernel
    matrix is recomputed per block, never materialised); the mask weight
    gets a zero cotangent."""

    @jax.custom_vjp
    def f(x, z, mask):
        return mmd_cross_sum(x, z, mask, sigma=sigma)

    def fwd(x, z, mask):
        return f(x, z, mask), (x, z, mask)

    def bwd(res, cot):
        x, z, mask = res
        dx, dz = mmd_cross_grads(x, z, mask, cot, sigma=sigma)
        return dx.astype(x.dtype), dz.astype(z.dtype), jnp.zeros_like(mask)

    f.defvjp(fwd, bwd)
    return f


def mmd_cross(x: Array, z: Array, weight: Array, sigma: float) -> Array:
    """Differentiable Σ_i w_i Σ_c k(x_i, z_c) via the Pallas kernel.

    The trainable entry point ``core.mmd.mmd_loss(use_kernel=True)`` routes
    its cross term through (``weight`` is the node mask, or all-ones for a
    sampled subset); backward is the fused ``mmd_cross_grads`` kernel.
    """
    return _mmd_cross_custom(float(sigma))(x, z, weight)


def mmd_loss_kernel(z: Array, x: Array, node_mask: Array, *, sigma: float = 1.5) -> Array:
    """Eq. 10 with the cross term computed by the Pallas kernel."""
    c = z.shape[0]
    zc = z[:, None, :] - z[None, :, :]
    term_vv = jnp.sum(jnp.exp(-jnp.sum(zc**2, -1) / (2 * sigma * sigma))) / (c * c)
    cross = mmd_cross(x, z, node_mask, sigma)
    denom = jnp.maximum(jnp.sum(node_mask), 1.0) * c
    return term_vv - cross / denom
