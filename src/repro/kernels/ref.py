"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Each function mirrors one kernel's contract exactly; tests sweep shapes and
dtypes asserting kernel(interpret=True) ≍ ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def virtual_pathway_ref(
    x: Array,  # (N, 3)
    h: Array,  # (N, Dh)
    z: Array,  # (C, 3)
    node_mask: Array,  # (N,)
    w1h: Array,  # (C, Dh, hid)   φ2 layer-1 weight for the h input
    w1d: Array,  # (C, hid)       φ2 layer-1 weight column for d²
    const1: Array,  # (C, hid)    φ2 layer-1 constant: W1_s s_c + W1_mv m^v_c + b1
    w2: Array,  # (C, hid, hid)   φ2 layer-2
    b2: Array,  # (C, hid)
    wg1: Array,  # (C, hid, hid)  φ_x^v layer-1
    bg1: Array,  # (C, hid)
    wg2: Array,  # (C, hid, 1)    φ_x^v layer-2 (no bias)
    wz1: Array,  # (C, hid, hid)  φ_Z layer-1
    bz1: Array,  # (C, hid)
    wz2: Array,  # (C, hid, 1)    φ_Z layer-2 (no bias)
):
    """Fused virtual pathway (Eq. 5 + virtual terms of Eqs. 6–8).

    Returns dx (N,3), mh (N,hid), dz_sum (C,3), ms_sum (C,hid).
    """
    c = z.shape[0]
    d2 = jnp.sum((x[:, None, :] - z[None, :, :]) ** 2, axis=-1)  # (N, C)
    t1 = (
        jnp.einsum("nd,cdh->nch", h, w1h)
        + d2[:, :, None] * w1d[None, :, :]
        + const1[None, :, :]
    )
    msg = jnp.einsum("nch,chk->nck", jax.nn.silu(t1), w2) + b2[None]  # (N,C,hid)
    gate_x = jnp.einsum("nch,chk->nck", jax.nn.silu(
        jnp.einsum("nch,chk->nck", msg, wg1) + bg1[None]), wg2)  # (N,C,1)
    rel = x[:, None, :] - z[None, :, :]  # (N, C, 3)
    dx = jnp.mean(rel * gate_x, axis=1)
    mh = jnp.mean(msg, axis=1)
    gate_z = jnp.einsum("nch,chk->nck", jax.nn.silu(
        jnp.einsum("nch,chk->nck", msg, wz1) + bz1[None]), wz2)  # (N,C,1)
    w = node_mask[:, None, None]
    dz_sum = jnp.sum(-rel * gate_z * w, axis=0)  # (C,3): Σ (z_c − x_i)·φ_Z
    ms_sum = jnp.sum(msg * w, axis=0)  # (C,hid)
    del c
    return dx, mh, dz_sum, ms_sum


def edge_pathway_ref(
    x: Array,  # (N, 3)
    h: Array,  # (N, Dh)      Dh ≥ 1 (zero-feature models pass a zero column)
    snd: Array,  # (E,) int32
    rcv: Array,  # (E,) int32
    em: Array,  # (E,)        edge validity mask
    w1r: Array,  # (Dh, H1)   φ1 layer-1 weight rows for h_receiver
    w1s: Array,  # (Dh, H1)   φ1 layer-1 weight rows for h_sender
    w1d: Array,  # (1, H1)    φ1 layer-1 weight row for d²
    b1: Array,  # (1, H1)
    w2: Array,  # (H1, M)     φ1 layer-2
    b2: Array,  # (1, M)
    wg1: Array,  # (M, HG)    gate layer-1 (gate_mode='mlp' only)
    bg1: Array,  # (1, HG)
    wg2: Array,  # (HG, 1)    gate layer-2 (no bias)
    *,
    gate_mode: str = "mlp",  # 'mlp' | 'identity' | 'none'
    rel_mode: str = "raw",  # 'raw' | 'inv1p'
    clamp: float = float("inf"),
):
    """Fused real-real edge pathway (Eq. 3 + real parts of Eqs. 6-7).

    Returns (dx (N,3), mh (N,M), deg (N,1)) — masked-mean aggregation onto
    receivers.  ``dx`` is zeros when gate_mode='none'.

    Edge-order invariant (segment sums commute), so this single oracle is
    the ground truth for every tiling of the fused kernel: the banded-CSR
    regrouping only permutes and mask-pads the edge list, which this
    function is insensitive to.  Parity at the new tilings is enforced in
    ``tests/test_kernels.py`` and ``tests/test_banded_csr.py``.
    """
    n = x.shape[0]
    rel = x[rcv] - x[snd]  # (E, 3)
    d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)  # (E, 1)
    t1 = jax.nn.silu(h[rcv] @ w1r + h[snd] @ w1s + d2 @ w1d + b1)
    msg = t1 @ w2 + b2  # (E, M)
    em2 = em[:, None]
    deg = jax.ops.segment_sum(em, rcv, num_segments=n)
    inv = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    mh = jax.ops.segment_sum(msg * em2, rcv, num_segments=n) * inv
    if gate_mode == "none":
        return jnp.zeros((n, 3), x.dtype), mh, deg[:, None]
    if gate_mode == "mlp":
        gate = jax.nn.silu(msg @ wg1 + bg1) @ wg2
    else:
        gate = msg
    gate = jnp.clip(gate, -clamp, clamp)
    if rel_mode == "inv1p":
        rel = rel / (jnp.sqrt(d2 + 1e-12) + 1.0)
    dx = jax.ops.segment_sum(rel * gate * em2, rcv, num_segments=n) * inv
    return dx, mh, deg[:, None]


def tfn_edge_pathway_ref(
    x: Array,  # (N, 3)
    a: Array,  # (N, H)       per-node part of the radial layer 1: h W_h + b_1
    v: Array,  # (N, 3)
    snd: Array,  # (E,) int32
    rcv: Array,  # (E,) int32
    em: Array,  # (E,)        edge validity mask
    centers: Array,  # (1, R) rbf centres on [0, cutoff]
    w1r: Array,  # (R, H)     radial layer-1 weight rows for rbf(|r|)
    w2: Array,  # (H, 6)      radial layer 2
    b2: Array,  # (1, 6)
    *,
    cutoff: float,
    clamp: float = float("inf"),
):
    """Fused TFN edge pathway (``kernels/tfn_edge.py``): on each edge j → i
    the path weights ``w = clip(silu(a_j + rbf(|r|) W_r) W_2 + b_2, ±clamp)``
    and the paths ``w0 v_j + w1 r̂ + w2 (r̂ × v_j) + w3 (r̂r̂ᵀ − I/3) v_j``
    (type 1) and ``[w4, w5 r̂·v_j]`` (type 0), ``r = x_i − x_j``.

    Returns (dx (N,3), h_agg (N,2), deg (N,1)): masked means onto the
    receivers.
    """
    n = x.shape[0]
    rel = x[rcv] - x[snd]
    d = jnp.sqrt(jnp.sum(rel * rel, axis=-1, keepdims=True) + 1e-12)
    rhat = rel / d
    vj = v[snd]
    rbf = jnp.exp(-(w1r.shape[0] / cutoff) * (d - centers) ** 2)
    w = jnp.clip(jax.nn.silu(a[snd] + rbf @ w1r) @ w2 + b2, -clamp, clamp)
    dot = jnp.sum(rhat * vj, axis=-1, keepdims=True)
    em2 = em[:, None]
    dx_e = (w[:, 0:1] * vj + w[:, 1:2] * rhat + w[:, 2:3] * jnp.cross(rhat, vj)
            + w[:, 3:4] * (rhat * dot - vj / 3.0)) * em2
    s0 = jnp.concatenate([w[:, 4:5], w[:, 5:6] * dot], axis=-1) * em2
    deg = jax.ops.segment_sum(em, rcv, num_segments=n)
    inv = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    dx = jax.ops.segment_sum(dx_e, rcv, num_segments=n) * inv
    h_agg = jax.ops.segment_sum(s0, rcv, num_segments=n) * inv
    return dx, h_agg, deg[:, None]


def mmd_cross_ref(x: Array, z: Array, node_mask: Array, sigma: float) -> Array:
    """Σ_i mask_i Σ_c exp(−‖x_i−z_c‖²/2σ²) — the MMD cross term numerator."""
    d2 = jnp.sum((x[:, None, :] - z[None, :, :]) ** 2, axis=-1)
    k = jnp.exp(-d2 / (2.0 * sigma * sigma))
    return jnp.sum(k * node_mask[:, None])

