"""Virtual node learning — the paper's core contribution (Secs. IV-A/IV-B, VI).

An *ordered* set of C virtual nodes ``(Z, S)`` with:
  * CoM initialisation of the coordinates (Eq. 2) — E(3)-equivariant,
    permutation-invariant;
  * per-channel learnable features ``S`` (free parameters);
  * the E(3)-invariant virtual global message ``m^v = (Z-x̄)ᵀ(Z-x̄)`` (Eq. 4);
  * per-channel real↔virtual messages (Eq. 5, the separated ``m_ic`` form the
    paper found to train better);
  * real-node aggregation terms (the virtual part of Eqs. 6–7);
  * virtual-node aggregation (Eqs. 8–9) with an optional ``axis_name`` that
    turns the node-sum into a cross-device ``psum`` — this *is* DistEGNN's
    Eqs. 16–17: under ``shard_map`` the sum over local nodes is all-reduced
    across the graph-partition axis, and because JAX collectives are
    differentiable the paper's custom autograd all_reduce comes for free.

Mutual distinctiveness is enforced structurally: every virtual channel owns
its own MLP parameters (``init_stacked_mlp`` + vmap over the channel axis).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.mlp import dot_precision, init_mlp, init_stacked_mlp, mlp

Array = jax.Array


class VirtualState(NamedTuple):
    z: Array  # (C, 3) coordinates
    s: Array  # (C, S) invariant features


def init_virtual_coords(x: Array, node_mask: Array, n_channels: int,
                        axis_name: Optional[str] = None) -> Array:
    """Eq. 2 / Alg. 1 line 1: every channel starts at the (global) CoM.

    With ``axis_name`` the CoM is taken over *all* shards (DistEGNN keeps the
    initialisation at the CoM of the entire large graph — Sec. VI).
    """
    w = node_mask[:, None]
    tot = jnp.sum(x * w, axis=0)
    cnt = jnp.sum(w)
    if axis_name is not None:
        tot = jax.lax.psum(tot, axis_name)
        cnt = jax.lax.psum(cnt, axis_name)
    com = tot / jnp.maximum(cnt, 1.0)
    return jnp.broadcast_to(com[None, :], (n_channels, 3))


def virtual_global_message(z: Array, com: Array) -> Array:
    """Eq. 4: E(3)-invariant Gram matrix of centred virtual coords, (C, C)."""
    zc = z - com[None, :]
    return jnp.matmul(zc, zc.T, precision=dot_precision(zc))


def init_virtual_block(key, n_channels: int, h_dim: int, s_dim: int, hidden: int,
                       shared: bool = False):
    """Parameters for one layer's virtual pathway.

    phi2   : per-channel message MLP  (h_i, s_c, d²_ic, m^v_c) → msg
    phi_xv : per-channel scalar gate for the real-coordinate update
    phi_z  : per-channel scalar gate for the virtual-coordinate update
    phi_s  : per-channel feature update for S

    ``shared=True`` builds the *FastEGNN w/ Global Nodes* ablation (Table II):
    one weight set shared by all channels — the permutation-equivariant,
    unordered-set variant the paper shows is strictly worse.  Apply functions
    detect sharing from the parameter rank.
    """
    k2, kx, kz, ks = jax.random.split(key, 4)
    msg_in = h_dim + s_dim + 1 + n_channels
    mk = init_mlp if shared else (lambda k, sizes, **kw: init_stacked_mlp(k, n_channels, sizes, **kw))
    return {
        "phi2": mk(k2, [msg_in, hidden, hidden]),
        "phi_xv": mk(kx, [hidden, hidden, 1], final_bias=False),
        "phi_z": mk(kz, [hidden, hidden, 1], final_bias=False),
        "phi_s": mk(ks, [s_dim + hidden, hidden, s_dim]),
    }


def _apply_channelwise(params, feats: Array) -> Array:
    """Apply a (possibly per-channel-stacked) MLP over (N, C, F) features."""
    stacked = params[0]["w"].ndim == 3
    if stacked:
        return jax.vmap(lambda p, f: mlp(p, f), in_axes=(0, 1), out_axes=1)(params, feats)
    return jax.vmap(lambda f: mlp(params, f), in_axes=1, out_axes=1)(feats)


def virtual_messages(params, h: Array, x: Array, vs: VirtualState, mv: Array) -> Array:
    """Eq. 5 (separated form): m_ic = φ2^{(c)}(h_i, s_c, ‖x_i−z_c‖², m^v_:,c).

    Returns (N, C, hidden).  φ2 differs per channel (stacked params).
    """
    n = x.shape[0]
    c = vs.z.shape[0]
    d2 = jnp.sum((x[:, None, :] - vs.z[None, :, :]) ** 2, axis=-1)  # (N, C)
    feats = jnp.concatenate(
        [
            jnp.broadcast_to(h[:, None, :], (n, c, h.shape[-1])),
            jnp.broadcast_to(vs.s[None, :, :], (n, c, vs.s.shape[-1])),
            d2[:, :, None],
            jnp.broadcast_to(mv.T[None, :, :], (n, c, c)),  # column c of m^v
        ],
        axis=-1,
    )  # (N, C, msg_in)
    return _apply_channelwise(params["phi2"], feats)  # (N, C, hidden)


def real_from_virtual(params, x: Array, vs: VirtualState, msgs: Array) -> tuple[Array, Array]:
    """Virtual→real terms of Eqs. 6–7.

    dx_i = (1/C) Σ_c (x_i − z_c) φ_x^{v,(c)}(m_ic)
    mh_i = (1/C) Σ_c m_ic                       (summation form, Sec. IV-B)
    """
    c = vs.z.shape[0]
    gate = _apply_channelwise(params["phi_xv"], msgs)  # (N, C, 1)
    rel = x[:, None, :] - vs.z[None, :, :]  # (N, C, 3)
    dx = jnp.mean(rel * gate, axis=1)  # (N, 3)
    mh = jnp.mean(msgs, axis=1)  # (N, hidden)
    del c
    return dx, mh


def virtual_node_sums(params, x: Array, vs: VirtualState, msgs: Array,
                      node_mask: Array) -> tuple[Array, Array]:
    """Local (per-shard) node sums feeding Eqs. 8–9 / 16–17.

    dz_sum_c = Σ_i m_i (z_c − x_i) φ_Z^{(c)}(m_ic)   (C, 3)
    ms_sum_c = Σ_i m_i m_ic                           (C, hidden)

    These two reductions (plus the real-side terms) are exactly what the
    fused Pallas kernel produces without materialising ``msgs`` in HBM.
    """
    w = node_mask[:, None, None]
    gate = _apply_channelwise(params["phi_z"], msgs)  # (N, C, 1)
    rel = vs.z[None, :, :] - x[:, None, :]  # (N, C, 3)
    dz_sum = jnp.sum(rel * gate * w, axis=0)  # (C, 3)
    ms_sum = jnp.sum(msgs * w, axis=0)  # (C, hidden)
    return dz_sum, ms_sum


def virtual_kernel_supported(params, h: Array) -> bool:
    """Virtual-kernel dispatch rule (DESIGN.md §3.2).

    The fused Pallas kernel implements exactly the per-channel stacked
    2-layer MLP form of φ2 / φ_x^v / φ_Z (the ordered-set variant) with at
    least one real feature column.  The shared 'Global Nodes' ablation
    (rank-2 weights), deeper MLPs, and zero-width features fall back to the
    jnp composition below.
    """
    for name in ("phi2", "phi_xv", "phi_z"):
        p = params[name]
        if len(p) != 2 or p[0]["w"].ndim != 3:
            return False
    return h.shape[-1] > 0


def virtual_pathway(params, h: Array, x: Array, vs: VirtualState, mv: Array,
                    node_mask: Array, *, use_kernel: bool = False,
                    precision=None) -> tuple[Array, Array, Array, Array]:
    """First-class virtual-pathway dispatch — the Eq. 5–9 hot path.

    Returns ``(dx (N,3), mh (N,hidden), dz_sum (C,3), ms_sum (C,hidden))``:
    the real-side terms of Eqs. 6–7 plus the local node sums feeding
    Eqs. 8–9 / 16–17.  With ``use_kernel`` and a kernel-eligible parameter
    block (:func:`virtual_kernel_supported`) this dispatches to the fused
    Pallas kernel (``kernels.ops.virtual_pathway``) which never
    materialises the (N, C, hidden) message tensor in HBM — including on
    the backward pass (DESIGN.md §9); otherwise it runs the pure-jnp
    composition.  Dispatch is recorded at trace time as
    ``'virtual_kernel'`` / ``'virtual_jnp'`` in
    ``message_passing.dispatch_counts()``.  ``precision`` selects the
    kernel compute/accumulate dtypes (``kernels.runtime.resolve_precision``
    — f32 default); the jnp path ignores it.

    Under ``shard_map`` (DistEGNN) each shard calls this on its local
    nodes; the returned sums are psum'd downstream in
    :func:`virtual_aggregate_from_sums`.
    """
    from repro.core.message_passing import record_dispatch

    if use_kernel and virtual_kernel_supported(params, h):
        from repro.kernels import ops as kops

        record_dispatch("virtual_kernel")
        return kops.virtual_pathway(params, h, x, vs, mv, node_mask,
                                    precision=precision)
    record_dispatch("virtual_jnp")
    msgs = virtual_messages(params, h, x, vs, mv)  # (N, C, hidden)
    dx, mh = real_from_virtual(params, x, vs, msgs)
    dz_sum, ms_sum = virtual_node_sums(params, x, vs, msgs, node_mask)
    return dx, mh, dz_sum, ms_sum


def launch_virtual_sums(
    dz_sum: Array,
    ms_sum: Array,
    n_local: Array,
    axis_name: Optional[str] = None,
) -> tuple[Array, Array, Array]:
    """Issue the Eqs. 16–17 collectives (the *communication* half).

    Returns the globally-reduced ``(dz_sum, ms_sum, n)`` triple.  The psums
    are issued here and the tiny ``phi_s`` epilogue lives in
    :func:`finish_virtual_aggregate`, so a caller can put arbitrary local
    compute between launch and finish — DistEGNN's overlap schedule issues
    these before the banded edge pathway of the *next* layer and consumes
    them after it, letting XLA's latency-hiding scheduler run the
    all-reduce under the edge kernel (DESIGN.md §11).  Splitting at the
    psum boundary keeps the reduction order — and hence the floats —
    identical to the serialized path.
    """
    if axis_name is not None:
        dz_sum = jax.lax.psum(dz_sum, axis_name)
        ms_sum = jax.lax.psum(ms_sum, axis_name)
        n_local = jax.lax.psum(n_local, axis_name)
    return dz_sum, ms_sum, n_local


def finish_virtual_aggregate(
    params,
    vs: VirtualState,
    dz_sum: Array,
    ms_sum: Array,
    n_total: Array,
) -> VirtualState:
    """Apply Eqs. 8–9's ``phi_Z``/``phi_S`` epilogue to already-reduced sums
    (the *compute* half of :func:`launch_virtual_sums`)."""
    n = jnp.maximum(n_total, 1.0)
    z_new = vs.z + dz_sum / n
    s_in = jnp.concatenate([vs.s, ms_sum / n], axis=-1)  # (C, S+hidden)
    if params["phi_s"][0]["w"].ndim == 3:
        ds = jax.vmap(lambda p, f: mlp(p, f))(params["phi_s"], s_in)  # (C, S)
    else:  # shared weights (Global Nodes ablation)
        ds = mlp(params["phi_s"], s_in)
    return VirtualState(z=z_new, s=vs.s + ds)


def virtual_aggregate_from_sums(
    params,
    vs: VirtualState,
    dz_sum: Array,
    ms_sum: Array,
    n_local: Array,
    axis_name: Optional[str] = None,
) -> VirtualState:
    """Complete Eqs. 8–9 (or 16–17 with ``axis_name``) from the node sums."""
    return finish_virtual_aggregate(
        params, vs, *launch_virtual_sums(dz_sum, ms_sum, n_local, axis_name))


def virtual_aggregate(
    params,
    x: Array,
    vs: VirtualState,
    msgs: Array,
    node_mask: Array,
    axis_name: Optional[str] = None,
) -> VirtualState:
    """Eqs. 8–9 (single device) / Eqs. 16–17 (distributed).

    z_c ← z_c + (1/N) Σ_i (z_c − x_i) φ_Z^{(c)}(m_ic)
    s_c ← s_c + φ_S^{(c)}(s_c, (1/N) Σ_i m_ic)

    ``axis_name`` turns Σ_i into a cross-shard psum — the DistEGNN bridge.
    """
    dz_sum, ms_sum = virtual_node_sums(params, x, vs, msgs, node_mask)
    return virtual_aggregate_from_sums(params, vs, dz_sum, ms_sum,
                                       jnp.sum(node_mask), axis_name)


def masked_com_sums(x: Array, node_mask: Array,
                    axis_name: Optional[str] = None) -> tuple[Array, Array]:
    """Issue the CoM collective: globally-reduced ``(Σ m_i x_i, Σ m_i)``.

    The launch half of :func:`masked_com` — DistEGNN's overlap schedule
    issues this before the layer's banded edge pathway and divides after
    it (DESIGN.md §11); the psum order is unchanged, so the resulting CoM
    is bitwise the serialized one.
    """
    w = node_mask[:, None]
    tot = jnp.sum(x * w, axis=0)
    cnt = jnp.sum(w)
    if axis_name is not None:
        tot = jax.lax.psum(tot, axis_name)
        cnt = jax.lax.psum(cnt, axis_name)
    return tot, cnt


def masked_com(x: Array, node_mask: Array, axis_name: Optional[str] = None) -> Array:
    """CoM over real nodes, optionally all-reduced (Alg. 1 line 4)."""
    tot, cnt = masked_com_sums(x, node_mask, axis_name)
    return tot / jnp.maximum(cnt, 1.0)
