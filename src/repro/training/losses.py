"""Training objectives: masked MSE + the paper's MMD regulariser (Eq. 11/18)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.mmd import mmd_loss

Array = jax.Array


def masked_mse(pred: Array, target: Array, node_mask: Array,
               axis_name: Optional[str] = None) -> Array:
    """Mean over real nodes of ‖pred − target‖² (per-coordinate mean).

    With ``axis_name``: global mean across shards (DistEGNN's Eq. 18 summed
    over devices — equivalent to the full-graph MSE).  Runs under the
    name scope ``mse_loss``.
    """
    with jax.named_scope("mse_loss"):
        err = jnp.sum((pred - target) ** 2, axis=-1) * node_mask
        tot = jnp.sum(err)
        cnt = jnp.sum(node_mask)
        if axis_name is not None:
            tot = jax.lax.psum(tot, axis_name)
            cnt = jax.lax.psum(cnt, axis_name)
        return tot / jnp.maximum(cnt, 1.0) / 3.0


def combined_objective(
    x_pred: Array,
    x_target: Array,
    node_mask: Array,
    z_virtual: Optional[Array],
    *,
    lam: float = 0.0,
    sigma: float = 1.5,
    mmd_sample: Optional[int] = None,
    key: Optional[Array] = None,
    axis_name: Optional[str] = None,
    use_kernel: bool = False,
) -> tuple[Array, dict]:
    """Eq. 11: L = MSE(X^L, X^GT) + λ·MMD(Z^L, X^GT).

    ``use_kernel`` routes the MMD cross term through the Pallas kernel
    (``core.mmd.mmd_loss(use_kernel=...)``) — the trainer forwards the
    model config's ``use_kernel`` flag, so the kernel-backed models run a
    kernel-backed objective too.
    """
    mse = masked_mse(x_pred, x_target, node_mask, axis_name)
    aux = {"mse": mse}
    loss = mse
    if z_virtual is not None and lam > 0.0:
        mmd = mmd_loss(z_virtual, x_target, node_mask, sigma=sigma,
                       sample_size=mmd_sample, key=key,
                       use_kernel=use_kernel)
        aux["mmd"] = mmd
        loss = loss + lam * mmd
    return loss, aux
