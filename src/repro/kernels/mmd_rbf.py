"""MMD RBF cross-term Pallas kernel (Eq. 10's Σ_ic k(x_i, z_c)).

The N×C kernel-matrix sum is the only O(N) part of the MMD loss (the C×C
virtual-virtual term is negligible).  Grid over node blocks, scalar
accumulation across the sequential grid — one pass over HBM, nothing written
back but a single (1,1) accumulator.

:func:`mmd_cross_grads` is the matching fused backward (DESIGN.md §9): the
same node-block grid recomputes the (BN, C) kernel matrix in VMEM and
contracts it directly against the scalar cotangent — dL/dx lands in the
node-blocked output, dL/dz accumulates across the grid; the (N, C) kernel
matrix never touches HBM in either direction.  The node mask weights the
sum but is not differentiated (``ops.mmd_cross`` returns a zero cotangent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# full-f32 contraction: the expanded ‖x‖² − 2x·z + ‖z‖² cancels, so a
# reduced-precision dot would dominate the distance error
_dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def _kernel(x_ref, mask_ref, z_ref, out_ref, *, inv_two_sigma2: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xb = x_ref[...]  # (BN, 3)
    mb = mask_ref[...]  # (BN, 1)
    z = z_ref[...]  # (C, 3)
    d2 = (
        jnp.sum(xb * xb, axis=-1, keepdims=True)
        - 2.0 * _dot(xb, z.T)
        + jnp.sum(z * z, axis=-1)[None, :]
    )  # (BN, C)
    k = jnp.exp(-d2 * inv_two_sigma2)
    # (1, 1) vector store: Mosaic refuses scalar stores to VMEM
    out_ref[...] += jnp.sum(k * mb, axis=(0, 1), keepdims=True)


@functools.partial(jax.jit, static_argnames=("sigma", "block_n", "interpret"))
def mmd_cross_sum(x: Array, z: Array, node_mask: Array, *, sigma: float,
                  block_n: int = 1024, interpret: bool | None = None) -> Array:
    """Σ_i mask_i Σ_c exp(−‖x_i−z_c‖²/(2σ²)) — matches ref.mmd_cross_ref.

    ``interpret=None`` auto-detects (compile on TPU, interpret elsewhere).
    """
    from repro.kernels.runtime import resolve_interpret

    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    c = z.shape[0]
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        node_mask = jnp.pad(node_mask, (0, n_pad - n))
    out = pl.pallas_call(
        functools.partial(_kernel, inv_two_sigma2=1.0 / (2.0 * sigma * sigma)),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((c, 3), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),
        interpret=interpret,
        name="mmd_cross_sum_fwd",
    )(x, node_mask[:, None], z)
    return out[0, 0]


def _grad_kernel(x_ref, mask_ref, z_ref, g_ref, dx_ref, dz_ref,
                 *, inv_two_sigma2: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dz_ref[...] = jnp.zeros_like(dz_ref)

    xb = x_ref[...]  # (BN, 3)
    mb = mask_ref[...]  # (BN, 1)
    z = z_ref[...]  # (C, 3)
    g = g_ref[...]  # (1, 1) output cotangent, broadcast over the block
    d2 = (
        jnp.sum(xb * xb, axis=-1, keepdims=True)
        - 2.0 * _dot(xb, z.T)
        + jnp.sum(z * z, axis=-1)[None, :]
    )  # (BN, C)
    w = jnp.exp(-d2 * inv_two_sigma2) * mb * g  # weighted kernel matrix
    inv_s2 = 2.0 * inv_two_sigma2  # 1/σ²
    # d k(x_i,z_c) / d x_i = −k·(x_i − z_c)/σ²; contract over channels/nodes
    # without ever materialising (N, C) outside VMEM
    dx_ref[...] = -inv_s2 * (xb * jnp.sum(w, axis=-1, keepdims=True)
                             - _dot(w, z))
    dz_ref[...] += inv_s2 * (_dot(w.T, xb) - jnp.sum(w, axis=0)[:, None] * z)


@functools.partial(jax.jit, static_argnames=("sigma", "block_n", "interpret"))
def mmd_cross_grads(x: Array, z: Array, node_mask: Array, g: Array, *,
                    sigma: float, block_n: int = 1024,
                    interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused (dL/dx, dL/dz) of :func:`mmd_cross_sum` given cotangent ``g``.

    Matches ``jax.vjp(ref.mmd_cross_ref)`` for the x and z arguments; the
    node mask is not differentiated.
    """
    from repro.kernels.runtime import resolve_interpret

    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    c = z.shape[0]
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        node_mask = jnp.pad(node_mask, (0, n_pad - n))
    dx, dz = pl.pallas_call(
        functools.partial(_grad_kernel,
                          inv_two_sigma2=1.0 / (2.0 * sigma * sigma)),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((c, 3), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n, 3), lambda i: (i, 0)),
            pl.BlockSpec((c, 3), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad, 3), x.dtype),
            jax.ShapeDtypeStruct((c, 3), x.dtype),
        ),
        interpret=interpret,
        name="mmd_cross_grads_bwd",
    )(x, node_mask[:, None], z, jnp.asarray(g, x.dtype).reshape(1, 1))
    return dx[:n], dz
