"""Minimal functional MLP layer used throughout the GNN stack.

Pure-pytree parameters (nested dicts of arrays) — no flax dependency.  All
model code in ``repro.models`` composes these.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def _glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init_linear(key, d_in: int, d_out: int, bias: bool = True):
    kw, _ = jax.random.split(key)
    p = {"w": _glorot(kw, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dot_precision(*operands):
    """Precision for an XLA dot over ``operands``: ``HIGHEST`` when they
    contract in f32, the backend default otherwise.  A TPU runs a
    default-precision f32 dot as one bf16 pass (~3 significant digits), so
    the model's own dots pin it, as ``kernels.edge_message._mm`` does
    inside the kernels (DESIGN.md §9.3)."""
    if jnp.result_type(*operands) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def linear(params, x: Array) -> Array:
    y = jnp.matmul(x, params["w"], precision=dot_precision(x, params["w"]))
    if "b" in params:
        y = y + params["b"]
    return y


def silu(x):
    return jax.nn.silu(x)


def init_mlp(key, sizes: Sequence[int], *, final_bias: bool = True):
    """``sizes = [d_in, h1, ..., d_out]`` → list of linear params."""
    keys = jax.random.split(key, len(sizes) - 1)
    layers = []
    for i, k in enumerate(keys):
        last = i == len(sizes) - 2
        layers.append(init_linear(k, sizes[i], sizes[i + 1], bias=(final_bias or not last)))
    return layers


def mlp(params, x: Array, act: Callable = silu, final_act: Callable | None = None) -> Array:
    for i, layer in enumerate(params):
        x = linear(layer, x)
        if i < len(params) - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


def init_stacked_mlp(key, n_copies: int, sizes: Sequence[int], **kw):
    """n_copies independent MLPs, params stacked on a leading axis.

    Used for the paper's *per-virtual-channel* message/aggregation functions
    (mutual distinctiveness, Sec. IV-B): apply with ``jax.vmap`` over axis 0.
    """
    keys = jax.random.split(key, n_copies)
    per = [init_mlp(k, sizes, **kw) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per)
