"""Weights and keys made from ``--seed``.

The weights are the benchmark's, not the program's: both the program under
test and the plain reference are handed the same tree, made here on the
device in one jitted call.  The tree's layout (names, shapes) is the
FastEGNN parameter layout the paper's equations name: per layer ``phi1``
(edge message), ``phi_xr`` (edge gate), ``phi_h`` (node update), ``phi_v``
(velocity gate) and the ordered virtual block ``phi2`` / ``phi_xv`` /
``phi_z`` / ``phi_s`` stacked over the C channels.  Dense layers are Glorot
uniform with zero biases; ``s_init`` is 0.1 N(0, 1).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (the driver's seeds exceed 32
    bits): two 32-bit words drawn by numpy from the seed."""
    words = np.random.default_rng([int(seed), 2]).integers(
        0, 2 ** 32, size=2, dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(words)


def layout(cfg: dict) -> dict:
    """The parameter tree as nested dicts/lists of shapes (tuples)."""
    hid, s_dim, c, h_in = cfg["hidden"], cfg["s_dim"], cfg["n_virtual"], cfg["h_in"]

    def mlp(sizes, final_bias=True, stack=None):
        out = []
        for i in range(len(sizes) - 1):
            pre = () if stack is None else (stack,)
            layer = {"w": pre + (sizes[i], sizes[i + 1])}
            if final_bias or i < len(sizes) - 2:
                layer["b"] = pre + (sizes[i + 1],)
            out.append(layer)
        return out

    def one_layer():
        return {
            "phi1": mlp([2 * hid + 1, hid, hid]),
            "phi_xr": mlp([hid, hid, 1], final_bias=False),
            "phi_h": mlp([3 * hid, hid, hid]),
            "virtual": {
                "phi2": mlp([hid + s_dim + 1 + c, hid, hid], stack=c),
                "phi_xv": mlp([hid, hid, 1], final_bias=False, stack=c),
                "phi_z": mlp([hid, hid, 1], final_bias=False, stack=c),
                "phi_s": mlp([s_dim + hid, hid, s_dim], stack=c),
            },
            "phi_v": mlp([hid, hid, 1]),
        }

    return {"embed": mlp([h_in, hid]), "s_init": (c, s_dim),
            "layers": [one_layer() for _ in range(cfg["n_layers"])]}


def _is_shape(t):
    return isinstance(t, tuple) and all(isinstance(i, int) for i in t)


@partial(jax.jit, static_argnums=(1,))
def _make(key, shapes_flat: tuple):
    out = []
    keys = jax.random.split(key, len(shapes_flat))
    for k, (kind, shape) in zip(keys, shapes_flat):
        if kind == "w":
            fan_in, fan_out = shape[-2], shape[-1]
            lim = np.sqrt(6.0 / (fan_in + fan_out))
            out.append(jax.random.uniform(k, shape, jnp.float32, -lim, lim))
        elif kind == "b":
            out.append(jnp.zeros(shape, jnp.float32))
        else:  # s_init
            out.append(0.1 * jax.random.normal(k, shape, jnp.float32))
    return out


def make_weights(cfg: dict, key: jax.Array):
    """The parameter tree for ``cfg``, made from ``key`` in one jitted call."""
    tree = layout(cfg)
    paths_shapes, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_is_shape)
    kinds = []
    for path, shape in paths_shapes:
        last = path[-1]
        name = getattr(last, "key", None)
        kinds.append(("w" if name == "w" else "b" if name == "b" else "s",
                      tuple(shape)))
    leaves = _make(key, tuple(kinds))
    return jax.tree_util.tree_unflatten(treedef, leaves)
