"""Plain reference for FastTFN training (arXiv:2506.19482 Sec. V, Eq. 15, on
Tensor Field Networks, arXiv:1802.08219).

Straightforward ``jax.numpy`` in float32: index gathers, ``segment_sum``
scatters, one Python loop over the virtual channels, no kernels, no
batching tricks.  It imports nothing of the program under test and takes
none of its data: the graph is built from the scene (``scenes.py``), the
weights come from the window.  The precision modes, the graph, the
optimizer and the shared pieces are the FastEGNN reference's
(``fast_egnn.py``).

Departures from Thomas et al., as the program states its model: one
channel of each type; spherical harmonics of degree 2 at most, written as
Cartesian tensors; type-1 (vector) outputs only, with two type-0 paths for
the feature update; and so equivariance to SO(3) only, not O(3) (the
cross-product path changes sign under a reflection).

For each of the L layers, on every edge j -> i of the radius graph with
``r = x_i - x_j``, ``d = sqrt(|r|² + 1e-12)``, ``r̂ = r / d``:

* radial network: ``w = clip(phi_R([rbf(d) | h_j]), +-coord_clamp)``, six
  path weights, ``rbf_k(d) = exp(-(n_rbf / rbf_cutoff) (d - c_k)²)`` over
  ``n_rbf`` centres ``c_k`` evenly spaced on ``[0, rbf_cutoff]``;
* type-1 message ``w0 v_j + w1 r̂ + w2 (r̂ × v_j) + w3 (r̂ r̂ᵀ - I/3) v_j``
  and type-0 message ``[w4, w5 r̂·v_j]``, both degree means over the
  receiver's edges;
* the virtual plug-in (Sec. V): ``mv = (Z - com)(Z - com)^T``; per channel
  ``m_ic = phi2_c([h_i, s_c, |x_i - z_c|², mv[:, c]])``; the coordinate
  term ``mean_c (x_i - z_c) phi_xv_c(m_ic)`` rescaled to norm 10 at most
  (the plug-in's own bound); the virtual messages do not enter ``h``;
* ``x <- x + dx_edges + dx_virtual``, ``h <- h + phi_out([h, h_agg])``;
* ``z_c <- z_c + mean_i (z_c - x_i) phi_z_c(m_ic)``, ``s_c <- s_c +
  phi_s_c([s_c, mean_i m_ic])``, from the pre-update coordinates.

Loss (Eq. 11): mean squared coordinate error plus ``lam_mmd`` times the
MMD of Eq. 10 between Z and ``mmd_sample`` targets drawn under the step's
key, as in ``fast_egnn.py``.

A fault that calibration and the tests read, named in ``cfg``:
``left_out="cross"`` computes the model without its cross-product path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference.fast_egnn import (MODES, _channel, _dot, _mlp, _rbf,
                                       adam_step)
from bench.reference.fast_egnn import scene_graph  # noqa: F401  (the window's)

#: the plug-in's bound on the norm of its coordinate term
VIRTUAL_CLAMP = 10.0


def _edge_messages(lp, g, x, h, cfg, mode):
    """Degree means of the type-1 (N, 3) and type-0 (N, 2) messages."""
    snd, rcv, em = g["snd"], g["rcv"], g["em"][:, None]
    n = x.shape[0]
    rel = x[rcv] - x[snd]
    d = jnp.sqrt(jnp.sum(rel * rel, axis=-1, keepdims=True) + 1e-12)
    rhat = rel / d
    vj = g["v"][snd]
    n_rbf, cutoff = cfg["n_rbf"], cfg["rbf_cutoff"]
    centres = jnp.linspace(0.0, cutoff, n_rbf)
    rbf = jnp.exp(-(n_rbf / cutoff) * (d - centres) ** 2)
    clamp = cfg["coord_clamp"]
    w = jnp.clip(_mlp(lp["radial"], jnp.concatenate([rbf, h[snd]], -1), mode),
                 -clamp, clamp)
    dot = jnp.sum(rhat * vj, axis=-1, keepdims=True)
    cross = jnp.stack([rhat[:, 1] * vj[:, 2] - rhat[:, 2] * vj[:, 1],
                       rhat[:, 2] * vj[:, 0] - rhat[:, 0] * vj[:, 2],
                       rhat[:, 0] * vj[:, 1] - rhat[:, 1] * vj[:, 0]], -1)
    if cfg.get("left_out") == "cross":
        cross = jnp.zeros_like(cross)
    m1 = (w[:, 0:1] * vj + w[:, 1:2] * rhat + w[:, 2:3] * cross
          + w[:, 3:4] * (rhat * dot - vj / 3.0))
    m0 = jnp.concatenate([w[:, 4:5], w[:, 5:6] * dot], -1)
    deg = jax.ops.segment_sum(g["em"], rcv, num_segments=n)
    inv = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    return (jax.ops.segment_sum(m1 * em, rcv, num_segments=n) * inv,
            jax.ops.segment_sum(m0 * em, rcv, num_segments=n) * inv)


def forward(params, g: dict, cfg: dict, mode: str):
    """(x, h, z, s) after the L layers.  ``g``: x, v, h (N, ·) real nodes
    only; snd, rcv, em (E,) edges (padding slots carry em = 0)."""
    x = g["x"]
    n = x.shape[0]
    c_n = cfg["n_virtual"]
    h = _mlp(params["embed"], g["h"], mode)
    z = jnp.broadcast_to(jnp.mean(x, axis=0), (c_n, 3))
    s = params["s_init"]
    for lp in params["layers"]:
        dx_r, h_agg = _edge_messages(lp, g, x, h, cfg, mode)
        com = jnp.mean(x, axis=0)
        zc = z - com
        mv = _dot(zc, zc.T, mode)
        vb = lp["virtual"]
        dx_v = jnp.zeros_like(x)
        dz, ms = [], []
        for c in range(c_n):
            rel_c = x - z[c]
            d2_c = jnp.sum(rel_c * rel_c, axis=-1, keepdims=True)
            feats = jnp.concatenate(
                [h, jnp.broadcast_to(s[c], (n, s.shape[1])), d2_c,
                 jnp.broadcast_to(mv[:, c], (n, c_n))], -1)
            m_c = _mlp(_channel(vb["phi2"], c), feats, mode)
            dx_v = dx_v + rel_c * _mlp(_channel(vb["phi_xv"], c), m_c, mode)
            dz.append(jnp.sum(-rel_c * _mlp(_channel(vb["phi_z"], c), m_c,
                                            mode), axis=0))
            ms.append(jnp.sum(m_c, axis=0))
        dx_v = dx_v / c_n
        norm = jnp.sqrt(jnp.sum(dx_v * dx_v, axis=-1, keepdims=True) + 1e-12)
        dx_v = dx_v * jnp.minimum(1.0, VIRTUAL_CLAMP / norm)
        h_new = h + _mlp(lp["h_out"], jnp.concatenate([h, h_agg], -1), mode)
        z = z + jnp.stack(dz) / n
        s_in = jnp.concatenate([s, jnp.stack(ms) / n], -1)
        s = s + jnp.stack([_mlp(_channel(vb["phi_s"], c), s_in[c], mode)
                           for c in range(c_n)])
        x, h = x + dx_r + dx_v, h_new
    return x, h, z, s


def scene_loss(params, g: dict, key, cfg: dict, mode: str):
    """Eq. 11 for one scene (MMD on ``mmd_sample`` targets drawn under
    ``key``)."""
    x, _, z, _ = forward(params, g, cfg, mode)
    t = g["x1"]
    mse = jnp.sum((x - t) ** 2) / x.shape[0] / 3.0
    c_n, k = z.shape[0], cfg["mmd_sample"]
    idx = jax.random.categorical(key, jnp.zeros(x.shape[0], jnp.float32),
                                 shape=(k,))
    sig = cfg["mmd_sigma"]
    mmd = (jnp.sum(_rbf(z, z, sig)) / (c_n * c_n)
           - jnp.sum(_rbf(t[idx], z, sig)) / (k * c_n))
    return mse + cfg["lam_mmd"] * mmd


@partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _value_and_grad(params, g, key, *, cfg_items, mode):
    return jax.value_and_grad(scene_loss)(params, g, key, dict(cfg_items),
                                          mode)


def train(params0, batches, keys, cfg: dict, mode: str = "highest") -> dict:
    """Run the first ``len(batches)`` training steps from ``params0``.

    ``batches``: per step, a list of scene graphs (see :func:`forward`,
    with ``x1`` the target); ``keys``: per step, the key the program's step
    was given.  Each scene's gradient is taken alone and averaged.  Returns
    ``losses`` (per step), ``grad1`` (the clipped first gradient) and
    ``params`` (after the last step).
    """
    if cfg.get("devices", 1) != 1:
        raise ValueError("the FastTFN reference runs one chip's scenes")
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}")
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    params = params0
    m = jax.tree.map(jnp.zeros_like, params0)
    v = jax.tree.map(jnp.zeros_like, params0)
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for step, (scenes, key) in enumerate(zip(batches, keys), start=1):
            skeys = jax.random.split(key, len(scenes))
            tot_loss, tot_grad = 0.0, None
            for g, k in zip(scenes, skeys):
                loss, grad = _value_and_grad(params, g, k,
                                             cfg_items=cfg_items, mode=mode)
                tot_loss = tot_loss + loss
                tot_grad = grad if tot_grad is None else jax.tree.map(
                    jnp.add, tot_grad, grad)
            b = float(len(scenes))
            grads = jax.tree.map(lambda a: a / b, tot_grad)
            params, m, v, clipped = adam_step(params, grads, m, v, step, cfg)
            losses.append(float(tot_loss / b))
            if grad1 is None:
                grad1 = clipped
    return dict(losses=losses, grad1=grad1, params=params)
