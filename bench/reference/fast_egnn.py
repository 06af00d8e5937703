"""Plain reference for FastEGNN / DistEGNN training (arXiv:2506.19482).

Straightforward ``jax.numpy`` in float32: index gathers, ``segment_sum``
scatters, one Python loop over the virtual channels, no kernels, no
batching tricks.  It imports nothing of the program under test and takes
none of its data: the graph is built here from the scene (``scenes.py``),
the weights come from ``weights.py``.

The equations (Sec. IV, VI), for each of the L layers:

* edge message (Eq. 3): ``m_ij = phi1([h_i, h_j, |x_i - x_j|²])`` on every
  edge j -> i of the radius graph; edge gate ``phi_xr(m_ij)`` clipped to
  ``+-coord_clamp``; the real terms of Eqs. 6-7 are degree means;
* virtual global message (Eq. 4): ``mv = (Z - com)(Z - com)^T``, ``com`` the
  centre of mass of the current coordinates, Z initialised at the centre of
  mass of the input;
* real-virtual messages (Eq. 5): ``m_ic = phi2_c([h_i, s_c, |x_i - z_c|²,
  mv[:, c]])`` with the channel's own weights; the virtual terms of Eqs. 6-7
  are channel means, the coordinate term rescaled to norm ``coord_clamp``;
* ``x <- x + dx_real + dx_virtual + phi_v(h) v``, ``h <- h + phi_h([h,
  mh_real, mh_virtual])``;
* virtual update (Eqs. 8-9 / 16-17) from the pre-update coordinates:
  ``z_c <- z_c + mean_i (z_c - x_i) phi_z_c(m_ic)``, ``s_c <- s_c +
  phi_s_c([s_c, mean_i m_ic])``.

Loss (Eq. 11 / 18): mean squared coordinate error (per coordinate) plus
``lam`` times the MMD of Eq. 10 between Z and the targets.  One device
samples ``mmd_sample`` targets per scene with ``jax.random.categorical``
under the step's key (split over the batch); DistEGNN averages each shard's
MMD over all of its nodes.  The optimizer is Adam with weight decay and a
global-norm gradient clip.

``mode`` names the matmul precision: ``"highest"`` is float32 as the
configuration states it; ``"high"`` is the three-pass bfloat16 product a
TPU runs for ``Precision.HIGH`` (written out here, so that it means the
same on any backend); ``"bf16"`` is one bfloat16 pass.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("highest", "high", "bf16")


def _dot(a, b, mode: str):
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}")
    if mode == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    bf = jnp.bfloat16
    mm = partial(jnp.matmul, preferred_element_type=jnp.float32)
    if mode == "bf16":
        return mm(a.astype(bf), b.astype(bf))
    a_hi, b_hi = _bf16_hi(a), _bf16_hi(b)
    a_lo, b_lo = (a - a_hi).astype(bf), (b - b_hi).astype(bf)
    a_hi, b_hi = a_hi.astype(bf), b_hi.astype(bf)
    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)


def _bf16_hi(a):
    """``a`` (f32) rounded to the nearest bfloat16 (ties to even), held in
    f32.  Cut from the bits, because a rounding round trip ``f32 -> bf16
    -> f32`` is one that XLA may fold away where it allows excess
    precision, and the low piece ``a - hi`` would then be 0."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _mlp(layers, x, mode):
    for i, p in enumerate(layers):
        x = _dot(x, p["w"], mode)
        if "b" in p:
            x = x + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.silu(x)
    return x


def _channel(layers, c):
    return [{k: v[c] for k, v in p.items()} for p in layers]


def forward(params, g: dict, cfg: dict, mode: str):
    """(x, h, z, s) after the L layers.  ``g``: x, v, h (N, ·) real nodes
    only; snd, rcv, em (E,) edges (padding slots carry em = 0)."""
    x, v, snd, rcv, em = g["x"], g["v"], g["snd"], g["rcv"], g["em"]
    n = x.shape[0]
    c_n = cfg["n_virtual"]
    clamp = cfg["coord_clamp"]
    h = _mlp(params["embed"], g["h"], mode)
    z = jnp.broadcast_to(jnp.mean(x, axis=0), (c_n, 3))
    s = params["s_init"]
    deg = jax.ops.segment_sum(em, rcv, num_segments=n)
    inv_deg = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    for lp in params["layers"]:
        # real-real edges (Eq. 3 and the real terms of Eqs. 6-7)
        rel = x[rcv] - x[snd]
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
        msg = _mlp(lp["phi1"], jnp.concatenate([h[rcv], h[snd], d2], -1), mode)
        gate = jnp.clip(_mlp(lp["phi_xr"], msg, mode), -clamp, clamp)
        w = em[:, None]
        mh_r = jax.ops.segment_sum(msg * w, rcv, num_segments=n) * inv_deg
        dx_r = jax.ops.segment_sum(rel * gate * w, rcv, num_segments=n) * inv_deg
        # virtual nodes (Eqs. 4-5 and the virtual terms of Eqs. 6-7)
        com = jnp.mean(x, axis=0)
        zc = z - com
        mv = _dot(zc, zc.T, mode)
        vb = lp["virtual"]
        dx_v = jnp.zeros_like(x)
        mh_v = jnp.zeros_like(h)
        dz, ms = [], []
        for c in range(c_n):
            rel_c = x - z[c]
            d2_c = jnp.sum(rel_c * rel_c, axis=-1, keepdims=True)
            feats = jnp.concatenate(
                [h, jnp.broadcast_to(s[c], (n, s.shape[1])), d2_c,
                 jnp.broadcast_to(mv[:, c], (n, c_n))], -1)
            m_c = _mlp(_channel(vb["phi2"], c), feats, mode)
            dx_v = dx_v + rel_c * _mlp(_channel(vb["phi_xv"], c), m_c, mode)
            mh_v = mh_v + m_c
            dz.append(jnp.sum(-rel_c * _mlp(_channel(vb["phi_z"], c), m_c,
                                            mode), axis=0))
            ms.append(jnp.sum(m_c, axis=0))
        dx_v, mh_v = dx_v / c_n, mh_v / c_n
        norm = jnp.sqrt(jnp.sum(dx_v * dx_v, axis=-1, keepdims=True) + 1e-12)
        dx_v = dx_v * jnp.minimum(1.0, clamp / norm)
        dx = dx_r + dx_v + _mlp(lp["phi_v"], h, mode) * v
        h = h + _mlp(lp["phi_h"], jnp.concatenate([h, mh_r, mh_v], -1), mode)
        # Eqs. 8-9 from the pre-update coordinates
        z = z + jnp.stack(dz) / n
        s_in = jnp.concatenate([s, jnp.stack(ms) / n], -1)
        s = s + jnp.stack([_mlp(_channel(vb["phi_s"], c), s_in[c], mode)
                           for c in range(c_n)])
        x = x + dx
    return x, h, z, s


def _rbf(a, b, sigma):
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    return jnp.exp(-d2 / (2.0 * sigma * sigma))


def scene_loss(params, g: dict, key, cfg: dict, mode: str):
    """Eq. 11 for one scene on one device (MMD on ``mmd_sample`` targets
    drawn under ``key``), with the virtual nodes (z, s) after the forward."""
    x, _, z, s = forward(params, g, cfg, mode)
    t = g["x1"]
    mse = jnp.sum((x - t) ** 2) / x.shape[0] / 3.0
    c_n, k = z.shape[0], cfg["mmd_sample"]
    idx = jax.random.categorical(key, jnp.zeros(x.shape[0], jnp.float32),
                                 shape=(k,))
    sig = cfg["mmd_sigma"]
    mmd = (jnp.sum(_rbf(z, z, sig)) / (c_n * c_n)
           - jnp.sum(_rbf(t[idx], z, sig)) / (k * c_n))
    return mse + cfg["lam_mmd"] * mmd, (z, s)


def union_loss(params, g: dict, cfg: dict, mode: str):
    """Eq. 18 for one scene split over shards: the graph is the union of
    the shards' local graphs, ``g["shard"]`` gives each node's shard, and
    each shard's MMD averages over all of its nodes.

    Two faults that calibration reads, named in ``cfg``: ``loss_shards``
    keeps the terms of the first shards alone, the mean taken over them;
    ``own_shard`` keeps the first shard's share of the global sums, the
    gradient one chip has before the all-reduce.  Returns the loss with
    the virtual nodes (z, s) after the forward."""
    x, _, z, s = forward(params, g, cfg, mode)
    t = g["x1"]
    d = cfg["devices"]
    err = jnp.sum((x - t) ** 2, axis=-1)
    c_n, sig = z.shape[0], cfg["mmd_sigma"]
    cross = jax.ops.segment_sum(jnp.sum(_rbf(t, z, sig), axis=1), g["shard"],
                                num_segments=d)
    n_d = jax.ops.segment_sum(jnp.ones(t.shape[0], t.dtype), g["shard"],
                              num_segments=d)
    mmd = jnp.sum(_rbf(z, z, sig)) / (c_n * c_n) - cross / (n_d * c_n)
    if cfg.get("own_shard"):
        mine = (g["shard"] == 0).astype(t.dtype)
        return (jnp.sum(err * mine) / x.shape[0] / 3.0
                + cfg["lam_mmd"] * mmd[0] / d), (z, s)
    keep = cfg.get("loss_shards", d)
    w = (g["shard"] < keep).astype(t.dtype)
    mse = jnp.sum(err * w) / jnp.sum(w) / 3.0
    return mse + cfg["lam_mmd"] * jnp.mean(mmd[:keep]), (z, s)


@partial(jax.jit, static_argnames=("cfg_items", "mode", "dist"))
def _value_and_grad(params, g, key, *, cfg_items, mode, dist):
    cfg = dict(cfg_items)
    if dist:
        return jax.value_and_grad(union_loss, has_aux=True)(params, g, cfg,
                                                            mode)
    return jax.value_and_grad(scene_loss, has_aux=True)(params, g, key, cfg,
                                                        mode)


def spread(x) -> float:
    """The RMS distance of a scene's particles from their centre of mass."""
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.mean(np.sum((x - x.mean(0)) ** 2, axis=-1))))


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(tree)))


def adam_step(params, grads, m, v, step: int, cfg: dict):
    """One Adam(W-style decay) step after a global-norm clip; returns the
    clipped gradients too (what the optimizer's state records)."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    lr, wd = cfg["lr"], cfg["weight_decay"]
    scale = jnp.minimum(1.0, cfg["grad_clip"] / (_global_norm(grads) + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
        params, m, v)
    return params, m, v, grads


def train(params0, batches, keys, cfg: dict, mode: str = "highest") -> dict:
    """Run the first ``len(batches)`` training steps from ``params0``.

    ``batches``: per step, a list of scene graphs (see :func:`forward`;
    with ``x1`` the target, and ``shard`` on DistEGNN).  ``keys``: per step,
    the key the program's step was given.  Each scene's gradient is taken
    alone and averaged, so the memory held is one scene's.

    Returns ``losses`` (per step), ``grad1`` (the clipped first gradient)
    and ``params`` (after the last step); on DistEGNN also ``virtual``,
    the virtual nodes after the forward of the first step's first scene at
    ``params0``: coordinates ``z`` (C, 3), features ``s`` (C, S) and the
    scene's ``spread`` (see :func:`spread`).
    """
    dist = cfg.get("devices", 1) > 1
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    params = params0
    m = jax.tree.map(jnp.zeros_like, params0)
    v = jax.tree.map(jnp.zeros_like, params0)
    losses, grad1 = [], None
    out = {}
    with jax.default_matmul_precision("highest"):
        for step, (scenes, key) in enumerate(zip(batches, keys), start=1):
            skeys = jax.random.split(key, len(scenes))
            tot_loss, tot_grad = 0.0, None
            for g, k in zip(scenes, skeys):
                (loss, (z, s)), grad = _value_and_grad(
                    params, g, k, cfg_items=cfg_items, mode=mode, dist=dist)
                if dist and "virtual" not in out:
                    out["virtual"] = dict(z=np.asarray(z), s=np.asarray(s),
                                          spread=spread(g["x"]))
                tot_loss = tot_loss + loss
                tot_grad = grad if tot_grad is None else jax.tree.map(
                    jnp.add, tot_grad, grad)
            b = float(len(scenes))
            grads = jax.tree.map(lambda a: a / b, tot_grad)
            params, m, v, clipped = adam_step(params, grads, m, v, step, cfg)
            losses.append(float(tot_loss / b))
            if grad1 is None:
                grad1 = clipped
    return dict(out, losses=losses, grad1=grad1, params=params)


def scene_graph(scene, r: float, edge_cap: int, assign=None) -> dict:
    """A scene as the reference's graph: its radius graph (restricted to
    edges within one shard where ``assign`` gives shards), padded to
    ``edge_cap`` slots so that every scene shares one compiled program."""
    from bench.scenes import radius_pairs

    snd, rcv = radius_pairs(scene.x0, r)
    if assign is not None:
        keep = assign[snd] == assign[rcv]
        snd, rcv = snd[keep], rcv[keep]
    e = snd.size
    if e > edge_cap:
        raise ValueError(f"scene has {e} edges, over the capacity {edge_cap}")
    pad = edge_cap - e
    g = dict(x=scene.x0, v=scene.v0, h=scene.h, x1=scene.x1,
             snd=np.concatenate([snd, np.zeros(pad, np.int64)]).astype(np.int32),
             rcv=np.concatenate([rcv, np.zeros(pad, np.int64)]).astype(np.int32),
             em=np.concatenate([np.ones(e, np.float32),
                                np.zeros(pad, np.float32)]))
    if assign is not None:
        g["shard"] = assign.astype(np.int32)
    return {k: jnp.asarray(a) for k, a in g.items()}
