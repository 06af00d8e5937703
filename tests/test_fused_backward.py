"""Fused Pallas backward kernels: end-to-end grad parity + precision modes.

The forward kernels are parity-tested in ``test_kernels.py``; this file
covers the PR's fused-backward contract (DESIGN.md §9):

  * registry-wide gradient parity — every model whose edge/virtual pathway
    can dispatch to the fused kernels produces ``use_kernel=True`` grads
    matching the jnp substrate, through the *fused Pallas backwards* (the
    custom_vjp no longer remats a jnp oracle);
  * layout-carrying vs trace-time-regroup dispatch, vmap'd batches, empty
    edge sets and masked nodes;
  * the bf16/f32-accumulate precision mode: forward closeness to f32 and
    E(3) equivariance at bf16 tolerances;
  * the train-step dispatch acceptance telemetry (``virtual_kernel > 0``,
    ``virtual_jnp == 0``, ``edge_layout_regroup == 0``) and the 2-shard
    DistEGNN gradient path.
"""
import json
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.models.registry import REGISTRY, resolve_model

# small-but-not-degenerate: enough nodes for several edge blocks, C>1
_N, _E, _HID = 48, 120, 16
_CFG = dict(n_layers=2, hidden=_HID, h_in=2)


def _graph(seed=0, n=_N, e=_E, masked_nodes=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, 3))
    v = jax.random.normal(ks[1], (n, 3)) * 0.1
    h = jax.random.normal(ks[2], (n, 2))
    snd = jax.random.randint(ks[3], (e,), 0, n)
    rcv = jnp.sort(jax.random.randint(ks[4], (e,), 0, n))
    em = (jax.random.uniform(ks[5], (e,)) > 0.2).astype(jnp.float32)
    nm = None
    if masked_nodes:
        nm = jnp.where(jnp.arange(n) < n - 8, 1.0, 0.0)
    return make_graph(x, v, h, snd, rcv, edge_mask=em, node_mask=nm)


def _grad_tree(apply_full, cfg, params, g, seed=0):
    tgt = g.x + 0.05 * jax.random.normal(jax.random.PRNGKey(seed), g.x.shape)

    def loss(params):
        x_pred, _ = apply_full(params, cfg, g)
        return jnp.sum(((x_pred - tgt) ** 2) * g.node_mask[:, None])

    return jax.grad(loss)(params)


def _assert_tree_close(a, b, rtol=1e-3, atol=1e-5):
    def close(x, y):
        if y.size == 0:
            return
        scale = float(jnp.max(jnp.abs(y))) + 1e-6
        np.testing.assert_allclose(np.asarray(x) / scale,
                                   np.asarray(y) / scale,
                                   rtol=rtol, atol=atol)

    jax.tree.map(close, a, b)


@pytest.mark.parametrize("name", sorted(REGISTRY))
@pytest.mark.parametrize("masked_nodes", [False, True])
def test_registry_fused_backward_grad_parity(name, masked_nodes):
    """Every registry model: fused-backward grads ≍ jnp-substrate grads,
    with full and partially-masked node sets."""
    g = _graph(seed=1, masked_nodes=masked_nodes)
    overrides = dict(_CFG)
    if REGISTRY[name].has_virtual:
        overrides.update(n_virtual=2, s_dim=8)
    fields = REGISTRY[name].make_config._fields
    overrides = {k: v for k, v in overrides.items() if k in fields}
    cfg_j, params, apply_full = resolve_model(
        name, jax.random.PRNGKey(2), **overrides)
    cfg_k = cfg_j._replace(use_kernel=True)

    mp.reset_dispatch_counts()
    gk = _grad_tree(apply_full, cfg_k, params, g)
    counts = mp.dispatch_counts()
    gj = _grad_tree(apply_full, cfg_j, params, g)
    # f32 accumulation-order noise compounds through the deeper stacks
    # (fast_tfn's CG paths), so the floor is a touch looser than rtol alone
    _assert_tree_close(gk, gj, rtol=1e-3, atol=5e-5)
    # models with a φ1-form edge pathway must actually have dispatched it;
    # fast_* models likewise the virtual kernel (linear has neither)
    if name not in ("linear", "tfn", "fast_tfn"):
        assert counts.get("edge_kernel", 0) > 0, counts
    if REGISTRY[name].has_virtual or name == "fast_egnn":
        if name == "fast_rf":  # zero-width features: kernel ineligible,
            assert counts.get("virtual_jnp", 0) > 0, counts  # clean fallback
        else:
            assert counts.get("virtual_kernel", 0) > 0, counts
            assert counts.get("virtual_jnp", 0) == 0, counts


def test_edge_grad_parity_layout_vs_regroup():
    """The two fused dispatch flavours — host-precomputed banded layout vs
    trace-time regroup — produce identical gradients (and both match jnp)."""
    from repro.data.radius_graph import banded_csr_layout
    from repro.kernels.edge_message import EdgeLayout, LayoutMeta

    spec = mp.EdgeSpec(coord_clamp=100.0)
    g = _graph(seed=3)
    from repro.core.mlp import init_mlp
    lp = {"phi1": init_mlp(jax.random.PRNGKey(4), [2 * 2 + 1, _HID, _HID]),
          "gate": init_mlp(jax.random.PRNGKey(5), [_HID, _HID, 1],
                           final_bias=False)}
    assert mp.kernel_supported(lp, g, spec)
    bl = banded_csr_layout(np.asarray(g.senders), np.asarray(g.receivers),
                           g.n_nodes,
                           edge_mask=np.asarray(g.edge_mask))
    layout = EdgeLayout(
        senders=jnp.asarray(bl.senders), receivers=jnp.asarray(bl.receivers),
        edge_mask=jnp.asarray(bl.edge_mask),
        block_rwin=jnp.asarray(bl.block_rwin),
        block_swin=jnp.asarray(bl.block_swin),
        meta=LayoutMeta(bl.window, bl.swindow, bl.n_pad, bl.block_e))

    def loss(lay):
        def f(lp, x, h):
            o = mp.edge_pathway(lp, h, x, g, spec, use_kernel=True, layout=lay)
            return jnp.sum(o.dx ** 2) + jnp.sum(o.mh ** 2)
        return f

    def loss_jnp(lp, x, h):
        o = mp.edge_pathway(lp, h, x, g, spec)
        return jnp.sum(o.dx ** 2) + jnp.sum(o.mh ** 2)

    args = (lp, g.x, g.h)
    g_lay = jax.grad(loss(layout), argnums=(0, 1, 2))(*args)
    g_regroup = jax.grad(loss(None), argnums=(0, 1, 2))(*args)
    g_jnp = jax.grad(loss_jnp, argnums=(0, 1, 2))(*args)
    _assert_tree_close(g_lay, g_jnp)
    _assert_tree_close(g_regroup, g_jnp)


def test_fused_backward_vmap_batch():
    """Batched (vmap) grads through both fused backwards — the trainer's
    value_and_grad-over-vmap pattern."""
    g = _graph(seed=6, n=24, e=60)
    cfg_j, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(7), n_layers=1, hidden=8, h_in=2,
        n_virtual=2, s_dim=4)
    cfg_k = cfg_j._replace(use_kernel=True)
    xb = jnp.stack([g.x, g.x * 1.1, g.x + 0.2])

    def batch_loss(cfg):
        def f(params):
            def one(x0):
                gg = g._replace(x=x0)
                x_pred, _ = apply_full(params, cfg, gg)
                return jnp.sum((x_pred - x0) ** 2)
            return jnp.sum(jax.vmap(one)(xb))
        return f

    gk = jax.grad(batch_loss(cfg_k))(params)
    gj = jax.grad(batch_loss(cfg_j))(params)
    _assert_tree_close(gk, gj)


def test_fused_backward_empty_edges():
    """Zero-edge graphs (p=1.0 edge dropping): fused backwards must return
    finite zero edge-grads, and the virtual pathway still trains."""
    g = _graph(seed=8, n=16, e=0)
    cfg_j, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(9), n_layers=1, hidden=8, h_in=2,
        n_virtual=2, s_dim=4)
    cfg_k = cfg_j._replace(use_kernel=True)
    gk = _grad_tree(apply_full, cfg_k, params, g)
    gj = _grad_tree(apply_full, cfg_j, params, g)
    for leaf in jax.tree.leaves(gk):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    _assert_tree_close(gk, gj)


# ------------------------------------------------------------ bf16 precision
def test_bf16_forward_close_to_f32():
    """precision='bf16' (bf16 compute, f32 accumulate) stays within bf16
    round-off of the f32 kernels on both pathways."""
    g = _graph(seed=10)
    cfg_f, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(11), use_kernel=True, **_CFG,
        n_virtual=2, s_dim=8)
    cfg_b = cfg_f._replace(precision="bf16")
    x_f, _ = apply_full(params, cfg_f, g)
    x_b, _ = apply_full(params, cfg_b, g)
    scale = float(jnp.max(jnp.abs(x_f))) + 1e-6
    np.testing.assert_allclose(np.asarray(x_b) / scale,
                               np.asarray(x_f) / scale, rtol=2e-2, atol=2e-2)


def test_bf16_grads_finite_and_close():
    """bf16-mode gradients flow through both fused backwards (f32
    accumulation keeps them finite and near the f32 grads)."""
    g = _graph(seed=12)
    cfg_f, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(13), use_kernel=True, **_CFG,
        n_virtual=2, s_dim=8)
    cfg_b = cfg_f._replace(precision="bf16")
    gb = _grad_tree(apply_full, cfg_b, params, g)
    gf = _grad_tree(apply_full, cfg_f, params, g)
    for leaf in jax.tree.leaves(gb):
        assert bool(jnp.all(jnp.isfinite(leaf)))

    # bf16 round-off compounds through the layer stack, so elementwise
    # bounds are noisy on near-zero entries; the per-leaf relative L2 error
    # is the stable contract (f32 accumulation keeps it ~1e-2, while a
    # genuinely wrong backward is O(1))
    def rel_l2(a, b):
        num = float(jnp.linalg.norm((a - b).ravel()))
        den = float(jnp.linalg.norm(b.ravel())) + 1e-6
        assert num / den < 0.1, f"rel L2 {num / den:.3f}"

    jax.tree.map(rel_l2, gb, gf)


@pytest.mark.parametrize("precision,event,other", [
    ("f32", "edge_onehot_split3", "edge_onehot_bf16"),
    ("bf16", "edge_onehot_bf16", "edge_onehot_split3")])
def test_onehot_counter_follows_precision(precision, event, other):
    """Each fused edge dispatch records the pieces of its one-hot
    products: three bf16 pieces under f32 compute, one under bf16."""
    g = _graph(seed=19)
    cfg, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(20), use_kernel=True, **_CFG,
        n_virtual=2, s_dim=8)
    cfg = cfg._replace(precision=precision)
    mp.reset_dispatch_counts()
    jax.eval_shape(lambda p: apply_full(p, cfg, g), params)
    c = mp.dispatch_counts()
    assert c.get(event, 0) == c.get("edge_kernel", 0) > 0, c
    assert c.get(other, 0) == 0, c


@pytest.mark.parametrize("precision", ["bf16"])
def test_kernel_equivariance_rotation_translation(precision):
    """E(3) equivariance of the kernelised FastEGNN forward in bf16 mode:
    rotating + translating the input rotates/translates the prediction to
    bf16 round-off (the cast is applied to invariant scalars and relative
    vectors, so equivariance degrades only by round-off, never
    structurally).  The f32 case is
    ``test_equivariance.py::test_e3_equivariance[fast_egnn-fused]``."""
    g = _graph(seed=14)
    cfg, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(15), use_kernel=True, **_CFG,
        n_virtual=2, s_dim=8)
    cfg = cfg._replace(precision=precision)
    # a random rotation via QR; flip to det +1
    q, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(16), (3, 3)))
    R = q * jnp.sign(jnp.linalg.det(q))
    t = jnp.array([0.7, -1.2, 0.4])

    x1, _ = apply_full(params, cfg, g)
    g2 = g._replace(x=g.x @ R.T + t, v=g.v @ R.T)
    x2, _ = apply_full(params, cfg, g2)
    scale = float(jnp.max(jnp.abs(x2))) + 1e-6
    np.testing.assert_allclose(np.asarray(x1 @ R.T + t) / scale,
                               np.asarray(x2) / scale, rtol=3e-2, atol=3e-2)


def _dot_dtypes(line: str) -> list[str]:
    """Element types of a StableHLO dot's two operands and its result."""
    sig = line.split(" : ", 1)[1]
    return [t.split("x")[-1] for t in re.findall(r"tensor<([^>]*)>", sig)]


@pytest.mark.parametrize("use_kernel", [False, True])
def test_f32_dots_pin_highest_precision(use_kernel):
    """The f32 configuration rounds no operand below f32 on any backend:
    each dot of the FastEGNN forward and backward with an f32 operand, the
    model's own and (interpreted) the kernels', carries
    ``precision=HIGHEST`` — a TPU runs a default-precision f32 dot as one
    bf16 pass.  Every other dot multiplies bf16 operands into an f32
    result: the edge kernel's one-hot gathers and scatters, exact in one
    pass (a one-hot and each piece of a three-piece split are bf16, and
    their products are exact in f32)."""
    g = _graph(seed=17)
    cfg, params, apply_full = resolve_model(
        "fast_egnn", jax.random.PRNGKey(18), use_kernel=use_kernel, **_CFG,
        n_virtual=2, s_dim=8)
    text = jax.jit(lambda p: _grad_tree(apply_full, cfg, p, g)).lower(
        params).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots
    f32 = [ln for ln in dots if "f32" in _dot_dtypes(ln)[:2]]
    assert all("precision = [HIGHEST, HIGHEST]" in ln for ln in f32), [
        ln for ln in f32 if "HIGHEST" not in ln][:3]
    onehot = [ln for ln in dots if ln not in f32]
    assert all(_dot_dtypes(ln) == ["bf16", "bf16", "f32"] for ln in onehot), [
        ln for ln in onehot if _dot_dtypes(ln) != ["bf16", "bf16", "f32"]][:3]
    assert bool(onehot) == use_kernel


# ------------------------------------------------- train-step acceptance
def test_train_step_dispatch_acceptance():
    """The PR's acceptance telemetry: a single-device FastEGNN training
    step with ``use_kernel=True`` over layout-carrying batches reports
    ``virtual_kernel > 0``, ``virtual_jnp == 0``, zero trace-time edge
    regroups, and f32 one-hot products by three exact bf16 pieces."""
    from repro.data.nbody import generate_nbody_dataset
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    data = generate_nbody_dataset(4, n_nodes=12, seed=0)
    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0), use_kernel=True,
                          train_cfg=TrainConfig(lam_mmd=0.01),
                          n_layers=2, hidden=16, h_in=1, n_virtual=3, s_dim=8)
    batches = pipe.make_batches(data, 2).materialize()
    st = pipe.opt.init(pipe.params)
    mp.reset_dispatch_counts()
    jax.block_until_ready(pipe.train_step(pipe.params, st, batches[0],
                                          jax.random.PRNGKey(1)))
    c = mp.dispatch_counts()
    assert c.get("virtual_kernel", 0) > 0, c
    assert c.get("virtual_jnp", 0) == 0, c
    assert c.get("edge_kernel", 0) > 0, c
    assert c.get("edge_onehot_split3", 0) > 0, c
    assert c.get("edge_onehot_bf16", 0) == 0, c
    assert c.get("edge_layout_regroup", 0) == 0, c
    assert c.get("edge_layout_host", 0) > 0, c


def test_train_step_live_walk_bitwise_full_walk(walks_agree):
    """A FastEGNN train step over a batch of layout-carrying scenes whose
    live counts differ (the edge kernels vmapped over the batch): new
    parameters, optimizer state and metrics bit for bit those of every
    pass walking its whole static grid."""
    from repro.data.fluid import generate_fluid_dataset
    from repro.kernels.edge_message import live_block_count
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    data = generate_fluid_dataset(2, n_particles=300, seed=3)
    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0), use_kernel=True,
                          train_cfg=TrainConfig(lam_mmd=0.01),
                          n_layers=2, hidden=16, h_in=1, n_virtual=3, s_dim=8)
    batch = pipe.make_batches(data, 2, r=0.15)[0]
    lay = batch.layout
    be = lay.senders.shape[-1] // lay.block_rwin.shape[-1]
    live = jax.vmap(live_block_count, (0, 0, None))(lay.edge_mask,
                                                    lay.block_rwin, be)
    live = np.asarray(live)[:, 0]
    assert live[0] != live[1] and live.max() < lay.block_rwin.shape[-1], live
    opt_state = pipe.opt.init(pipe.params)
    walks_agree(lambda: pipe.train_step(pipe.params, opt_state, batch,
                                        jax.random.PRNGKey(1)))


def test_loss_scale_grads_invariant():
    """TrainConfig.loss_scale: scaled-then-unscaled training matches the
    unscaled step (static scaling is numerically inert in f32)."""
    from repro.data.nbody import generate_nbody_dataset
    from repro.pipeline import build_pipeline
    from repro.training.optim import Adam
    from repro.training.trainer import TrainConfig, build_train_step

    data = generate_nbody_dataset(4, n_nodes=10, seed=1)
    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(2),
                          n_layers=1, hidden=8, h_in=1, n_virtual=2, s_dim=4)
    batches = pipe.make_batches(data, 2).materialize()
    opt = Adam(lr=1e-3)
    outs = {}
    for scale in (1.0, 1024.0):
        tc = TrainConfig(lam_mmd=0.01, loss_scale=scale)
        ts, _ = build_train_step(pipe.apply_full, pipe.cfg, tc, opt)
        p, _, parts = ts(pipe.params, opt.init(pipe.params), batches[0],
                         jax.random.PRNGKey(3))
        outs[scale] = (p, float(parts["loss"]))
    np.testing.assert_allclose(outs[1.0][1], outs[1024.0][1], rtol=1e-6)
    _assert_tree_close(outs[1024.0][0], outs[1.0][0], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------ 2-shard dist path
_DIST_GRAD = """
import json
import jax, jax.numpy as jnp
from repro.core import message_passing as mp
from repro.data.fluid import generate_fluid_dataset
from repro.data.partition import partition_sample
from repro.distributed.dist_egnn import (make_gnn_mesh, stack_partitions,
                                         build_dist_train_step)
from repro.models.fast_egnn import FastEGNNConfig, init_fast_egnn
from repro.training.optim import Adam

data = generate_fluid_dataset(1, n_particles=128, seed=0)
pgs = [partition_sample(s.x0, s.v0, s.h, s.x1, d=2, r=0.08, seed=j)
       for j, s in enumerate(data)]
sb = stack_partitions(pgs)
mesh = make_gnn_mesh(2)
opt = Adam(lr=1e-3)
grads, counts = {}, {}
for use_kernel in (False, True):
    cfg = FastEGNNConfig(n_layers=1, hidden=16, h_in=1, n_virtual=2,
                         s_dim=8, use_kernel=use_kernel)
    params = init_fast_egnn(jax.random.PRNGKey(0), cfg)
    mp.reset_dispatch_counts()
    _, loss_fn = build_dist_train_step(cfg, mesh, opt, lam_mmd=0.01)
    g = jax.grad(loss_fn)(params, sb)
    counts[use_kernel] = mp.dispatch_counts()
    grads[use_kernel] = g
rel = jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6)),
    grads[True], grads[False])
print(json.dumps({"max_rel": max(jax.tree.leaves(rel)),
                  "counts": counts[True]}))
"""


def test_dist_2shard_fused_backward_grad_parity():
    """DistEGNN on 2 forced host shards: per-shard fused kernels (edge +
    virtual, forward and backward) reproduce the jnp gradients, and the
    per-shard virtual pathway dispatched to the kernel."""
    env_code = textwrap.dedent(_DIST_GRAD)
    import os
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", env_code],
                         capture_output=True, text=True, env=env,
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["max_rel"] < 5e-3, res
    assert res["counts"].get("virtual_kernel", 0) > 0, res
    assert res["counts"].get("virtual_jnp", 0) == 0, res
