"""FastTFN on the fused path: the TFN edge kernel (``kernels/tfn_edge.py``,
interpret mode here) against its oracle and the jnp path, forward and every
gradient; its symmetry; the objective's MMD term for every plug-in
variant; and the train step's dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import walkcases

from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.core.mlp import init_mlp
from repro.data.radius_graph import banded_csr_layout
from repro.kernels import ref
from repro.kernels.edge_message import layout_from_host
from repro.models import tfn

N, E, HID, RBF = 300, 1800, 16, 8
CUTOFF = 1.5
# f32 throughout: a fused sum differs from segment_sum's only in the order
# of its additions (the one-hot products move exact bf16 pieces), so
# results agree to a few ulps of each output's largest entry; the
# tolerance leaves room for the chained products of the backward
RTOL = 2e-5


def _graph(seed=0, n=N, e=E, pad=200, hid=HID):
    """A random graph without self-loops (a radius graph has none), its
    last ``pad`` edge slots masked padding ``0 -> 0`` as ``pad_edges``
    leaves them, and about a tenth of the rest masked too."""
    rng = np.random.default_rng(seed)
    snd = rng.integers(0, n, e)
    rcv = np.sort(rng.integers(0, n, e))
    em = ((rng.random(e) > 0.1) & (snd != rcv)).astype(np.float32)
    snd = np.concatenate([snd, np.zeros(pad, np.int64)])
    rcv = np.concatenate([rcv, np.zeros(pad, np.int64)])
    em = np.concatenate([em, np.zeros(pad, np.float32)])
    return make_graph(rng.normal(size=(n, 3)), rng.normal(size=(n, 3)),
                      rng.normal(size=(n, hid)), snd, rcv, edge_mask=em)


def _layout(g, capacity=None, window=128, swindow=256):
    """The host banded layout, at windows small enough that this graph
    spans several receiver and sender windows."""
    return layout_from_host(banded_csr_layout(
        np.asarray(g.senders), np.asarray(g.receivers), g.n_nodes,
        edge_mask=np.asarray(g.edge_mask), window=window, swindow=swindow,
        capacity=capacity))


def _cfg(use_kernel=True, clamp=0.4, hid=HID):
    # a clamp that binds on some edges, so the clip's zero gradient shows
    return tfn.TFNConfig(hidden=hid, n_rbf=RBF, rbf_cutoff=CUTOFF,
                         coord_clamp=clamp, use_kernel=use_kernel)


def _radial(seed=1, hid=HID):
    return init_mlp(jax.random.PRNGKey(seed), [RBF + hid, hid, 6])


def _loss(pathway, g):
    """A scalar of both outputs with distinct weights per node, so every
    gradient entry is exercised."""
    wn = jnp.cos(jnp.arange(g.n_nodes, dtype=jnp.float32))[:, None]

    def f(radial, x, h, v):
        dx, h_agg = pathway(radial, h, x, g._replace(x=x, h=h, v=v))
        return jnp.sum(dx * wn * dx) + jnp.sum(h_agg * wn), (dx, h_agg)
    return f


def _value_and_grads(pathway, g, radial):
    return jax.value_and_grad(_loss(pathway, g), argnums=(0, 1, 2, 3),
                              has_aux=True)(radial, g.x, g.h, g.v)


def _assert_close(got, want, rtol=RTOL):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=rtol)


def _fused(lay, cfg=None):
    cfg = cfg or _cfg()
    return lambda r, h, x, g: tfn.tfn_edge_pathway(r, h, x, g, cfg, lay)


def _jnp(cfg=None):
    cfg = (cfg or _cfg())._replace(use_kernel=False)
    return lambda r, h, x, g: tfn.tfn_edge_pathway(r, h, x, g, cfg)


def _oracle(cfg=None):
    """``ref.tfn_edge_pathway_ref`` behind the same interface: the per-node
    product of the radial layer 1 made as ``ops.tfn_edge_pathway`` makes
    it."""
    cfg = cfg or _cfg()

    def f(radial, h, x, g):
        w1, b1 = radial[0]["w"], radial[0]["b"]
        a = jnp.matmul(h, w1[RBF:], precision="highest") + b1
        centers = jnp.linspace(0.0, CUTOFF, RBF)[None, :]
        dx, h_agg, _ = ref.tfn_edge_pathway_ref(
            x, a, g.v, g.senders, g.receivers, g.edge_mask, centers,
            w1[:RBF], radial[1]["w"], radial[1]["b"][None, :],
            cutoff=CUTOFF, clamp=cfg.coord_clamp)
        return dx, h_agg
    return f


@pytest.mark.parametrize("clamp,hid", [(0.4, HID), (float("inf"), HID),
                                       (0.4, 12)])
def test_fused_matches_oracle_and_jnp_path(clamp, hid):
    """Forward and the gradients of x, h, v and every radial weight, with
    masked padding edges, over several receiver and sender windows; also
    at a hidden width off the 8-row tile."""
    g = _graph(hid=hid)
    cfg = _cfg(clamp=clamp, hid=hid)
    radial = _radial(hid=hid)
    assert tfn.edge_kernel_supported(radial, cfg, g, _layout(g))
    (_, out_k), g_k = _value_and_grads(_fused(_layout(g), cfg), g, radial)
    (_, out_r), g_r = _value_and_grads(_oracle(cfg), g, radial)
    (_, out_j), g_j = _value_and_grads(_jnp(cfg), g, radial)
    _assert_close((out_k, g_k), (out_r, g_r))
    _assert_close((out_k, g_k), (out_j, g_j))
    if np.isfinite(clamp):  # the clip binds on some real edges
        from repro.core.mlp import mlp

        rel = g.x[g.receivers] - g.x[g.senders]
        d = jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)
        w = mlp(radial, jnp.concatenate(
            [tfn._rbf(d, RBF, CUTOFF), g.h[g.senders]], axis=-1))
        bound = jnp.any(jnp.abs(w) > clamp, axis=-1) & (g.edge_mask > 0)
        assert 0 < int(jnp.sum(bound)) < int(jnp.sum(g.edge_mask))


def test_fused_empty_edge_list():
    """No edges: zero outputs and zero gradients, as the jnp path."""
    g = make_graph(np.random.default_rng(0).normal(size=(N, 3)),
                   np.ones((N, 3)), np.ones((N, HID)),
                   np.zeros(0, np.int32), np.zeros(0, np.int32))
    radial = _radial()
    (_, out_k), g_k = _value_and_grads(_fused(_layout(g)), g, radial)
    (_, out_j), g_j = _value_and_grads(_jnp(), g, radial)
    for a, b in zip(jax.tree.leaves((out_k, g_k)),
                    jax.tree.leaves((out_j, g_j))):
        np.testing.assert_array_equal(np.asarray(a), 0.0)
        np.testing.assert_array_equal(np.asarray(b), 0.0)


def test_fused_under_vmap_matches_each_graph():
    """A batch of two graphs with stacked layouts, as the trainer's vmap
    calls it: each slot's forward and gradients as its graph's alone."""
    gs = [_graph(seed=s) for s in (3, 4)]
    cap = max(_layout(g).senders.shape[0] for g in gs)
    lays = [_layout(g, capacity=cap) for g in gs]
    batch_g = jax.tree.map(lambda *a: jnp.stack(a), *gs)
    batch_l = jax.tree.map(lambda *a: jnp.stack(a), *lays)
    radial = _radial()
    cfg = _cfg()

    def total(radial, x, h, v):
        def one(g, lay, x, h, v):
            return _loss(_fused(lay, cfg), g)(radial, x, h, v)[0]
        return jnp.sum(jax.vmap(one)(batch_g, batch_l, x, h, v))

    got = jax.grad(total, argnums=(0, 1, 2, 3))(
        radial, batch_g.x, batch_g.h, batch_g.v)
    for i, (g, lay) in enumerate(zip(gs, lays)):
        _, want = _value_and_grads(_fused(lay, cfg), g, radial)
        _assert_close((got[1][i], got[2][i], got[3][i]), want[1:])
    _, w0 = _value_and_grads(_fused(lays[0], cfg), gs[0], radial)
    _, w1 = _value_and_grads(_fused(lays[1], cfg), gs[1], radial)
    _assert_close(got[0], jax.tree.map(jnp.add, w0[0], w1[0]))


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("case", walkcases.CASES)
def test_fused_live_walk_bitwise_full_walk(case, batched, walks_agree):
    """The TFN edge kernel's forward and both backward passes give bit for
    bit what a walk of the whole static grid gives, on layouts with dead
    blocks, alone and under vmap over samples whose live counts differ."""
    from repro.kernels.tfn_edge import tfn_edge_bwd_fused, tfn_edge_fused

    edges, lays, live, n_blocks = walkcases.walk_layouts(
        case, 2 if batched else 1)
    assert max(live) < n_blocks, (live, n_blocks)
    if batched and case == "pads":
        assert live[0] != live[1], live
    b, n = len(lays), walkcases.N
    rng = np.random.default_rng(5)
    x, v, g_dx = (jnp.asarray(rng.normal(size=(b, n, 3)), jnp.float32)
                  for _ in range(3))
    a = jnp.asarray(rng.normal(size=(b, n, HID)), jnp.float32)
    g_h = jnp.asarray(rng.normal(size=(b, n, 2)), jnp.float32)
    lay = jax.tree.map(lambda *t: jnp.stack(t), *lays)
    radial = _radial()
    ws = (jnp.linspace(0.0, CUTOFF, RBF)[None, :], radial[0]["w"][:RBF],
          radial[1]["w"], radial[1]["b"][None, :])
    kw = dict(cutoff=CUTOFF, clamp=0.4, interpret=True)

    def one(x, a, v, lay, g_dx, g_h):
        out = tfn_edge_fused(x, a, v, lay, *ws, **kw)
        return out, tfn_edge_bwd_fused(x, a, v, lay, *ws, out[2], g_dx, g_h,
                                       **kw)

    args = (x, a, v, lay, g_dx, g_h)
    run = (lambda: jax.vmap(one)(*args)) if batched else \
        (lambda: one(*jax.tree.map(lambda t: t[0], args)))
    (dx, _, deg), grads = walks_agree(run)
    assert np.all(np.isfinite(dx)) and np.all(np.isfinite(grads[0]))
    em = np.stack([ed[2] for ed in edges])
    assert float(deg.sum()) == float(em.sum())


def _rotation(seed, det=1.0):
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(3, 3)))
    q = q * np.sign(np.linalg.det(q))  # a proper rotation
    return jnp.asarray(q @ np.diag([1.0, 1.0, det]), jnp.float32)


@pytest.mark.parametrize("det", [1.0, -1.0])
def test_fused_so3_equivariant_not_o3(det):
    """Rotating x and v rotates the type-1 output and leaves the type-0
    one; a reflection does not pass, because the cross-product path is a
    pseudovector (the model is SO(3)-, not O(3)-equivariant)."""
    g = _graph(seed=5)
    lay = _layout(g)
    radial = _radial(seed=2)
    cfg = _cfg(clamp=100.0)  # an unbound clip: the paths alone decide
    f = _fused(lay, cfg)
    q = _rotation(7, det)
    dx, h_agg = f(radial, g.h, g.x, g)
    dx_q, h_agg_q = f(radial, g.h, g.x @ q.T, g._replace(v=g.v @ q.T))
    # f32 round-off of rotated coordinates, relative to the largest entry
    np.testing.assert_allclose(np.asarray(h_agg_q), np.asarray(h_agg),
                               atol=1e-4 * float(jnp.max(jnp.abs(h_agg))))
    err = float(jnp.max(jnp.abs(dx_q - dx @ q.T)) / jnp.max(jnp.abs(dx)))
    if det > 0:
        assert err < 1e-4, err
    else:
        assert err > 1e-2, err


@pytest.mark.parametrize("name", ["fast_tfn", "fast_rf", "fast_schnet"])
def test_objective_has_a_nonzero_mmd_term(name):
    """Every plug-in variant hands the trainer its virtual nodes, so the
    objective adds lam_mmd times a nonzero MMD (Eq. 11)."""
    from repro.data.nbody import generate_nbody_dataset
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    kw = dict(n_layers=2, hidden=16, n_virtual=2)
    if name != "fast_rf":
        kw.update(s_dim=8, h_in=1)
    pipe = build_pipeline(name, jax.random.PRNGKey(0),
                          train_cfg=TrainConfig(lam_mmd=0.03), **kw)
    batch = pipe.make_batches(generate_nbody_dataset(2, n_nodes=12, seed=0),
                              2, with_layout=False)[0]
    params, opt_state, m = pipe.train_step(
        pipe.params, pipe.opt.init(pipe.params), batch,
        jax.random.PRNGKey(1))
    assert "mmd" in m and abs(float(m["mmd"])) > 0, m
    np.testing.assert_allclose(float(m["loss"]),
                               float(m["mse"]) + 0.03 * float(m["mmd"]),
                               rtol=1e-6)


def test_train_step_dispatches_both_fused_pathways():
    """``build_pipeline("fast_tfn", use_kernel=True)`` -> ``make_batches``
    (layout-carrying) -> ``train_step``: the TFN edge kernel and the
    virtual kernel in every layer, no jnp edge pathway."""
    from repro.data.fluid import generate_fluid_dataset
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    pipe = build_pipeline("fast_tfn", jax.random.PRNGKey(0),
                          train_cfg=TrainConfig(lam_mmd=0.03), n_layers=2,
                          hidden=16, h_in=1, n_virtual=3, s_dim=16,
                          rbf_cutoff=0.2, use_kernel=True)
    data = generate_fluid_dataset(2, n_particles=64)
    batch = pipe.make_batches(data, 2, r=0.2)[0]
    assert batch.layout is not None
    mp.reset_dispatch_counts()
    _, _, m = pipe.train_step(pipe.params, pipe.opt.init(pipe.params),
                              batch, jax.random.PRNGKey(1))
    counts = pipe.dispatch_report()["counts"]
    assert counts.get("tfn_edge_kernel", 0) > 0, counts
    assert counts.get("tfn_edge_jnp", 0) == 0, counts
    assert counts.get("virtual_kernel", 0) > 0, counts
    assert np.isfinite(float(m["loss"])) and float(m["mmd"]) != 0


def test_layout_free_graphs_take_the_jnp_path():
    """Without a layout the fused kernel has nothing to walk: the jnp path
    runs, and says so."""
    g = _graph()
    mp.reset_dispatch_counts()
    tfn.tfn_edge_pathway(_radial(), g.h, g.x, g, _cfg())
    counts = mp.dispatch_counts()
    assert counts == {"tfn_edge_jnp": 1}, counts
