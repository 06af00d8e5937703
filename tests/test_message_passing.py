"""Substrate tests: aggregate_edges semantics, CSR layout step, pad_edges
truncation policy, and registry-wide jnp ↔ Pallas pathway parity."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from modelcases import HIN, MODELS

from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.data.radius_graph import pad_edges, sort_edges_by_receiver
from repro.models.registry import REGISTRY, make_model

N, E = 18, 50


def _graph(seed=0, csr=False):
    k = jax.random.PRNGKey(seed)
    kx, kv, kh, ks, kr = jax.random.split(k, 5)
    snd = jax.random.randint(ks, (E,), 0, N)
    rcv = jax.random.randint(kr, (E,), 0, N)
    if csr:
        snd_np, rcv_np = sort_edges_by_receiver(np.asarray(snd), np.asarray(rcv))
        snd, rcv = jnp.asarray(snd_np), jnp.asarray(rcv_np)
    return make_graph(
        jax.random.normal(kx, (N, 3)),
        jax.random.normal(kv, (N, 3)),
        jax.random.normal(kh, (N, HIN)),
        snd, rcv,
    )


# ------------------------------------------------------------- aggregation
def test_aggregate_edges_masked_mean():
    g = _graph(1)
    g = g._replace(edge_mask=(jnp.arange(E) % 3 > 0).astype(jnp.float32))
    vals = jax.random.normal(jax.random.PRNGKey(2), (E, 4)) * g.edge_mask[:, None]
    got = mp.aggregate_edges(vals, g)
    want_sum = jax.ops.segment_sum(vals, g.receivers, num_segments=N)
    deg = jax.ops.segment_sum(g.edge_mask, g.receivers, num_segments=N)
    want = want_sum / jnp.maximum(deg, 1.0)[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    got_sum = mp.aggregate_edges(vals, g, normalize=False)
    np.testing.assert_allclose(np.asarray(got_sum), np.asarray(want_sum),
                               rtol=1e-6)


def test_edge_order_invariance():
    """CSR sorting is a layout optimisation: permuting the edge list must
    not change the pathway output (both jnp and kernel paths)."""
    from repro.core.mlp import init_mlp
    g = _graph(3)
    spec = mp.EdgeSpec(coord_clamp=100.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (N, 8))
    lp = {"phi1": init_mlp(jax.random.PRNGKey(5), [17, 16, 16]),
          "gate": init_mlp(jax.random.PRNGKey(6), [16, 16, 1], final_bias=False)}
    perm = jax.random.permutation(jax.random.PRNGKey(7), E)
    gp = g._replace(senders=g.senders[perm], receivers=g.receivers[perm],
                    edge_mask=g.edge_mask[perm])
    for use_kernel in (False, True):
        a = mp.edge_pathway(lp, h, g.x, g, spec, use_kernel=use_kernel)
        b = mp.edge_pathway(lp, h, g.x, gp, spec, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(a.dx), np.asarray(b.dx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a.mh), np.asarray(b.mh),
                                   rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------- CSR layout
def test_sort_edges_by_receiver_csr():
    rng = np.random.default_rng(0)
    snd = rng.integers(0, 30, size=200).astype(np.int32)
    rcv = rng.integers(0, 30, size=200).astype(np.int32)
    s2, r2 = sort_edges_by_receiver(snd, rcv)
    assert np.all(np.diff(r2) >= 0)  # receiver-monotone
    assert set(zip(s2.tolist(), r2.tolist())) == set(zip(snd.tolist(), rcv.tolist()))
    # canonical (receiver, sender) order: within one receiver, senders
    # ascend — the build-order-independent contract the rollout engine's
    # Verlet lists and the host edge drop's tie-break rely on
    # (DESIGN.md §10.2)
    for r in np.unique(r2):
        np.testing.assert_array_equal(s2[r2 == r],
                                      np.sort(snd[rcv == r], kind="stable"))
    # empty input round-trips
    s0, r0 = sort_edges_by_receiver(snd[:0], rcv[:0])
    assert s0.size == 0 and r0.size == 0


def test_pad_edges_truncation_keeps_shortest():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(20, 3)).astype(np.float32)
    snd = rng.integers(0, 20, size=60).astype(np.int32)
    rcv = rng.integers(0, 20, size=60).astype(np.int32)
    d2 = np.sum((x[snd] - x[rcv]) ** 2, axis=-1)
    with pytest.warns(UserWarning, match="truncating"):
        sp, rp, em = pad_edges(snd, rcv, 25, x)
    assert em.sum() == 25
    kept = np.sum((x[sp[:25]] - x[rp[:25]]) ** 2, axis=-1)
    # the kept set is exactly the 25 shortest edges (Sec. VII-B semantics)
    assert np.max(kept) <= np.sort(d2)[24] + 1e-12
    # under capacity: no warning, mask marks the real prefix
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sp, rp, em = pad_edges(snd[:10], rcv[:10], 16, x)
    assert em.sum() == 10 and np.all(sp[10:] == 0)


def test_edge_attr_only_consumed_by_sized_phi1():
    """Graphs carrying edge attributes must not break models whose φ1
    isn't sized for them (only EGNN's spec opts in via use_edge_attr)."""
    g = _graph(2)
    g = g._replace(edge_attr=jnp.ones((E, 2)))
    for name, kw in [("mpnn", dict(h_in=HIN, n_layers=1, hidden=8)),
                     ("schnet", dict(h_in=HIN, n_layers=1, hidden=8)),
                     ("rf", dict(n_layers=1, hidden=8))]:
        cfg, params, apply_full = make_model(name, jax.random.PRNGKey(1), **kw)
        x, _ = apply_full(params, cfg, g)  # must not raise
        assert bool(jnp.all(jnp.isfinite(x))), name
    # EGNN consumes them when configured for it
    cfg, params, apply_full = make_model(
        "egnn", jax.random.PRNGKey(1), h_in=HIN, n_layers=1, hidden=8,
        edge_attr_dim=2)
    x_attr, _ = apply_full(params, cfg, g)
    x_zero, _ = apply_full(params, cfg, g._replace(edge_attr=jnp.zeros((E, 2))))
    assert float(jnp.max(jnp.abs(x_attr - x_zero))) > 1e-6


# ------------------------------------------------- registry-wide parity
def test_registry_covers_overrides():
    assert set(MODELS) == set(REGISTRY)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_registry_kernel_parity(name):
    """Every registry entry: the jnp substrate and the Pallas pathways
    produce identical predictions from identical seeds (spec composition
    guarantees init is unaffected by use_kernel)."""
    g = _graph(0, csr=True)
    cfg_j, params_j, apply_j = make_model(name, jax.random.PRNGKey(1),
                                          **MODELS[name])
    cfg_k, params_k, apply_k = make_model(name, jax.random.PRNGKey(1),
                                          use_kernel=True, **MODELS[name])
    # seed parity: identical parameter trees
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params_j, params_k)
    xj, _ = apply_j(params_j, cfg_j, g)
    xk, _ = apply_k(params_k, cfg_k, g)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xj),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", ["egnn", "fast_egnn", "schnet"])
def test_registry_kernel_grad_parity(name):
    g = _graph(0, csr=True)
    cfg_j, params, apply_j = make_model(name, jax.random.PRNGKey(1),
                                        **MODELS[name])
    cfg_k, _, apply_k = make_model(name, jax.random.PRNGKey(1),
                                   use_kernel=True, **MODELS[name])
    tgt = g.x + 0.1
    loss_j = lambda p: jnp.mean((apply_j(p, cfg_j, g)[0] - tgt) ** 2)
    loss_k = lambda p: jnp.mean((apply_k(p, cfg_k, g)[0] - tgt) ** 2)
    gj = jax.grad(loss_j)(params)
    gk = jax.grad(loss_k)(params)

    def assert_close(a, b):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-3, atol=1e-4)

    jax.tree.map(assert_close, gj, gk)


def test_models_free_of_raw_segment_sum():
    """Acceptance criterion: edge aggregation lives in the substrate only."""
    import pathlib

    import repro.models as models_pkg
    root = pathlib.Path(models_pkg.__file__).parent
    for f in root.glob("*.py"):
        assert "segment_sum" not in f.read_text(), f
