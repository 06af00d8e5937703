"""Unit tests for the virtual-node core (init, messages, aggregation, MMD)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from modelcases import HIN, assert_path_taken, forward, model_paths

from repro.core.graph import make_graph
from repro.core.mmd import mmd_loss, rbf_kernel
from repro.core.virtual_nodes import (VirtualState, init_virtual_block,
                                      init_virtual_coords, masked_com,
                                      virtual_aggregate, virtual_aggregate_from_sums,
                                      virtual_global_message, virtual_messages,
                                      virtual_node_sums)
from repro.models.fast_egnn import FastEGNNConfig, fast_egnn_apply, init_fast_egnn


def test_init_at_com():
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 3))
    mask = jnp.ones((10,))
    z = init_virtual_coords(x, mask, 4)
    np.testing.assert_allclose(np.asarray(z), np.tile(np.asarray(x.mean(0)), (4, 1)),
                               rtol=1e-6)
    # padding must not shift the CoM
    xp = jnp.concatenate([x, 100.0 * jnp.ones((5, 3))])
    mp = jnp.concatenate([mask, jnp.zeros(5)])
    z2 = init_virtual_coords(xp, mp, 4)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z), rtol=1e-6)


def test_virtual_global_message_gram():
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 3))
    com = jnp.zeros(3)
    mv = virtual_global_message(z, com)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(z @ z.T), rtol=1e-6)
    assert mv.shape == (3, 3)


def test_ordered_set_channels_differ():
    """Mutual distinctiveness: distinct channels produce distinct messages
    even from identical coordinates (per-channel parameters + S)."""
    n, c, hid = 12, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, hid))
    z = jnp.tile(x.mean(0)[None], (c, 1))  # all channels at the CoM (init state)
    s = jax.random.normal(ks[2], (c, 8))
    vb = init_virtual_block(ks[3], c, hid, 8, hid)
    mv = virtual_global_message(z, x.mean(0))
    msgs = virtual_messages(vb, h, x, VirtualState(z=z, s=s), mv)
    # channel outputs must differ pairwise
    for a in range(c):
        for b in range(a + 1, c):
            assert float(jnp.max(jnp.abs(msgs[:, a] - msgs[:, b]))) > 1e-3


def test_aggregate_from_sums_equals_aggregate():
    n, c, hid = 20, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (n, 3))
    msgs = jax.random.normal(ks[1], (n, c, hid))
    z = jax.random.normal(ks[2], (c, 3))
    s = jax.random.normal(ks[3], (c, 8))
    mask = (jax.random.uniform(ks[4], (n,)) > 0.3).astype(jnp.float32)
    vb = init_virtual_block(jax.random.PRNGKey(4), c, hid, 8, hid)
    vs = VirtualState(z=z, s=s)
    a = virtual_aggregate(vb, x, vs, msgs, mask)
    dz, ms = virtual_node_sums(vb, x, vs, msgs, mask)
    b = virtual_aggregate_from_sums(vb, vs, dz, ms, jnp.sum(mask))
    np.testing.assert_allclose(np.asarray(a.z), np.asarray(b.z), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a.s), np.asarray(b.s), rtol=1e-6)


@pytest.mark.parametrize("name,path", model_paths())
def test_padding_invariance_full_model(name, path):
    """Padded nodes/edges must not change real outputs (SPMD static shapes)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    n, e = 15, 40
    x = jax.random.normal(ks[0], (n, 3))
    v = jax.random.normal(ks[1], (n, 3))
    h = jax.random.normal(ks[2], (n, HIN))
    snd = jax.random.randint(ks[3], (e,), 0, n)
    rcv = jax.random.randint(ks[4], (e,), 0, n)
    g = make_graph(x, v, h, snd, rcv)
    x1, aux1 = forward(name, path, g)

    pad_n, pad_e = 7, 13
    gp = make_graph(
        jnp.concatenate([x, jnp.ones((pad_n, 3)) * 9.0]),
        jnp.concatenate([v, jnp.zeros((pad_n, 3))]),
        jnp.concatenate([h, jnp.zeros((pad_n, HIN))]),
        jnp.concatenate([snd, jnp.zeros(pad_e, jnp.int32)]),
        jnp.concatenate([rcv, jnp.zeros(pad_e, jnp.int32)]),
        node_mask=jnp.concatenate([jnp.ones(n), jnp.zeros(pad_n)]),
        edge_mask=jnp.concatenate([jnp.ones(e), jnp.zeros(pad_e)]),
    )
    assert_path_taken(name, path, gp)
    x2, aux2 = forward(name, path, gp)
    np.testing.assert_allclose(np.asarray(x2[:n]), np.asarray(x1), rtol=2e-4, atol=1e-4)
    if "h" in aux1:
        np.testing.assert_allclose(np.asarray(aux2["h"][:n]), np.asarray(aux1["h"]),
                                   rtol=2e-4, atol=1e-4)
    if "virtual" in aux1:
        np.testing.assert_allclose(np.asarray(aux2["virtual"].z),
                                   np.asarray(aux1["virtual"].z), rtol=2e-4, atol=1e-4)


def test_mmd_terms_signs():
    """First term repels virtual nodes from each other; cross term attracts
    them to the reals (Sec. IV-C discussion)."""
    x = jnp.zeros((10, 3))
    mask = jnp.ones((10,))
    z_far = jnp.array([[10.0, 0, 0], [0, 10.0, 0], [0, 0, 10.0]])
    z_on = jnp.zeros((3, 3))
    assert float(mmd_loss(z_on, x, mask)) < float(mmd_loss(z_far, x, mask)) + 1.0
    # identical virtual nodes maximise the vv term
    z_same = jnp.ones((3, 3))
    k_same = rbf_kernel(z_same, z_same, 1.5)
    np.testing.assert_allclose(np.asarray(k_same), np.ones((3, 3)), rtol=1e-6)


def test_edge_drop_graceful():
    """FastEGNN still runs and stays finite with ALL edges dropped (p=1.0) —
    the Sec. IV-D story; EGNN on an empty graph degenerates to velocity-only."""
    cfg = FastEGNNConfig(n_layers=2, hidden=16, h_in=1, n_virtual=3, s_dim=8)
    params = init_fast_egnn(jax.random.PRNGKey(0), cfg)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    g = make_graph(jax.random.normal(ks[0], (12, 3)),
                   jax.random.normal(ks[1], (12, 3)),
                   jax.random.normal(ks[2], (12, 1)),
                   jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    x, _, vs = fast_egnn_apply(params, cfg, g)
    assert not bool(jnp.any(jnp.isnan(x)))
    # virtual pathway actually moved the coordinates (beyond velocity)
    base = g.x  # with zero edges, real-real term contributes nothing
    assert float(jnp.max(jnp.abs(x - base))) > 1e-4
