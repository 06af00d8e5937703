"""Property tests: E(3)/SO(3) equivariance of every geometric model
(Proposition IV.1), permutation equivariance of every model and
permutation invariance of the virtual state — on the jnp path and on the
fused path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st
from modelcases import (HIN, MODELS, SO3_ONLY, VIRTUAL, assert_path_taken,
                        forward, model_paths)

from repro.core.equivariant import apply_e3, random_orthogonal, random_rotation
from repro.core.graph import make_graph
from repro.core.mmd import mmd_loss

N, E = 18, 50
# MPNN reads raw coordinates as features: not equivariant, by design
GEOMETRIC = sorted(n for n in MODELS if n != "mpnn")


def _graph(seed=0):
    k = jax.random.PRNGKey(seed)
    kx, kv, kh, ks, kr = jax.random.split(k, 5)
    return make_graph(
        jax.random.normal(kx, (N, 3)),
        jax.random.normal(kv, (N, 3)),
        jax.random.normal(kh, (N, HIN)),
        jax.random.randint(ks, (E,), 0, N),
        jax.random.randint(kr, (E,), 0, N),
    )


def _permuted(g, perm):
    """``g`` with its real nodes reordered by ``perm`` and its edges
    relabelled to match."""
    inv = jnp.argsort(perm)
    return g._replace(x=g.x[perm], v=g.v[perm], h=g.h[perm],
                      senders=inv[g.senders], receivers=inv[g.receivers])


def _assert_equivariant(got, want):
    """Within 2e-3, and within 1e-4 of the largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    scale = float(np.max(np.abs(got))) + 1e-6
    np.testing.assert_allclose(want / scale, got / scale, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,path", model_paths(GEOMETRIC))
@given(seed=st.integers(0, 100))
@settings(max_examples=8, deadline=None)
def test_e3_equivariance(name, path, seed):
    g = _graph(0)
    assert_path_taken(name, path, g)
    kk = jax.random.PRNGKey(seed)
    rot = random_rotation(kk) if name in SO3_ONLY else random_orthogonal(kk)
    t = jax.random.normal(jax.random.fold_in(kk, 1), (3,)) * 3.0

    x1, _ = forward(name, path, g)
    x2, _ = forward(name, path, g._replace(x=apply_e3(g.x, rot, t), v=g.v @ rot))
    _assert_equivariant(x2, apply_e3(x1, rot, t))


@pytest.mark.parametrize("name,path", model_paths())
def test_node_permutation_equivariance(name, path):
    """Permuting the real nodes, with the edges relabelled to match,
    permutes the predicted coordinates and features the same way."""
    g = _graph(0)
    perm = jax.random.permutation(jax.random.PRNGKey(3), N)
    gp = _permuted(g, perm)
    assert_path_taken(name, path, gp)
    x1, aux1 = forward(name, path, g)
    xp, auxp = forward(name, path, gp)
    np.testing.assert_allclose(np.asarray(xp), np.asarray(x1[perm]),
                               rtol=2e-4, atol=1e-4)
    if "h" in aux1:
        np.testing.assert_allclose(np.asarray(auxp["h"]),
                                   np.asarray(aux1["h"][perm]),
                                   rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("name,path", model_paths(VIRTUAL))
@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_virtual_state_equivariant_and_perm_invariant(name, path, seed):
    """Prop IV.1: Z is E(3)-equivariant AND permutation-invariant w.r.t. X."""
    g = _graph(0)
    assert_path_taken(name, path, g)
    x1, aux1 = forward(name, path, g)
    kk = jax.random.PRNGKey(seed)
    rot = random_rotation(kk) if name in SO3_ONLY else random_orthogonal(kk)
    t = jax.random.normal(jax.random.fold_in(kk, 1), (3,))
    _, aux2 = forward(name, path,
                      g._replace(x=apply_e3(g.x, rot, t), v=g.v @ rot))
    np.testing.assert_allclose(np.asarray(aux2["virtual"].z),
                               np.asarray(apply_e3(aux1["virtual"].z, rot, t)),
                               rtol=2e-3, atol=2e-3)
    # permutation of real nodes leaves Z unchanged
    perm = jax.random.permutation(kk, N)
    xp, auxp = forward(name, path, _permuted(g, perm))
    np.testing.assert_allclose(np.asarray(auxp["virtual"].z),
                               np.asarray(aux1["virtual"].z), rtol=2e-3, atol=2e-3)
    # ... while X' is permutation-equivariant
    np.testing.assert_allclose(np.asarray(xp), np.asarray(x1[perm]),
                               rtol=2e-3, atol=2e-3)


@given(seed=st.integers(0, 1000), sigma=st.floats(0.5, 3.0))
@settings(max_examples=15, deadline=None)
def test_mmd_e3_invariant(seed, sigma):
    kk = jax.random.PRNGKey(seed)
    z = jax.random.normal(kk, (4, 3))
    x = jax.random.normal(jax.random.fold_in(kk, 1), (20, 3))
    mask = jnp.ones((20,))
    rot = random_orthogonal(jax.random.fold_in(kk, 2))
    t = jnp.array([0.3, -1.0, 2.0])
    m1 = mmd_loss(z, x, mask, sigma=sigma)
    m2 = mmd_loss(apply_e3(z, rot, t), apply_e3(x, rot, t), mask, sigma=sigma)
    np.testing.assert_allclose(float(m1), float(m2), rtol=1e-4, atol=1e-5)


def test_mmd_drives_distributedness():
    """Gradient descent on MMD spreads CoM-initialised virtual nodes over the reals.

    Paper-faithful setup: Eq. 2 initialises Z at the CoM of the real nodes
    (never far from the cloud), so the RBF cross-term gradient is live.  The
    MMD objective must (a) decrease, (b) keep the virtual nodes inside the
    point cloud (global distributedness), and (c) push them apart
    (mutual distinctiveness, the k(z_i,z_j) repulsion term).
    """
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 3)) * 2.0
    com = x.mean(0)
    z = com[None, :] + 0.05 * jax.random.normal(jax.random.PRNGKey(1), (3, 3))
    mask = jnp.ones((64,))
    loss = lambda z: mmd_loss(z, x, mask, sigma=1.5)
    l0 = float(loss(z))
    d0 = float(jnp.mean(jnp.linalg.norm(z[:, None] - z[None, :], axis=-1)))
    for _ in range(200):
        z = z - 0.5 * jax.grad(loss)(z)
    assert float(loss(z)) < l0
    # (b) virtual nodes stayed inside the point cloud
    assert float(jnp.max(jnp.linalg.norm(z - com, axis=-1))) < float(
        jnp.max(jnp.linalg.norm(x - com, axis=-1)))
    # (c) mutual distinctiveness: the set spread out from its collapsed init
    d1 = float(jnp.mean(jnp.linalg.norm(z[:, None] - z[None, :], axis=-1)))
    assert d1 > d0
