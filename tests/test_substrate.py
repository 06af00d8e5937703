"""Substrate-layer tests: optimizer, checkpoint, losses, radius graph,
data loader, sharding rules, claims-check parser."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.data.radius_graph import radius_graph
from repro.training.checkpoint import restore_checkpoint, save_checkpoint
from repro.training.losses import combined_objective, masked_mse
from repro.training.optim import Adam


# ------------------------------------------------------------------ optimizer
def test_adam_matches_reference_scalar():
    """Single-scalar Adam vs the closed-form first-step update."""
    opt = Adam(lr=0.1, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.asarray(2.0)}
    st_ = opt.init(p)
    g = {"w": jnp.asarray(0.5)}
    p2, st2 = opt.update(g, st_, p)
    # step 1: m̂ = g, v̂ = g² → update = lr·g/(|g|+eps) = lr·sign(g)
    np.testing.assert_allclose(float(p2["w"]), 2.0 - 0.1 * 1.0, rtol=1e-5)
    assert int(st2.step) == 1


def test_adam_grad_clip_bounds_update():
    opt = Adam(lr=1.0, grad_clip=1e-3, weight_decay=0.0)
    p = {"w": jnp.ones((4,))}
    s = opt.init(p)
    huge = {"w": 1e9 * jnp.ones((4,))}
    p2, _ = opt.update(huge, s, p)
    assert np.all(np.isfinite(np.asarray(p2["w"])))


@given(steps=st.integers(2, 10))
@settings(max_examples=5, deadline=None)
def test_adam_descends_quadratic(steps):
    opt = Adam(lr=0.05, weight_decay=0.0)
    p = {"w": jnp.asarray([3.0, -2.0])}
    s = opt.init(p)
    f = lambda p: jnp.sum(p["w"] ** 2)
    l0 = float(f(p))
    for _ in range(steps):
        g = jax.grad(f)(p)
        p, s = opt.update(g, s, p)
    assert float(f(p)) < l0


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)},
            "lst": [jnp.zeros(2), jnp.full((1,), 7.0)]}
    path = os.path.join(tmp_path, "ck.npz")
    save_checkpoint(path, tree, metadata={"step": 42})
    restored, meta = restore_checkpoint(path, tree)
    assert meta["step"] == 42
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                            np.asarray(b)),
                 tree, restored)


def test_checkpoint_structure_mismatch_raises(tmp_path):
    path = os.path.join(tmp_path, "ck.npz")
    save_checkpoint(path, {"a": jnp.zeros(2)})
    with pytest.raises(ValueError):
        restore_checkpoint(path, {"a": jnp.zeros(2), "b": jnp.zeros(3)})


def test_checkpoint_optimizer_state_roundtrip(tmp_path):
    opt = Adam(lr=1e-3)
    p = {"w": jnp.ones((3, 3))}
    s = opt.init(p)
    _, s = opt.update({"w": jnp.full((3, 3), 0.1)}, s, p)
    path = os.path.join(tmp_path, "opt.npz")
    save_checkpoint(path, s._asdict())
    restored, _ = restore_checkpoint(path, s._asdict())
    np.testing.assert_array_equal(np.asarray(restored["m"]["w"]),
                                  np.asarray(s.m["w"]))
    assert int(restored["step"]) == 1


# --------------------------------------------------------------------- losses
def test_masked_mse_ignores_padding():
    pred = jnp.array([[1.0, 0, 0], [99.0, 99, 99]])
    tgt = jnp.zeros((2, 3))
    m_all = masked_mse(pred, tgt, jnp.array([1.0, 1.0]))
    m_masked = masked_mse(pred, tgt, jnp.array([1.0, 0.0]))
    assert float(m_masked) == pytest.approx(1.0 / 3.0)
    assert float(m_all) > float(m_masked)


def test_combined_objective_adds_lambda_mmd():
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 3))
    z = x[:2]
    mask = jnp.ones((10,))
    base, aux0 = combined_objective(x, x, mask, None, lam=0.5)
    tot, aux = combined_objective(x, x, mask, z, lam=0.5)
    assert float(base) == 0.0 and "mmd" not in aux0
    assert float(tot) == pytest.approx(0.5 * float(aux["mmd"]), rel=1e-6)


# --------------------------------------------------------------- radius graph
@given(seed=st.integers(0, 50), r=st.floats(0.2, 1.5))
@settings(max_examples=15, deadline=None)
def test_radius_graph_matches_bruteforce(seed, r):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(40, 3)).astype(np.float32)
    snd, rcv = radius_graph(x, r)
    got = set(zip(snd.tolist(), rcv.tolist()))
    d = np.linalg.norm(x[:, None] - x[None, :], axis=-1)
    want = {(i, j) for i in range(40) for j in range(40)
            if i != j and d[i, j] <= r}
    assert got == want


def test_radius_graph_infinite_is_fully_connected():
    x = np.zeros((5, 3), np.float32)
    snd, rcv = radius_graph(x, np.inf)
    assert snd.size == 5 * 4
    assert np.all(snd != rcv)


# -------------------------------------------------------------- claims parser
def test_claims_check_parser(tmp_path):
    from benchmarks.claims_check import parse
    p = os.path.join(tmp_path, "bench.csv")
    with open(p, "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("table1/nbody/egnn,123.4,mse=0.014;rel_time=1.00\n")
        f.write("table1/nbody/fast_egnn_c3_p0.00,140.0,mse=0.010;rel_time=1.15\n")
    rows = parse(p)
    assert rows["table1/nbody/egnn"]["mse"] == pytest.approx(0.014)
    assert rows["table1/nbody/fast_egnn_c3_p0.00"]["rel_time"] == pytest.approx(1.15)


def test_claims_check_end_to_end(tmp_path):
    from benchmarks import claims_check
    p = os.path.join(tmp_path, "bench.csv")
    with open(p, "w") as f:
        f.write("table1/nbody/egnn,1.0,mse=0.0140;rel_time=1.00\n")
        f.write("table1/nbody/egnn_star,1.0,mse=0.1160;rel_time=0.03\n")
        f.write("table1/nbody/fast_egnn_c3_p0.00,1.0,mse=0.0104;rel_time=1.15\n")
        f.write("table1/nbody/fast_egnn_c3_p1.00,1.0,mse=0.0952;rel_time=0.11\n")
    rc = claims_check.main(["--csv", p])
    assert rc == 0  # paper's Table I orderings hold for this synthetic run
