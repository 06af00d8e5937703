"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; multi-device tests spawn subprocesses."""
import os
import sys

import jax
import numpy as np
import pytest

# repo root (for ``import benchmarks``) — PYTHONPATH=src covers ``repro`` only
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)



def _full_count(em_b, block_rwin, block_e):
    import jax.numpy as jnp

    return jnp.full((1,), block_rwin.shape[0], jnp.int32)


def _assert_bitwise(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a.reshape(-1).view(np.uint8),
                                      b.reshape(-1).view(np.uint8))


@pytest.fixture()
def walks_agree():
    """``check(fn)``: asserts that ``fn()`` gives, bit for bit, the same
    leaves with the edge kernels walking their live prefix and with every
    pass forced to walk the whole static grid (the live count replaced by
    the block count); returns the live walk's result.  Jit caches are
    cleared around each walk, so no trace of one serves the other."""
    from repro.kernels import edge_message

    def check(fn):
        jax.clear_caches()
        live = jax.device_get(fn())
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(edge_message, "live_block_count", _full_count)
            jax.clear_caches()
            try:
                full = jax.device_get(fn())
            finally:
                jax.clear_caches()
        _assert_bitwise(live, full)
        return live

    return check
