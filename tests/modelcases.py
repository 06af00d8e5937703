"""The paper's model registry as a test matrix.

Each registry entry at a small configuration, run on each of its two
paths: ``"jnp"`` (the reference, ``use_kernel=False``) and ``"fused"``
(the Pallas pathways, interpret mode off the TPU).  A fused run hands the
model its graph's host banded layout, as the batches of the training path
do; without one the TFN edge pathway would fall back to jnp.  ``linear``
has no edge pathway, so it runs on the jnp path only.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import message_passing as mp
from repro.data.radius_graph import banded_csr_layout
from repro.kernels.edge_message import layout_from_host
from repro.models.registry import REGISTRY, resolve_model

HIN = 2

MODELS = {
    "linear": {},
    "mpnn": dict(h_in=HIN, n_layers=2, hidden=16),
    "egnn": dict(h_in=HIN, n_layers=2, hidden=16),
    "fast_egnn": dict(h_in=HIN, n_layers=2, hidden=16, n_virtual=3, s_dim=8),
    "rf": dict(n_layers=2, hidden=16),
    "fast_rf": dict(n_layers=2, hidden=16, n_virtual=2),
    "schnet": dict(h_in=HIN, n_layers=2, hidden=16),
    "fast_schnet": dict(h_in=HIN, n_layers=2, hidden=16, n_virtual=2, s_dim=8),
    "tfn": dict(h_in=HIN, n_layers=2, hidden=16),
    "fast_tfn": dict(h_in=HIN, n_layers=2, hidden=16, n_virtual=2, s_dim=8),
}
# TFN's cross-product path is chiral: SO(3) only (like the paper's TFN).
SO3_ONLY = {"tfn", "fast_tfn"}
# the entries with the virtual-node plug-in (FastEGNN's own, and Sec. V's)
VIRTUAL = sorted(n for n, s in REGISTRY.items() if s.has_virtual)


def model_paths(names=None):
    """``(name, path)`` parameters for ``names`` (default: every entry)."""
    names = sorted(MODELS) if names is None else names
    return [pytest.param(n, p, id=f"{n}-{p}") for n in names
            for p in ("jnp", "fused") if n != "linear" or p == "jnp"]


def host_layout(g):
    """The graph's host banded layout, at the default band policy."""
    return layout_from_host(banded_csr_layout(
        np.asarray(g.senders), np.asarray(g.receivers), g.n_nodes,
        edge_mask=np.asarray(g.edge_mask)))


@functools.lru_cache(maxsize=None)
def _model(name, path):
    """``(params, run, jitted run)``: ``run(params, g, layout)`` is the
    entry's ``(x, aux)``; built once a process."""
    cfg, params, apply_full = resolve_model(
        name, jax.random.PRNGKey(1), use_kernel=path == "fused",
        **MODELS[name])

    def run(p, g, layout):
        return apply_full(p, cfg, g, edge_layout=layout)

    return params, run, jax.jit(run)


def forward(name, path, g):
    """``(x, aux)`` of the entry's model on ``g``, along ``path``."""
    params, _, jitted = _model(name, path)
    return jitted(params, g, host_layout(g) if path == "fused" else None)


def assert_path_taken(name, path, g):
    """Traces the entry on ``g`` and checks the edge pathway it dispatched:
    on the fused path the fused edge kernel and never a jnp edge pathway,
    on the jnp path the reverse."""
    params, run, _ = _model(name, path)
    layout = host_layout(g) if path == "fused" else None
    mp.reset_dispatch_counts()
    # a fresh callable: a cached trace would record no dispatch
    jax.eval_shape(lambda p: run(p, g, layout), params)
    c = mp.dispatch_counts()
    fused = c.get("edge_kernel", 0) + c.get("tfn_edge_kernel", 0)
    jnp_ = c.get("edge_jnp", 0) + c.get("tfn_edge_jnp", 0)
    if name == "linear":
        assert fused == jnp_ == 0, c
    elif path == "fused":
        assert fused > 0 and jnp_ == 0, c
    else:
        assert jnp_ > 0 and fused == 0, c
