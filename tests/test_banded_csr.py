"""Banded-CSR layout: host (numpy) builder ↔ trace-time (jnp) regrouping
parity, layout invariants, and the VMEM-budget eligibility envelope."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.core.mlp import init_mlp
from repro.data.radius_graph import banded_csr_layout, sort_edges_by_receiver
from repro.kernels.edge_message import (banded_layout, layout_capacity,
                                        pick_windows)


def _random_edges(n, e, seed=0, masked=True):
    rng = np.random.default_rng(seed)
    snd = rng.integers(0, n, e).astype(np.int32)
    rcv = rng.integers(0, n, e).astype(np.int32)
    snd, rcv = sort_edges_by_receiver(snd, rcv)
    em = ((rng.random(e) > 0.2).astype(np.float32) if masked
          else np.ones(e, np.float32))
    return snd, rcv, em


@pytest.mark.parametrize("n,e,block_e", [(100, 400, 32), (1000, 3000, 64),
                                         (8192, 10000, 128)])
def test_host_layout_matches_trace_layout(n, e, block_e):
    """The data layer's numpy pass, the kernel's jnp regrouping and the
    rollout's device build use the same stable grouping, so they must
    agree slot-for-slot.  Masked slots, the random fifth and a tail of
    ``0 -> 0`` pad slots, are placed in no band: only live edges fill
    the blocks before the last band's end, every one of those blocks
    holds one or zeroes an empty receiver window, and the derived live
    count ends there."""
    from repro.data.cell_list import device_banded_layout
    from repro.kernels.edge_message import live_block_count

    snd, rcv, em = _random_edges(n, e, seed=n)
    pad = np.zeros(e // 4, np.int32)
    snd, rcv = np.concatenate([snd, pad]), np.concatenate([rcv, pad])
    em = np.concatenate([em, pad.astype(np.float32)])
    host = banded_csr_layout(snd, rcv, n, edge_mask=em, block_e=block_e)
    window, swindow, n_pad = pick_windows(n)
    assert (host.window, host.swindow, host.n_pad) == (window, swindow, n_pad)

    snd_l, rcv_l, em_b, rwin, swin, nb = banded_layout(
        jnp.asarray(snd), jnp.asarray(rcv), jnp.asarray(em),
        n_pad=n_pad, window=window, swindow=swindow, block_e=block_e)
    dev = device_banded_layout(jnp.asarray(snd), jnp.asarray(rcv),
                               jnp.asarray(em), n_nodes=n, block_e=block_e)
    assert nb == host.block_rwin.size
    want = (host.edge_mask, host.block_rwin, host.block_swin)
    for got in ((em_b, rwin, swin),
                (dev.edge_mask, dev.block_rwin, dev.block_swin)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(dev.senders), host.senders)
    np.testing.assert_array_equal(np.asarray(dev.receivers), host.receivers)
    np.testing.assert_array_equal(np.asarray(snd_l), host.senders % swindow)
    np.testing.assert_array_equal(np.asarray(rcv_l), host.receivers % window)

    # masked slots unbanded: the live edges and the blocks before the end
    end = int(host.window_offsets[-1])
    assert int((host.edge_mask[:end] > 0).sum()) == int((em > 0).sum())
    assert not host.edge_mask[end:].any()
    holds = (host.edge_mask[:end].reshape(-1, block_e) > 0).any(axis=1)
    opens = np.diff(host.block_rwin[:end // block_e], prepend=-1) != 0
    assert (holds | opens).all()
    n_live = live_block_count(jnp.asarray(host.edge_mask),
                              jnp.asarray(host.block_rwin), block_e)
    assert n_live.shape == (1,) and n_live.dtype == jnp.int32
    assert int(n_live[0]) == end // block_e < nb


@pytest.mark.parametrize("n,e", [(300, 900), (5000, 20000)])
def test_layout_invariants(n, e):
    """Every live edge sits in a block whose window coordinates contain
    both its endpoints; every receiver window owns ≥ 1 block; blocks of a
    window are contiguous (the kernel's init/normalise contract)."""
    snd, rcv, em = _random_edges(n, e, seed=e)
    L = banded_csr_layout(snd, rcv, n, edge_mask=em)
    be = L.block_e
    nb = L.block_rwin.size
    assert nb * be == L.senders.size
    for b in range(nb):
        sl = slice(b * be, (b + 1) * be)
        live = L.edge_mask[sl] > 0
        if live.any():
            r = L.receivers[sl][live]
            s = L.senders[sl][live]
            assert (r // L.window == L.block_rwin[b]).all()
            assert (s // L.swindow == L.block_swin[b]).all()
    nw = L.n_pad // L.window
    assert sorted(set(L.block_rwin.tolist())) == list(range(nw))
    # contiguity: receiver-window ids are non-decreasing over blocks
    assert (np.diff(L.block_rwin) >= 0).all()
    # conservation: no live edge lost or duplicated
    assert int((L.edge_mask > 0).sum()) == int((em > 0).sum())
    # per-window CSR offsets cover all blocks
    assert L.window_offsets[0] == 0
    assert L.window_offsets[-1] <= L.senders.size
    assert (np.diff(L.window_offsets) >= 0).all()


def test_cache_serves_no_layout_of_an_older_format(tmp_path, monkeypatch):
    """An entry cached under an older ``_FORMAT_VERSION`` (built by an
    older banding rule) is never served: the layout is built again.  The
    live-block telemetry counts every layout handed out, built or
    cached."""
    from repro.data import layout_cache as lc

    n, e = 600, 2000
    snd, rcv, em = _random_edges(n, e, seed=5)
    cache = lc.LayoutCache(tmp_path)
    version = lc._FORMAT_VERSION
    with monkeypatch.context() as old:
        old.setattr(lc, "_FORMAT_VERSION", version - 1)
        lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    lc.reset_cache_stats()
    mp.reset_dispatch_counts()
    lay = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    assert lc.cache_stats()["hits"] == 0 and lc.cache_stats()["builds"] == 1
    again = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    assert lc.cache_stats()["hits"] == 1 and lc.cache_stats()["builds"] == 1
    for f in ("senders", "edge_mask", "block_rwin", "block_swin"):
        np.testing.assert_array_equal(getattr(again, f), getattr(lay, f))
    c = mp.dispatch_counts()
    live = int(lay.window_offsets[-1]) // lay.block_e
    assert c["edge_blocks_live"] == 2 * live
    assert c["edge_blocks_walked"] == 2 * lay.block_rwin.size > 2 * live


def test_layout_capacity_bound():
    """Used slots never exceed the static capacity bound."""
    for n, e, seed in [(128, 50, 0), (4096, 100, 1), (9000, 40000, 2)]:
        snd, rcv, em = _random_edges(n, e, seed=seed, masked=False)
        window, swindow, n_pad = pick_windows(n)
        nw, nsw = n_pad // window, n_pad // swindow
        L = banded_csr_layout(snd, rcv, n, edge_mask=em)
        assert L.senders.size == layout_capacity(e, nw, nsw, L.block_e)


def test_pick_windows_policy():
    """Small graphs degenerate to one window; large graphs saturate the
    defaults; window always divides swindow divides n_pad."""
    for n in [1, 33, 128, 600, 4096, 4097, 8192, 65536, 113000]:
        w, sw, n_pad = pick_windows(n)
        assert sw % w == 0 and n_pad % sw == 0 and n_pad >= n
    assert pick_windows(8192) == (512, 2048, 8192)
    assert pick_windows(65536) == (512, 2048, 65536)
    assert pick_windows(100)[:2] == (128, 128)


@pytest.mark.parametrize("n", [8192, 65536, 113000])
def test_kernel_eligible_at_paper_scales(n):
    """The tentpole acceptance criterion: the fused path is eligible at
    Water-3D (8K) and Fluid113K scale — the VMEM budget is constant in N."""
    spec = mp.EdgeSpec(coord_clamp=100.0)
    hid = 64
    lp = {"phi1": init_mlp(jax.random.PRNGKey(0), [2 * hid + 1, hid, hid]),
          "gate": init_mlp(jax.random.PRNGKey(1), [hid, hid, 1],
                           final_bias=False)}
    g = make_graph(jnp.zeros((n, 3)), None, jnp.zeros((n, hid)),
                   jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32))
    assert mp.kernel_supported(lp, g, spec)
    assert mp.edge_kernel_vmem_bytes(n, hid, hid, hid) \
        == mp.edge_kernel_vmem_bytes(10 * n, hid, hid, hid)


def test_edge_pathway_precomputed_layout_matches_regroup():
    """A host-built EdgeLayout threaded through edge_pathway produces the
    same fwd/grad as the trace-time regroup path — and the dispatch
    telemetry shows zero regroups (the DESIGN.md §6.6 contract)."""
    from repro.kernels.edge_message import layout_from_host

    n, e, hid = 612, 2391, 32
    snd, rcv, em = _random_edges(n, e, seed=7)
    lay = layout_from_host(banded_csr_layout(snd, rcv, n, edge_mask=em))
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, hid))
    g = make_graph(x, None, h, snd, rcv, edge_mask=em)
    lp = {"phi1": init_mlp(ks[2], [2 * hid + 1, hid, hid]),
          "gate": init_mlp(ks[3], [hid, hid, 1], final_bias=False)}
    spec = mp.EdgeSpec(coord_clamp=100.0)

    mp.reset_dispatch_counts()
    want = jax.jit(lambda lp, h, x: mp.edge_pathway(
        lp, h, x, g, spec, use_kernel=True))(lp, h, x)
    got = jax.jit(lambda lp, h, x: mp.edge_pathway(
        lp, h, x, g, spec, use_kernel=True, layout=lay))(lp, h, x)
    counts = mp.dispatch_counts()
    assert counts.get("edge_layout_host", 0) == 1, counts
    assert counts.get("edge_layout_regroup", 0) == 1, counts  # the want path
    np.testing.assert_allclose(np.asarray(got.dx), np.asarray(want.dx),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.mh), np.asarray(want.mh),
                               atol=1e-5)

    def loss(kw):
        def f(lp, x, h):
            o = mp.edge_pathway(lp, h, x, g, spec, **kw)
            return jnp.sum(o.dx * 0.3) + jnp.sum(o.mh * 0.1)
        return f

    g_re = jax.grad(loss(dict(use_kernel=True)), argnums=(0, 1, 2))(lp, x, h)
    g_ly = jax.grad(loss(dict(use_kernel=True, layout=lay)),
                    argnums=(0, 1, 2))(lp, x, h)
    err = jax.tree.reduce(max, jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), g_re, g_ly))
    assert err < 1e-5, err


def test_edge_pathway_precomputed_layout_vmap_batch():
    """Per-batch-element host layouts under vmap — the DistEGNN usage
    pattern (each shard × batch element carries its own layout arrays)."""
    from repro.kernels.edge_message import layout_from_host

    n, e, hid, B = 260, 700, 16, 3
    rng = np.random.default_rng(3)
    snds, rcvs, lays = [], [], []
    for _ in range(B):
        s, r, _ = _random_edges(n, e, seed=int(rng.integers(1 << 30)),
                                masked=False)
        snds.append(s)
        rcvs.append(r)
        lays.append(layout_from_host(banded_csr_layout(s, r, n)))
    snds, rcvs = jnp.asarray(np.stack(snds)), jnp.asarray(np.stack(rcvs))
    lay_b = jax.tree.map(lambda *a: jnp.stack(a), *lays)
    em = jnp.ones((B, e))
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    xb = jax.random.normal(ks[0], (B, n, 3))
    hb = jax.random.normal(ks[1], (B, n, hid))
    lp = {"phi1": init_mlp(ks[2], [2 * hid + 1, hid, hid]),
          "gate": init_mlp(ks[3], [hid, hid, 1], final_bias=False)}
    spec = mp.EdgeSpec(coord_clamp=100.0)

    def one_k(x, h, s, r, m, lay):
        g = make_graph(x, None, h, s, r, edge_mask=m)
        return mp.edge_pathway(lp, h, x, g, spec, use_kernel=True,
                               layout=lay).dx

    def one_j(x, h, s, r, m):
        g = make_graph(x, None, h, s, r, edge_mask=m)
        return mp.edge_pathway(lp, h, x, g, spec).dx

    dk = jax.jit(jax.vmap(one_k))(xb, hb, snds, rcvs, em, lay_b)
    dj = jax.jit(jax.vmap(one_j))(xb, hb, snds, rcvs, em)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dj), atol=1e-5)


def test_precomputed_layout_rejects_wrong_block_size():
    """A layout built at a different block_e must fail loudly, not silently
    mis-tile."""
    from repro.kernels.edge_message import edge_pathway_fused, layout_from_host

    n, e, hid = 200, 500, 8
    snd, rcv, em = _random_edges(n, e, seed=1, masked=False)
    lay = layout_from_host(banded_csr_layout(snd, rcv, n, block_e=64))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, hid))
    z = jnp.zeros
    with pytest.raises(ValueError, match="block size|block_e"):
        edge_pathway_fused(
            x, h, jnp.asarray(snd), jnp.asarray(rcv), jnp.asarray(em),
            z((hid, hid)), z((hid, hid)), z((1, hid)), z((1, hid)),
            z((hid, hid)), z((1, hid)), z((hid, hid)), z((1, hid)),
            z((hid, 1)), layout=lay)


def test_kernel_ineligible_when_budget_exceeded():
    """Unusually wide hidden dims still fall back to jnp."""
    spec = mp.EdgeSpec(coord_clamp=100.0)
    hid = 4096
    lp = {"phi1": init_mlp(jax.random.PRNGKey(0), [2 * hid + 1, hid, hid]),
          "gate": init_mlp(jax.random.PRNGKey(1), [hid, hid, 1],
                           final_bias=False)}
    g = make_graph(jnp.zeros((512, 3)), None, jnp.zeros((512, hid)),
                   jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32))
    assert not mp.kernel_supported(lp, g, spec)
