"""Streaming data plane tests (DESIGN.md §8).

Covers the BatchStream iterator contract (stream ≡ eager list, re-iterable
epochs, per-epoch reshuffle), the streamed-fit-per-step-loss parity
acceptance criterion on the single-device path (the mesh twin lives in
``tests/test_distributed.py``), and the on-disk layout cache (round-trip,
staleness/capacity invalidation, corrupt-entry rebuild, warm-run
zero-rebuild telemetry).
"""
import os

import jax
import numpy as np
import pytest

from repro.data import layout_cache as lc
from repro.data.loader import dataset_to_batches
from repro.data.nbody import generate_nbody_dataset
from repro.data.radius_graph import banded_csr_layout
from repro.data.stream import BatchStream
from repro.pipeline import build_pipeline
from repro.training.trainer import TrainConfig

# hidden deliberately differs from test_pipeline's KW: these tests compile
# fast_egnn programs of their own shapes, so they can run in any order
# without jit-cache hits suppressing the trace-time dispatch telemetry the
# pipeline tests assert on
KW = dict(h_in=1, n_layers=2, hidden=12, n_virtual=2, s_dim=8)


def _data(n_samples=8, n_nodes=24, seed=0):
    return generate_nbody_dataset(n_samples, n_nodes=n_nodes, seed=seed)


def _assert_batches_equal(got, want):
    got, want = list(got), list(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for xa, xb in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ------------------------------------------------------- iterator contract
@pytest.mark.parametrize("with_layout", [True, False])
def test_stream_matches_eager_batches(with_layout):
    """Acceptance criterion: iterating the stream yields bit-identical
    batches, in the same order, as the eager ``dataset_to_batches`` list —
    layout-carrying and layout-free, shuffled and unshuffled, including
    the mask-padded trailing partial batch."""
    data = _data(7)
    for seed in (None, 3):
        eager = dataset_to_batches(data, 3, drop_rate=0.4, shuffle_seed=seed,
                                   with_layout=with_layout)
        stream = BatchStream(data, 3, drop_rate=0.4, shuffle_seed=seed,
                             with_layout=with_layout)
        assert len(stream) == len(eager)
        _assert_batches_equal(iter(stream), eager)
        # indexing materializes the same list
        _assert_batches_equal([stream[i] for i in range(len(stream))], eager)


def test_stream_reiterates_identically():
    """Epochs replay the same order by default (reshuffle off) — the
    reproducibility contract streamed ``fit`` parity rests on."""
    stream = BatchStream(_data(6), 2, shuffle_seed=11)
    _assert_batches_equal(iter(stream), list(iter(stream)))


def test_stream_sync_and_async_agree():
    """prefetch=0 (the shim's synchronous path) and the threaded path
    build identical batches."""
    data = _data(5)
    sync = BatchStream(data, 2, prefetch=0)
    thr = BatchStream(data, 2, prefetch=2, num_workers=3)
    _assert_batches_equal(iter(thr), list(iter(sync)))


def test_reshuffle_each_epoch_varies_order_not_content():
    """Satellite: ``reshuffle_each_epoch`` draws a fresh epoch-keyed
    permutation — batch composition changes across epochs, the underlying
    sample set does not."""
    data = _data(8, n_nodes=12)
    stream = BatchStream(data, 2, shuffle_seed=5, reshuffle_each_epoch=True,
                         with_layout=False)
    e1 = [np.asarray(b.graph.x) for b in iter(stream)]
    e2 = [np.asarray(b.graph.x) for b in iter(stream)]
    assert not all(np.array_equal(a, b) for a, b in zip(e1, e2))
    key = lambda eps: sorted(round(float(x[i].sum()), 5)
                             for x in eps for i in range(x.shape[0]))
    assert key(e1) == key(e2)  # same samples, different grouping


def test_stream_propagates_build_errors():
    class Bad:
        x0 = "not an array"

    stream = BatchStream([Bad()], 1)
    with pytest.raises(Exception):
        list(iter(stream))


def test_stream_spans_on_the_host_plane(tmp_path):
    """Each piece of the stream's work is a profiler span on the host plane,
    on the thread that does it: over two epochs of a threaded stream,
    ``stream.prepare`` once, ``stream.collate`` once per host batch on the
    producer, ``stream.queue_wait`` once per item taken from the queue (the
    batches and each epoch's end) and ``stream.to_device`` once per
    yielded batch, both on the consumer."""
    from jax.profiler import ProfileData

    stream = BatchStream(_data(6), 2, prefetch=2, num_workers=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        yielded = [len(list(iter(stream))) for _ in range(2)]
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    count, threads = {}, {}  # threads: the host lines a span is on
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("stream."):
                    count[e.name] = count.get(e.name, 0) + 1
                    threads.setdefault(e.name, set()).add((plane.name, i))
    assert yielded == [3, 3]
    assert count == {"stream.prepare": 1, "stream.collate": 6,
                     "stream.queue_wait": 8, "stream.to_device": 6}
    assert len(threads["stream.to_device"]) == 1
    assert threads["stream.queue_wait"] == threads["stream.to_device"]
    assert threads["stream.collate"].isdisjoint(threads["stream.to_device"])


# ------------------------------------------------------- streamed fit parity
@pytest.mark.parametrize("use_kernel", [False, True])
def test_streamed_fit_matches_eager_fit(use_kernel):
    """Acceptance criterion (mesh=None): ``fit`` over the stream reproduces
    the list-of-batches per-step losses/history on a fixed seed, on both
    edge-pathway modes."""
    data = _data(7)
    tc = TrainConfig(epochs=3, lam_mmd=0.03, seed=0)

    def run(batch_source):
        pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0),
                              train_cfg=tc, use_kernel=use_kernel, **KW)
        tr = batch_source(pipe, data[:5])
        va = batch_source(pipe, data[5:])
        return pipe.fit(tr, va)

    res_stream = run(lambda p, d: p.make_batches(d, 2))
    res_eager = run(lambda p, d: dataset_to_batches(
        d, 2, with_layout=use_kernel))
    assert len(res_stream.history) == len(res_eager.history)
    for hs, he in zip(res_stream.history, res_eager.history):
        np.testing.assert_allclose(hs["train_loss"], he["train_loss"],
                                   rtol=1e-6)
        np.testing.assert_allclose(hs["val_mse"], he["val_mse"], rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), res_stream.params, res_eager.params)


# ------------------------------------------------------------ layout cache
def _sample_edges(n=40, seed=0):
    from repro.data.loader import sample_h, sample_to_arrays

    s = _data(1, n_nodes=n, seed=seed)[0]
    a = sample_to_arrays(s.x0, s.v0, sample_h(s), s.x1, drop_rate=0.5)
    return a["senders"], a["receivers"], a["edge_mask"], a["x"].shape[0]


def test_layout_cache_roundtrip(tmp_path):
    """Satellite: a cached layout loads back equal to a freshly built one,
    field for field."""
    snd, rcv, em, n = _sample_edges()
    cache = lc.LayoutCache(tmp_path)
    built = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    loaded = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    fresh = banded_csr_layout(snd, rcv, n, edge_mask=em)
    for got in (built, loaded):
        for f in fresh._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(fresh, f)),
                                          err_msg=f)


def test_layout_cache_warm_run_zero_builds(tmp_path):
    """Acceptance criterion: a warm layout cache performs zero host layout
    rebuilds — counted by telemetry, not inferred."""
    data = _data(5)
    lc.reset_cache_stats()
    dataset_to_batches(data, 2, cache_dir=str(tmp_path))
    cold = lc.cache_stats()
    assert cold["builds"] > 0 and cold["hits"] + cold["misses"] > 0
    lc.reset_cache_stats()
    warm = dataset_to_batches(data, 2, cache_dir=str(tmp_path))
    stats = lc.cache_stats()
    assert stats["builds"] == 0, stats
    assert stats["hits"] > 0 and stats["misses"] == 0, stats
    _assert_batches_equal(warm, dataset_to_batches(data, 2))


def test_layout_cache_stale_meta_rebuilds(tmp_path):
    """Satellite: an entry whose stored band geometry disagrees with the
    current ``pick_windows`` policy (LayoutMeta mismatch) is stale — it is
    rebuilt, not served."""
    snd, rcv, em, n = _sample_edges()
    cache = lc.LayoutCache(tmp_path)
    key = lc.layout_key(snd, rcv, n, edge_mask=em, block_e=128)
    good = banded_csr_layout(snd, rcv, n, edge_mask=em)
    # simulate a policy drift: same key, entry recorded at another window
    cache.store(key, good._replace(window=good.window * 2))
    lc.reset_cache_stats()
    got = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    stats = lc.cache_stats()
    assert stats["builds"] == 1 and stats["errors"] == 1, stats
    np.testing.assert_array_equal(got.senders, good.senders)
    # the rebuild repaired the entry: next lookup hits
    lc.reset_cache_stats()
    lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    assert lc.cache_stats()["hits"] == 1


def test_layout_cache_capacity_mismatch_rebuilds(tmp_path):
    """Satellite: an entry whose capacity is inconsistent with its block
    count (truncated/mangled arrays) is rejected and rebuilt."""
    snd, rcv, em, n = _sample_edges()
    cache = lc.LayoutCache(tmp_path)
    key = lc.layout_key(snd, rcv, n, edge_mask=em, block_e=128)
    good = banded_csr_layout(snd, rcv, n, edge_mask=em)
    cache.store(key, good._replace(senders=good.senders[:-7]))
    lc.reset_cache_stats()
    got = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    stats = lc.cache_stats()
    assert stats["builds"] == 1 and stats["errors"] == 1, stats
    assert got.senders.shape == good.senders.shape


def test_layout_cache_corrupt_entry_rebuilds(tmp_path):
    """Satellite: garbage bytes on disk → rebuild, never a crash."""
    snd, rcv, em, n = _sample_edges()
    cache = lc.LayoutCache(tmp_path)
    key = lc.layout_key(snd, rcv, n, edge_mask=em, block_e=128)
    lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    path = cache._path(key)
    with open(path, "wb") as f:
        f.write(b"definitely not an npz")
    lc.reset_cache_stats()
    got = lc.get_or_build(cache, snd, rcv, n, edge_mask=em)
    stats = lc.cache_stats()
    assert stats["builds"] == 1 and stats["errors"] == 1, stats
    fresh = banded_csr_layout(snd, rcv, n, edge_mask=em)
    np.testing.assert_array_equal(got.senders, fresh.senders)


def test_layout_cache_shared_across_streams(tmp_path):
    """The stream wires the cache through ``attach_layout``: a second
    stream over the same data is all hits, and its batches are identical."""
    data = _data(4)
    a = BatchStream(data, 2, cache_dir=str(tmp_path)).materialize()
    lc.reset_cache_stats()
    b = BatchStream(data, 2, cache_dir=str(tmp_path)).materialize()
    stats = lc.cache_stats()
    assert stats["builds"] == 0 and stats["hits"] > 0, stats
    _assert_batches_equal(b, a)
    assert any(f.endswith(".npz") for f in os.listdir(tmp_path))
