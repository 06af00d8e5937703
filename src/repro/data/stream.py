"""Streaming data plane: async batch/layout prefetch behind one iterator
contract (DESIGN.md §8).

PRs 1–4 made the device-side step fast (fused banded-CSR edge kernel, host
layouts, zero trace-time regroups); at Water-3D/Fluid113K scale the
bottleneck is then the *host*: the eager loader built every radius graph
and banded layout serially up front, ``fit`` walked Python lists, and every
run re-derived layouts from scratch.  :class:`BatchStream` replaces the
eager list with a re-iterable stream:

* **one iterator contract** — ``iter(stream)`` yields one epoch of
  fixed-shape batches (``GraphBatch``, or ``ShardedBatch`` on the mesh
  path).  ``fit`` re-iterates per epoch; plain lists satisfy the same
  contract, so every consumer of ``dataset_to_batches`` keeps working and
  ``dataset_to_batches`` itself is now a materialize-the-stream shim;
* **background prep** — per-sample ``sample_to_arrays`` + ``attach_layout``
  (mesh: per-batch ``partition_sample`` + ``stack_partitions_host``) run in
  worker threads behind a bounded queue, so host prep overlaps step
  compute (the jitted step releases the GIL while XLA runs);
* **double-buffered device transfer** — the consumer converts batch k+1 to
  device arrays (``jnp.asarray`` dispatches asynchronously) while batch k
  trains, so H2D overlaps compute as well;
* **per-epoch reshuffle** — off by default (epochs replay the eager order,
  parity-pinned); ``reshuffle_each_epoch=True`` keys a fresh permutation
  per epoch from ``(shuffle_seed, epoch)``;
* **layout cache** — ``cache_dir`` persists banded layouts to disk
  (``data.layout_cache``): warm runs load instead of rebuilding, counted
  by telemetry and CI-gated (``kernel_bench --gate-input-pipeline``);
* **spans** — each piece of work records a ``jax.profiler.TraceAnnotation``
  on the thread that does it, so a profile shows what the host was doing
  while the device waited: ``stream.prepare`` (the per-sample graphs and
  layouts, once), ``stream.collate`` (one host batch, on the producer),
  ``stream.queue_wait`` (the consumer blocked on the producer) and
  ``stream.to_device`` (one batch's device transfer, once per yielded
  batch).

Parity guarantee (tested in ``tests/test_stream.py`` /
``tests/test_distributed.py``): with ``reshuffle_each_epoch=False`` every
epoch yields bit-identical batches in the same order as the eager
``dataset_to_batches`` list (resp. the eager mesh ``make_batches`` list) at
the same ``shuffle_seed`` — streamed ``fit`` reproduces the list-of-batches
per-step losses exactly.
"""
from __future__ import annotations

import queue as queue_lib
import threading
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

DEFAULT_PREFETCH = 2  # bounded-queue depth (host batches ahead of consume)
DEFAULT_WORKERS = 4  # per-sample / per-batch build threads

_SHARED_POOL: ThreadPoolExecutor | None = None
_SHARED_POOL_LOCK = threading.Lock()


def shared_worker_pool(max_workers: int = DEFAULT_WORKERS) -> ThreadPoolExecutor:
    """The process-wide stream worker pool (lazily created, daemonized).

    `BatchStream` epochs spin transient executors (their lifetime is one
    epoch); long-lived consumers — the rollout engine's asynchronous
    Verlet rebuilds (DESIGN.md §10) — share this pool instead, so
    concurrent rollouts don't each spawn threads and host rebuild work is
    capped at the same worker budget as the data plane.
    """
    global _SHARED_POOL
    with _SHARED_POOL_LOCK:
        if _SHARED_POOL is None or getattr(_SHARED_POOL, "_shutdown", False):
            _SHARED_POOL = ThreadPoolExecutor(
                max_workers=max_workers, thread_name_prefix="repro-stream")
        return _SHARED_POOL


_END = object()  # producer → consumer: epoch exhausted


class _Failure:
    """Producer-side exception, re-raised on the consumer thread."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def _put(q: queue_lib.Queue, item, stop: threading.Event) -> bool:
    """Bounded put that gives up when the consumer abandoned the epoch."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue_lib.Full:
            continue
    return False


class BatchStream:
    """Re-iterable stream of fixed-shape training batches.

    Single-device mode (``n_shards=None``) yields
    :class:`~repro.data.loader.GraphBatch`; mesh mode (``n_shards=D``)
    yields :class:`~repro.distributed.dist_egnn.ShardedBatch` built via
    ``partition_sample`` (strategy = ``partition``) — trailing samples
    short of a full batch are dropped there (the shard_map program carries
    no sample mask), mask-padded into a final partial batch otherwise.

    Random access for legacy callers: ``len(stream)`` is the epoch batch
    count, ``stream[i]`` / ``stream[a:b]`` index the materialized eager
    list (built once, cached), ``stream.materialize()`` returns it whole.
    Iteration does **not** materialize — epochs stream through the bounded
    queue with ``prefetch`` host batches in flight; ``prefetch=0`` or
    ``num_workers=0`` degrades to fully synchronous iteration (no
    threads), used by :func:`~repro.data.loader.dataset_to_batches`.
    """

    def __init__(
        self,
        samples: Sequence,
        batch_size: int,
        *,
        r: float = np.inf,
        drop_rate: float = 0.0,
        edge_cap: Optional[int] = None,
        shuffle_seed: Optional[int] = None,
        reshuffle_each_epoch: bool = False,
        with_layout: bool = True,
        drop_last: bool = False,
        cache_dir: Optional[str] = None,
        prefetch: int = DEFAULT_PREFETCH,
        num_workers: int = DEFAULT_WORKERS,
        block_e: Optional[int] = None,
        n_shards: Optional[int] = None,
        partition: str = "random",
        mesh=None,
        process_sharded: Optional[bool] = None,
    ):
        self._samples = list(samples)
        self.batch_size = int(batch_size)
        self.r = r
        self.drop_rate = drop_rate
        self.edge_cap = edge_cap
        self.shuffle_seed = shuffle_seed
        self.reshuffle_each_epoch = bool(reshuffle_each_epoch)
        self.with_layout = with_layout
        self.drop_last = bool(drop_last) or n_shards is not None
        self.prefetch = int(prefetch)
        self.num_workers = int(num_workers)
        self.block_e = block_e
        self.n_shards = n_shards
        self.partition = partition
        self.mesh = mesh
        # multi-process mesh mode (DESIGN.md §11): each host builds only
        # its own contiguous block of graph shards; the device convert
        # assembles the global array from the per-process local rows.
        # Defaults on exactly when the jax runtime is multi-process.
        self._shard_range = None
        if n_shards is not None:
            import jax

            if process_sharded is None:
                process_sharded = jax.process_count() > 1
            if process_sharded and jax.process_count() > 1:
                from repro.distributed.sharding import process_shard_range

                if mesh is None:
                    raise ValueError(
                        "BatchStream: process-sharded mode needs the mesh "
                        "(global-array assembly is sharding-aware) — pass "
                        "mesh=... or build via Pipeline.make_batches")
                if edge_cap is None:
                    raise ValueError(
                        "BatchStream: process-sharded mode needs an explicit "
                        "edge_cap — the default capacity is a max over all "
                        "shards' edge counts, which a host building only its "
                        "own shards cannot compute consistently")
                self._shard_range = process_shard_range(n_shards)
        if cache_dir is not None:
            from repro.data.layout_cache import LayoutCache

            self._cache = LayoutCache(cache_dir)
        else:
            self._cache = None
        self._lock = threading.Lock()
        self._epoch = 0  # epochs handed out by __iter__ (reshuffle key)
        self._prepared = None  # single-device: per-sample padded+layout dicts
        self._host_cache = None  # mesh: base-order host batches
        self._host_cache_order = None
        self._materialized = None
        self._warned_drop = False

    # ------------------------------------------------------------ contract
    def __len__(self) -> int:
        n = len(self._samples)
        full, rem = divmod(n, self.batch_size)
        return full + (1 if rem and not self.drop_last else 0)

    def __getitem__(self, i):
        return self.materialize()[i]

    def __iter__(self):
        with self._lock:
            epoch = self._epoch
            self._epoch += 1
        order = self._order(epoch)
        self._warn_dropped()
        if self.prefetch <= 0 or self.num_workers <= 0:
            return (self._to_device(h) for h in self._host_batches(order))
        return self._async_iter(order)

    def materialize(self) -> list:
        """The eager list view: one base-order epoch, built synchronously
        in the calling thread and cached — what ``dataset_to_batches``
        returns.  Identical batches to iteration (same build functions,
        same order)."""
        if self._materialized is None:
            self._warn_dropped()
            self._materialized = [self._to_device(h)
                                  for h in self._host_batches(self._order(None))]
        return self._materialized

    # ------------------------------------------------------------ ordering
    def _order(self, epoch: Optional[int]) -> np.ndarray:
        """Sample permutation for one epoch.  ``epoch=None`` or reshuffle
        off → the eager order (``shuffle_seed`` applied once — the exact
        permutation ``rng.shuffle(arrays)`` produced in the old loader);
        reshuffle on → keyed by ``(shuffle_seed, epoch)``."""
        idx = np.arange(len(self._samples))
        if self.reshuffle_each_epoch and epoch is not None:
            np.random.default_rng((self.shuffle_seed or 0, int(epoch))
                                  ).shuffle(idx)
        elif self.shuffle_seed is not None:
            np.random.default_rng(self.shuffle_seed).shuffle(idx)
        return idx

    def _warn_dropped(self) -> None:
        rem = len(self._samples) % self.batch_size
        if not rem or not self.drop_last or self._warned_drop:
            return
        self._warned_drop = True
        where = (f"mesh n_shards={self.n_shards}; the sharded program has "
                 f"no sample mask" if self.n_shards is not None
                 else "drop_last=True")
        warnings.warn(
            f"BatchStream: dropping the trailing {rem} samples "
            f"({where}, batch_size={self.batch_size})", stacklevel=3)

    # ----------------------------------------------------- host batch build
    def _host_batches(self, order: np.ndarray):
        """Generator of host (numpy) batches for one epoch, in order."""
        if self.n_shards is not None:
            yield from self._host_batches_mesh(order)
        else:
            yield from self._host_batches_single(order)

    def _host_batches_single(self, order):
        from repro.data.loader import collate_host

        prepared = self._ensure_prepared()
        if not prepared:
            return
        bs, n = self.batch_size, len(prepared)
        for i in range(0, n - bs + 1, bs):
            with TraceAnnotation("stream.collate"):
                host = collate_host([prepared[j] for j in order[i : i + bs]])
            yield host
        rem = n % bs
        if rem and not self.drop_last:
            with TraceAnnotation("stream.collate"):
                host = collate_host([prepared[j] for j in order[n - rem :]],
                                    pad_to=bs)
            yield host

    def _ensure_prepared(self) -> list:
        """Per-sample padded (+ layout-attached) array dicts at the shared
        dataset capacities — built once (worker-parallel), reused by every
        epoch; re-batching an epoch is then a cheap numpy collate."""
        with self._lock:
            if self._prepared is not None:
                return self._prepared
            with TraceAnnotation("stream.prepare"):
                self._prepared = self._prepare()
            return self._prepared

    def _prepare(self) -> list:
        from repro.data.loader import (attach_layout, repad_arrays,
                                       sample_h, sample_to_arrays)

        def build(s):
            return sample_to_arrays(s.x0, s.v0, sample_h(s), s.x1,
                                    r=self.r, drop_rate=self.drop_rate,
                                    edge_cap=self.edge_cap)

        arrays = self._pmap(build, self._samples)
        if arrays:
            n_cap = max(a["x"].shape[0] for a in arrays)
            e_cap = self.edge_cap or max(a["senders"].shape[0]
                                         for a in arrays)
            arrays = [a if a["x"].shape[0] == n_cap
                      and a["senders"].shape[0] == e_cap
                      else repad_arrays(a, n_cap, e_cap) for a in arrays]
            if self.with_layout:
                attach = lambda a: attach_layout(a, block_e=self.block_e,
                                                 cache=self._cache)
                arrays = self._pmap(attach, arrays)
        return arrays

    def _host_batches_mesh(self, order):
        """Mesh epochs build per-batch (capacities are per batch, so no
        global capacity pass): a sliding window of worker-built batches
        keeps ≤ ``num_workers`` partitions in flight.  With reshuffle off
        the host batches are cached after the first full epoch — later
        epochs only re-stack onto the device."""
        key = tuple(int(i) for i in order)
        with self._lock:
            if self._host_cache is not None and self._host_cache_order == key:
                cached = list(self._host_cache)
            else:
                cached = None
        if cached is not None:
            yield from cached
            return

        from repro.data.loader import sample_h
        from repro.data.partition import partition_sample
        from repro.distributed.dist_egnn import stack_partitions_host

        def build(idxs):
            # shard_range: process-local rows only (the global assignment
            # inside partition_sample is deterministic in the seed, so
            # every host agrees on membership)
            with TraceAnnotation("stream.collate"):
                pgs = [partition_sample(s.x0, s.v0, sample_h(s), s.x1,
                                        d=self.n_shards, r=self.r,
                                        strategy=self.partition,
                                        drop_rate=self.drop_rate, seed=j,
                                        e_cap=self.edge_cap,
                                        layout_cache=self._cache,
                                        shard_range=self._shard_range)
                       for j, s in enumerate(self._samples[i] for i in idxs)]
                return stack_partitions_host(pgs, layout_cache=self._cache)

        bs, n = self.batch_size, len(order)
        slices = [order[i : i + bs] for i in range(0, n - bs + 1, bs)]
        built = []
        if self.num_workers > 1 and len(slices) > 1:
            window = max(2, self.num_workers)
            with ThreadPoolExecutor(max_workers=self.num_workers) as ex:
                pending = deque()
                it = iter(slices)
                exhausted = False
                while pending or not exhausted:
                    while not exhausted and len(pending) < window:
                        try:
                            pending.append(ex.submit(build, next(it)))
                        except StopIteration:
                            exhausted = True
                    if not pending:
                        break
                    host = pending.popleft().result()
                    built.append(host)
                    yield host
        else:
            for sl in slices:
                host = build(sl)
                built.append(host)
                yield host
        if not self.reshuffle_each_epoch and len(built) == len(slices):
            with self._lock:
                self._host_cache, self._host_cache_order = built, key

    def _pmap(self, fn, items: list) -> list:
        """Order-preserving worker-thread map (serial under 2 items or
        ``num_workers <= 1``)."""
        if self.num_workers > 1 and len(items) > 1:
            with ThreadPoolExecutor(max_workers=self.num_workers) as ex:
                return list(ex.map(fn, items))
        return [fn(x) for x in items]

    # ------------------------------------------------------- device convert
    def _to_device(self, host):
        with TraceAnnotation("stream.to_device"):
            if self.n_shards is not None:
                if self.mesh is not None:
                    from repro.distributed.sharding import (
                        sharded_batch_from_process_local)

                    return sharded_batch_from_process_local(self.mesh, host)
                from repro.distributed.dist_egnn import (
                    sharded_batch_to_device)

                return sharded_batch_to_device(host)
            from repro.data.loader import batch_to_device

            return batch_to_device(host)

    # ---------------------------------------------------------- async epoch
    def _async_iter(self, order: np.ndarray):
        q = queue_lib.Queue(maxsize=max(1, self.prefetch))
        stop = threading.Event()

        def produce():
            try:
                for host in self._host_batches(order):
                    if not _put(q, host, stop):
                        return
                _put(q, _END, stop)
            except BaseException as e:  # re-raised consumer-side
                _put(q, _Failure(e), stop)

        thread = threading.Thread(target=produce, daemon=True,
                                  name="BatchStream-producer")

        def gen():
            # start the producer lazily: an iterator that is never advanced
            # must not leak a thread (its finally below would never run)
            thread.start()
            buf = deque()  # device-side double buffer (one batch in flight)
            try:
                while True:
                    with TraceAnnotation("stream.queue_wait"):
                        item = q.get()
                    if item is _END:
                        break
                    if isinstance(item, _Failure):
                        raise item.exc
                    buf.append(self._to_device(item))
                    if len(buf) > 1:
                        yield buf.popleft()
                while buf:
                    yield buf.popleft()
            finally:
                stop.set()
                while True:  # unblock a producer stuck on a full queue
                    try:
                        q.get_nowait()
                    except queue_lib.Empty:
                        break

        return gen()
