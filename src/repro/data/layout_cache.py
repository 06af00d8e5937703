"""On-disk cache of host banded-CSR layouts (DESIGN.md §8.2).

The banded layout pass (``data.radius_graph.banded_csr_layout``) is pure in
its inputs — the padded edge arrays, the padded node count and the band
policy — so a run over the same dataset rebuilds byte-identical layouts
every time.  This module persists them: entries are keyed by a **content
hash** of the padded edge arrays plus the :class:`LayoutMeta` band geometry
the current ``pick_windows`` policy derives, so

* a warm run loads layouts instead of rebuilding them (the CI gate
  ``kernel_bench --gate-input-pipeline`` asserts *zero* builds on a warm
  cache via :func:`cache_stats`);
* any drift — different edge content, a new window policy, a different
  ``block_e`` — changes the key, and entries whose *stored* geometry
  disagrees with the derived one are treated as stale (the same
  ``LayoutMeta`` check ``layout_from_host`` stamps for the kernel's
  dispatch-time guard, applied at load time);
* a corrupt or truncated entry is a miss (rebuild + rewrite), never a
  crash.

Every layout build in the data plane goes through :func:`get_or_build`
(``cache=None`` simply builds), which is what makes the build count a
meaningful telemetry signal rather than an inference from timings.
Writes are atomic (tempfile + ``os.replace``), so the stream's worker
threads — and concurrent runs sharing one cache dir — cannot tear entries.

Multi-*process* runs sharing one cache dir (the process-sharded stream of
DESIGN.md §11 — hosts overlap on entries only when shard ranges collide
or a re-run changes the process count) additionally coordinate through a
**build claim**: the first writer to create ``<key>.claim``
(``O_CREAT|O_EXCL`` — atomic on POSIX and NFS-safe enough for a cache)
owns the build; a loser re-checks the entry once (the owner may already
have finished) and otherwise *builds anyway* — a duplicate build is
wasted work, never a correctness problem (entries are content-addressed,
so both writers produce byte-identical payloads) — counted as
``duplicate_builds`` in :func:`cache_stats` so tests and benchmarks can
assert cross-process dedup actually happened.  Claims are best-effort:
never blocked on, expired after :data:`CLAIM_TTL_S` (a crashed owner
must not wedge the cache), and an existence re-check before ``store``
skips rewriting an entry the owner already landed.
"""
from __future__ import annotations

import hashlib
import os
import tempfile
import threading
import time

import numpy as np

from repro.data.radius_graph import BandedCSR, banded_csr_layout

#: bumped whenever the layout a build produces changes, so that no entry
#: built by an older rule is served (2: masked slots are placed in no band)
_FORMAT_VERSION = 2

#: a claim file older than this is a crashed/stalled owner — steal it
CLAIM_TTL_S = 300.0

# build/hit telemetry (module-level, mirroring message_passing's dispatch
# counters): "the warm run rebuilt nothing" must be counted, not inferred —
# locked, because the stream's worker threads record concurrently
_STATS = {"builds": 0, "hits": 0, "misses": 0, "errors": 0,
          "duplicate_builds": 0}
_STATS_LOCK = threading.Lock()


def cache_stats() -> dict:
    """Snapshot of the layout build/hit counters.  ``builds`` counts every
    actual ``banded_csr_layout`` execution routed through
    :func:`get_or_build` — with or without a cache attached."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_cache_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _record(event: str) -> None:
    with _STATS_LOCK:
        _STATS[event] = _STATS.get(event, 0) + 1


def derive_meta(n_nodes: int, block_e: int):
    """The ``LayoutMeta`` the current window policy assigns an
    ``n_nodes``-padded graph — the geometry a cached entry must match."""
    from repro.kernels.edge_message import LayoutMeta, pick_windows

    window, swindow, n_pad = pick_windows(n_nodes)
    return LayoutMeta(window, swindow, n_pad, block_e)


def layout_key(snd: np.ndarray, rcv: np.ndarray, n_nodes: int, *,
               edge_mask: np.ndarray | None = None,
               block_e: int = 128) -> str:
    """Content hash + band geometry → cache key.

    Hashes the *padded* edge arrays (the exact layout inputs) together with
    the derived :class:`LayoutMeta`, so identical graphs share entries
    across runs and any policy/content drift misses cleanly.
    """
    meta = derive_meta(n_nodes, block_e)
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(snd, np.int32).tobytes())
    h.update(np.ascontiguousarray(rcv, np.int32).tobytes())
    if edge_mask is not None:
        h.update(np.ascontiguousarray(edge_mask, np.float32).tobytes())
    else:
        h.update(b"nomask")
    h.update(f"v{_FORMAT_VERSION}:{n_nodes}:{tuple(meta)}".encode())
    return h.hexdigest()


_ARRAY_FIELDS = ("senders", "receivers", "edge_mask", "block_rwin",
                 "block_swin", "window_offsets")
_SCALAR_FIELDS = ("window", "swindow", "block_e", "n_pad",
                  "sender_band_max", "fill")


class LayoutCache:
    """Directory of ``<content-hash>.npz`` banded-layout entries."""

    def __init__(self, cache_dir: str | os.PathLike):
        self.dir = os.fspath(cache_dir)
        os.makedirs(self.dir, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.npz")

    def load(self, key: str, n_nodes: int, block_e: int) -> BandedCSR | None:
        """Load one entry; ``None`` on miss, staleness or corruption."""
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path) as f:
                fields = {k: f[k] for k in _ARRAY_FIELDS}
                fields.update({k: f[k].item() for k in _SCALAR_FIELDS})
            lay = BandedCSR(**fields)
        except Exception:
            _record("errors")  # corrupt/truncated entry → rebuild, not crash
            return None
        # staleness: the stored band geometry must equal what today's
        # pick_windows policy derives (the layout_from_host meta check,
        # applied at load time) and the capacity must be block-consistent
        from repro.kernels.edge_message import LayoutMeta

        meta = LayoutMeta(lay.window, lay.swindow, lay.n_pad, lay.block_e)
        cap = lay.senders.shape[0]
        if (meta != derive_meta(n_nodes, block_e)
                or cap % max(lay.block_e, 1)
                or lay.block_rwin.shape[0] * lay.block_e != cap):
            _record("errors")
            return None
        return lay

    def claim(self, key: str) -> bool:
        """Try to claim the build of ``key`` (multi-process dedup).

        Returns True when this process now owns the build.  A fresh claim
        by another writer returns False; a claim older than
        :data:`CLAIM_TTL_S` is stolen (unlink + retry once).  Failures
        report ownership — a cache that cannot coordinate degrades to
        every writer building, which is correct, just duplicated.
        """
        path = self._path(key) + ".claim"
        for _ in range(2):
            try:
                fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                with os.fdopen(fd, "w") as f:
                    f.write(f"pid={os.getpid()}\n")
                return True
            except FileExistsError:
                try:
                    if time.time() - os.path.getmtime(path) <= CLAIM_TTL_S:
                        return False
                    os.unlink(path)  # stale: steal and retry the create
                except OSError:
                    return False  # owner raced us (released/refreshed)
            except OSError:
                return True
        return False

    def release(self, key: str) -> None:
        try:
            os.unlink(self._path(key) + ".claim")
        except OSError:
            pass

    def store(self, key: str, lay: BandedCSR,
              overwrite: bool = True) -> None:
        """Atomic write (tempfile + rename) — safe under worker threads and
        concurrent runs; failures degrade to an unsaved entry.

        ``overwrite=False`` leaves an existing entry alone: losers of a
        multi-process build claim pass it so they don't re-land a payload
        the owner already wrote (content-addressed keys ⇒ identical
        bytes; skipping is an optimisation, not a correctness need).
        Repairs of stale/corrupt entries must overwrite (the default).
        """
        if not overwrite and os.path.exists(self._path(key)):
            return
        payload = {k: getattr(lay, k) for k in _ARRAY_FIELDS}
        payload.update({k: np.asarray(getattr(lay, k)) for k in _SCALAR_FIELDS})
        try:
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    np.savez(f, **payload)
                os.replace(tmp, self._path(key))
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        except OSError:
            pass  # a cache that cannot write is a slow cache, not a crash


def get_or_build(cache: LayoutCache | None, snd: np.ndarray, rcv: np.ndarray,
                 n_nodes: int, *, edge_mask: np.ndarray | None = None,
                 block_e: int = 128) -> BandedCSR:
    """The single layout-build entry point of the data plane.

    With a cache: content-hash lookup, stale/corrupt entries rebuilt and
    rewritten.  Without: plain build.  Either way the telemetry counters
    record what happened.

    On a miss the build is claimed (``<key>.claim``, ``O_CREAT|O_EXCL``)
    so concurrent *processes* sharing the cache dir don't all build the
    same entry.  Losing the claim never blocks: the entry is re-checked
    once (the owner may have finished) and otherwise built anyway, with
    the wasted work counted as ``duplicate_builds``.
    """
    lay = _get_or_build(cache, snd, rcv, n_nodes, edge_mask=edge_mask,
                        block_e=block_e)
    from repro.core.message_passing import record_dispatch

    # the edge kernels' live count of this layout (their grid steps that
    # do work) against its static grid
    record_dispatch("edge_blocks_live",
                    int(lay.window_offsets[-1]) // lay.block_e)
    record_dispatch("edge_blocks_walked", lay.block_rwin.shape[0])
    return lay


def _get_or_build(cache, snd, rcv, n_nodes, *, edge_mask, block_e):
    if cache is None:
        _record("builds")
        return banded_csr_layout(snd, rcv, n_nodes, edge_mask=edge_mask,
                                 block_e=block_e)
    key = layout_key(snd, rcv, n_nodes, edge_mask=edge_mask, block_e=block_e)
    lay = cache.load(key, n_nodes, block_e)
    if lay is not None:
        _record("hits")
        return lay
    _record("misses")
    repair = os.path.exists(cache._path(key))  # present but stale/corrupt
    owned = cache.claim(key)
    if not owned:
        lay = cache.load(key, n_nodes, block_e)  # owner may have landed it
        if lay is not None:
            _record("hits")
            return lay
        _record("duplicate_builds")
    _record("builds")
    try:
        lay = banded_csr_layout(snd, rcv, n_nodes, edge_mask=edge_mask,
                                block_e=block_e)
        cache.store(key, lay, overwrite=owned or repair)
    finally:
        if owned:
            cache.release(key)
    return lay
