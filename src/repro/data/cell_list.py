"""Device-resident neighbor search + banded layout build (DESIGN.md §13).

The rollout engine's Verlet rebuilds used to round-trip through the host:
fetch coordinates, numpy ``radius_graph`` + ``banded_csr_layout`` on a
worker thread, re-upload edges and layout.  This module moves the whole
rebuild onto the device as a second jitted program:

- :func:`device_radius_build` — cell-list binning (spatial hash at cell
  size ``r + skin``, one flattened-key argsort, per-cell candidate
  windows of static size ``cell_cap``) and a 27-neighbor-stencil pair
  sweep that emits a padded ``(senders, receivers, edge_mask)`` edge set
  at pinned ``edge_cap``.
- :func:`device_banded_layout` — trace-time mirror of the host
  ``data.radius_graph.banded_csr_layout`` producing a kernel-ready
  :class:`~repro.kernels.edge_message.EdgeLayout` with *global* endpoint
  indices (the same arrays ``layout_from_host`` would upload).

Bitwise-parity contract (the PR-7 schedule-independence argument then
carries over unchanged):

1. The stencil sweep enumerates exactly the pairs the host cell list
   enumerates (any pair within ``r_build`` is in adjacent cells, for any
   binning origin), and the keep predicate ``d² ≤ f32(r_build)²`` is the
   same f32 arithmetic (3-term sum in axis order) the host build and the
   engine's on-device drop mask apply.
2. Over-capacity truncation keeps the ``edge_cap`` lowest edges under
   the ``(d², receiver, sender)`` lexicographic rank — bitwise the host
   ``pad_edges`` rule (stable argsort by d² over canonically sorted
   edges).
3. Kept edges are packed in canonical ``(receiver, sender)`` order with
   zero-filled masked slots — bitwise the host ``sort_edges_by_receiver``
   + ``pad_edges`` output.
4. The layout pass is the same stable band grouping as the host
   ``banded_csr_layout`` at the same (window, swindow, block_e,
   capacity), so every EdgeLayout array matches ``layout_from_host``
   element for element.

Capacity/overflow contract: ``cell_cap`` bounds per-cell occupancy; a
rebuild whose densest cell exceeds it (or whose integer grid would
overflow the flattened int32 key space) raises the ``overflow`` flag
instead of silently dropping neighbors, and the engine falls back to a
host rebuild for that boundary.  PBC is handled upstream: the engine
wraps coordinates into the box before building (``wrap_box``), matching
the host path's semantics (no minimum-image pairs across faces —
DESIGN.md §10).

Pure-jax v1 (sorts + segment lookups, vmap/shard_map-friendly); a Pallas
pair-sweep kernel can replace the candidate materialisation later
without touching the contract.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.edge_message import (
    EdgeLayout, LayoutMeta, layout_capacity, pick_windows,
)

# Headroom multiplier for auto-sized per-cell capacity: rollout densities
# drift, and an overflow costs one host-fallback rebuild (correct but
# slow), so size generously — candidate memory is linear in cell_cap.
DEFAULT_CELL_HEADROOM = 1.5

_CENTER = 13  # flat index of offset (0, 0, 0) in the 3×3×3 stencil
_GRID_LIMIT = float(2 ** 30)  # int32-injectivity bound on Dx·Dy·Dz
_MAX_DIM = 1000.0  # per-axis cell-grid bound: (1000 + 3)³ < 2³⁰


class DeviceBuild(NamedTuple):
    """One device rebuild: padded canonical edges + validity scalars."""

    senders: jnp.ndarray  # (edge_cap,) int32, canonical order, masked = 0
    receivers: jnp.ndarray  # (edge_cap,) int32
    edge_mask: jnp.ndarray  # (edge_cap,) float32
    n_edges: jnp.ndarray  # () int32 — edges built *before* truncation
    max_occupancy: jnp.ndarray  # () int32 — densest cell this rebuild
    overflow: jnp.ndarray  # () bool — cell_cap exceeded or grid too large


def device_radius_build(x, node_mask, *, r_build: float, edge_cap: int,
                        cell_cap: int) -> DeviceBuild:
    """All pairs within ``r_build``, padded to ``edge_cap`` — on device.

    ``x`` is (n, 3) f32 (node-capacity padded), ``node_mask`` (n,) with
    >0 marking real rows.  Masked rows are hashed to unique sentinel
    cells so they never occupy (or overflow) a real bucket.  Output is
    bitwise what the host path emits at the same capacities:
    ``pad_edges(*sort_edges_by_receiver(*radius_graph(x, r_build)),
    edge_cap, x)``.
    """
    n = x.shape[0]
    x = x.astype(jnp.float32)
    rb = jnp.float32(r_build)
    real = node_mask > 0

    # --- spatial hash: flatten 3-D cells into one sortable int32 key ----
    # Cell size is at least r_build but grows with the coordinate extent
    # so Dx·Dy·Dz stays within the int32 key budget for arbitrarily
    # spread-out clouds.  Coarser cells keep the 27-stencil a superset of
    # all pairs within r_build; the exact f32 d² predicate below does the
    # selection, so the emitted edge set is independent of cell size.
    xm = jnp.min(jnp.where(real[:, None], x, jnp.inf), axis=0)
    xM = jnp.max(jnp.where(real[:, None], x, -jnp.inf), axis=0)
    cs = jnp.maximum(rb, jnp.max(xM - xm) / jnp.float32(_MAX_DIM))
    cf = jnp.floor(x / cs)  # (n, 3) f32 cell coords
    mn = jnp.min(jnp.where(real[:, None], cf, jnp.inf), axis=0)
    mx = jnp.max(jnp.where(real[:, None], cf, -jnp.inf), axis=0)
    spans = mx - mn + 3.0  # one ghost cell per face
    grid_ok = ((jnp.isfinite(spans).all()
                & (spans[0] * spans[1] * spans[2] < _GRID_LIMIT))
               # an all-masked shard has no pairs to find — never a reason
               # to fall back to the host
               | ~real.any())
    spans = jnp.where(grid_ok, spans, 3.0)
    d1 = spans[1].astype(jnp.int32)
    d2_ = spans[2].astype(jnp.int32)
    c = jnp.where(grid_ok & real[:, None], cf - mn[None, :] + 1.0, 0.0)
    c = c.astype(jnp.int32)
    key = (c[:, 0] * d1 + c[:, 1]) * d2_ + c[:, 2]
    # unique sentinel keys beyond the real grid for masked rows (real
    # stencil probes stay < grid volume, so no aliasing either way)
    grid_vol = spans[0].astype(jnp.int32) * d1 * d2_
    key = jnp.where(real, key, grid_vol + jnp.arange(n, dtype=jnp.int32))

    order = jnp.argsort(key, stable=True)
    sk = key[order]
    off = jnp.array([-1, 0, 1], jnp.int32)
    off_flat = ((off[:, None, None] * d1 + off[None, :, None]) * d2_
                + off[None, None, :]).reshape(-1)  # (27,)
    probe = key[:, None] + off_flat[None, :]  # (n, 27)
    lo = jnp.searchsorted(sk, probe, side="left")
    hi = jnp.searchsorted(sk, probe, side="right")
    cnt = (hi - lo).astype(jnp.int32)  # (n, 27) bucket sizes

    occ = jnp.max(jnp.where(real, cnt[:, _CENTER], 0))
    overflow = (occ > cell_cap) | ~grid_ok

    # --- candidate sweep: (n, 27, cell_cap) static window per bucket ----
    ar = jnp.arange(cell_cap, dtype=jnp.int32)
    cidx = jnp.clip(lo[:, :, None] + ar[None, None, :], 0, n - 1)
    cand = order[cidx].reshape(n, 27 * cell_cap)  # (n, K) sender candidates
    in_bucket = (ar[None, None, :] < cnt[:, :, None]).reshape(n, -1)
    rcv_i = jnp.arange(n, dtype=jnp.int32)
    valid = (in_bucket
             & (cand != rcv_i[:, None])
             & real[:, None]
             & real[cand])
    diff = x[cand] - x[:, None, :]  # (n, K, 3)
    d2 = jnp.sum(diff * diff, axis=-1)  # f32, axis-order sum = host d²
    valid &= d2 <= rb * rb

    # --- canonical (receiver, sender) order: rows are receiver-major
    # already, so one within-row stable sort by sender finishes it -------
    int_max = jnp.iinfo(jnp.int32).max
    rord = jnp.argsort(jnp.where(valid, cand, int_max), axis=-1, stable=True)
    snd_flat = jnp.take_along_axis(cand, rord, axis=-1).reshape(-1)
    val_flat = jnp.take_along_axis(valid, rord, axis=-1).reshape(-1)
    d2_flat = jnp.take_along_axis(d2, rord, axis=-1).reshape(-1)
    rcv_flat = jnp.broadcast_to(rcv_i[:, None], cand.shape).reshape(-1)

    # --- drop-longest rank under (d², receiver, sender): a stable argsort
    # by d² over the canonical order — bitwise the pad_edges rule --------
    m = snd_flat.shape[0]
    gord = jnp.argsort(jnp.where(val_flat, d2_flat, jnp.inf), stable=True)
    rank = jnp.zeros((m,), jnp.int32).at[gord].set(
        jnp.arange(m, dtype=jnp.int32))
    kept = val_flat & (rank < edge_cap)

    # --- compact kept edges into the first slots, zero-fill the rest ----
    pos = jnp.cumsum(kept) - 1
    pos = jnp.where(kept, pos, m)  # out-of-bounds ⇒ dropped by the scatter
    out_s = jnp.zeros((edge_cap,), jnp.int32).at[pos].set(
        snd_flat, mode="drop")
    out_r = jnp.zeros((edge_cap,), jnp.int32).at[pos].set(
        rcv_flat, mode="drop")
    out_m = jnp.zeros((edge_cap,), jnp.float32).at[pos].set(1.0, mode="drop")
    n_edges = val_flat.sum().astype(jnp.int32)
    return DeviceBuild(out_s, out_r, out_m, n_edges,
                       occ.astype(jnp.int32), overflow)


def device_banded_layout(snd, rcv, em, *, n_nodes: int,
                         window: int | None = None,
                         swindow: int | None = None, block_e: int = 128,
                         capacity: int | None = None) -> EdgeLayout:
    """On-device mirror of ``data.radius_graph.banded_csr_layout``.

    Same stable band grouping, counts, block padding, empty-window fix,
    scatter positions, and block window coords — but emitting *global*
    endpoint indices straight into an :class:`EdgeLayout`, so the result
    is bitwise the arrays ``layout_from_host(banded_csr_layout(...))``
    would have uploaded at the same (window, swindow, block_e, capacity).
    (The trace-time ``kernels.edge_message.banded_layout`` is the
    window-*local* sibling used by the regroup-on-trace path.)
    """
    e = snd.shape[0]
    window, swindow, n_pad = pick_windows(n_nodes, window=window,
                                          swindow=swindow)
    nw, nsw = n_pad // window, n_pad // swindow
    snd = snd.astype(jnp.int32)
    rcv = rcv.astype(jnp.int32)
    em = em.astype(jnp.float32)

    cap = layout_capacity(e, nw, nsw, block_e)
    if capacity is not None:
        assert capacity >= cap, (capacity, cap)
        cap = capacity
    n_blocks = cap // block_e
    # masked slots: a sentinel band past the last, starting at the
    # capacity, whose scatters drop
    band = jnp.where(em != 0, (rcv // window) * nsw + snd // swindow,
                     nw * nsw)
    order = jnp.argsort(band, stable=True)
    bs = band[order]
    counts = jnp.zeros((nw * nsw + 1,), jnp.int32).at[bs].add(1)
    padded = ((counts[:-1] + block_e - 1) // block_e) * block_e
    per_w = padded.reshape(nw, nsw).sum(axis=1)
    padded = (padded.reshape(nw, nsw)
              .at[:, 0].add(jnp.where(per_w == 0, block_e, 0))
              .reshape(-1))
    ends = jnp.cumsum(padded)
    offs = jnp.append(ends - padded, cap)
    gstart = jnp.cumsum(counts) - counts
    pos = offs[bs] + (jnp.arange(e, dtype=jnp.int32) - gstart[bs])
    put = lambda v, dt: jnp.zeros((cap,), dt).at[pos].set(v, mode="drop")
    out_s = put(snd[order], jnp.int32)
    out_r = put(rcv[order], jnp.int32)
    out_m = put(em[order], jnp.float32)
    bfirst = jnp.arange(n_blocks, dtype=jnp.int32) * block_e
    bid = jnp.searchsorted(ends, bfirst, side="right").astype(jnp.int32)
    bid = jnp.where(bfirst < ends[-1], bid, nw * nsw - 1)
    return EdgeLayout(out_s, out_r, out_m, bid // nsw, bid % nsw,
                      meta=LayoutMeta(window, swindow, n_pad, block_e))


# ------------------------------------------------------------- host sizing
def cell_occupancy(x: np.ndarray, r_build: float) -> int:
    """Densest-cell occupancy of ``x`` at cell size ``r_build`` (numpy).

    Sizes ``cell_cap`` at the engine's first (host) build; the device
    build re-measures every rebuild and flags overflow.
    """
    x = np.asarray(x)
    if x.shape[0] == 0:
        return 1
    rt = x.dtype.type(r_build)
    cell = np.floor(x / rt).astype(np.int64)
    c = cell - cell.min(axis=0)
    dims = c.max(axis=0) + 1
    key = (c[:, 0] * dims[1] + c[:, 1]) * dims[2] + c[:, 2]
    return int(np.bincount(np.unique(key, return_inverse=True)[1]).max())


def auto_cell_cap(occupancy: int,
                  headroom: float = DEFAULT_CELL_HEADROOM) -> int:
    """Per-cell candidate capacity from a measured occupancy."""
    return max(4, int(math.ceil(occupancy * headroom)) + 1)
