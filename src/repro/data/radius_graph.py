"""Host-side radius-graph construction, edge dropping, CSR layout, padding.

Graph building is host-side numpy (DESIGN.md §6.3): cell-list radius
search in O(N), distance-sorted edge dropping (the paper drops the top-p
*longest* edges, Sec. VII-B), a receiver-sort (CSR) layout pass that feeds
the fused Pallas edge kernel (DESIGN.md §3.1), and fixed-capacity padding
so the jitted model sees static shapes.  It serves two consumers: the
training data pipeline (every sample, ahead of time, in stream workers —
DESIGN.md §8) and the rollout engine's Verlet rebuild path (once per skin
violation at inference, asynchronously — DESIGN.md §10).  The *skin
criterion* that decides when a rebuild is due is the one pure-jax function
here (:func:`displacement_exceeds_skin`), so the rollout inner loop can
evaluate it on device without a host round-trip.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import numpy as np


def radius_graph(x: np.ndarray, r: float, max_num_neighbors: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """All directed edges (i→j, i≠j) with ‖x_i−x_j‖ ≤ r.  Cell-list, O(N·deg).

    Returns (senders, receivers) int32 arrays in canonical
    (receiver, sender) lexicographic order (``sort_edges_by_receiver`` on
    the result is a no-op).  Fully vectorised: nodes are binned into cells
    of side ``r`` via one flattened-key argsort, candidates gathered per
    27-cell stencil with ``searchsorted`` range lookups — no Python loop
    over cells, so clustered inputs that land in one cell no longer
    degenerate to an O(N²) scan (DESIGN.md §13).

    The distance cutoff is evaluated in ``x``'s dtype (f32 inputs compare
    ``d² ≤ f32(r)²`` in f32) so the predicate is bitwise the one the
    device-resident build (``data/cell_list.py``) and the rollout engine's
    on-device drop mask apply.
    """
    n = x.shape[0]
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    if not np.isfinite(r):
        idx = np.arange(n)
        snd = np.repeat(idx, n)
        rcv = np.tile(idx, n)
        keep = snd != rcv
        snd, rcv = snd[keep], rcv[keep]
        order = np.lexsort((snd, rcv))
        return snd[order].astype(np.int32), rcv[order].astype(np.int32)

    rt = np.asarray(x).dtype.type(r)
    cell = np.floor(x / rt).astype(np.int64)
    # Flatten 3-D cell coords to one sortable key over a grid padded by one
    # ghost cell per face, so every stencil offset stays a valid key.
    c = cell - cell.min(axis=0) + 1
    dims = c.max(axis=0) + 2
    key = (c[:, 0] * dims[1] + c[:, 1]) * dims[2] + c[:, 2]
    order = np.argsort(key, kind="stable")
    sk = key[order]

    off = np.array([-1, 0, 1], np.int64)
    off_flat = ((off[:, None, None] * dims[1] + off[None, :, None])
                * dims[2] + off[None, None, :]).reshape(-1)
    probe = key[:, None] + off_flat[None, :]  # (n, 27) neighbor-cell keys
    lo = np.searchsorted(sk, probe, side="left")
    hi = np.searchsorted(sk, probe, side="right")
    cnt = (hi - lo).reshape(-1)
    tot = int(cnt.sum())
    # Expand the (n, 27) [lo, hi) runs into one flat candidate index list.
    starts = lo.reshape(-1)
    run0 = np.cumsum(cnt) - cnt
    idx = np.repeat(starts - run0, cnt) + np.arange(tot)
    cand = order[idx]
    rcv = np.repeat(np.arange(n, dtype=np.int64), cnt.reshape(n, 27).sum(axis=1))
    d2 = np.sum((x[cand] - x[rcv]) ** 2, axis=-1)
    keep = (d2 <= rt * rt) & (cand != rcv)
    snd, rcv = cand[keep], rcv[keep]
    order = np.lexsort((snd, rcv))
    snd, rcv = snd[order], rcv[order]
    if max_num_neighbors is not None and snd.size:
        # keep nearest max_num_neighbors per receiver
        d2 = np.sum((x[snd] - x[rcv]) ** 2, axis=-1)
        order = np.lexsort((d2, rcv))
        snd, rcv, d2 = snd[order], rcv[order], d2[order]
        rank = np.arange(rcv.size) - np.searchsorted(rcv, rcv, side="left")
        keep = rank < max_num_neighbors
        snd, rcv = snd[keep], rcv[keep]
    return snd.astype(np.int32), rcv.astype(np.int32)


def drop_longest_edges(x: np.ndarray, snd: np.ndarray, rcv: np.ndarray, p: float) -> tuple[np.ndarray, np.ndarray]:
    """Sec. VII-B edge dropping: sort by length, drop the top-p fraction.

    The kept edges come back in their *original* relative order (selection
    by length, not reordering).  Callers feed this *canonically sorted*
    edges (``sort_edges_by_receiver`` first), so the stable argsort's
    tie-break among equal-length directed twins is (receiver, sender) —
    exactly the (d², receiver, sender) lexicographic rank the rollout
    engine's on-device drop mask uses, which is what makes device-side
    selection bitwise-equal to this host path (DESIGN.md §10).
    """
    if p <= 0.0 or snd.size == 0:
        return snd, rcv
    if p >= 1.0:
        return snd[:0], rcv[:0]
    d2 = np.sum((x[snd] - x[rcv]) ** 2, axis=-1)
    n_keep = int(round((1.0 - p) * snd.size))
    keep = np.sort(np.argsort(d2, kind="stable")[:n_keep])
    return snd[keep], rcv[keep]


def sort_edges_by_receiver(
    snd: np.ndarray, rcv: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """CSR layout pass: sort edges by (receiver, sender) (DESIGN.md §3.1).

    Receiver-sorted edges make the segment reduction's scatter targets
    monotone — the layout contract of the fused Pallas edge kernel (each
    edge block then writes a narrow band of receiver rows) and a better
    access pattern for XLA's segment_sum.  The within-receiver sender
    tiebreak makes the order *canonical* — independent of the cell-list
    traversal, hence of the build radius: a Verlet list built at
    ``r + skin`` holds its radius-``r`` subset in exactly the order a
    fresh radius-``r`` build would, which is what makes the rollout
    engine's trajectories bitwise independent of ``skin`` (masked extras
    contribute exact zeros without perturbing the fp summation order —
    DESIGN.md §10).
    """
    if snd.size == 0:
        return snd, rcv
    order = np.lexsort((snd, rcv))
    return snd[order], rcv[order]


class BandedCSR(NamedTuple):
    """Banded-CSR edge layout + band metadata (DESIGN.md §3.1).

    The fused edge kernel tiles the node axis into receiver windows of
    ``window`` rows and sender windows of ``swindow`` rows; edges are
    regrouped by the (receiver-window × sender-window) band they live in,
    each band padded to whole blocks of ``block_e`` edges; masked edges
    are placed in no band.  ``senders`` /
    ``receivers`` / ``edge_mask`` are the regrouped (capacity-padded)
    global edge arrays; ``block_rwin`` / ``block_swin`` give each edge
    block's window coordinates; ``window_offsets`` are per-receiver-window
    CSR row offsets into the banded arrays (length n_windows + 1).
    """

    senders: np.ndarray  # (cap,) int32, banded order, masked slots = 0
    receivers: np.ndarray  # (cap,) int32
    edge_mask: np.ndarray  # (cap,) float32
    block_rwin: np.ndarray  # (n_blocks,) int32 receiver-window per block
    block_swin: np.ndarray  # (n_blocks,) int32 sender-window per block
    window_offsets: np.ndarray  # (n_windows + 1,) int32 CSR rows per window
    window: int
    swindow: int
    block_e: int
    n_pad: int
    sender_band_max: int  # max sender-index span inside one edge block
    fill: float  # real edges / capacity (layout efficiency)


def banded_csr_layout(
    snd: np.ndarray, rcv: np.ndarray, n_nodes: int, *,
    edge_mask: np.ndarray | None = None,
    window: int | None = None, swindow: int | None = None,
    block_e: int = 128, capacity: int | None = None,
) -> BandedCSR:
    """Host-side banded-CSR layout pass, emitted alongside the CSR sort.

    Numpy mirror of the trace-time ``kernels.edge_message.banded_layout``
    (same stable grouping ⇒ identical slot assignment, parity-tested in
    ``tests/test_banded_csr.py``), plus the per-window CSR row offsets and
    band-width diagnostics the data pipeline records.  ``capacity``
    overrides the static slot bound (must be ≥ the computed bound) so a
    dataset of varying graphs can share one jitted program.
    """
    from repro.kernels.edge_message import layout_capacity, pick_windows

    e = snd.size
    window, swindow, n_pad = pick_windows(n_nodes, window=window,
                                          swindow=swindow)
    nw, nsw = n_pad // window, n_pad // swindow
    em = (np.ones(e, np.float32) if edge_mask is None
          else np.asarray(edge_mask, np.float32))
    snd = np.asarray(snd, np.int32)
    rcv = np.asarray(rcv, np.int32)

    # masked slots go in no band: they sort past the last band and are
    # dropped, so every block before the last band's end holds a live edge
    # or zeroes an empty receiver window (DESIGN.md §3.1)
    live = em != 0
    band = np.where(live, (rcv // window) * nsw + snd // swindow, nw * nsw)
    order = np.argsort(band, kind="stable")[:int(live.sum())]
    bs = band[order]
    counts = np.bincount(bs, minlength=nw * nsw).astype(np.int64)
    padded = -(-counts // block_e) * block_e
    per_w = padded.reshape(nw, nsw).sum(axis=1)
    padded = padded.reshape(nw, nsw)
    padded[:, 0] += np.where(per_w == 0, block_e, 0)
    padded = padded.reshape(-1)
    ends = np.cumsum(padded)
    offs = ends - padded
    gstart = np.cumsum(counts) - counts
    pos = (offs[bs] + (np.arange(bs.size) - gstart[bs])).astype(np.int64)

    cap = layout_capacity(e, nw, nsw, block_e)
    if capacity is not None:
        assert capacity >= cap, (capacity, cap)
        cap = capacity
    n_blocks = cap // block_e
    out_s = np.zeros(cap, np.int32)
    out_r = np.zeros(cap, np.int32)
    out_m = np.zeros(cap, np.float32)
    out_s[pos] = snd[order]
    out_r[pos] = rcv[order]
    out_m[pos] = em[order]

    bfirst = np.arange(n_blocks, dtype=np.int64) * block_e
    bid = np.searchsorted(ends, bfirst, side="right")
    bid = np.where(bfirst < ends[-1], bid, nw * nsw - 1)
    block_rwin = (bid // nsw).astype(np.int32)
    block_swin = (bid % nsw).astype(np.int32)

    w_end = ends.reshape(nw, nsw)[:, -1]
    window_offsets = np.concatenate([[0], w_end]).astype(np.int32)

    # max sender-index span inside any one edge block, vectorised (this
    # runs per shard per sample in the partition pipeline — a Python loop
    # over blocks would dominate the layout pass at scale)
    span = 0
    live = out_m > 0
    if live.any():
        blk = np.nonzero(live)[0] // block_e
        mn = np.full(n_blocks, np.iinfo(np.int64).max)
        mx = np.full(n_blocks, -1)
        np.minimum.at(mn, blk, out_s[live])
        np.maximum.at(mx, blk, out_s[live])
        nz = mx >= 0
        span = int((mx[nz] - mn[nz] + 1).max())

    return BandedCSR(
        senders=out_s, receivers=out_r, edge_mask=out_m,
        block_rwin=block_rwin, block_swin=block_swin,
        window_offsets=window_offsets, window=window, swindow=swindow,
        block_e=block_e, n_pad=n_pad, sender_band_max=span,
        fill=float(em.sum()) / max(cap, 1),
    )


_TRUNCATION_WARNED: set[tuple[int, int]] = set()


def reset_truncation_warnings() -> None:
    """Re-arm the once-per-(capacity, overflow) truncation warning."""
    _TRUNCATION_WARNED.clear()


def warn_edge_truncation(e: int, capacity: int, how: str) -> None:
    """Warn that ``e`` built edges exceeded ``capacity`` — once per
    (capacity, overflow) pair, not per batch: at Fluid113K scale with a
    tight ``edge_cap`` every sample overflows identically and a per-batch
    warning is pure noise, while a *new* overflow magnitude at the same
    capacity is real signal and warns again."""
    sig = (int(capacity), int(e) - int(capacity))
    if sig in _TRUNCATION_WARNED:
        return
    _TRUNCATION_WARNED.add(sig)
    warnings.warn(
        f"edge truncation: capacity {capacity} short by {e - capacity} "
        f"edges ({e} built; {how} drop) — warning once per "
        f"(capacity, overflow) pair",
        stacklevel=3)


def pad_edges(
    snd: np.ndarray, rcv: np.ndarray, capacity: int, x: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad/truncate to ``capacity``; returns (senders, receivers, edge_mask).

    Over capacity, the *longest* edges are dropped (consistent with the
    Sec. VII-B drop-longest semantics) when ``x`` is given; without
    coordinates the tail of the (receiver-sorted) edge list is dropped.
    Truncation warns once per (capacity, overflow) pair
    (:func:`warn_edge_truncation`) — silent capacity loss reads as
    "covered every edge" when it didn't, but repeating the identical
    warning every batch buries everything else.
    """
    e = snd.size
    if e > capacity:
        warn_edge_truncation(
            e, capacity, "longest-first" if x is not None else "tail-first")
        if x is not None:
            d2 = np.sum((x[snd] - x[rcv]) ** 2, axis=-1)
            keep = np.sort(np.argsort(d2, kind="stable")[:capacity])
            snd, rcv = snd[keep], rcv[keep]
        else:
            snd, rcv = snd[:capacity], rcv[:capacity]
        e = capacity
    out_s = np.zeros(capacity, np.int32)
    out_r = np.zeros(capacity, np.int32)
    mask = np.zeros(capacity, np.float32)
    out_s[:e] = snd
    out_r[:e] = rcv
    mask[:e] = 1.0
    return out_s, out_r, mask


# --------------------------------------------------------------- Verlet skin
# The rollout engine (DESIGN.md §10) builds its radius graph at r + skin and
# reuses it across steps: a list built at reference positions x_ref contains
# every pair within r of each other as long as no node has moved more than
# skin/2 from x_ref (two nodes approaching each other head-on close the gap
# at twice the per-node displacement — hence the factor 2).  The criterion
# is pure jax so the jit-resident inner loop checks it per step on device.


def max_displacement2(x, x_ref, node_mask=None):
    """Max squared displacement ``max_i ‖x_i − x_ref_i‖²`` (device scalar).

    ``node_mask`` excludes padded rows (their coordinates are clamped
    artifacts, not simulation state).
    """
    import jax.numpy as jnp

    d2 = jnp.sum((x - x_ref) ** 2, axis=-1)
    if node_mask is not None:
        d2 = d2 * node_mask
    return jnp.max(d2)


def displacement_exceeds_skin(x, x_ref, skin, node_mask=None):
    """Pure-jax Verlet rebuild criterion: True once any (real) node has
    moved more than ``skin / 2`` from the positions the neighbor list was
    built at — beyond that the ``r + skin`` list may miss a pair now
    within ``r``, so the edge list must be rebuilt before the next step."""
    return max_displacement2(x, x_ref, node_mask) > (0.5 * skin) ** 2


def pad_nodes(arr: np.ndarray, capacity: int, fill: float = 0.0) -> tuple[np.ndarray, np.ndarray]:
    """Pad node array (N, ...) to (capacity, ...); returns (padded, node_mask)."""
    n = arr.shape[0]
    assert n <= capacity, (n, capacity)
    out = np.full((capacity,) + arr.shape[1:], fill, arr.dtype)
    out[:n] = arr
    mask = np.zeros(capacity, np.float32)
    mask[:n] = 1.0
    return out, mask
