"""Fused TFN edge pathway: Pallas TPU kernels over the banded-CSR layout.

The single-channel ℓ ≤ 2 TFN (``models/tfn.py``, DESIGN.md §6.5) sends,
on every edge j → i with ``r = x_i − x_j``, ``r̂ = r/|r|``:

* type-1: ``w0 v_j + w1 r̂ + w2 (r̂ × v_j) + w3 (r̂ r̂ᵀ − I/3) v_j``;
* type-0: ``[w4, w5 (r̂·v_j)]``;

with the six path weights ``w = clip(φ_R([rbf(|r|) | h_j]), ±clamp)`` from
a two-layer radial network, and reduces both onto the receivers as a
masked degree mean.  The jnp composition gathers ``h_j``, ``v_j`` and both
endpoints' ``x`` as (E, ·) rows in HBM and scatters with ``segment_sum``.
These kernels walk the edge blocks of the same :class:`EdgeLayout` the
FastEGNN kernel walks (``kernels/edge_message.py``), and keep every
per-edge quantity in VMEM:

* the radial network's first layer is split by input slice: the ``h_j``
  part is one per-node product ``a = h W_h + b_1`` (N, hidden), made in
  XLA and gathered in ``h``'s place; the rbf part ``W_r rbf(|r|)`` runs
  per block;
* forward (``tfn_edge_fused_fwd``): per block, gather ``x_i`` from the
  receiver window and ``[a | x | v]`` from the sender window, form rbf,
  the path weights and the paths, and scatter ``[type-1 (3) | type-0 (2)
  | degree (1)]`` into the receiver window's sums;
* backward, two passes over the same blocks like the FastEGNN kernel's:
  receiver-major (``tfn_edge_bwd_fused_recv``: dL/dx_i, and the weight
  gradients of ``W_r``, ``W_2``, ``b_2`` over the whole grid) and
  sender-major over the blocks sorted by sender window
  (``tfn_edge_bwd_fused_send``: dL/da_j, dL/dv_j, dL/dx_j).  Both
  recompute the forward per block; the only residual is the degree
  column.  XLA turns dL/da into the gradients of ``h``, ``W_h`` and
  ``b_1``.

Edges along lanes.  Most of this pathway's per-edge work is on vectors
of 3 and scalars, which in the FastEGNN kernel's (edge, feature) layout
fill one lane of 128 each.  Here a block of 128 edges lies along the
lanes: a feature is a (1, 128) row, one vreg, and the node windows, the
sums and the weights are stored transposed (feature rows, node lanes).
The one-hot products are the FastEGNN kernel's, exact at one bf16 MXU
pass, with their operands swapped: a gather is ``window (K, rows) @
one-hot (rows, 128)``, a scatter ``values (K, 128) @ one-hot (128,
rows)``, every f32 value split into three bf16 pieces (``split_pieces``)
stacked along the rows under f32 compute (one piece under bf16).

The clip's gradient is zero outside ``±clamp``, as ``jnp.clip``'s is.  The
edge mask is not differentiated; masked slots gather window-local row 0
and contribute exact zeros.  The kernels need the layout: the dispatch
(``models.tfn.edge_kernel_supported``) sends layout-free graphs to the jnp
path, which stays the reference (oracle: ``kernels.ref.tfn_edge_pathway_ref``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.edge_message import (EdgeLayout, _compiler_params,
                                        _live_only, _mm, _resolve_banded,
                                        _round_up, _silu_grad, onehot_pieces,
                                        pick_windows, sender_walk,
                                        split_pieces, walk_coords)

Array = jax.Array

#: path weights the radial network emits: four type-1 paths, two type-0
N_PATHS = 6
#: inside the square root of |r|², as the jnp path has it
EPS = 1e-12
#: rows of an f32 tile: every row group of a packed operand starts on one
SUB = 8
#: rows of a group of per-node or per-edge scalars: [x (3) | v (3)], the
#: six path weights, the forward's [type-1 (3) | type-0 (2) | degree (1)]
GROUP = SUB
#: rows of the receiver window the backward gathers: [x (3) | 1/deg (1) |
#: g_dx (3) | g_h (2)]
BWD_RECV_ROWS = 2 * SUB


def _x_row(hidden: int) -> int:
    """First row of ``[x | v]`` in the sender operand ``[a | x | v]``:
    ``a``'s rows rounded up to a whole tile."""
    return _round_up(hidden, SUB)


def tfn_edge_vmem_bytes(n_nodes: int, hidden: int, n_rbf: int,
                        block_e: int = 128, precision: str = "f32") -> int:
    """Per-grid-step VMEM footprint model of the three kernels at the
    :func:`pick_windows` band sizes: the largest of the forward and the
    two backward passes, counting the edge-id and mask rows, the packed
    bf16 node windows (sender ``[a | x | v]``, receiver as each pass
    gathers it), the packed f32 sums, the weights, the f32 weight-gradient
    accumulators and the bf16 one-hots.  Window-bounded: independent of N
    once the windows reach their defaults."""
    from repro.core.message_passing import _tile_bytes as t
    from repro.kernels.runtime import resolve_precision

    window, swindow, _ = pick_windows(n_nodes)
    cdt = resolve_precision(precision).compute_dtype
    c, p, be, w, sw = cdt.itemsize, onehot_pieces(cdt), block_e, window, swindow
    rows = lambda per_piece: _round_up(p * per_piece, 2 * SUB)
    weights = ((n_rbf, 1), (hidden, n_rbf), (n_rbf, hidden), (GROUP, hidden),
               (hidden, GROUP), (GROUP, 1))
    w_c = sum(t(r, k, c) for r, k in weights)
    w_f = sum(t(r, k, 4) for r, k in weights)
    edges = 2 * (3 * t(1, be, 4) + t(be, 1, 4))
    send = rows(_x_row(hidden) + GROUP)
    fwd = (edges + w_c + t(sw, be, 2) + t(w, be, 2) + t(be, w, 2)
           + 2 * (t(send, sw, 2) + t(rows(GROUP), w, 2)
                  + t(rows(GROUP), w, 4)))
    bwd_in = (edges + w_c + t(sw, be, 2) + t(w, be, 2) + t(send, sw, 2)
              + 2 * t(rows(BWD_RECV_ROWS), w, 2))
    bwd_a = bwd_in + t(be, w, 2) + 2 * t(rows(GROUP), w, 4) + w_f
    bwd_b = bwd_in + t(be, sw, 2) + t(send, sw, 4)
    return max(fwd, bwd_a, bwd_b)


# ------------------------------------------------------ transposed packing
def _pack_t(m: Array, pieces: int) -> Array:
    """(P, n) f32 rows, P a multiple of 8 -> (K, n) bf16: the rows' bf16
    pieces stacked, piece k at rows [k P, (k+1) P), zeros up to a whole
    number of bf16 tiles.  Stacked in f32 (8-row aligned) and cast once:
    every piece is a bf16 already, so the cast is exact."""
    ps = [q.astype(jnp.float32) for q in split_pieces(m, pieces)]
    tail = _round_up(pieces * m.shape[0], 2 * SUB) - pieces * m.shape[0]
    if tail:
        ps.append(jnp.zeros((tail, m.shape[1]), jnp.float32))
    return jnp.concatenate(ps, axis=0).astype(jnp.bfloat16)


def _unpack_t(g: Array, rows: int, pieces: int) -> Array:
    """The sum of the pieces of a product with a :func:`_pack_t` operand:
    (K, n) -> (rows, n), ``(hi + mid) + lo``."""
    out = g[:rows]
    for k in range(1, pieces):
        out = out + g[k * rows:(k + 1) * rows]
    return out


def _rows(rows: list[Array]) -> Array:
    """Up to 8 (1, n) rows stacked into one (8, n) group, zeros below: by
    selects on an iota, so that no row has to land on a tile boundary."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (GROUP, rows[0].shape[1]), 0)
    out = jnp.zeros((GROUP, rows[0].shape[1]), rows[0].dtype)
    for k, r in enumerate(rows):
        out = jnp.where(idx == k, r, out)
    return out


def _split(m: Array, start: int, n: int) -> list[Array]:
    return [m[start + k:start + k + 1] for k in range(n)]


def _gather_t(ids_row: Array, win: Array, rows: int, pieces: int,
              adt) -> Array:
    """Columns ``ids_row`` (1, BE) of a window of a :func:`_pack_t`-ed
    operand (K, W): ``win @ one-hot (W, BE)``, exact at one bf16 pass ->
    (rows, BE)."""
    oh = (ids_row == jax.lax.broadcasted_iota(
        jnp.int32, (win.shape[1], ids_row.shape[1]), 0)).astype(jnp.bfloat16)
    return _unpack_t(jnp.dot(win, oh, preferred_element_type=adt), rows,
                     pieces)


def _scatter_t(ids_col: Array, m: Array, width: int, pieces: int,
               adt) -> Array:
    """Sums of the edge columns of ``m`` (P, BE) into a ``width``-node
    window by the window-local ids ``ids_col`` (BE, 1): ``pack(m) @
    one-hot (BE, width)``; the packed sums, recombined after the grid."""
    oh = (ids_col == jax.lax.broadcasted_iota(
        jnp.int32, (ids_col.shape[0], width), 1)).astype(jnp.bfloat16)
    return jnp.dot(_pack_t(m, pieces), oh, preferred_element_type=adt)


def _mm_nt(a: Array, b: Array, *, cdt, adt) -> Array:
    """``a @ b.T`` contracting the edge lanes of both: a weight gradient."""
    prec = jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=adt, precision=prec)


# ------------------------------------------------------------ per block
def _cross(a: list, b: list) -> list:
    return [a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0]]


def _dot3(a: list, b: list) -> Array:
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


def _edge_terms(xr: list, s: Array, centers, w1rT, w2T, b2, mm, *,
                hidden: int, gamma: float, clamp: float) -> dict:
    """The forward chain of one block of edges from the receiver's
    coordinate rows ``xr`` and the sender rows ``s`` = ``[a | x | v]``."""
    xo = _x_row(hidden)
    xs, vs = _split(s, xo, 3), _split(s, xo + 3, 3)
    rel = [xr[k] - xs[k] for k in range(3)]
    d = jnp.sqrt(_dot3(rel, rel) + EPS)  # (1, BE)
    rhat = [r / d for r in rel]
    diff = d - centers  # (n_rbf, BE)
    rbf = jnp.exp(-gamma * diff * diff)
    pre1 = s[:hidden] + mm(w1rT, rbf)  # (hidden, BE)
    t1 = jax.nn.silu(pre1)
    wp = mm(w2T, t1) + b2  # (8, BE): the six path weights before the clip
    w = jnp.clip(wp, -clamp, clamp)
    dot = _dot3(rhat, vs)
    return dict(vs=vs, rel=rel, d=d, rhat=rhat, diff=diff, rbf=rbf,
                pre1=pre1, t1=t1, wp=wp, w=_split(w, 0, N_PATHS), dot=dot,
                cross=_cross(rhat, vs),
                quad=[rhat[k] * dot - vs[k] / 3.0 for k in range(3)])


def _tfn_fwd_kernel(rwin_ref, swin_ref, sndr_ref, rcvr_ref, rcvc_ref,
                    emr_ref, r_ref, s_ref, c_ref, w1rT_ref, w2T_ref, b2_ref,
                    acc_ref, *, step, hidden: int, gamma: float, clamp: float,
                    compute: str, accum: str):
    b = step
    rw_prev = jnp.where(b > 0, rwin_ref[jnp.maximum(b - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    pieces = onehot_pieces(cdt)

    @pl.when(rwin_ref[b] != rw_prev)  # first block of this receiver window
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    em = emr_ref[...]  # (1, BE)
    s = _gather_t(sndr_ref[...], s_ref[...], _x_row(hidden) + GROUP, pieces,
                  adt)
    xr = _split(_gather_t(rcvr_ref[...], r_ref[...], GROUP, pieces, adt),
                0, 3)
    t = _edge_terms(xr, s, c_ref[...], w1rT_ref[...], w2T_ref[...],
                    b2_ref[...], functools.partial(_mm, cdt=cdt, adt=adt),
                    hidden=hidden, gamma=gamma, clamp=clamp)
    w = t["w"]
    dx = [(w[0] * t["vs"][k] + w[1] * t["rhat"][k] + w[2] * t["cross"][k]
           + w[3] * t["quad"][k]) * em for k in range(3)]
    acc_ref[...] += _scatter_t(
        rcvc_ref[...], _rows(dx + [w[4] * em, w[5] * t["dot"] * em, em]),
        acc_ref.shape[1], pieces, adt)


def _bwd_common(sndr, rcvr, em, s_win, r_win, centers, w1rT, w1r, w2T, w2,
                b2, mm, pieces: int, adt, *, hidden: int, gamma: float,
                clamp: float) -> dict:
    """Per-block recompute and backprop shared by both backward passes:
    from the gathered cotangents (``1/deg`` and the edge mask folded in)
    down to ``g_w`` (8, BE; the path weights' cotangent), ``g_pre1``
    (hidden, BE; = dL/da_j), and the rows ``g_v`` and ``g_rel`` (the
    cotangent of ``x_i − x_j``)."""
    s = _gather_t(sndr, s_win, _x_row(hidden) + GROUP, pieces, adt)
    r = _gather_t(rcvr, r_win, BWD_RECV_ROWS, pieces, adt)
    t = _edge_terms(_split(r, 0, 3), s, centers, w1rT, w2T, b2, mm,
                    hidden=hidden, gamma=gamma, clamp=clamp)
    vs, rhat, rel, d, w = t["vs"], t["rhat"], t["rel"], t["d"], t["w"]
    scale = r[3:4] * em
    g = [q * scale for q in _split(r, 4, 3)]  # the type-1 message's
    s0, s1 = r[7:8] * scale, r[8:9] * scale  # the type-0 message's
    g_rh = _dot3(g, rhat)
    g_w = _rows([_dot3(g, vs), g_rh, _dot3(g, t["cross"]),
                 _dot3(g, t["quad"]), s0, s1 * t["dot"]])
    if math.isfinite(clamp):  # the clip passes gradient inside the band only
        wp = t["wp"]
        g_w = g_w * ((wp >= -clamp) & (wp <= clamp)).astype(g_w.dtype)
    g_pre1 = mm(w2, g_w) * _silu_grad(t["pre1"])
    g_d = jnp.sum(mm(w1r, g_pre1) * t["rbf"] * (-2.0 * gamma) * t["diff"],
                  axis=0, keepdims=True)
    g_x_rh, v_x_g = _cross(g, rhat), _cross(vs, g)
    g_v = [w[0] * g[k] + w[2] * g_x_rh[k] + w[3] * (g_rh * rhat[k] - g[k] / 3.0)
           + w[5] * s1 * rhat[k] for k in range(3)]
    g_rhat = [w[1] * g[k] + w[2] * v_x_g[k]
              + w[3] * (t["dot"] * g[k] + g_rh * vs[k]) + w[5] * s1 * vs[k]
              for k in range(3)]
    # r̂ = r/d and d = sqrt(|r|² + eps): d r̂/dr = I/d − r rᵀ/d³, dd/dr = r/d
    proj = _dot3(g_rhat, rel) / (d * d * d)
    g_rel = [g_rhat[k] / d - rel[k] * proj + g_d * rel[k] / d
             for k in range(3)]
    return dict(rbf=t["rbf"], t1=t["t1"], g_w=g_w, g_pre1=g_pre1, g_v=g_v,
                g_rel=g_rel)


def _tfn_bwd_r_kernel(rwin_ref, swin_ref, sndr_ref, rcvr_ref, rcvc_ref,
                      emr_ref, r_ref, s_ref, c_ref, w1rT_ref, w1r_ref,
                      w2T_ref, w2_ref, b2_ref, accr_ref, dw1rT_ref, dw2T_ref,
                      db2_ref, *, step, hidden: int, gamma: float, clamp: float,
                      compute: str, accum: str):
    """Receiver-major pass: dL/dx_i per receiver window, and the weight
    gradients over the whole grid."""
    b = step
    rw_prev = jnp.where(b > 0, rwin_ref[jnp.maximum(b - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    pieces = onehot_pieces(cdt)

    @pl.when(rwin_ref[b] != rw_prev)  # first block of this receiver window
    def _init_window():
        accr_ref[...] = jnp.zeros_like(accr_ref)

    @pl.when(b == 0)  # weight grads accumulate over the entire grid
    def _init_weight_grads():
        for ref in (dw1rT_ref, dw2T_ref, db2_ref):
            ref[...] = jnp.zeros_like(ref)

    c = _bwd_common(sndr_ref[...], rcvr_ref[...], emr_ref[...], s_ref[...],
                    r_ref[...], c_ref[...], w1rT_ref[...], w1r_ref[...],
                    w2T_ref[...], w2_ref[...], b2_ref[...],
                    functools.partial(_mm, cdt=cdt, adt=adt), pieces, adt,
                    hidden=hidden, gamma=gamma, clamp=clamp)
    accr_ref[...] += _scatter_t(rcvc_ref[...], _rows(c["g_rel"]),
                                accr_ref.shape[1], pieces, adt)
    nt = functools.partial(_mm_nt, cdt=cdt, adt=adt)
    dw1rT_ref[...] += nt(c["g_pre1"], c["rbf"])
    dw2T_ref[...] += nt(c["g_w"], c["t1"])
    db2_ref[...] += jnp.sum(c["g_w"], axis=1, keepdims=True)


def _tfn_bwd_s_kernel(perm_ref, rwp_ref, swp_ref, sndr_ref, rcvr_ref,
                      sndc_ref, emr_ref, r_ref, s_ref, c_ref, w1rT_ref,
                      w1r_ref, w2T_ref, w2_ref, b2_ref, accs_ref, *, step,
                      hidden: int, gamma: float, clamp: float, compute: str,
                      accum: str):
    """Sender-major pass over the blocks in sender-window order
    (``edge_message.sender_walk``): ``[dL/da_j | dL/dv_j | dL/dx_j]`` per
    sender window."""
    del perm_ref, rwp_ref  # consumed by the BlockSpec index maps only
    j = step
    sw_prev = jnp.where(j > 0, swp_ref[jnp.maximum(j - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    pieces = onehot_pieces(cdt)

    @pl.when(swp_ref[j] != sw_prev)  # first block of this sender window
    def _init_window():
        accs_ref[...] = jnp.zeros_like(accs_ref)

    c = _bwd_common(sndr_ref[...], rcvr_ref[...], emr_ref[...], s_ref[...],
                    r_ref[...], c_ref[...], w1rT_ref[...], w1r_ref[...],
                    w2T_ref[...], w2_ref[...], b2_ref[...],
                    functools.partial(_mm, cdt=cdt, adt=adt), pieces, adt,
                    hidden=hidden, gamma=gamma, clamp=clamp)
    g_a = c["g_pre1"]
    if _x_row(hidden) > hidden:
        g_a = jnp.concatenate([g_a, jnp.zeros(
            (_x_row(hidden) - hidden, g_a.shape[1]), g_a.dtype)], axis=0)
    m = jnp.concatenate(
        [g_a, _rows(c["g_v"] + [-q for q in c["g_rel"]])], axis=0)
    accs_ref[...] += _scatter_t(sndc_ref[...], m, accs_ref.shape[1], pieces,
                                adt)


# ------------------------------------------------------------ entry points
def _banded(x, a, v, layout: EdgeLayout):
    """The layout's block geometry, endpoints localised to their windows,
    and the node operands zero-padded to whole sender windows.  The band
    sizes are the layout's own where it states them (``meta``), the
    :func:`pick_windows` policy otherwise."""
    n_blocks = layout.block_rwin.shape[0]
    block_e = layout.senders.shape[0] // n_blocks
    hidden = a.shape[1]
    meta = layout.meta
    (snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks, n_live, x, av,
     n_pad, window, swindow) = _resolve_banded(
        x, jnp.concatenate([a, v], axis=-1), layout.senders,
        layout.receivers, layout.edge_mask, n=x.shape[0], block_e=block_e,
        window=meta and meta.window, swindow=meta and meta.swindow,
        layout=layout, record=None)
    return dict(snd=snd_loc, rcv=rcv_loc, em=em_b, rwin=block_rwin,
                swin=block_swin, n_blocks=n_blocks, n_live=n_live,
                block_e=block_e, x=x,
                a=av[:, :hidden], v=av[:, hidden:], n_pad=n_pad,
                window=window, swindow=swindow)


def _node_rows(cols: list[Array], height: int) -> Array:
    """Node columns (n, w_i) as one (height, n) f32 operand of rows, in
    order, zeros below."""
    m = jnp.concatenate([c.astype(jnp.float32) for c in cols], axis=1).T
    return jnp.pad(m, ((0, height - m.shape[0]), (0, 0)))


def _send_pack(k, hidden: int, cdt, pieces: int) -> Array:
    """The sender operand ``[a | x | v]``, packed: zero rows after ``a``
    up to :func:`_x_row`, made as one concatenation of node columns."""
    a = k["a"].astype(cdt)
    gap = _x_row(hidden) - hidden
    cols = [a] + ([jnp.zeros((a.shape[0], gap), cdt)] if gap else [])
    return _pack_t(_node_rows(cols + [k["x"].astype(cdt), k["v"].astype(cdt)],
                              _x_row(hidden) + GROUP), pieces)


def _weights(centers, w1r, w2, b2, cdt) -> tuple:
    """The kernels' weight operands, transposed for edges along lanes:
    centres (n_rbf, 1), W_rᵀ, W_r, W_2ᵀ and W_2 with the six paths padded
    to 8 rows, b_2 (8, 1)."""
    pad = GROUP - N_PATHS
    w2T = jnp.pad(w2.T, ((0, pad), (0, 0)))
    return (centers.astype(jnp.float32).T, w1r.T.astype(cdt), w1r.astype(cdt),
            w2T.astype(cdt), w2T.T.astype(cdt),
            jnp.pad(b2.T, ((0, pad), (0, 0))).astype(cdt))


def _specs(k, *, permuted: bool):
    """BlockSpec factories over the grid: edge-id rows (1, BE), columns
    (BE, 1), receiver and sender windows of a transposed operand, and whole
    arrays; steps past the live count clamped, and on the sender-major
    pass through the block permutation (``edge_message.walk_coords``)."""
    be, window, swindow = k["block_e"], k["window"], k["swindow"]
    blk, rwin, swin = walk_coords(permuted)
    row = pl.BlockSpec((1, be), lambda *i: (0, blk(*i)))
    col = pl.BlockSpec((be, 1), lambda *i: (blk(*i), 0))
    rblk = lambda h: pl.BlockSpec((h, window), lambda *i: (0, rwin(*i)))
    # sender windows single-buffered on the backward, as in the FastEGNN
    # backward: the window changes only at band boundaries
    sblk = lambda h, **kw: pl.BlockSpec(
        (h, swindow), lambda *i: (0, swin(*i)), **kw)
    full = lambda arr: pl.BlockSpec(arr.shape, lambda *i: (0,) * arr.ndim)
    return row, col, rblk, sblk, full


@functools.partial(jax.jit, static_argnames=("cutoff", "clamp", "interpret",
                                             "precision"))
def tfn_edge_fused(x: Array, a: Array, v: Array, layout: EdgeLayout,
                   centers: Array, w1r: Array, w2: Array, b2: Array, *,
                   cutoff: float, clamp: float = math.inf,
                   interpret: bool | None = None, precision=None):
    """Forward.  x, v (N, 3); a (N, hidden), the per-node part of the
    radial network's first layer (``h W_h + b_1``); ``layout`` the graph's
    banded layout; centers (1, n_rbf) the rbf centres on ``[0, cutoff]``;
    w1r (n_rbf, hidden), w2 (hidden, 6), b2 (1, 6).  Returns (dx (N, 3),
    h_agg (N, 2), deg (N, 1)), degree means.  See
    ``kernels.ref.tfn_edge_pathway_ref``."""
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    cdt, adt = prec.compute_dtype, prec.accumulate_dtype
    pieces = onehot_pieces(cdt)
    n, hidden = a.shape
    k = _banded(x, a, v, layout)
    s_pack = _send_pack(k, hidden, cdt, pieces)
    r_pack = _pack_t(_node_rows([k["x"].astype(cdt)], GROUP), pieces)
    c, w1rT, _, w2T, _, b2T = _weights(centers, w1r, w2, b2, cdt)
    ws = (c, w1rT, w2T, b2T)
    out_rows = _round_up(pieces * GROUP, 2 * SUB)
    row, col, rblk, sblk, full = _specs(k, permuted=False)
    acc = pl.pallas_call(
        functools.partial(_live_only(_tfn_fwd_kernel), hidden=hidden,
                          gamma=w1r.shape[0] / cutoff, clamp=clamp,
                          compute=prec.compute, accum=prec.accumulate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k["n_blocks"],),
            in_specs=[row, row, col, row, rblk(r_pack.shape[0]),
                      sblk(s_pack.shape[0])] + [full(w) for w in ws],
            out_specs=rblk(out_rows)),
        name="tfn_edge_fused_fwd",
        out_shape=jax.ShapeDtypeStruct((out_rows, k["n_pad"]), adt),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(k["n_live"], k["rwin"], k["swin"], k["snd"][None, :],
      k["rcv"][None, :], k["rcv"][:, None], k["em"].astype(adt)[None, :],
      r_pack, s_pack, *ws)
    sums = _unpack_t(acc[:, :n], GROUP, pieces).T  # (n, 8)
    deg = sums[:, 5:6]
    inv = 1.0 / jnp.maximum(deg, 1.0)
    out = x.dtype
    return ((sums[:, :3] * inv).astype(out), (sums[:, 3:5] * inv).astype(out),
            deg.astype(out))


@functools.partial(jax.jit, static_argnames=("cutoff", "clamp", "interpret",
                                             "precision"))
def tfn_edge_bwd_fused(x: Array, a: Array, v: Array, layout: EdgeLayout,
                       centers: Array, w1r: Array, w2: Array, b2: Array,
                       deg: Array, g_dx: Array, g_h: Array, *, cutoff: float,
                       clamp: float = math.inf,
                       interpret: bool | None = None, precision=None):
    """Backward of :func:`tfn_edge_fused` from its primals, its ``deg``
    output (the one residual) and the cotangents of dx and h_agg.  Returns
    ``(gx, ga, gv, gw1r, gw2, gb2)`` in the accumulate dtype."""
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    cdt, adt = prec.compute_dtype, prec.accumulate_dtype
    pieces = onehot_pieces(cdt)
    n, hidden = a.shape
    k = _banded(x, a, v, layout)
    pad = ((0, k["n_pad"] - n), (0, 0))
    # the degree mean folded into the upstream: pad rows get inv = 1
    # against zero cotangents, exact no-ops
    inv = 1.0 / jnp.maximum(jnp.pad(deg.astype(adt), pad), 1.0)
    s_pack = _send_pack(k, hidden, cdt, pieces)
    r_pack = _pack_t(_node_rows(
        [k["x"].astype(cdt), inv, jnp.pad(g_dx.astype(adt), pad),
         jnp.pad(g_h.astype(adt), pad)], BWD_RECV_ROWS), pieces)
    ws = _weights(centers, w1r, w2, b2, cdt)
    kw = dict(hidden=hidden, gamma=w1r.shape[0] / cutoff, clamp=clamp,
              compute=prec.compute, accum=prec.accumulate)
    f = lambda shape: jax.ShapeDtypeStruct(shape, adt)
    sndr, rcvr = k["snd"][None, :], k["rcv"][None, :]
    emr = k["em"].astype(adt)[None, :]
    rows_r = _round_up(pieces * GROUP, 2 * SUB)
    xo = _x_row(hidden)
    rows_s = _round_up(pieces * (xo + GROUP), 2 * SUB)
    w_grads = (ws[1], ws[3], ws[5])  # W_rᵀ, W_2ᵀ, b_2: what pass A sums

    # ---- pass A: receiver-major (dx_i, weight grads) ---------------------
    row, col, rblk, sblk, full = _specs(k, permuted=False)
    acc_r, gw1rT, gw2T, gb2 = pl.pallas_call(
        functools.partial(_live_only(_tfn_bwd_r_kernel), **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k["n_blocks"],),
            in_specs=[row, row, col, row, rblk(r_pack.shape[0]),
                      sblk(s_pack.shape[0], pipeline_mode=pl.Buffered(1))]
            + [full(w) for w in ws],
            out_specs=(rblk(rows_r),) + tuple(full(w) for w in w_grads)),
        name="tfn_edge_bwd_fused_recv",
        out_shape=(f((rows_r, k["n_pad"])),) + tuple(f(w.shape)
                                                      for w in w_grads),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(k["n_live"], k["rwin"], k["swin"], sndr, rcvr, k["rcv"][:, None], emr,
      r_pack, s_pack, *ws)

    # ---- pass B: sender-major over the block permutation (a_j, v_j, x_j) -
    swindow = k["swindow"]
    perm, rw_p, sw_p, visited = sender_walk(k["rwin"], k["swin"], k["n_live"],
                                            k["n_pad"] // swindow)
    row, col, rblk, sblk, full = _specs(k, permuted=True)
    acc_s = pl.pallas_call(
        functools.partial(_live_only(_tfn_bwd_s_kernel), **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(k["n_blocks"],),
            in_specs=[row, row, col, row, rblk(r_pack.shape[0]),
                      sblk(s_pack.shape[0], pipeline_mode=pl.Buffered(1))]
            + [full(w) for w in ws],
            out_specs=sblk(rows_s, pipeline_mode=pl.Buffered(1))),
        name="tfn_edge_bwd_fused_send",
        out_shape=f((rows_s, k["n_pad"])),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(k["n_live"], perm, rw_p, sw_p, sndr, rcvr, k["snd"][:, None], emr,
      r_pack, s_pack, *ws)
    # sender windows no live block gathers from are never visited: mask them
    acc_s = jnp.where(jnp.repeat(visited, swindow)[None, :], acc_s, 0.0)
    g_s = _unpack_t(acc_s[:, :n], xo + GROUP, pieces).T  # [g_a | g_v | g_x]
    g_r = _unpack_t(acc_r[:, :n], GROUP, pieces).T  # [g_x]
    gx = g_r[:, :3] + g_s[:, xo + 3:xo + 6]
    return (gx, g_s[:, :hidden], g_s[:, xo:xo + 3], gw1rT.T,
            gw2T[:N_PATHS].T, gb2[:N_PATHS].T)
