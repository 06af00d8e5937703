"""Fused real-real edge-pathway Pallas TPU kernel, banded-CSR tiled (DESIGN.md §3).

The dominant cost of every model in the zoo is the real-real edge pathway
(Eq. 3 + the real parts of Eqs. 6-7).  The pure-jnp path materialises the
``(E, hidden)`` message tensor in HBM, reads it back for the gate MLP,
writes the gated edge vectors, and reads them again for the segment
reduction — four HBM round-trips of O(E·hidden) each.  Following the
E2Former-V2 idiom (linear activation memory via on-the-fly recomputation),
this kernel streams banded edge blocks through VMEM and performs
messages + gates + masked segment reduction in one pass.

Banded-CSR tiling
-----------------
The original formulation kept ``x``/``h`` fully VMEM-resident and expressed
gather/scatter as one-hot matmuls of shape ``(block_e, N)``, which bounded
eligibility to ~4K nodes — silently excluding the Water-3D (8K) and
Fluid113K (113K) scales the paper targets.  The tiled formulation bounds
every VMEM buffer by a *node window* instead of N:

  * the node axis is cut into **receiver windows** of ``window`` rows and
    **sender windows** of ``swindow`` rows (``window | swindow | n_pad``);
  * :func:`banded_layout` regroups the (receiver-sorted) edge list by the
    ``(receiver-window, sender-window)`` band each edge lives in, padding
    every band to whole blocks of ``block_e`` edges — so *by construction*
    each edge block gathers from exactly one sender window and scatters
    into exactly one receiver window, for any graph (senders that stray
    outside a narrow band simply land in a different band's blocks);
  * a 1-D grid walks the edge blocks in receiver-window-major order; the
    per-block window coordinates are scalar-prefetched
    (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps stream
    the right ``(window, ·)`` / ``(swindow, ·)`` slices of x/h — the
    windowed double-buffer (Pallas pipelines the next block's DMA while
    the current one computes);
  * gather/scatter one-hots shrink from ``(block_e, N)`` to
    ``(block_e, swindow)`` / ``(block_e, window)`` — the MXU-native
    segment-sum formulation, now with N-independent VMEM;
  * the ``(block_e, hidden)`` messages, gates and edge vectors live only
    in VMEM registers: nothing of size O(E·hidden) ever touches HBM;
  * output blocks are revisited only by the contiguous run of their
    window's edge blocks (TPU keeps a revisited output block
    VMEM-resident across consecutive grid steps): the first block of a
    window zeroes it.  They hold packed sums (below), recombined and
    degree-normalised once after the grid.

The live prefix
---------------
The grid is static, ``layout_capacity // block_e`` steps, so that one
program holds any graph of its size; a real layout fills a prefix of it
(DESIGN.md §3.1).  Masked slots are placed in no band, so every block
before the last band's end holds a live edge or zeroes an empty receiver
window, and every block after it is the all-masked capacity tail.  Each
pass reads the prefix's length, :func:`live_block_count`, as its first
scalar-prefetch operand and runs its body under ``pl.when(step <
n_live)``; every index map clamps the step to the last live one
(:func:`walk_coords`), so a skipped step asks for the blocks already
resident: no DMA, and no write-back of an output block it never
initialised.  The sender-major pass sorts the dead blocks after the live
ones and masks the sender windows no live block visits
(:func:`sender_walk`).

Eligibility is now a *VMEM budget* (``message_passing.kernel_supported``)
computed from ``block_e``, the window sizes and the hidden dims — constant
in N — instead of a node-count ceiling.

Static flags select the model variant (DESIGN.md §3.2): ``gate_mode`` in
{'mlp', 'identity', 'none'} and ``rel_mode`` in {'raw', 'inv1p'} cover
EGNN/FastEGNN, SchNet's Eq. 13 coordinate head, RF's normalised radial
field and MPNN's invariant aggregation with one kernel.

Fused backward (DESIGN.md §9)
-----------------------------
:func:`edge_pathway_bwd_fused` is the flash-attention-style fused backward:
the only forward residual is ``deg`` (one (N, 1) column — the masked-mean
denominators), and everything per-edge (messages, gates, silu
pre-activations) is *recomputed in VMEM* from the streamed x/h windows, so
the backward, like the forward, never materialises an O(E·hidden) tensor.
Gradients split by scatter target into two passes over the same banded
blocks:

  * **receiver-major pass** — the forward's block order: per receiver
    window accumulate dL/dx and dL/dh contributions through the receiver
    endpoint, plus *all nine weight/bias gradients* (full-resident output
    blocks, zeroed at the first grid step and accumulated across the
    whole sequential grid);
  * **sender-major pass** — the same blocks walked in sender-window
    order (:func:`sender_walk`: a stable argsort of the per-block sender
    windows, dead blocks last, scalar-prefetched like the window ids), so
    each sender window's blocks form one contiguous run and dL/dx, dL/dh
    can be accumulated into (swindow, ·) output blocks with the same
    init-on-first-block discipline.  Sender windows no live block
    touches are masked to zero afterwards.

The masked-mean ``inv = 1/max(deg, 1)`` is folded into the per-edge
upstream cotangents, so neither pass needs a normalisation epilogue.  The
edge mask ``em`` participates only as a multiplicative gate (masked slots
contribute exact zeros) and is **not differentiated** — ``ops.edge_pathway``
returns a zero cotangent for it, along with float0 for the integer
endpoints and zeros for a threaded layout.

Each pass's ``pallas_call`` carries its own ``name``
(``edge_pathway_fused_fwd``, ``edge_pathway_bwd_fused_recv``,
``edge_pathway_bwd_fused_send``), which the compiled program keeps in its
``op_name`` metadata, also where ``vmap`` wraps the call in a per-sample
loop: a profile tells the three passes apart by it.

Precision contract
------------------
Both directions take a static ``precision`` (``kernels.runtime.Precision``):
operands are cast to ``precision.compute`` before every dense weight
matmul while ``preferred_element_type=precision.accumulate`` keeps segment
sums and weight-gradient accumulation wide.  The f32 default contracts the
dense weight matmuls at full f32 precision (``_mm``).

The one-hot gathers and scatters are exact at one bf16 MXU pass: a one-hot
is exact in bf16, and a bf16 × bf16 product is exact in f32.  Under f32
compute every gathered or scattered value travels as three bf16 pieces
(:func:`split_pieces`, ``hi + mid + lo``), laid side by side in one
lane-dense operand (:func:`pack`): the node operands once per call in
XLA, the per-edge values per block in the kernel.  A gather returns each
value bit for bit; a scatter rounds only in its f32 accumulation.  The
split is exact for every finite f32 of magnitude 2**-103 or more (and
zero); below that a piece can be subnormal, and arithmetic that flushes
subnormals (XLA's) drops it.  Under bf16 compute a value is one piece,
itself rounded to bf16 (:func:`onehot_pieces`).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


class LayoutMeta(NamedTuple):
    """Static band geometry an :class:`EdgeLayout` was built at."""

    window: int
    swindow: int
    n_pad: int
    block_e: int


@jax.tree_util.register_pytree_node_class
class EdgeLayout:
    """Host-precomputed banded-CSR layout, as kernel operands (DESIGN.md §6.6).

    The array twin of ``data.radius_graph.BandedCSR``: endpoint indices are
    *global* (the kernel localises them with a cheap elementwise ``%`` —
    no trace-time argsort/scatter).  Registered pytree: the five arrays are
    children, so a layout batches/shards through ``jit`` / ``jax.vmap`` /
    ``shard_map`` like any other operand; ``meta`` — the static band
    geometry it was built at — rides along as aux data, letting the fused
    kernel verify it against its own :func:`pick_windows` derivation and
    fail loudly on a layout built for a different graph size or ``block_e``
    (``meta=None`` skips that check — capacity alignment is still
    enforced).
    """

    __slots__ = ("senders", "receivers", "edge_mask", "block_rwin",
                 "block_swin", "meta")

    def __init__(self, senders, receivers, edge_mask, block_rwin,
                 block_swin, meta: LayoutMeta | None = None):
        self.senders = senders  # (cap,) int32, banded order, masked slots = 0
        self.receivers = receivers  # (cap,)
        self.edge_mask = edge_mask  # (cap,)
        self.block_rwin = block_rwin  # (cap // block_e,) receiver-window/block
        self.block_swin = block_swin  # (cap // block_e,) sender-window/block
        self.meta = None if meta is None else LayoutMeta(*meta)

    def tree_flatten(self):
        return ((self.senders, self.receivers, self.edge_mask,
                 self.block_rwin, self.block_swin), self.meta)

    @classmethod
    def tree_unflatten(cls, meta, children):
        return cls(*children, meta=meta)


def layout_from_host(bcsr) -> EdgeLayout:
    """``data.radius_graph.BandedCSR`` (numpy) → kernel operand arrays."""
    return EdgeLayout(
        senders=jnp.asarray(bcsr.senders), receivers=jnp.asarray(bcsr.receivers),
        edge_mask=jnp.asarray(bcsr.edge_mask),
        block_rwin=jnp.asarray(bcsr.block_rwin),
        block_swin=jnp.asarray(bcsr.block_swin),
        meta=LayoutMeta(bcsr.window, bcsr.swindow, bcsr.n_pad, bcsr.block_e))

LANE = 128  # TPU lane width: one-hot minor dims should be multiples of this
DEFAULT_WINDOW = 512  # receiver-window rows (scatter band)
DEFAULT_SWINDOW = 2048  # sender-window rows (gather band)
#: scoped-VMEM limit every edge pallas_call is compiled with.  It is also
#: the limit XLA holds a kernel to once vmap has wrapped it in a per-sample
#: loop (batched scalar-prefetch operands), so raising it here would not
#: raise it there.  ``message_passing.kernel_supported`` budgets against
#: this same number, so eligibility and the compiler agree (DESIGN.md §3.2).
VMEM_LIMIT_BYTES = 16 * 2**20


def _compiler_params():
    # the grid is a sequential walk: output blocks are revisited across
    # consecutive steps (window runs, whole-grid weight-grad accumulators)
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def pick_windows(n_nodes: int, *, window: int | None = None,
                 swindow: int | None = None) -> tuple[int, int, int]:
    """Window policy: (window, swindow, n_pad) for an ``n_nodes`` graph.

    Small graphs degenerate to a single window (the dense formulation,
    minus the N-residency); large graphs tile at the default band sizes.
    Invariant: ``window | swindow`` and ``swindow | n_pad`` so every
    window boundary is block-aligned for the BlockSpec index maps.
    """
    base = _round_up(max(n_nodes, 1), LANE)
    if swindow is None:
        swindow = min(DEFAULT_SWINDOW, base)
    if window is None:
        window = swindow
        for cand in (DEFAULT_WINDOW, 256, LANE):
            if swindow % cand == 0:
                window = min(window, cand) if swindow > cand else window
                break
        if swindow % window != 0:  # pragma: no cover - policy invariant
            window = swindow
    assert swindow % window == 0, (window, swindow)
    n_pad = _round_up(max(n_nodes, 1), swindow)
    return window, swindow, n_pad


def layout_capacity(e: int, nw: int, nsw: int, block_e: int) -> int:
    """Static upper bound on banded-layout slots (DESIGN.md §3.1).

    Each nonempty (receiver-window × sender-window) band wastes at most
    ``block_e − 1`` padding slots; each empty receiver window still gets
    one all-masked block so its output block is visited (zeroed) exactly
    once.  Bands nonempty ≤ min(nw·nsw, e).  The bound has to hold any
    graph of ``e`` edges, so it budgets a part-filled block for every
    band; a layout fills a prefix of it, and the kernels walk only that
    prefix (:func:`live_block_count`), not the capacity tail.
    """
    used = e + min(nw * nsw, max(e, 1)) * (block_e - 1) + nw * block_e
    return _round_up(used, block_e)


def banded_layout(snd: Array, rcv: Array, em: Array, *, n_pad: int,
                  window: int, swindow: int, block_e: int):
    """Regroup edges into (receiver-window × sender-window) bands.

    Trace-time (jnp) mirror of the host-side
    ``data.radius_graph.banded_csr_layout`` — same stable grouping, so the
    two agree slot-for-slot (tested in ``tests/test_banded_csr.py``).

    Returns ``(snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks)``:
    window-local endpoint indices in banded order (capacity-padded; unused
    slots are 0 with em=0) plus per-block window coordinates for scalar
    prefetch.  Masked input slots (em=0) are placed in no band.
    ``n_blocks`` is static (from :func:`layout_capacity`).
    """
    e = snd.shape[0]
    nw = n_pad // window
    nsw = n_pad // swindow
    n_bands = nw * nsw
    cap = layout_capacity(e, nw, nsw, block_e)
    n_blocks = cap // block_e
    snd = snd.astype(jnp.int32)
    rcv = rcv.astype(jnp.int32)
    # masked slots go to a sentinel band past the last, which starts at the
    # capacity: their scatters drop, so they are placed in no band
    band = jnp.where(em != 0, (rcv // window) * nsw + snd // swindow,
                     n_bands)  # (E,)
    order = jnp.argsort(band, stable=True)
    bs = band[order]
    counts = jnp.zeros((n_bands + 1,), jnp.int32).at[bs].add(1)
    padded = ((counts[:n_bands] + block_e - 1) // block_e) * block_e
    # every receiver window gets ≥ 1 block so its output block is zeroed
    per_w = padded.reshape(nw, nsw).sum(axis=1)
    padded = (padded.reshape(nw, nsw)
              .at[:, 0].add(jnp.where(per_w == 0, block_e, 0))
              .reshape(-1))
    ends = jnp.cumsum(padded)
    offs = jnp.append(ends - padded, cap)
    gstart = jnp.cumsum(counts) - counts
    pos = offs[bs] + (jnp.arange(e, dtype=jnp.int32) - gstart[bs])
    put = lambda v, dt: jnp.zeros((cap,), dt).at[pos].set(v, mode="drop")
    snd_loc = put(snd[order] % swindow, jnp.int32)
    rcv_loc = put(rcv[order] % window, jnp.int32)
    em_b = put(em[order], em.dtype)
    bfirst = jnp.arange(n_blocks, dtype=jnp.int32) * block_e
    bid = jnp.searchsorted(ends, bfirst, side="right").astype(jnp.int32)
    # capacity-tail blocks (all-masked) extend the last receiver window's
    # contiguous run, so init/normalise stay once-per-window
    bid = jnp.where(bfirst < ends[-1], bid, n_bands - 1)
    block_rwin = bid // nsw
    block_swin = bid % nsw
    return snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks


def live_block_count(em_b: Array, block_rwin: Array, block_e: int) -> Array:
    """Grid steps a pass does work on, (1,) int32: 1 + the index of the
    last block that holds a live edge or opens its receiver window (whose
    output block it zeroes).  Every block past it is dead, so skipping it
    changes no sum.  The banded layouts (:func:`banded_layout` and its
    numpy and device twins) leave only the capacity tail past it
    (DESIGN.md §3.1)."""
    nb = block_rwin.shape[0]
    holds = jnp.any(em_b.reshape(nb, block_e) != 0, axis=1)
    opens = jnp.concatenate([jnp.ones((1,), bool),
                             block_rwin[1:] != block_rwin[:-1]])
    step = jnp.arange(1, nb + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(holds | opens, step, 0)).reshape(1)


def _live_only(body):
    """A kernel that takes the live count as its first scalar-prefetch ref
    and runs ``body`` on the live steps only, given the other refs and the
    grid step as ``step`` (read outside the ``pl.when``, where the
    interpreter can lower it)."""
    def kernel(nl_ref, *refs, **kw):
        step = pl.program_id(0)

        @pl.when(step < nl_ref[0])
        def _run():
            body(*refs, step=step, **kw)

    return kernel


def walk_coords(permuted: bool):
    """(edge block, receiver window, sender window) of a grid step, as
    functions of the index-map arguments: the step, the live count ``nl``
    and the scalar-prefetched block coordinates (``rw, sw``, or ``pm, rp,
    sp`` for the sender-major walk through the permutation ``pm``).  A
    step past the live count reads as the last live step, so it asks for
    the blocks already resident: no DMA, and no write-back of an output
    block it never initialised."""
    at = lambda i, nl: jnp.minimum(i, nl[0] - 1)
    if permuted:
        return (lambda i, nl, pm, rp, sp: pm[at(i, nl)],
                lambda i, nl, pm, rp, sp: rp[at(i, nl)],
                lambda i, nl, pm, rp, sp: sp[at(i, nl)])
    return (lambda i, nl, rw, sw: at(i, nl),
            lambda i, nl, rw, sw: rw[at(i, nl)],
            lambda i, nl, rw, sw: sw[at(i, nl)])


def sender_walk(block_rwin: Array, block_swin: Array, n_live: Array,
                nsw: int):
    """The sender-major pass's block order and what it visits: ``perm``
    sorts the live blocks by sender window (stable, so each window's
    blocks keep their receiver-major order) with the dead ones after them,
    and ``visited`` (nsw,) marks the sender windows a live block gathers
    from, whose output blocks the pass initialised."""
    live = jnp.arange(block_swin.shape[0]) < n_live[0]
    swin = jnp.where(live, block_swin, nsw)
    perm = jnp.argsort(swin, stable=True).astype(jnp.int32)
    visited = jnp.zeros((nsw,), bool).at[swin].set(True, mode="drop")
    return perm, block_rwin[perm], block_swin[perm], visited


def _mm(a: Array, b: Array, *, cdt, adt) -> Array:
    """The precision-contract matmul of the dense weights: compute-dtype
    operands, wide result.

    f32 compute asks Mosaic for full f32 contraction.  At its default
    precision an f32 dot runs as a bf16 pass: on a v5e a one-hot gather of
    unit-range values was then off by 2e-3, about 3 significant digits.
    The one-hot products do not go through here: they move exact bf16
    pieces (:func:`pack`).
    """
    prec = jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None
    return jnp.matmul(a.astype(cdt), b.astype(cdt), preferred_element_type=adt,
                      precision=prec)


def _silu_grad(u: Array) -> Array:
    s = jax.nn.sigmoid(u)
    return s * (1.0 + u * (1.0 - s))


# ------------------------------------------------ exact one-hot products
def onehot_pieces(compute_dtype) -> int:
    """bf16 pieces each value splits into for the one-hot products.

    Three under f32 compute: ``hi + mid + lo`` (:func:`split_pieces`) sums
    back to the value exactly for every finite f32 of magnitude 2**-103 or
    more (module docstring).  One under bf16 compute, whose operands
    already are bf16.
    """
    return 3 if jnp.dtype(compute_dtype) == jnp.float32 else 1


#: the sign, exponent and top 7 mantissa bits of an f32: a bf16's bits
_BF16_BITS = -(1 << 16)


def split_pieces(v: Array, pieces: int) -> list[Array]:
    """``v`` as ``pieces`` bf16 arrays: ``hi = trunc(v)``, ``mid = trunc(v −
    hi)``, ``lo = v − hi − mid``, where ``trunc`` keeps an f32's top 16
    bits (a bf16, exactly) and every difference is exact in f32.

    The pieces come from the bits, not from a rounding ``v →
    bf16 → f32`` round trip, which a compiler that allows excess precision
    may fold to ``v`` (XLA on the TPU does, leaving ``mid = lo = 0``).
    """
    out, rest = [], v.astype(jnp.float32)
    for k in range(pieces):
        p = rest
        if k < pieces - 1:
            p = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(rest, jnp.int32) & _BF16_BITS,
                jnp.float32)
            rest = rest - p
        out.append(p.astype(jnp.bfloat16))
    return out


def pack(cols: list[Array], pieces: int) -> Array:
    """``cols`` side by side, split into bf16 pieces: (rows, width) values
    → (rows, lanes) bf16 with piece k at lanes [k·width, (k+1)·width) and
    zeros up to a whole number of lane tiles."""
    v = jnp.concatenate([c.astype(jnp.float32) for c in cols], axis=-1)
    width = v.shape[-1]
    ps = split_pieces(v, pieces)
    pad = _round_up(pieces * width, LANE) - pieces * width
    if pad:
        ps.append(jnp.zeros(v.shape[:-1] + (pad,), jnp.bfloat16))
    return jnp.concatenate(ps, axis=-1)


def unpack(g: Array, width: int, pieces: int) -> Array:
    """Recombine the pieces of a product with a :func:`pack`-ed operand:
    (rows, lanes) → (rows, width), ``(hi + mid) + lo``."""
    out = g[:, :width]
    for k in range(1, pieces):
        out = out + g[:, k * width:(k + 1) * width]
    return out


def _gather(ids: Array, packed: Array, width: int, pieces: int,
            adt) -> Array:
    """Rows ``ids`` (BE, 1) of a window of a :func:`pack`-ed array: one
    bf16 one-hot matmul, exact — each output element is one piece times 1
    plus zeros."""
    oh = (ids == jax.lax.broadcasted_iota(
        jnp.int32, (ids.shape[0], packed.shape[0]), 1)).astype(jnp.bfloat16)
    return unpack(jnp.dot(oh, packed, preferred_element_type=adt), width,
                  pieces)


def _scatter(ids_row: Array, cols: list[Array], rows: int, pieces: int,
             adt) -> Array:
    """Sums of the edge values ``cols`` into a ``rows``-row window by the
    window-local ids ``ids_row`` (1, BE): the one-hot is built transposed,
    (rows, BE), from the lane-major ids.  Returns the packed sums; the
    pieces are recombined once, after the grid (:func:`unpack`)."""
    oh_t = (ids_row == jax.lax.broadcasted_iota(
        jnp.int32, (rows, ids_row.shape[1]), 0)).astype(jnp.bfloat16)
    return jnp.dot(oh_t, pack(cols, pieces), preferred_element_type=adt)


def _edge_kernel(
    rwin_ref, swin_ref,  # scalar-prefetched (n_blocks,) window coords
    snd_ref, rcv_ref, rcvt_ref, em_ref, xhr_ref, xhs_ref,
    w1r_ref, w1s_ref, w1d_ref, b1_ref, w2_ref, b2_ref,
    wg1_ref, bg1_ref, wg2_ref,
    acc_ref,
    *, step, gate_mode: str, rel_mode: str, clamp: float, compute: str,
    accum: str,
):
    b = step
    rwb = rwin_ref[b]
    rw_prev = jnp.where(b > 0, rwin_ref[jnp.maximum(b - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    mm = functools.partial(_mm, cdt=cdt, adt=adt)
    pieces = onehot_pieces(cdt)
    dh = w1r_ref.shape[0]

    @pl.when(rwb != rw_prev)  # first block of this receiver window
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    em = em_ref[...]  # (BE, 1)
    # Banded one-hot gathers (MXU-native segment ops): (BE, swindow)
    # against the sender window of packed [h | x], (BE, window) against
    # the receiver window — VMEM cost independent of N.  Masked slots
    # carry local index 0: they gather finite garbage and scatter em=0.
    hxs = _gather(snd_ref[...], xhs_ref[...], dh + 3, pieces, adt)
    hxr = _gather(rcv_ref[...], xhr_ref[...], dh + 3, pieces, adt)
    rel = hxr[:, dh:] - hxs[:, dh:]
    d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)  # (BE, 1)

    # φ1 layer 1 over [h_r | h_s | d²] with the weight matrix pre-split by
    # input slice; zero-width/zero-weight slices fall out as no-ops.
    t1 = jax.nn.silu(
        mm(hxr[:, :dh], w1r_ref[...])
        + mm(hxs[:, :dh], w1s_ref[...])
        + mm(d2, w1d_ref[...])
        + b1_ref[...]
    )
    msg = mm(t1, w2_ref[...]) + b2_ref[...]  # (BE, M) — never written to HBM
    out = [msg * em, em]

    if gate_mode != "none":
        if gate_mode == "mlp":
            gate = mm(jax.nn.silu(mm(msg, wg1_ref[...]) + bg1_ref[...]),
                      wg2_ref[...])
        else:  # 'identity': the (width-1) message is the gate
            gate = msg
        gate = jnp.clip(gate, -clamp, clamp)
        if rel_mode == "inv1p":
            rel = rel / (jnp.sqrt(d2 + 1e-12) + 1.0)
        out.append(rel * gate * em)
    # packed [Σ msg·em | Σ em | Σ rel·gate·em] of the receiver window
    acc_ref[...] += _scatter(rcvt_ref[...], out, acc_ref.shape[0], pieces,
                             adt)


def _specs(block_e: int, window: int, swindow: int, *, permuted: bool):
    """BlockSpec factories of a walk (:func:`walk_coords`): edge-id columns
    (BE, 1) and rows (1, BE), receiver and sender windows of ``k``-lane
    node operands, and whole arrays."""
    blk, rwin, swin = walk_coords(permuted)
    eblk = pl.BlockSpec((block_e, 1), lambda *i: (blk(*i), 0))
    erow = pl.BlockSpec((1, block_e), lambda *i: (0, blk(*i)))
    rblk = lambda k: pl.BlockSpec((window, k), lambda *i: (rwin(*i), 0))
    sblk = lambda k, **kw: pl.BlockSpec((swindow, k),
                                        lambda *i: (swin(*i), 0), **kw)
    full = lambda a: pl.BlockSpec(a.shape, lambda *i: (0,) * a.ndim)
    return eblk, erow, rblk, sblk, full


def _resolve_banded(x, h, snd, rcv, em, *, n, block_e, window, swindow,
                    layout, record: str | None):
    """Shared fwd/bwd banding step: host layout or trace-time regroup.

    Returns ``(snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks,
    n_live, x, h, n_pad, window, swindow)`` with x/h zero-padded to
    ``n_pad`` rows and the per-slot endpoints window-localised, all (cap,);
    ``n_live`` (1,) is the layout's :func:`live_block_count`.  ``record``
    names the dispatch event to log (None on the backward — the forward
    already recorded the pair's layout provenance, and double counts would
    skew the telemetry the regroup gates assert on).
    """
    window, swindow, n_pad = pick_windows(n, window=window, swindow=swindow)
    if layout is not None:
        meta = getattr(layout, "meta", None)
        if meta is not None and meta != LayoutMeta(window, swindow, n_pad,
                                                  block_e):
            raise ValueError(
                f"EdgeLayout was built at band geometry {meta}, but this "
                f"call derives LayoutMeta(window={window}, swindow={swindow}, "
                f"n_pad={n_pad}, block_e={block_e}) from the graph's padded "
                f"node count — rebuild the layout for this graph")
        cap = layout.senders.shape[0]
        if cap % block_e or layout.block_rwin.shape[0] * block_e != cap:
            raise ValueError(
                f"EdgeLayout capacity {cap} inconsistent with block_e="
                f"{block_e} × {layout.block_rwin.shape[0]} blocks — was the "
                f"layout built with a different block size?")
        if record is not None:
            from repro.core.message_passing import record_dispatch

            record_dispatch("edge_layout_host")
        n_blocks = cap // block_e
        # localise global endpoints to their windows: elementwise, no
        # argsort/scatter — this is NOT a regroup
        snd_loc = layout.senders.astype(jnp.int32) % swindow
        rcv_loc = layout.receivers.astype(jnp.int32) % window
        em_b = layout.edge_mask
        block_rwin = layout.block_rwin.astype(jnp.int32)
        block_swin = layout.block_swin.astype(jnp.int32)
    else:
        if record is not None:
            from repro.core.message_passing import record_dispatch

            record_dispatch("edge_layout_regroup")
        snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks = banded_layout(
            snd, rcv, em, n_pad=n_pad, window=window, swindow=swindow,
            block_e=block_e)
    if n_pad != n:
        pad = n_pad - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
    n_live = live_block_count(em_b, block_rwin, block_e)
    return (snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks, n_live,
            x, h, n_pad, window, swindow)


@functools.partial(
    jax.jit,
    static_argnames=("gate_mode", "rel_mode", "clamp", "block_e",
                     "window", "swindow", "interpret", "precision"),
)
def edge_pathway_fused(
    x: Array, h: Array, snd: Array, rcv: Array, em: Array,
    w1r: Array, w1s: Array, w1d: Array, b1: Array,
    w2: Array, b2: Array,
    wg1: Array, bg1: Array, wg2: Array,
    *, gate_mode: str = "mlp", rel_mode: str = "raw",
    clamp: float = math.inf, block_e: int = 128,
    window: int | None = None, swindow: int | None = None,
    interpret: bool | None = None, layout: EdgeLayout | None = None,
    precision=None,
):
    """See ``repro.kernels.ref.edge_pathway_ref`` for the exact contract.

    Shapes: x (N,3), h (N,Dh≥1), snd/rcv (E,) int32 receiver-sorted,
    em (E,); weights as 2-D matrices (row vectors for biases).  Returns
    (dx (N,3), mh (N,M), deg (N,1)) with masked-mean normalisation.

    ``window``/``swindow`` override the :func:`pick_windows` band policy
    (tests sweep them); the banded regrouping runs at trace time, so any
    edge order and any sender distribution are handled — receiver sorting
    only improves band fill, never correctness.

    ``layout`` supplies a host-precomputed :class:`EdgeLayout` (built by
    ``data.radius_graph.banded_csr_layout`` for the *same* N, band policy
    and ``block_e``): the trace-time regrouping is skipped entirely and
    ``snd``/``rcv``/``em`` are ignored by the forward (they remain the
    fused backward's regroup inputs in ``ops.edge_pathway``).

    ``interpret=None`` (default) auto-detects: compile on TPU, interpret
    elsewhere (``kernels.runtime.default_interpret``).  ``precision``
    (static: None / 'bf16' / a ``runtime.Precision``) selects the
    compute/accumulate dtype pair; outputs keep ``x.dtype``.
    """
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    n = x.shape[0]
    m = w2.shape[1]
    e = snd.shape[0]
    out_dt = x.dtype
    if e == 0:  # empty graph: nothing to reduce (edge-drop p=1.0 story)
        return (jnp.zeros((n, 3), out_dt), jnp.zeros((n, m), out_dt),
                jnp.zeros((n, 1), out_dt))
    (snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks, n_live, x, h,
     n_pad, window, swindow) = _resolve_banded(
        x, h, snd, rcv, em, n=n, block_e=block_e, window=window,
        swindow=swindow, layout=layout, record="fwd")
    cdt, adt = prec.compute_dtype, prec.accumulate_dtype
    pieces = onehot_pieces(cdt)
    # the node operands, cast to the compute dtype and packed once into
    # bf16 pieces: one (n_pad, lanes) array both endpoints gather from
    xh = pack([h.astype(cdt), x.astype(cdt)], pieces)
    ws = tuple(a.astype(cdt) for a in (w1r, w1s, w1d, b1, w2, b2,
                                       wg1, bg1, wg2))
    width = m + 1 + (3 if gate_mode != "none" else 0)  # [msg | em | dx]
    lanes = _round_up(pieces * width, LANE)
    eblk, erow, rblk, sblk, full = _specs(block_e, window, swindow,
                                          permuted=False)
    kernel = functools.partial(_live_only(_edge_kernel), gate_mode=gate_mode,
                               rel_mode=rel_mode, clamp=clamp,
                               compute=prec.compute, accum=prec.accumulate)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks,),
        in_specs=[
            eblk, eblk, erow, eblk,
            rblk(xh.shape[1]), sblk(xh.shape[1]),
            full(ws[0]), full(ws[1]), full(ws[2]), full(ws[3]), full(ws[4]),
            full(ws[5]), full(ws[6]), full(ws[7]), full(ws[8]),
        ],
        out_specs=rblk(lanes),
    )
    acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="edge_pathway_fused_fwd",
        out_shape=jax.ShapeDtypeStruct((n_pad, lanes), adt),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(n_live, block_rwin, block_swin, snd_loc[:, None], rcv_loc[:, None],
      rcv_loc[None, :], em_b.astype(adt)[:, None], xh, xh, *ws)
    sums = unpack(acc[:n], width, pieces)
    deg = sums[:, m:m + 1]
    inv = 1.0 / jnp.maximum(deg, 1.0)
    mh = sums[:, :m] * inv
    dx = sums[:, m + 1:] * inv if gate_mode != "none" else jnp.zeros((n, 3))
    return dx.astype(out_dt), mh.astype(out_dt), deg.astype(out_dt)


# ------------------------------------------------------------ fused backward
def _edge_bwd_common(snd, rcv, em, s_win, r_win, w1r, w1s, w1d, b1, w2, b2,
                     wg1, bg1, wg2, mm, pieces: int, adt, gate_mode: str,
                     rel_mode: str, clamp: float) -> dict:
    """Per-block recompute + upstream backprop shared by both bwd passes.

    Gathers the block's endpoints from the packed sender window ``s_win``
    (``[h | x]``) and receiver window ``r_win`` (``[h | x | inv | g_mh |
    g_dx]``), recomputes the forward chain (messages, gates,
    pre-activations) entirely in VMEM, then backpropagates the gathered
    output cotangents down to the per-edge quantities both passes scatter:
    ``g_pre1`` (E-block × H1 — the φ1 layer-1 cotangent, source of every
    dh and weight grad) and ``g_rel_tot`` (E-block × 3 — the total
    cotangent of ``x_r − x_s``).  The masked-mean ``inv`` and the edge
    mask are folded into the upstream here, so masked slots (which gather
    window-local index 0) produce exact zeros throughout.
    """
    dh, m = w1r.shape[0], w2.shape[1]
    gated = gate_mode != "none"
    hxs = _gather(snd, s_win, dh + 3, pieces, adt)
    rr = _gather(rcv, r_win, dh + 4 + m + (3 if gated else 0), pieces, adt)
    hs_e, xs = hxs[:, :dh], hxs[:, dh:]
    hr_e, xr = rr[:, :dh], rr[:, dh:dh + 3]
    rel = xr - xs
    d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
    pre1 = mm(hr_e, w1r) + mm(hs_e, w1s) + mm(d2, w1d) + b1
    t1 = jax.nn.silu(pre1)
    msg = mm(t1, w2) + b2
    scale = rr[:, dh + 3:dh + 4] * em  # per-edge upstream factor inv[r]·em
    g_msg = rr[:, dh + 4:dh + 4 + m] * scale
    g_rel = jnp.zeros_like(rel)
    g_d2 = jnp.zeros_like(d2)
    out = {}
    if gated:
        p = rr[:, dh + 4 + m:] * scale  # (BE, 3) cotangent of rel_used·gate
        if gate_mode == "mlp":
            gp1 = mm(msg, wg1) + bg1
            gt = jax.nn.silu(gp1)
            gate_pre = mm(gt, wg2)
        else:
            gate_pre = msg
        gate = jnp.clip(gate_pre, -clamp, clamp)
        if rel_mode == "inv1p":
            sd = jnp.sqrt(d2 + 1e-12)
            kf = 1.0 / (sd + 1.0)
            rel_used = rel * kf
        else:
            rel_used = rel
        g_gate = jnp.sum(p * rel_used, axis=-1, keepdims=True)
        g_rel_used = p * gate
        if math.isfinite(clamp):  # clip vjp: pass-through inside the band
            inside = (gate_pre >= -clamp) & (gate_pre <= clamp)
            g_gate = g_gate * inside.astype(g_gate.dtype)
        if gate_mode == "mlp":
            g_gp1 = mm(g_gate, wg2.T) * _silu_grad(gp1)
            g_msg = g_msg + mm(g_gp1, wg1.T)
            out.update(gt=gt, g_gp1=g_gp1, g_gate=g_gate)
        else:  # identity gate: M == 1, the message IS the gate
            g_msg = g_msg + g_gate
        if rel_mode == "inv1p":
            g_rel = g_rel_used * kf
            g_d2 = (jnp.sum(g_rel_used * rel, axis=-1, keepdims=True)
                    * (-(kf * kf) / (2.0 * sd)))
        else:
            g_rel = g_rel_used
    g_pre1 = mm(g_msg, w2.T) * _silu_grad(pre1)
    g_d2 = g_d2 + mm(g_pre1, w1d.T)
    out.update(hr_e=hr_e, hs_e=hs_e, d2=d2, t1=t1, msg=msg, g_msg=g_msg,
               g_pre1=g_pre1, g_rel_tot=g_rel + 2.0 * rel * g_d2)
    return out


def _edge_bwd_r_kernel(
    rwin_ref, swin_ref,
    snd_ref, rcv_ref, rcvt_ref, em_ref, r_ref, s_ref,
    w1r_ref, w1s_ref, w1d_ref, b1_ref, w2_ref, b2_ref,
    wg1_ref, bg1_ref, wg2_ref,
    accr_ref,
    dw1r_ref, dw1s_ref, dw1d_ref, db1_ref, dw2_ref, db2_ref,
    dwg1_ref, dbg1_ref, dwg2_ref,
    *, step, gate_mode: str, rel_mode: str, clamp: float, compute: str,
    accum: str,
):
    """Receiver-major backward pass: forward's block order, so receiver
    windows form contiguous runs — accumulates the receiver-endpoint x/h
    gradients per window (packed ``[dh | dx]`` sums) and every weight
    gradient across the whole grid."""
    b = step
    rwb = rwin_ref[b]
    rw_prev = jnp.where(b > 0, rwin_ref[jnp.maximum(b - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    mm = functools.partial(_mm, cdt=cdt, adt=adt)
    pieces = onehot_pieces(cdt)

    @pl.when(rwb != rw_prev)  # first block of this receiver window
    def _init_window():
        accr_ref[...] = jnp.zeros_like(accr_ref)

    @pl.when(b == 0)  # weight grads accumulate over the entire grid
    def _init_weight_grads():
        for r in (dw1r_ref, dw1s_ref, dw1d_ref, db1_ref, dw2_ref, db2_ref,
                  dwg1_ref, dbg1_ref, dwg2_ref):
            r[...] = jnp.zeros_like(r)

    c = _edge_bwd_common(
        snd_ref[...], rcv_ref[...], em_ref[...], s_ref[...], r_ref[...],
        w1r_ref[...], w1s_ref[...], w1d_ref[...], b1_ref[...], w2_ref[...],
        b2_ref[...], wg1_ref[...], bg1_ref[...], wg2_ref[...], mm, pieces,
        adt, gate_mode, rel_mode, clamp)
    # dL/dh_r, dL/dx_r += +g_rel
    accr_ref[...] += _scatter(
        rcvt_ref[...], [mm(c["g_pre1"], w1r_ref[...].T), c["g_rel_tot"]],
        accr_ref.shape[0], pieces, adt)
    dw1r_ref[...] += mm(c["hr_e"].T, c["g_pre1"])
    dw1s_ref[...] += mm(c["hs_e"].T, c["g_pre1"])
    dw1d_ref[...] += mm(c["d2"].T, c["g_pre1"])
    db1_ref[...] += jnp.sum(c["g_pre1"], axis=0, keepdims=True)
    dw2_ref[...] += mm(c["t1"].T, c["g_msg"])
    db2_ref[...] += jnp.sum(c["g_msg"], axis=0, keepdims=True)
    if gate_mode == "mlp":
        dwg1_ref[...] += mm(c["msg"].T, c["g_gp1"])
        dbg1_ref[...] += jnp.sum(c["g_gp1"], axis=0, keepdims=True)
        dwg2_ref[...] += mm(c["gt"].T, c["g_gate"])


def _edge_bwd_s_kernel(
    perm_ref, rwp_ref, swp_ref,
    snd_ref, rcv_ref, sndt_ref, em_ref, r_ref, s_ref,
    w1r_ref, w1s_ref, w1d_ref, b1_ref, w2_ref, b2_ref,
    wg1_ref, bg1_ref, wg2_ref,
    accs_ref,
    *, step, gate_mode: str, rel_mode: str, clamp: float, compute: str,
    accum: str,
):
    """Sender-major backward pass: the same blocks in sender-window order
    (``perm`` of :func:`sender_walk`, in every index map), so sender
    windows form contiguous runs and the sender-endpoint x/h gradients
    accumulate (packed ``[dh | dx]`` sums) with the standard
    init-on-first-block discipline."""
    del perm_ref, rwp_ref  # consumed by the BlockSpec index maps only
    j = step
    swb = swp_ref[j]
    sw_prev = jnp.where(j > 0, swp_ref[jnp.maximum(j - 1, 0)], -1)
    cdt, adt = jnp.dtype(compute), jnp.dtype(accum)
    mm = functools.partial(_mm, cdt=cdt, adt=adt)
    pieces = onehot_pieces(cdt)

    @pl.when(swb != sw_prev)  # first block of this sender window
    def _init_window():
        accs_ref[...] = jnp.zeros_like(accs_ref)

    c = _edge_bwd_common(
        snd_ref[...], rcv_ref[...], em_ref[...], s_ref[...], r_ref[...],
        w1r_ref[...], w1s_ref[...], w1d_ref[...], b1_ref[...], w2_ref[...],
        b2_ref[...], wg1_ref[...], bg1_ref[...], wg2_ref[...], mm, pieces,
        adt, gate_mode, rel_mode, clamp)
    # dL/dh_s, dL/dx_s −= g_rel
    accs_ref[...] += _scatter(
        sndt_ref[...], [mm(c["g_pre1"], w1s_ref[...].T), -c["g_rel_tot"]],
        accs_ref.shape[0], pieces, adt)


@functools.partial(
    jax.jit,
    static_argnames=("gate_mode", "rel_mode", "clamp", "block_e",
                     "window", "swindow", "interpret", "precision"),
)
def edge_pathway_bwd_fused(
    x: Array, h: Array, snd: Array, rcv: Array, em: Array,
    w1r: Array, w1s: Array, w1d: Array, b1: Array,
    w2: Array, b2: Array,
    wg1: Array, bg1: Array, wg2: Array,
    deg: Array, g_dx: Array, g_mh: Array,
    *, gate_mode: str = "mlp", rel_mode: str = "raw",
    clamp: float = math.inf, block_e: int = 128,
    window: int | None = None, swindow: int | None = None,
    interpret: bool | None = None, layout: EdgeLayout | None = None,
    precision=None,
):
    """Fused backward of :func:`edge_pathway_fused` (module docstring §9).

    Inputs are the forward primals, the forward's ``deg`` output (the only
    saved residual — one (N, 1) column), and the output cotangents
    ``g_dx`` (N, 3) / ``g_mh`` (N, M); the ``deg`` output's own cotangent
    is structurally zero (deg depends only on the non-differentiated edge
    mask).  Returns the 11 gradients
    ``(gx, gh, gw1r, gw1s, gw1d, gb1, gw2, gb2, gwg1, gbg1, gwg2)`` in the
    accumulate dtype — the caller casts back to primal dtypes.

    Matches ``jax.vjp(ref.edge_pathway_ref)`` on every (gate_mode,
    rel_mode) variant; nothing O(E·hidden) is stored or streamed — both
    passes recompute messages/gates per block in VMEM.
    """
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    adt = prec.accumulate_dtype
    cdt = prec.compute_dtype
    n = x.shape[0]
    e = snd.shape[0]
    weights = (w1r, w1s, w1d, b1, w2, b2, wg1, bg1, wg2)
    if e == 0:
        return tuple(jnp.zeros(a.shape, adt) for a in ((x, h) + weights))
    m = w2.shape[1]
    (snd_loc, rcv_loc, em_b, block_rwin, block_swin, n_blocks, n_live, x, h,
     n_pad, window, swindow) = _resolve_banded(
        x, h, snd, rcv, em, n=n, block_e=block_e, window=window,
        swindow=swindow, layout=layout, record=None)
    snd2, rcv2 = snd_loc[:, None], rcv_loc[:, None]
    em2 = em_b.astype(adt)[:, None]
    pad = n_pad - n
    g_dx = jnp.pad(g_dx.astype(adt), ((0, pad), (0, 0)))
    g_mh = jnp.pad(g_mh.astype(adt), ((0, pad), (0, 0)))
    # fold the masked-mean denominators into the upstream (pad rows get
    # inv=1 against zero cotangents — exact no-ops)
    inv = 1.0 / jnp.maximum(jnp.pad(deg.astype(adt), ((0, pad), (0, 0))), 1.0)
    x, h = x.astype(cdt), h.astype(cdt)
    ws = tuple(a.astype(cdt) for a in weights)
    dh = h.shape[1]
    pieces = onehot_pieces(cdt)
    # what each endpoint gathers, packed once into bf16 pieces: senders
    # [h | x], receivers [h | x | inv | g_mh | g_dx]
    s_pack = pack([h, x], pieces)
    r_pack = pack([h, x, inv, g_mh] + ([g_dx] if gate_mode != "none" else []),
                  pieces)
    lanes = _round_up(pieces * (dh + 3), LANE)  # packed [dh | dx] sums

    kw = dict(gate_mode=gate_mode, rel_mode=rel_mode, clamp=clamp,
              compute=prec.compute, accum=prec.accumulate)
    f = lambda shape: jax.ShapeDtypeStruct(shape, adt)

    # sender-window blocks are single-buffered in both backward passes:
    # their double buffers are the largest term of the VMEM budget, and
    # the window changes only at band boundaries
    one = dict(pipeline_mode=pl.Buffered(1))

    # ---- pass A: receiver-major (dx_r, dh_r, all weight grads) ----------
    eblk, erow, rblk, sblk, full = _specs(block_e, window, swindow,
                                          permuted=False)
    grid_a = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks,),
        in_specs=[
            eblk, eblk, erow, eblk,
            rblk(r_pack.shape[1]), sblk(s_pack.shape[1], **one),
            full(ws[0]), full(ws[1]), full(ws[2]), full(ws[3]), full(ws[4]),
            full(ws[5]), full(ws[6]), full(ws[7]), full(ws[8]),
        ],
        out_specs=(rblk(lanes),
                   full(ws[0]), full(ws[1]), full(ws[2]), full(ws[3]),
                   full(ws[4]), full(ws[5]), full(ws[6]), full(ws[7]),
                   full(ws[8])),
    )
    acc_r, *gws = pl.pallas_call(
        functools.partial(_live_only(_edge_bwd_r_kernel), **kw),
        grid_spec=grid_a,
        name="edge_pathway_bwd_fused_recv",
        out_shape=(f((n_pad, lanes)),) + tuple(f(a.shape) for a in weights),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(n_live, block_rwin, block_swin, snd2, rcv2, rcv_loc[None, :], em2,
      r_pack, s_pack, *ws)

    # ---- pass B: sender-major over the block permutation (dx_s, dh_s) ---
    perm, rw_p, sw_p, visited = sender_walk(block_rwin, block_swin, n_live,
                                            n_pad // swindow)
    eblk, erow, rblk, sblk, full = _specs(block_e, window, swindow,
                                          permuted=True)
    grid_b = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_blocks,),
        in_specs=[
            eblk, eblk, erow, eblk,
            rblk(r_pack.shape[1]), sblk(s_pack.shape[1], **one),
            full(ws[0]), full(ws[1]), full(ws[2]), full(ws[3]), full(ws[4]),
            full(ws[5]), full(ws[6]), full(ws[7]), full(ws[8]),
        ],
        out_specs=sblk(lanes, **one),
    )
    acc_s = pl.pallas_call(
        functools.partial(_live_only(_edge_bwd_s_kernel), **kw),
        grid_spec=grid_b,
        name="edge_pathway_bwd_fused_send",
        out_shape=f((n_pad, lanes)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(n_live, perm, rw_p, sw_p, snd2, rcv2, snd_loc[None, :], em2,
      r_pack, s_pack, *ws)
    # sender windows no live block gathers from are never visited → mask,
    # don't trust their (uninitialised) output blocks
    acc_s = jnp.where(jnp.repeat(visited, swindow)[:, None], acc_s, 0.0)
    g = unpack(acc_r[:n], dh + 3, pieces) + unpack(acc_s[:n], dh + 3, pieces)
    return (g[:, dh:], g[:, :dh], *gws)
