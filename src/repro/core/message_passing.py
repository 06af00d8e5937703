"""Shared equivariant message-passing substrate (DESIGN.md §2).

Every model in ``repro.models`` used to hand-roll the same real-real edge
pathway (Eq. 3 + the real parts of Eqs. 6-7): gather endpoint features,
run a small MLP over ``[h_i | h_j | ‖x_i−x_j‖² | e_ij]``, gate the edge
vector with a scalar head, and segment-reduce onto receivers with masked
degree normalisation.  This module is now the *only* place that pathway —
and the underlying masked segment reduction — lives:

  * :func:`edge_pathway` — the canonical gather → φ1 → gate → reduce hot
    path, parameterised by a static :class:`EdgeSpec` so that EGNN (full
    form), SchNet's Eq. 13 coordinate head (identity gate), RF (geometry
    only) and MPNN (no geometry) are all instances of one abstraction;
  * :func:`aggregate_edges` — the masked segment-reduce + degree
    normalisation primitive for models whose per-edge message does not fit
    the φ1 form (TFN's Cartesian tensor paths, SchNet's cfconv);
  * :func:`edge_rel_d2` / :func:`receiver_degree` — shared edge geometry.

When ``use_kernel=True`` and the spec is kernel-eligible (see
:func:`kernel_supported`), :func:`edge_pathway` dispatches to the fused
Pallas TPU kernel in ``repro.kernels.edge_message`` which never
materialises the ``(E, hidden)`` message tensor in HBM; otherwise it runs
the pure-jnp reference path below.  Both paths are validated against each
other in ``tests/test_kernels.py`` and ``tests/test_message_passing.py``.
"""
from __future__ import annotations

import math
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GeometricGraph
from repro.core.mlp import mlp

Array = jax.Array


class EdgeSpec(NamedTuple):
    """Static description of one model's edge pathway.

    use_h:       gather ``h_i, h_j`` into the φ1 input (EGNN/SchNet/MPNN).
    use_d2:      append ``‖x_i−x_j‖²`` to the φ1 input (all but MPNN).
    use_edge_attr: append ``e_ij`` to the φ1 input — only models whose φ1
                 is sized for it (EGNN's ``edge_attr_dim``); others ignore
                 any edge attributes on the graph.
    gate:        'mlp'      — scalar gate = φ_x(φ1(·)) (EGNN Eq. 6);
                 'identity' — φ1 itself emits the scalar gate (SchNet
                              Eq. 13, RF: the message *is* the gate);
                 'none'     — invariant-only pathway, no coordinate update
                              (MPNN, SchNet's cfconv).
    rel:         'raw'    — gate multiplies x_i − x_j (EGNN/SchNet);
                 'inv1p'  — gate multiplies (x_i − x_j)/(‖x_i−x_j‖+1)
                            (RF's normalised radial field).
    coord_clamp: clamp on the scalar gate (numerical stability).
    normalize:   divide segment sums by the masked receiver degree
                 (α_i = 1/|N(i)|); ``False`` → plain masked sum (cfconv).
    precision:   kernel compute precision — ``'f32'`` (default) or
                 ``'bf16'`` (bf16 compute, f32 accumulate; DESIGN.md §9).
                 Only the fused Pallas path honours it; the jnp path always
                 runs f32.
    """

    use_h: bool = True
    use_d2: bool = True
    use_edge_attr: bool = False
    gate: str = "mlp"
    rel: str = "raw"
    coord_clamp: float = math.inf
    normalize: bool = True
    precision: str = "f32"


class EdgePathwayOut(NamedTuple):
    dx: Optional[Array]  # (N, 3) coordinate update, None when gate == 'none'
    mh: Array  # (N, M) aggregated messages


def clamp_vector_norm(v: Array, max_norm: float) -> Array:
    """Equivariantly bound a (..., 3) update: rescale to ``max_norm`` when
    longer.  Componentwise ``jnp.clip`` would break E(3) equivariance the
    moment it binds (the clip box is axis-aligned); rescaling by an
    invariant factor preserves Prop. IV.1."""
    n = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True) + 1e-12)
    return v * jnp.minimum(1.0, max_norm / n)


def receiver_degree(g: GeometricGraph) -> Array:
    """Masked in-degree per node: Σ_{e: rcv(e)=i} edge_mask_e, (N,)."""
    return jax.ops.segment_sum(g.edge_mask, g.receivers,
                               num_segments=g.n_nodes)


def aggregate_edges(values: Array, g: GeometricGraph, *,
                    normalize: bool = True) -> Array:
    """Masked segment-reduce of per-edge values onto receivers.

    ``values``: (E, F) — already masked by the caller (multiplied by
    ``edge_mask``) or intrinsically zero on padded edges.  With
    ``normalize`` the sum is divided by ``max(deg_i, 1)`` (masked mean —
    the α_i = 1/|N(i)| aggregation every model here uses).
    """
    out = jax.ops.segment_sum(values, g.receivers, num_segments=g.n_nodes)
    if normalize:
        inv = 1.0 / jnp.maximum(receiver_degree(g), 1.0)
        out = out * inv.reshape((-1,) + (1,) * (values.ndim - 1))
    return out


def edge_rel_d2(x: Array, g: GeometricGraph) -> tuple[Array, Array]:
    """Edge vectors r_e = x_rcv − x_snd (E, 3) and ‖r_e‖² (E, 1)."""
    rel = x[g.receivers] - x[g.senders]
    return rel, jnp.sum(rel * rel, axis=-1, keepdims=True)


def _phi1_features(h: Array, d2: Array, g: GeometricGraph,
                   spec: EdgeSpec) -> Array:
    feats = []
    if spec.use_h:
        feats.append(h[g.receivers])
        feats.append(h[g.senders])
    if spec.use_d2:
        feats.append(d2)
    if spec.use_edge_attr and g.edge_attr.shape[-1] > 0:
        feats.append(g.edge_attr)
    return jnp.concatenate(feats, axis=-1)


def _scaled_rel(rel: Array, d2: Array, spec: EdgeSpec) -> Array:
    if spec.rel == "inv1p":
        # eps inside the sqrt: padded zero-edges otherwise give
        # d(sqrt)/d(d²) = ∞ and the masked-out gradient becomes 0·∞ = NaN.
        return rel / (jnp.sqrt(d2 + 1e-12) + 1.0)
    return rel


# --------------------------------------------------------------- telemetry
# Dispatch counters, incremented at *trace* time (dispatch is static).
# Tests and the distributed benches assert the fused path actually
# dispatched — and, when a host layout is supplied, that zero trace-time
# regroups happened — instead of inferring it from the absence of errors.
# Events: 'edge_kernel' / 'edge_jnp' (this module) and, with each
# 'edge_kernel', the pieces of its one-hot products: 'edge_onehot_split3'
# (f32 values as three exact bf16 pieces) or 'edge_onehot_bf16' (bf16
# compute, one piece); 'tfn_edge_kernel' / 'tfn_edge_jnp' (models.tfn's
# edge pathway); 'virtual_kernel' / 'virtual_jnp' (core.virtual_nodes),
# 'edge_layout_host' / 'edge_layout_regroup' (kernels.edge_message).
# Because jit caches traces, counts reflect *traces*, not executions:
# reset before building a fresh jitted program to observe its dispatch
# decisions.  Beside them, the data plane's layout telemetry
# ('edge_blocks_live' / 'edge_blocks_walked', ``data.layout_cache``):
# summed over every host layout handed out, the blocks before the live
# count and the blocks of the static grid; their ratio is the share of
# the edge kernels' grid steps that do work.  Locked: the stream's worker
# threads record concurrently.
DISPATCH_COUNTS: dict[str, int] = {}
_DISPATCH_LOCK = threading.Lock()


def record_dispatch(event: str, n: int = 1) -> None:
    with _DISPATCH_LOCK:
        DISPATCH_COUNTS[event] = DISPATCH_COUNTS.get(event, 0) + n


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def dispatch_counts() -> dict[str, int]:
    return dict(DISPATCH_COUNTS)


def dispatch_mode(counts: dict, use_kernel: bool, backend_mode: str) -> str:
    """Classify a traced program's edge dispatch for bench rows.

    The single home of the ``dist_kernel_mode`` semantics every bench
    writer records into ``BENCH_edge_kernel.json``: ``'jnp'`` when the
    kernel was never requested, ``backend_mode`` (``'tpu'`` /
    ``'interpret'``) when the fused path dispatched with zero trace-time
    regroups, ``'fallback'`` otherwise.
    """
    if not use_kernel:
        return "jnp"
    fused = counts.get("edge_kernel", 0) or counts.get("tfn_edge_kernel", 0)
    if fused and not counts.get("edge_layout_regroup", 0):
        return backend_mode
    return "fallback"


# Per-window VMEM budget of the banded-CSR tiling (DESIGN.md §3.2): the
# kernels' working set is bounded by the window sizes, not by N, so
# eligibility is a budget on the per-step VMEM footprint — constant in
# graph size.  The budget is the scoped-VMEM limit the kernels are compiled
# with (``edge_message.VMEM_LIMIT_BYTES``), and the footprint covers the
# fused backward, whose two passes are the largest of the three kernels.
EDGE_KERNEL_BLOCK_E = 128


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block: (8, 128) tiles of 32-bit words,
    (16, 128) for 16-bit ones — a width-3 block occupies 128 lanes."""
    sub = 8 * (4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def edge_kernel_vmem_bytes(n_nodes: int, dh: int, h1: int, m: int,
                           block_e: int = EDGE_KERNEL_BLOCK_E,
                           precision: str = "f32") -> int:
    """Per-grid-step VMEM footprint model of the fused edge kernels.

    Largest of the forward and the two backward passes at the
    :func:`pick_windows` band sizes, counting what each pass keeps in
    VMEM: the (block_e, ·) edge streams, the windows of the packed bf16
    node operands (``edge_message.pack``: ``[h | x]`` on the sender side,
    the backward's receiver side adds ``[inv | g_mh | g_dx]``), the packed
    f32 accumulators, each double-buffered but for the backward's
    single-buffered sender-window blocks; the weights in the compute
    dtype, the f32 weight-gradient accumulators, and the two bf16 gather
    one-hots.  Calibrated against the v5e compiler
    (``tests/test_tpu_compile.py``): at the default windows hidden 256
    (f32) / 512 (bf16) compiles and 320 (f32) / 576 (bf16) does not, and
    this model puts the 16 MiB budget between them.  For the first kernel
    it refuses of those two, the compiler reports 16.70 MiB (f32) and
    17.13 MiB (bf16); this model's forward and pass A read 17.1 and
    17.5 MiB there.  All terms are
    window-bounded — the model is independent of N once the windows
    saturate their defaults.
    """
    from repro.kernels.edge_message import LANE, onehot_pieces, pick_windows
    from repro.kernels.runtime import resolve_precision

    window, swindow, _ = pick_windows(n_nodes)
    cdt = resolve_precision(precision).compute_dtype
    c = cdt.itemsize
    p = onehot_pieces(cdt)
    f = 4
    t = _tile_bytes
    lanes = lambda width: -(-p * width // LANE) * LANE
    be, w, sw = block_e, window, swindow
    weights = ((dh, h1), (dh, h1), (1, h1), (1, h1), (h1, m), (1, m),
               (m, h1), (1, h1), (h1, 1))
    w_c = sum(t(r, k, c) for r, k in weights)
    w_f = sum(t(r, k, f) for r, k in weights)
    edges = 2 * (3 * t(be, 1, 4) + t(1, be, 4))
    one_hots = t(be, sw, 2) + t(be, w, 2)
    common = edges + w_c + one_hots
    ks, kr = lanes(dh + 3), lanes(dh + 4 + m + 3)  # gathered: send, receive
    kf, kb = lanes(m + 4), lanes(dh + 3)  # accumulated: fwd, bwd
    fwd = common + 2 * (t(sw, ks, 2) + t(w, ks, 2) + t(w, kf, f))
    bwd_in = common + t(sw, ks, 2) + 2 * t(w, kr, 2)
    bwd_a = bwd_in + 2 * t(w, kb, f) + w_f
    bwd_b = bwd_in + t(sw, kb, f)
    return max(fwd, bwd_a, bwd_b)


def kernel_supported(lp: dict, g: GeometricGraph, spec: EdgeSpec) -> bool:
    """Kernel-dispatch rule (DESIGN.md §3.2).

    The fused Pallas edge kernel implements exactly: 2-layer φ1 over
    ``[h_i | h_j | d²]``, 2-layer (or identity) gate, masked mean
    reduction.  Graph size no longer gates dispatch — the banded-CSR
    tiling bounds VMEM by the node windows, so the check is a per-window
    budget (:func:`edge_kernel_vmem_bytes`, forward and backward, against
    the scoped-VMEM limit the kernels compile with) that only unusually
    wide hidden dims can exceed.  Anything else — extra edge attributes,
    deeper MLPs, unnormalised sums — falls back to the jnp path.
    """
    from repro.kernels.edge_message import VMEM_LIMIT_BYTES

    if spec.use_edge_attr and g.edge_attr.shape[-1] > 0:
        return False
    if not spec.normalize:
        return False
    if len(lp["phi1"]) != 2:
        return False
    if spec.gate == "mlp" and len(lp.get("gate", ())) != 2:
        return False
    w1 = lp["phi1"][0]["w"]
    w2 = lp["phi1"][1]["w"]
    dh = g.feat_dim if spec.use_h else 1
    vmem = edge_kernel_vmem_bytes(g.n_nodes, dh, w1.shape[1], w2.shape[1],
                                  precision=spec.precision)
    return vmem <= VMEM_LIMIT_BYTES


def edge_pathway(lp: dict, h: Array, x: Array, g: GeometricGraph,
                 spec: EdgeSpec, *, use_kernel: bool = False,
                 layout=None) -> EdgePathwayOut:
    """The unified real-real edge pathway (Eq. 3 + real parts of Eqs. 6-7).

    ``lp`` holds ``"phi1"`` (the message MLP) and, when ``spec.gate ==
    'mlp'``, ``"gate"`` (the scalar coordinate head).  Returns the
    degree-normalised (or plain-sum) coordinate update ``dx`` and message
    aggregate ``mh``; ``dx`` is None for invariant-only specs.

    ``layout`` optionally supplies a host-precomputed banded-CSR layout
    (``kernels.edge_message.EdgeLayout``, built by
    ``data.radius_graph.banded_csr_layout`` at the default band policy for
    this graph's padded size) so the fused kernel skips its trace-time
    regrouping — the DistEGNN per-shard path (DESIGN.md §6.6).  Ignored by
    the jnp path and when the spec is not kernel-eligible.
    """
    if use_kernel and kernel_supported(lp, g, spec):
        from repro.kernels import ops as kops
        from repro.kernels.edge_message import onehot_pieces
        from repro.kernels.runtime import resolve_precision

        record_dispatch("edge_kernel")
        pieces = onehot_pieces(resolve_precision(spec.precision).compute_dtype)
        record_dispatch("edge_onehot_split3" if pieces == 3
                        else "edge_onehot_bf16")
        dx, mh = kops.edge_pathway(lp, h, x, g, spec, layout=layout)
        return EdgePathwayOut(dx=dx if spec.gate != "none" else None, mh=mh)
    record_dispatch("edge_jnp")

    rel, d2 = edge_rel_d2(x, g)
    msg = mlp(lp["phi1"], _phi1_features(h, d2, g, spec))  # (E, M)
    em = g.edge_mask[:, None]
    mh = aggregate_edges(msg * em, g, normalize=spec.normalize)
    if spec.gate == "none":
        return EdgePathwayOut(dx=None, mh=mh)
    gate = mlp(lp["gate"], msg) if spec.gate == "mlp" else msg
    gate = jnp.clip(gate, -spec.coord_clamp, spec.coord_clamp)
    dx_e = _scaled_rel(rel, d2, spec) * gate * em
    dx = aggregate_edges(dx_e, g, normalize=spec.normalize)
    return EdgePathwayOut(dx=dx, mh=mh)
