"""Device-resident recursive rollout over Verlet neighbor lists (DESIGN.md §10).

The paper's headline rollout claims (Figs. 3 & 7) need recursive
prediction: feed the model its own output, re-estimate velocities by
finite differences, repeat.  The naive loop drops to Python every step —
rebuild the radius graph, rebuild the banded layout, round-trip the
coordinates through numpy — so at Fluid113K scale the host rebuild dwarfs
the model step.  This module keeps the recursion *on device*:

* the neighbor list is built once at ``r + skin`` (a **Verlet list**) and
  reused: built at reference positions ``x_ref`` it contains every pair
  within ``r`` of each other until some node has moved more than
  ``skin/2`` from ``x_ref`` (two nodes approaching head-on close their gap
  at twice the per-node displacement — the factor 2 in
  :func:`~repro.data.radius_graph.displacement_exceeds_skin`);
* each step applies the *exact* radius-``r`` + drop-longest edge semantics
  as an **on-device mask** over the Verlet candidate list (so the model
  sees the same edge set it would on a fresh host build — the effective
  graph is independent of the rebuild schedule);
* a single jitted **chunk** function runs a ``lax.while_loop`` —
  mask → model → ``v = (x' − x) / dt`` → trajectory write — until the
  skin criterion (or the step budget) trips; the only per-chunk host
  traffic is one scalar fetch of the step count;
* when the criterion trips, the list + banded layout are rebuilt on the
  host.  With ``async_rebuild`` the rebuild is *submitted early* (at
  ``rebuild_margin`` of the skin budget) to the shared
  :func:`~repro.data.stream.shared_worker_pool` and the still-valid list
  keeps stepping while the build runs — the stale-list phase is bounded by
  **both** the old reference's skin budget and the pending build's
  reference (triangle inequality: each bound alone would let a pair close
  more than the skin), so the swapped-in list is valid by construction;
* all rebuilds reuse one (node, edge, band) capacity and one
  ``(window, swindow)`` geometry, so the chunk program **never retraces**:
  steady-state stepping is zero host transfers and zero recompiles, and
  the engine counts both (``RolloutResult.steady_state_d2h_bytes``,
  ``.recompiles``) so ``kernel_bench --gate-rollout`` can assert it.

:class:`RolloutEngine` is model-agnostic: it composes any ``PredictFn``
``(params, graph(B,·), layout|None) -> (B, N, 3)`` — in practice the one
``Pipeline._build_steps`` builds — and is surfaced as ``Pipeline.rollout``.
:class:`DistRolloutEngine` is the mesh sibling: the same while_loop chunk
runs *inside* ``shard_map`` (DESIGN.md §11), with the skin criterion
``pmax``-reduced across shards so every shard exits the loop on the same
step — one scalar fetch per chunk, not per step — and the partition
assignment frozen so every rebuild reuses the per-shard capacities and
banded layouts (zero retraces, zero steady-state d2h, same contract).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import GeometricGraph
from repro.data.cell_list import (auto_cell_cap, cell_occupancy,
                                  device_banded_layout, device_radius_build)
from repro.data.radius_graph import (banded_csr_layout, pad_edges, pad_nodes,
                                     radius_graph, sort_edges_by_receiver,
                                     warn_edge_truncation)

Array = jax.Array

#: extra edge capacity over the first build, absorbing density fluctuations
#: across rebuilds without a reshape (a breach truncates longest-first with
#: a warning — ``pad_edges``)
DEFAULT_EDGE_HEADROOM = 1.25

_DIVERGED_MSG = ("rollout diverged: non-finite coordinates after step {} — "
                 "train the model, shorten the horizon, or bound the "
                 "dynamics with wrap_box")


def _resolve_rebuild_mode(rebuild_mode: str, r_build: float,
                          want_async: Optional[bool]) -> str:
    """``'auto'`` → ``'device'`` when the cell list is eligible.

    Eligibility: a finite positive build radius (``r = inf`` means a fully
    connected graph — no cell structure to exploit).  An *explicit*
    ``async_rebuild=True`` keeps the host path: device rebuilds are
    synchronous jitted programs with nothing to overlap, so honoring the
    async request means host mode (DESIGN.md §13).
    """
    if rebuild_mode not in ("auto", "device", "host"):
        raise ValueError(f"rebuild_mode must be 'auto', 'device' or "
                         f"'host', got {rebuild_mode!r}")
    if rebuild_mode != "auto":
        return rebuild_mode
    if want_async is True or not (np.isfinite(r_build) and r_build > 0):
        return "host"
    return "device"


@dataclass
class RolloutResult:
    """What a rollout returns — trajectory plus the engine's accounting.

    ``trajectory`` is the predicted positions per step, real nodes only.
    ``per_step_mse`` (when targets were given) matches the historical
    benchmark metric: mean squared *coordinate* error, i.e. mean over
    nodes of ‖x̂ − x‖² / 3.  The remaining fields are the evidence for the
    engine's contract: ``steady_state_d2h_bytes`` counts device→host bytes
    moved *outside* rebuild/result boundaries (structurally zero — the
    while_loop body contains no host transfer), ``recompiles`` counts
    chunk retraces after the first (zero when every rebuild reuses the
    capacities), and ``chunk_calls ≤ 2·rebuild_count + 2`` bounds the jit
    dispatch overhead.  ``rebuild_waits`` counts async rebuilds that were
    not finished when the stale-list budget ran out (the host blocked).

    PR-10 (device rebuilds, DESIGN.md §13) tightens the contract:
    ``rebuild_mode`` records which path rebuilt the Verlet lists,
    ``coord_d2h_bytes`` counts coordinate fetches at rebuild boundaries
    and ``edge_h2d_bytes`` counts host-built edge/layout uploads *after*
    the first install — both exactly zero in ``'device'`` mode
    (``cell_overflows`` counts capacity adaptations, which re-run the
    rebuild on device without ever touching the host), where the only
    remaining rollout d2h is per-chunk/per-rebuild scalar fetches plus
    the final trajectory.  ``rebuild_s`` is host wall-time spent in
    (blocking) rebuild installs.
    """

    trajectory: np.ndarray  # (n_steps, n, 3)
    per_step_mse: Optional[np.ndarray]  # (n_steps,) | None
    rebuild_count: int
    steps_per_rebuild: float  # n_steps / (rebuild_count + 1)
    n_steps: int
    rebuild_steps: list = field(default_factory=list)  # step index of each swap
    trigger_steps: list = field(default_factory=list)  # step index of each submit
    rebuild_waits: int = 0
    chunk_calls: int = 0
    recompiles: int = 0
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    steady_state_d2h_bytes: int = 0
    rebuild_mode: str = "host"
    coord_d2h_bytes: int = 0
    edge_h2d_bytes: int = 0
    cell_overflows: int = 0
    rebuild_s: float = 0.0


def _nbytes(a) -> int:
    return int(np.asarray(a).size) * np.asarray(a).dtype.itemsize


class _Telemetry:
    """Shared transfer/retrace accounting for both engines.

    Byte counters track array payloads the engine itself moves (coordinate
    fetches at rebuilds, rebuilt edge/layout uploads, the per-chunk step
    count, the final trajectory fetch) — jit scalar operands are noise and
    not counted.  ``_fetch(·, steady=True)`` marks a transfer as happening
    *inside* the steady state; the engines only ever fetch at boundaries,
    so ``steady_d2h`` is structurally zero — the counter exists so any
    future host round-trip added to the hot path fails the bench gate
    instead of silently landing.
    """

    def __init__(self):
        self.d2h = 0
        self.h2d = 0
        self.steady_d2h = 0
        self.coord_d2h = 0  # coordinate fetches at rebuild boundaries
        self.edge_h2d = 0  # host-built edge/layout uploads
        self.d2h_fetches = 0
        self.traces = 0  # incremented at *trace time* in the jitted step
        self.rebuild_traces = 0  # same, for the device rebuild program

    def fetch(self, arr, steady: bool = False,
              coords: bool = False) -> np.ndarray:
        out = np.asarray(arr)
        b = out.size * out.dtype.itemsize
        self.d2h += b
        self.d2h_fetches += 1
        if steady:
            self.steady_d2h += b
        if coords:
            self.coord_d2h += b
        return out

    def uploaded(self, *arrays, edges: bool = False) -> None:
        b = sum(_nbytes(a) for a in arrays)
        self.h2d += b
        if edges:
            self.edge_h2d += b


def _step_edge_masks(x, snd, rcv, em, r2: float, p: float):
    """Per-step on-device edge selection over the Verlet candidate list.

    Recomputes squared lengths at the *current* positions and applies the
    exact host-build semantics: radius-``r`` filter, then Sec. VII-B
    drop-longest — ``n_keep = round((1−p)·n_valid)`` edges kept.  The
    selection is by *rank* under the lexicographic key ``(d², receiver,
    sender)``, not by a value threshold: every undirected pair appears as
    two directed edges with bitwise-identical d², so a value threshold
    would keep both twins whenever the cut splits a pair, where the host
    path (a stable argsort by d² over canonically (receiver, sender)-
    sorted edges — ``drop_longest_edges``) keeps exactly one.  The lex key
    reproduces that stable tie-break as a pure function of edge identity,
    so the same kept *set* falls out no matter the storage order — which
    is how the banded layout copy of the edges (a permutation of this
    multiset, masked by a second call to this function) stays consistent
    with the graph copy.  Masked-out edges contribute exact zeros to the
    segment sums and kept edges keep their receiver-sorted relative
    order, so the result is bitwise what a fresh host build at radius
    ``r`` would produce.
    """
    d = x[snd] - x[rcv]
    d2 = jnp.sum(d * d, axis=-1)
    valid = (em > 0) & (d2 <= r2)
    if p <= 0.0:
        return valid
    n_valid = jnp.sum(valid)
    n_keep = jnp.round((1.0 - p) * n_valid).astype(jnp.int32)
    key = jnp.where(valid, d2, jnp.inf)
    order = jnp.lexsort((snd, rcv, key))
    rank = jnp.zeros(order.shape, jnp.int32).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return valid & (rank < n_keep)


class RolloutEngine:
    """Jit-resident recursive rollout for the single-device path.

    ``predict_fn(params, graph(B=1,·), layout|None) -> (1, N, 3)`` is the
    model surface (compose ``Pipeline.predict_fn``); ``r``/``drop_rate``
    are the *model's* graph semantics, ``skin`` is purely an execution
    knob: the trajectory is (up to float ties at the cutoffs) independent
    of it, and ``skin=0`` degenerates to a synchronous rebuild-every-step
    oracle — the parity anchor ``tests/test_rollout.py`` pins.

    ``rebuild_mode`` selects where Verlet rebuilds run (DESIGN.md §13).
    ``'device'`` (the ``'auto'`` default whenever ``r + skin`` is finite
    and ``async_rebuild`` wasn't explicitly requested) rebuilds the edge
    list *and* banded layout in a second jitted program
    (``data/cell_list.py``) whose output is bitwise the host build at the
    same capacities — zero coordinate d2h, zero edge/layout h2d, only
    per-rebuild scalar flag fetches.  A cell-capacity overflow (density
    drifted past ``cell_cap``) adapts ``cell_cap`` from the reported
    occupancy and re-runs the retraced rebuild on the still-resident
    coordinates — the host path is never touched.  ``'host'`` is the
    PR-7 path:
    numpy builds on the worker pool, with ``async_rebuild`` (default: on
    whenever ``skin > 0``) submitting them at ``rebuild_margin`` of the
    skin budget while the still-valid list keeps stepping; see the module
    docstring for the two-reference validity argument.  Device rebuilds
    are synchronous by construction (nothing to overlap), so
    ``rebuild_mode='device'`` forces ``async_rebuild`` off.

    ``wrap_box`` applies periodic boundary conditions: each predicted
    position is wrapped into ``[0, wrap_box)^3`` *before* the
    finite-difference velocity is formed, so every quantity the model
    sees is bounded by the box (``|v| <= wrap_box * sqrt(3) / dt``) and
    the recursion cannot diverge over any horizon — the regime long
    benchmark rollouts of untrained models need.  The neighbour search
    is not minimum-image (pairs across a face are simply not found);
    nodes crossing a face register a ~box-sized displacement and
    trigger a rebuild, which is conservative and correct.
    """

    def __init__(self, predict_fn: Callable, *, r: float, skin: float,
                 dt: float, drop_rate: float = 0.0,
                 node_cap: Optional[int] = None,
                 edge_cap: Optional[int] = None,
                 with_layout: bool = False, block_e: Optional[int] = None,
                 async_rebuild: Optional[bool] = None,
                 rebuild_margin: float = 0.5,
                 edge_headroom: float = DEFAULT_EDGE_HEADROOM, pool=None,
                 wrap_box: Optional[float] = None,
                 rebuild_mode: str = "auto",
                 cell_cap: Optional[int] = None):
        if skin < 0:
            raise ValueError(f"skin must be >= 0, got {skin}")
        if not 0 < rebuild_margin <= 1:
            raise ValueError(f"rebuild_margin must be in (0, 1], got "
                             f"{rebuild_margin}")
        if wrap_box is not None and not wrap_box > 0:
            raise ValueError(f"wrap_box must be > 0, got {wrap_box}")
        self.predict_fn = predict_fn
        self.r = float(r)
        self.skin = float(skin)
        self.dt = float(dt)
        self.drop_rate = float(drop_rate)
        self.rebuild_margin = float(rebuild_margin)
        self.edge_headroom = float(edge_headroom)
        self.wrap_box = None if wrap_box is None else float(wrap_box)
        self.rebuild_mode = _resolve_rebuild_mode(
            rebuild_mode, self.r + self.skin, async_rebuild)
        self.async_rebuild = (self.rebuild_mode == "host"
                              and (skin > 0 if async_rebuild is None
                                   else bool(async_rebuild)))
        self.with_layout = bool(with_layout)
        self._node_cap = node_cap
        self._edge_cap = edge_cap
        self._block_e = block_e
        self._cell_cap = cell_cap
        self._pool = pool
        self._chunk = None
        self._rebuild = None  # jitted device rebuild program
        self._traj_cap = 0
        self._tel = _Telemetry()
        self._rebuild_s = 0.0
        self._cell_overflows = 0
        # filled by the first build
        self._g: Optional[GeometricGraph] = None
        self._lay = None
        self._n_real = 0
        self._window = self._swindow = self._lay_cap = None

    # ------------------------------------------------------------- host side
    def _host_build(self, x_np: np.ndarray) -> dict:
        """Rebuild the Verlet edge list (+ banded layout) at positions
        ``x_np`` — pure numpy, worker-thread safe.  Capacities and band
        geometry are pinned at the first build, so every product has the
        same shape and the jitted chunk never retraces."""
        snd, rcv = radius_graph(x_np, self.r + self.skin)
        snd, rcv = sort_edges_by_receiver(snd, rcv)
        sp, rp, em = pad_edges(snd, rcv, self._edge_cap, x_np)
        out = dict(senders=sp, receivers=rp, edge_mask=em)
        if self.with_layout:
            out["layout"] = banded_csr_layout(
                sp, rp, self._node_cap, edge_mask=em, window=self._window,
                swindow=self._swindow, block_e=self._block_e,
                capacity=self._lay_cap)
        return out

    def _install(self, build: dict) -> None:
        """Swap a host build in as the chunk's edge operands (B=1)."""
        from repro.kernels.edge_message import layout_from_host

        self._tel.uploaded(build["senders"], build["receivers"],
                           build["edge_mask"], edges=True)
        self._g = self._g._replace(
            senders=jnp.asarray(build["senders"])[None],
            receivers=jnp.asarray(build["receivers"])[None],
            edge_mask=jnp.asarray(build["edge_mask"])[None])
        if self.with_layout:
            bcsr = build["layout"]
            self._tel.uploaded(bcsr.senders, bcsr.receivers, bcsr.edge_mask,
                               bcsr.block_rwin, bcsr.block_swin, edges=True)
            self._lay = jax.tree.map(lambda a: a[None],
                                     layout_from_host(bcsr))

    # ---------------------------------------------------------- device side
    def _build_rebuild(self) -> Callable:
        """The second jitted program of device mode: cell-list edge build
        + banded layout, bitwise the host ``_host_build`` products at the
        pinned capacities (DESIGN.md §13).  Returns the device arrays plus
        a 4-scalar flag vector — the only bytes that cross to the host."""
        r_build = self.r + self.skin
        edge_cap, cell_cap = self._edge_cap, self._cell_cap
        node_cap = self._node_cap
        with_layout = self.with_layout
        window, swindow = self._window, self._swindow
        block_e, lay_cap = self._block_e, self._lay_cap

        def rebuild(x, nm):
            self._tel.rebuild_traces += 1
            db = device_radius_build(x, nm, r_build=r_build,
                                     edge_cap=edge_cap, cell_cap=cell_cap)
            lay = (device_banded_layout(
                db.senders, db.receivers, db.edge_mask, n_nodes=node_cap,
                window=window, swindow=swindow, block_e=block_e,
                capacity=lay_cap) if with_layout else None)
            flags = jnp.stack([
                jnp.isfinite(x).all().astype(jnp.int32),
                db.overflow.astype(jnp.int32), db.n_edges,
                db.max_occupancy])
            return db, lay, flags

        return jax.jit(rebuild)

    def _device_rebuild(self, x, step: int) -> None:
        """One device-mode rebuild: run the jitted build on the carried
        coordinates, fetch the 4-scalar flags, install.  A cell-capacity
        /grid overflow never touches the host path: the flags carry the
        exact max occupancy, so the engine adapts ``cell_cap``, retraces
        only the small rebuild program, and re-runs it on the same
        resident coordinates (``cell_cap`` is clamped at the node count,
        so the loop terminates — a cell can never hold more nodes than
        exist)."""
        t0 = time.perf_counter()
        if self._rebuild is None:
            self._rebuild = self._build_rebuild()
        db, lay, flags = self._rebuild(x, self._g.node_mask[0])
        f = self._tel.fetch(flags)
        if not f[0]:
            raise FloatingPointError(_DIVERGED_MSG.format(step))
        while f[1]:
            # densest cell outgrew cell_cap (or the grid outgrew the int32
            # key space): adapt and re-run on device — the coordinates
            # never leave the accelerator
            self._cell_overflows += 1
            self._cell_cap = min(self._n_real,
                                 max(auto_cell_cap(int(f[3])),
                                     self._cell_cap + 1))
            self._rebuild = self._build_rebuild()
            db, lay, flags = self._rebuild(x, self._g.node_mask[0])
            f = self._tel.fetch(flags)
        if int(f[2]) > self._edge_cap:
            warn_edge_truncation(int(f[2]), self._edge_cap,
                                 "longest-first")
        self._g = self._g._replace(
            senders=db.senders[None], receivers=db.receivers[None],
            edge_mask=db.edge_mask[None])
        if self.with_layout:
            self._lay = jax.tree.map(lambda a: a[None], lay)
        self._rebuild_s += time.perf_counter() - t0

    def _first_build(self, x0, v0, h) -> tuple[Array, Array]:
        """Size the capacities, build the B=1 graph template, install the
        first edge list.  Returns the device (x, v) state."""
        from repro.core.message_passing import EDGE_KERNEL_BLOCK_E
        from repro.kernels.edge_message import layout_capacity, pick_windows

        if self.wrap_box is not None:
            b = np.float32(self.wrap_box)
            x0 = x0 - b * np.floor(x0 / b)
        n = x0.shape[0]
        self._n_real = n
        self._node_cap = int(self._node_cap or n)
        if self._block_e is None:
            self._block_e = EDGE_KERNEL_BLOCK_E
        device = self.rebuild_mode == "device"
        # the engine state (and every rebuild) is f32 — building the first
        # list from the same f32 coordinates keeps it bitwise identical
        # across rebuild modes even for f64 inputs
        x32 = np.asarray(x0, np.float32)
        snd = rcv = None
        if self._edge_cap is None:
            # sizing pass — host numpy, but in device mode its edges are
            # never uploaded (the device rebuild installs the first list)
            snd, rcv = radius_graph(x32, self.r + self.skin)
            snd, rcv = sort_edges_by_receiver(snd, rcv)
            self._edge_cap = max(1, int(np.ceil(snd.size
                                                * self.edge_headroom)))
        self._window, self._swindow, n_pad = pick_windows(self._node_cap)
        nw, nsw = n_pad // self._window, n_pad // self._swindow
        self._lay_cap = layout_capacity(self._edge_cap, nw, nsw,
                                        self._block_e)
        if device and self._cell_cap is None:
            # clamped at n: occupancy can never exceed the node count, so
            # small scenes are overflow-proof by construction
            self._cell_cap = min(n, auto_cell_cap(
                cell_occupancy(x32, self.r + self.skin)))

        xp, nm = pad_nodes(x32, self._node_cap)
        vp, _ = pad_nodes(np.asarray(v0, np.float32), self._node_cap)
        hp, _ = pad_nodes(np.asarray(h, np.float32), self._node_cap)
        self._tel.uploaded(xp, vp, hp, nm)
        self._g = GeometricGraph(
            x=jnp.asarray(xp)[None], v=jnp.asarray(vp)[None],
            h=jnp.asarray(hp)[None],
            senders=jnp.zeros((1, self._edge_cap), jnp.int32),
            receivers=jnp.zeros((1, self._edge_cap), jnp.int32),
            edge_attr=jnp.zeros((1, self._edge_cap, 0), jnp.float32),
            node_mask=jnp.asarray(nm)[None],
            edge_mask=jnp.zeros((1, self._edge_cap), jnp.float32))
        if device:
            self._device_rebuild(self._g.x[0], 0)
        else:
            if snd is None:
                snd, rcv = radius_graph(x32, self.r + self.skin)
                snd, rcv = sort_edges_by_receiver(snd, rcv)
            sp, rp, em = pad_edges(snd, rcv, self._edge_cap, x32)
            self._install(dict(
                senders=sp, receivers=rp,
                edge_mask=em, layout=(banded_csr_layout(
                    sp, rp, self._node_cap, edge_mask=em,
                    window=self._window, swindow=self._swindow,
                    block_e=self._block_e, capacity=self._lay_cap)
                    if self.with_layout else None)))
        return self._g.x[0], self._g.v[0]

    # ----------------------------------------------------------- device side
    def _build_chunk(self) -> Callable:
        """The one jitted program: while_loop until the skin criterion,
        a second reference's criterion, or the step budget trips.

        Thresholds, references, start offset and budget are *operands*
        (device scalars/arrays), so phase A (single reference, trigger
        threshold) and phase B (old + pending references, full skin
        budget) share one trace.  The crossing is checked **before** each
        step — the body never applies a possibly-stale list.
        """
        r2 = np.float32(self.r) ** 2
        p = self.drop_rate
        dt = self.dt

        def chunk(params, g, lay, x, v, ref_a, ref_b, traj,
                  start, budget, lim_a2, lim_b2):
            self._tel.traces += 1
            nm = g.node_mask[0]
            snd, rcv, em = g.senders[0], g.receivers[0], g.edge_mask[0]

            def disp2(xc, ref):
                return jnp.max(jnp.sum((xc - ref) ** 2, axis=-1) * nm)

            def cond(c):
                i, x, _, _ = c
                return ((i < budget) & (disp2(x, ref_a) <= lim_a2)
                        & (disp2(x, ref_b) <= lim_b2))

            def body(c):
                i, x, v, traj = c
                keep = _step_edge_masks(x, snd, rcv, em, r2, p)
                gi = g._replace(x=x[None], v=v[None],
                                edge_mask=keep.astype(jnp.float32)[None])
                if lay is None:
                    li = None
                else:
                    lk = _step_edge_masks(x, lay.senders[0], lay.receivers[0],
                                          lay.edge_mask[0], r2, p)
                    li = type(lay)(lay.senders, lay.receivers,
                                   lk.astype(jnp.float32)[None],
                                   lay.block_rwin, lay.block_swin,
                                   meta=lay.meta)
                xp = self.predict_fn(params, gi, li)[0]
                xp = jnp.where(nm[:, None] > 0, xp, 0.0)
                if self.wrap_box is not None:
                    b = jnp.float32(self.wrap_box)
                    xp = xp - b * jnp.floor(xp / b)
                vn = (xp - x) / dt
                traj = jax.lax.dynamic_update_slice(
                    traj, xp[None], (start + i, 0, 0))
                return i + jnp.int32(1), xp, vn, traj

            i, x, v, traj = jax.lax.while_loop(
                cond, body, (jnp.int32(0), x, v, traj))
            return x, v, traj, i

        # donating the trajectory buffer keeps one live copy regardless of
        # horizon; CPU jit can't donate (warns), so gate on the backend
        donate = (7,) if jax.default_backend() != "cpu" else ()
        return jax.jit(chunk, donate_argnums=donate)

    # ------------------------------------------------------------------- run
    def run(self, params, x0, v0, h, n_steps: int, *,
            targets: Optional[np.ndarray] = None,
            traj_capacity: Optional[int] = None) -> RolloutResult:
        """Roll the model ``n_steps`` forward from ``(x0, v0, h)``.

        ``targets``, when given, must cover every step — ``targets[k]`` is
        the ground truth for step ``k+1``'s prediction; a short target
        array *raises* (comparing late predictions against a frozen last
        frame silently understates the error — size ``n_steps`` at the
        call site instead).

        The trajectory buffer is the one chunk operand whose shape depends
        on ``n_steps``, so it is allocated at the *largest* capacity any
        run of this engine has requested (monotone ``self._traj_cap``) and
        sliced to ``n_steps`` on fetch: re-running at any shorter length
        reuses the compiled chunk with zero retraces.  ``traj_capacity``
        pre-sizes it — a 2-step warmup with ``traj_capacity=40`` compiles
        the exact program a 40-step timed run dispatches.
        """
        from repro.data.stream import shared_worker_pool

        n_steps = int(n_steps)
        if n_steps <= 0:
            raise ValueError(f"n_steps must be positive, got {n_steps}")
        if targets is not None:
            targets = np.asarray(targets)
            if targets.shape[0] < n_steps:
                raise ValueError(
                    f"rollout targets cover {targets.shape[0]} steps but "
                    f"n_steps={n_steps}: refusing to clamp ground truth to "
                    f"the last frame (it silently understates late-step "
                    f"error) — pass n_steps <= len(targets) or more frames")

        tel = self._tel
        # engines are cached/reused: report per-run deltas, not lifetime sums
        base = (tel.d2h, tel.h2d, tel.steady_d2h)
        x, v = self._first_build(np.asarray(x0), np.asarray(v0),
                                 np.asarray(h))
        # warmup boundary: coordinate-d2h / edge-h2d deltas count rebuild
        # traffic only (the first install is the warmup the gate excludes)
        base2 = (tel.coord_d2h, tel.edge_h2d, self._rebuild_s,
                 self._cell_overflows)
        if self._chunk is None:
            self._chunk = self._build_chunk()
        n = self._n_real
        self._traj_cap = max(self._traj_cap, n_steps, int(traj_capacity or 0))
        traj = jnp.zeros((self._traj_cap, self._node_cap, 3), jnp.float32)

        inf = np.float32(np.inf)
        lim2 = np.float32((0.5 * self.skin) ** 2)
        trig2 = (np.float32((self.rebuild_margin * 0.5 * self.skin) ** 2)
                 if self.async_rebuild else lim2)
        pool = None
        x_ref = x
        pending = None  # (future, x_trigger) during an async build
        done = 0
        chunk_calls = 0
        waits = 0
        rebuild_steps: list[int] = []
        trigger_steps: list[int] = []
        base_traces = tel.traces
        while done < n_steps:
            if pending is None:  # phase A: fresh list, watch the trigger
                refs, lims = (x_ref, x_ref), (trig2, inf)
            else:  # phase B: stale list, bounded by old ref AND trigger ref
                refs, lims = (x_ref, pending[1]), (lim2, lim2)
            x, v, traj, i = self._chunk(
                params, self._g, self._lay, x, v, refs[0], refs[1], traj,
                np.int32(done), np.int32(n_steps - done), lims[0], lims[1])
            chunk_calls += 1
            done += int(tel.fetch(i))
            if done >= n_steps:
                break
            if pending is None:
                trigger_steps.append(done)
                if self.rebuild_mode == "device":
                    # rebuild is a second jitted program on the carried
                    # coordinates: no coordinate fetch, no edge upload —
                    # only the 4-scalar flag vector crosses to the host
                    # (divergence is checked from those flags)
                    self._device_rebuild(x, done)
                    x_ref = x
                    rebuild_steps.append(done)
                    continue
                x_np = tel.fetch(x, coords=True)[:n]
                if not np.isfinite(x_np).all():
                    # the skin criterion can never advance past NaN/Inf
                    # state (every displacement comparison is False), so
                    # without this check the loop would rebuild at the
                    # same positions forever
                    raise FloatingPointError(_DIVERGED_MSG.format(done))
                if self.async_rebuild:
                    if pool is None:
                        pool = self._pool or shared_worker_pool()
                    pending = (pool.submit(self._host_build, x_np), x)
                else:
                    t0 = time.perf_counter()
                    self._install(self._host_build(x_np))
                    self._rebuild_s += time.perf_counter() - t0
                    x_ref = x
                    rebuild_steps.append(done)
            else:
                fut, x_trig = pending
                if not fut.done():
                    waits += 1  # budget ran out before the build landed
                t0 = time.perf_counter()
                self._install(fut.result())
                self._rebuild_s += time.perf_counter() - t0
                x_ref = x_trig
                rebuild_steps.append(done)
                pending = None

        traj_np = tel.fetch(traj)[:n_steps, :n]
        mse = None
        if targets is not None:
            err = np.sum((traj_np - targets[:n_steps, :n]) ** 2, axis=-1)
            mse = np.mean(err, axis=-1) / 3.0
        rebuilds = len(rebuild_steps)
        return RolloutResult(
            trajectory=traj_np, per_step_mse=mse, rebuild_count=rebuilds,
            steps_per_rebuild=n_steps / (rebuilds + 1), n_steps=n_steps,
            rebuild_steps=rebuild_steps, trigger_steps=trigger_steps,
            rebuild_waits=waits, chunk_calls=chunk_calls,
            recompiles=max(0, tel.traces - base_traces
                           - (1 if base_traces == 0 else 0)),
            d2h_bytes=tel.d2h - base[0], h2d_bytes=tel.h2d - base[1],
            steady_state_d2h_bytes=tel.steady_d2h - base[2],
            rebuild_mode=self.rebuild_mode,
            coord_d2h_bytes=tel.coord_d2h - base2[0],
            edge_h2d_bytes=tel.edge_h2d - base2[1],
            cell_overflows=self._cell_overflows - base2[3],
            rebuild_s=self._rebuild_s - base2[2])


@dataclass
class BatchedRolloutResult:
    """What a batched rollout returns — per-scene trajectories plus the
    shared engine accounting.

    ``trajectories[j]`` is scene ``j``'s predicted positions, real nodes
    only — bitwise what an independent single-scene
    :class:`RolloutEngine` run at the same capacities would produce (the
    per-scene compute is the same vmapped program slot by slot, and the
    per-step masking makes the result independent of the batch-global
    rebuild schedule).  The telemetry fields carry the same contract as
    :class:`RolloutResult`: ``steady_state_d2h_bytes`` is structurally
    zero, ``recompiles`` counts chunk retraces after the first, and one
    rebuild covers *all* scenes (``rebuild_count`` is batch-global).

    ``rebuild_waits`` counts rebuilds where the *host* blocked the batch
    (batched rebuilds are synchronous, so in ``'host'`` mode every loop
    rebuild is a wait; ``'device'`` mode never involves the host — a
    ``cell_overflows`` adaptation re-runs the rebuild on device — so
    device waits are zero).  ``coord_d2h_bytes`` / ``edge_h2d_bytes``
    follow the :class:`RolloutResult` contract — zero in device mode
    after warmup.
    """

    trajectories: list  # per real scene: (n_steps, n_j, 3) float32
    n_steps: int
    n_scenes: int
    batch_size: int
    rebuild_count: int
    rebuild_steps: list = field(default_factory=list)
    chunk_calls: int = 0
    recompiles: int = 0
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    steady_state_d2h_bytes: int = 0
    rebuild_mode: str = "host"
    rebuild_waits: int = 0
    coord_d2h_bytes: int = 0
    edge_h2d_bytes: int = 0
    cell_overflows: int = 0
    rebuild_s: float = 0.0


class BatchedRolloutEngine:
    """Jit-resident rollout over a *stack* of same-capacity scenes.

    The serving plane's workhorse (DESIGN.md §12): ``batch_size`` scenes,
    every one padded to the same pinned ``(node_cap, edge_cap)`` capacity
    bucket and one band geometry, step together through a single vmapped
    ``lax.while_loop`` chunk.  The loop condition reduces the per-scene
    skin criteria with *any* (a max over the batched masked
    displacements²), so the chunk exits uniformly — every scene takes the
    same number of steps per chunk and a rebuild covers all scenes at
    once, with the per-scene host builds submitted to the shared worker
    pool concurrently.

    Per-scene results are bitwise equal to ``batch_size`` independent
    single-scene :class:`RolloutEngine` runs at the same capacities and
    seeds: the body vmaps the exact single-scene step (the same
    ``_step_edge_masks`` rank selection, the same ``PredictFn``), each
    batch slot's computation is slot-independent, and the any-reduced
    exit only changes *when* lists rebuild — which the per-step masking
    makes invisible (DESIGN.md §10).  ``tests/test_serving.py`` asserts
    the parity in both kernel modes.

    Unlike :class:`RolloutEngine`, every capacity is pinned at
    *construction* (serving knows its buckets up front), so the cache key
    ``(model, capacity bucket, band geometry, batch size)`` fully
    determines the compiled program: admitting any scene of the bucket
    never retraces.  A short batch (``len(scenes) < batch_size``) pads
    the remaining slots with replicas of the last scene — replicas
    compute identical trajectories (slot-independent determinism), so
    they never perturb the uniform exit, and they are dropped from the
    result.  Rebuilds are synchronous (but host-parallel across scenes);
    the trajectory buffer is donated between chunks and its capacity is
    monotone, so shorter re-runs reuse the compiled chunk.
    """

    def __init__(self, predict_fn: Callable, *, batch_size: int,
                 node_cap: int, edge_cap: int, r: float, skin: float,
                 dt: float, drop_rate: float = 0.0,
                 with_layout: bool = False, block_e: Optional[int] = None,
                 wrap_box: Optional[float] = None, pool=None,
                 rebuild_mode: str = "auto",
                 cell_cap: Optional[int] = None):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        if skin < 0:
            raise ValueError(f"skin must be >= 0, got {skin}")
        if wrap_box is not None and not wrap_box > 0:
            raise ValueError(f"wrap_box must be > 0, got {wrap_box}")
        from repro.core.message_passing import EDGE_KERNEL_BLOCK_E
        from repro.kernels.edge_message import layout_capacity, pick_windows

        self.predict_fn = predict_fn
        self.batch_size = int(batch_size)
        self.node_cap = int(node_cap)
        self.edge_cap = int(edge_cap)
        self.r = float(r)
        self.skin = float(skin)
        self.dt = float(dt)
        self.drop_rate = float(drop_rate)
        self.with_layout = bool(with_layout)
        self.wrap_box = None if wrap_box is None else float(wrap_box)
        self.rebuild_mode = _resolve_rebuild_mode(
            rebuild_mode, self.r + self.skin, None)
        self._block_e = int(block_e or EDGE_KERNEL_BLOCK_E)
        self._window, self._swindow, n_pad = pick_windows(self.node_cap)
        nw, nsw = n_pad // self._window, n_pad // self._swindow
        self._lay_cap = layout_capacity(self.edge_cap, nw, nsw,
                                        self._block_e)
        self._pool = pool
        self._chunk = None
        self._rebuild = None  # jitted (vmapped) device rebuild program
        self._cell_cap = cell_cap
        self._rebuild_s = 0.0
        self._cell_overflows = 0
        self._traj_cap = 0
        self._tel = _Telemetry()
        self._g: Optional[GeometricGraph] = None
        self._lay = None

    @property
    def band_geometry(self) -> tuple[int, int]:
        """(window, swindow) — the pinned band geometry, part of the
        serving program-cache key."""
        return (self._window, self._swindow)

    @property
    def traces(self) -> int:
        """Lifetime chunk traces (1 after the first run; serving's
        steady-state gate asserts it never grows again)."""
        return self._tel.traces

    # ------------------------------------------------------------- host side
    def _host_build_scene(self, x_np: np.ndarray) -> dict:
        """One scene's Verlet list (+ layout) at the pinned capacities —
        pure numpy, worker-thread safe (same product as
        :meth:`RolloutEngine._host_build`)."""
        snd, rcv = radius_graph(x_np, self.r + self.skin)
        snd, rcv = sort_edges_by_receiver(snd, rcv)
        sp, rp, em = pad_edges(snd, rcv, self.edge_cap, x_np)
        out = dict(senders=sp, receivers=rp, edge_mask=em)
        if self.with_layout:
            out["layout"] = banded_csr_layout(
                sp, rp, self.node_cap, edge_mask=em, window=self._window,
                swindow=self._swindow, block_e=self._block_e,
                capacity=self._lay_cap)
        return out

    def _build_scenes(self, scene_x: list) -> list:
        """All real scenes' host builds, concurrently on the worker pool."""
        from repro.data.stream import shared_worker_pool

        if len(scene_x) == 1:
            return [self._host_build_scene(scene_x[0])]
        pool = self._pool or shared_worker_pool()
        futs = [pool.submit(self._host_build_scene, x) for x in scene_x]
        return [f.result() for f in futs]

    def _install(self, builds: list, slot_src: list) -> None:
        """Swap per-scene host builds in as the stacked chunk operands.
        ``slot_src[b]`` maps batch slot ``b`` to its (real) scene build —
        padding slots replicate the last real scene."""
        from repro.kernels.edge_message import layout_from_host

        snd = np.stack([builds[j]["senders"] for j in slot_src])
        rcv = np.stack([builds[j]["receivers"] for j in slot_src])
        em = np.stack([builds[j]["edge_mask"] for j in slot_src])
        self._tel.uploaded(snd, rcv, em, edges=True)
        self._g = self._g._replace(
            senders=jnp.asarray(snd), receivers=jnp.asarray(rcv),
            edge_mask=jnp.asarray(em))
        if self.with_layout:
            for j in set(slot_src):
                b = builds[j]["layout"]
                self._tel.uploaded(b.senders, b.receivers, b.edge_mask,
                                   b.block_rwin, b.block_swin, edges=True)
            lays = [layout_from_host(builds[j]["layout"]) for j in slot_src]
            self._lay = jax.tree.map(lambda *a: jnp.stack(a), *lays)

    # ----------------------------------------------------------- device side
    def _build_rebuild(self) -> Callable:
        """Device rebuild for the whole batch: the single-scene cell-list
        build vmapped over the scene axis (one program, one dispatch for
        all ``batch_size`` slots)."""
        r_build = self.r + self.skin
        edge_cap, cell_cap = self.edge_cap, self._cell_cap
        node_cap, with_layout = self.node_cap, self.with_layout
        window, swindow = self._window, self._swindow
        block_e, lay_cap = self._block_e, self._lay_cap

        def one(x, nm):
            db = device_radius_build(x, nm, r_build=r_build,
                                     edge_cap=edge_cap, cell_cap=cell_cap)
            flags = jnp.stack([
                jnp.isfinite(x).all().astype(jnp.int32),
                db.overflow.astype(jnp.int32), db.n_edges,
                db.max_occupancy])
            if with_layout:
                lay = device_banded_layout(
                    db.senders, db.receivers, db.edge_mask,
                    n_nodes=node_cap, window=window, swindow=swindow,
                    block_e=block_e, capacity=lay_cap)
                return db, lay, flags
            return db, flags

        def rebuild(x, nm):
            self._tel.rebuild_traces += 1
            out = jax.vmap(one)(x, nm)
            if with_layout:
                return out
            db, flags = out
            return db, None, flags

        return jax.jit(rebuild)

    def _device_rebuild(self, x, step: int, ns: list) -> None:
        """One batch-global device rebuild.  A cell overflow in *any*
        scene adapts the shared ``cell_cap`` and re-runs the (retraced)
        rebuild on the same resident coordinates — no scene ever
        round-trips through the host, so device mode never blocks on a
        ``rebuild_wait``."""
        t0 = time.perf_counter()
        if self._rebuild is None:
            self._rebuild = self._build_rebuild()
        db, lay, flags = self._rebuild(x, self._g.node_mask)
        f = self._tel.fetch(flags)[:len(ns)]  # real scenes only
        if not f[:, 0].all():
            raise FloatingPointError(
                f"batched rollout diverged: non-finite coordinates "
                f"after step {step} — train the model, shorten the "
                f"horizon, or bound the dynamics with wrap_box")
        while f[:, 1].any():
            self._cell_overflows += 1
            self._cell_cap = min(self.node_cap,
                                 max(auto_cell_cap(int(f[:, 3].max())),
                                     self._cell_cap + 1))
            self._rebuild = self._build_rebuild()
            db, lay, flags = self._rebuild(x, self._g.node_mask)
            f = self._tel.fetch(flags)[:len(ns)]
        worst = int(f[:, 2].max())
        if worst > self.edge_cap:
            warn_edge_truncation(worst, self.edge_cap, "longest-first")
        self._g = self._g._replace(
            senders=db.senders, receivers=db.receivers,
            edge_mask=db.edge_mask)
        if self.with_layout:
            self._lay = lay
        self._rebuild_s += time.perf_counter() - t0

    # ----------------------------------------------------------- device side
    def _build_chunk(self) -> Callable:
        """The one jitted batched program: the single-scene while_loop body
        vmapped over the scene axis, the exit criterion any-reduced so all
        scenes leave the loop on the same step."""
        r2 = np.float32(self.r) ** 2
        p = self.drop_rate
        dt = self.dt

        def chunk(params, g, lay, x, v, ref, traj, start, budget, lim2):
            self._tel.traces += 1
            nm = g.node_mask  # (B, N)
            masks = jax.vmap(_step_edge_masks,
                             in_axes=(0, 0, 0, 0, None, None))

            def cond(c):
                i, xc, _, _ = c
                # any scene past its budget ⇒ uniform exit for the batch
                d2 = jnp.max(jnp.sum((xc - ref) ** 2, axis=-1) * nm)
                return (i < budget) & (d2 <= lim2)

            def body(c):
                i, xc, vc, traj = c
                keep = masks(xc, g.senders, g.receivers, g.edge_mask, r2, p)
                gi = g._replace(x=xc, v=vc,
                                edge_mask=keep.astype(jnp.float32))
                if lay is None:
                    li = None
                else:
                    lk = masks(xc, lay.senders, lay.receivers,
                               lay.edge_mask, r2, p)
                    li = type(lay)(lay.senders, lay.receivers,
                                   lk.astype(jnp.float32),
                                   lay.block_rwin, lay.block_swin,
                                   meta=lay.meta)
                xp = self.predict_fn(params, gi, li)  # (B, N, 3)
                xp = jnp.where(nm[..., None] > 0, xp, 0.0)
                if self.wrap_box is not None:
                    b = jnp.float32(self.wrap_box)
                    xp = xp - b * jnp.floor(xp / b)
                vn = (xp - xc) / dt
                traj = jax.lax.dynamic_update_slice(
                    traj, xp[:, None], (0, start + i, 0, 0))
                return i + jnp.int32(1), xp, vn, traj

            i, x, v, traj = jax.lax.while_loop(
                cond, body, (jnp.int32(0), x, v, traj))
            return x, v, traj, i

        donate = (6,) if jax.default_backend() != "cpu" else ()
        return jax.jit(chunk, donate_argnums=donate)

    # ------------------------------------------------------------------- run
    def run(self, params, scenes, n_steps: int, *,
            traj_capacity: Optional[int] = None,
            on_chunk: Optional[Callable] = None) -> BatchedRolloutResult:
        """Roll 1..``batch_size`` scenes forward together.

        ``scenes`` is a sequence of ``(x0, v0, h)`` numpy triples, each
        with at most ``node_cap`` nodes (a larger scene belongs to a
        larger capacity bucket — it raises here).  ``on_chunk``, when
        given, streams: after every chunk it is called with
        ``(start_step, frames)`` where ``frames`` is the
        ``(n_scenes, k, node_cap, 3)`` block of freshly computed
        positions for steps ``start_step..start_step+k`` — clients see
        frames at rebuild boundaries, before the horizon completes; the
        final result is then assembled from the streamed blocks (no
        second trajectory fetch).
        """
        n_steps = int(n_steps)
        if n_steps <= 0:
            raise ValueError(f"n_steps must be positive, got {n_steps}")
        scenes = list(scenes)
        if not 1 <= len(scenes) <= self.batch_size:
            raise ValueError(
                f"got {len(scenes)} scenes for a batch_size="
                f"{self.batch_size} engine (need 1..{self.batch_size})")
        n_real = len(scenes)
        slot_src = (list(range(n_real))
                    + [n_real - 1] * (self.batch_size - n_real))
        tel = self._tel
        base = (tel.d2h, tel.h2d, tel.steady_d2h)
        base_traces = tel.traces

        xs, vs, hs, ns, nms = [], [], [], [], []
        for (x0, v0, h) in scenes:
            x0 = np.asarray(x0, np.float32)
            if self.wrap_box is not None:
                b = np.float32(self.wrap_box)
                x0 = x0 - b * np.floor(x0 / b)
            n = x0.shape[0]
            if n > self.node_cap:
                raise ValueError(
                    f"scene has {n} nodes but this engine's capacity "
                    f"bucket is node_cap={self.node_cap} — route it to a "
                    f"larger bucket")
            xp, nm = pad_nodes(x0, self.node_cap)
            vp, _ = pad_nodes(np.asarray(v0, np.float32), self.node_cap)
            hp, _ = pad_nodes(np.asarray(h, np.float32), self.node_cap)
            xs.append(xp)
            vs.append(vp)
            hs.append(hp)
            nms.append(nm)
            ns.append(n)
        xq = np.stack([xs[j] for j in slot_src])
        vq = np.stack([vs[j] for j in slot_src])
        hq = np.stack([hs[j] for j in slot_src])
        nmq = np.stack([nms[j] for j in slot_src])
        tel.uploaded(xq, vq, hq, nmq)
        self._g = GeometricGraph(
            x=jnp.asarray(xq), v=jnp.asarray(vq), h=jnp.asarray(hq),
            senders=jnp.zeros((self.batch_size, self.edge_cap), jnp.int32),
            receivers=jnp.zeros((self.batch_size, self.edge_cap), jnp.int32),
            edge_attr=jnp.zeros((self.batch_size, self.edge_cap, 0),
                                jnp.float32),
            node_mask=jnp.asarray(nmq),
            edge_mask=jnp.zeros((self.batch_size, self.edge_cap),
                                jnp.float32))
        device = self.rebuild_mode == "device"
        scene_x0 = [xs[j][:ns[j]] for j in range(n_real)]
        if device:
            if self._cell_cap is None:
                self._cell_cap = min(self.node_cap, auto_cell_cap(
                    max(cell_occupancy(sx, self.r + self.skin)
                        for sx in scene_x0)))
            self._device_rebuild(self._g.x, 0, ns)
        else:
            self._install(self._build_scenes(scene_x0), slot_src)
        # warmup boundary: the first install (and in device mode its
        # rebuild-program trace) is setup cost, not steady rebuild traffic
        base2 = (tel.coord_d2h, tel.edge_h2d, self._rebuild_s,
                 self._cell_overflows)
        if self._chunk is None:
            self._chunk = self._build_chunk()
        self._traj_cap = max(self._traj_cap, n_steps, int(traj_capacity or 0))
        traj = jnp.zeros((self.batch_size, self._traj_cap, self.node_cap, 3),
                         jnp.float32)

        lim2 = np.float32((0.5 * self.skin) ** 2)
        x, v = self._g.x, self._g.v
        ref = x
        done = 0
        chunk_calls = 0
        waits = 0
        rebuild_steps: list[int] = []
        parts: list[np.ndarray] = []  # streamed frame blocks
        while done < n_steps:
            x, v, traj, i = self._chunk(
                params, self._g, self._lay, x, v, ref, traj,
                np.int32(done), np.int32(n_steps - done), lim2)
            chunk_calls += 1
            k = int(tel.fetch(i))
            if on_chunk is not None:
                new = tel.fetch(traj[:, done:done + k])
                parts.append(new)
                on_chunk(done, new[:n_real])
            done += k
            if done >= n_steps:
                break
            if device:
                self._device_rebuild(x, done, ns)
                ref = x
                rebuild_steps.append(done)
                continue
            x_np = tel.fetch(x, coords=True)
            scene_x = [x_np[j, :ns[j]] for j in range(n_real)]
            if not all(np.isfinite(sx).all() for sx in scene_x):
                raise FloatingPointError(
                    f"batched rollout diverged: non-finite coordinates "
                    f"after step {done} — train the model, shorten the "
                    f"horizon, or bound the dynamics with wrap_box")
            t0 = time.perf_counter()
            self._install(self._build_scenes(scene_x), slot_src)
            self._rebuild_s += time.perf_counter() - t0
            waits += 1  # batched host rebuilds are always blocking
            ref = x
            rebuild_steps.append(done)
        if on_chunk is not None:
            full = np.concatenate(parts, axis=1)
        else:
            full = tel.fetch(traj)[:, :n_steps]
        trajectories = [full[j, :n_steps, :ns[j]] for j in range(n_real)]
        return BatchedRolloutResult(
            trajectories=trajectories, n_steps=n_steps, n_scenes=n_real,
            batch_size=self.batch_size,
            rebuild_count=len(rebuild_steps), rebuild_steps=rebuild_steps,
            chunk_calls=chunk_calls,
            recompiles=max(0, tel.traces - base_traces
                           - (1 if base_traces == 0 else 0)),
            d2h_bytes=tel.d2h - base[0], h2d_bytes=tel.h2d - base[1],
            steady_state_d2h_bytes=tel.steady_d2h - base[2],
            rebuild_mode=self.rebuild_mode, rebuild_waits=waits,
            coord_d2h_bytes=tel.coord_d2h - base2[0],
            edge_h2d_bytes=tel.edge_h2d - base2[1],
            cell_overflows=self._cell_overflows - base2[3],
            rebuild_s=self._rebuild_s - base2[2])


class DistRolloutEngine:
    """Mesh-path rollout: the while_loop chunk *inside* ``shard_map``.

    ``apply_full(params, cfg, g, axis_name=..., edge_layout=...)`` is the
    registry per-shard forward (``Pipeline.apply_full``) — the engine
    wraps it in its own ``shard_map`` because the pipeline's jitted
    ``shard_map`` forward cannot nest inside another one.  Each shard
    carries its local (x, v) and steps its Verlet list exactly like
    :class:`RolloutEngine`; the skin criterion is the ``pmax`` across
    shards of the local masked max displacement², so the ``lax.while_loop``
    condition is *uniform* — every shard exits on the same step and the
    only per-chunk host traffic is one step-count fetch (steady-state
    d2h is structurally zero, the property ``--gate-rollout`` asserts).

    The partition assignment is computed **once** at the initial positions
    and frozen for the whole rollout — shard membership changing mid-
    trajectory would reshuffle every carried buffer; with the per-shard
    node/edge/band capacities also pinned at the first build, rebuilds
    swap operands under one fixed program (zero retraces).  Rebuilds run
    the PR-7 two-reference async protocol per shard: the build is
    submitted at ``rebuild_margin`` of the skin budget and the stale list
    keeps stepping, bounded by both the old reference and the pending
    build's reference (DESIGN.md §10.5 / §11).
    """

    def __init__(self, apply_full: Callable, cfg, mesh, *, r: float,
                 skin: float, dt: float, drop_rate: float = 0.0,
                 strategy: str = "random", seed: int = 0,
                 n_cap: Optional[int] = None, e_cap: Optional[int] = None,
                 async_rebuild: Optional[bool] = None,
                 rebuild_margin: float = 0.5,
                 edge_headroom: float = DEFAULT_EDGE_HEADROOM, pool=None,
                 wrap_box: Optional[float] = None,
                 rebuild_mode: str = "auto",
                 cell_cap: Optional[int] = None):
        if skin < 0:
            raise ValueError(f"skin must be >= 0, got {skin}")
        if not 0 < rebuild_margin <= 1:
            raise ValueError(f"rebuild_margin must be in (0, 1], got "
                             f"{rebuild_margin}")
        if wrap_box is not None and not wrap_box > 0:
            raise ValueError(f"wrap_box must be > 0, got {wrap_box}")
        self.apply_full = apply_full
        self.cfg = cfg
        self.mesh = mesh
        self.d = int(mesh.devices.size)
        self.r = float(r)
        self.skin = float(skin)
        self.dt = float(dt)
        self.drop_rate = float(drop_rate)
        self.strategy = strategy
        self.seed = int(seed)
        self.rebuild_margin = float(rebuild_margin)
        self.edge_headroom = float(edge_headroom)
        self.wrap_box = None if wrap_box is None else float(wrap_box)
        self.rebuild_mode = _resolve_rebuild_mode(
            rebuild_mode, self.r + self.skin, async_rebuild)
        self.async_rebuild = (self.rebuild_mode == "host"
                              and (skin > 0 if async_rebuild is None
                                   else bool(async_rebuild)))
        self._n_cap = n_cap
        self._e_cap = e_cap
        self._cell_cap = cell_cap
        self._rebuild = None  # jitted shard_map device rebuild program
        self._rebuild_s = 0.0
        self._cell_overflows = 0
        self._pool = pool
        self._tel = _Telemetry()
        self._chunk = None
        self._traj_cap = 0
        self._idx = None  # per-shard global node indices (frozen)

    def _freeze_assignment(self, x0: np.ndarray) -> None:
        from repro.data.partition import (metis_like_partition,
                                          random_partition)

        n = x0.shape[0]
        rng = np.random.default_rng(self.seed)
        if self.strategy == "random":
            assign = random_partition(rng, n, self.d)
        elif self.strategy == "metis":
            gs, gr = radius_graph(x0, self.r + self.skin)
            assign = metis_like_partition(x0, gs, gr, self.d)
        else:
            raise ValueError(f"unknown partition strategy "
                             f"{self.strategy!r}")
        self._idx = [np.nonzero(assign == p)[0] for p in range(self.d)]
        if self._n_cap is None:
            self._n_cap = max(1, max(i.size for i in self._idx))

    def _host_build(self, x: np.ndarray, v: np.ndarray, h: np.ndarray):
        """Per-shard Verlet lists + layouts at frozen assignment → stacked
        numpy ShardedBatch fields (B=1)."""
        from repro.data.partition import shard_layout_fields
        from repro.distributed.dist_egnn import ShardedBatch

        shards = []
        for idx in self._idx:
            xs = x[idx]
            snd, rcv = radius_graph(xs, self.r + self.skin)
            snd, rcv = sort_edges_by_receiver(snd, rcv)
            shards.append((xs, v[idx], h[idx], snd, rcv))
        if self._e_cap is None:
            e_max = max(1, max(s[3].size for s in shards))
            self._e_cap = max(1, int(np.ceil(e_max * self.edge_headroom)))
        cols = {k: [] for k in ("x", "v", "h", "x_target", "senders",
                                "receivers", "node_mask", "edge_mask")}
        for xs, vs, hs, snd, rcv in shards:
            xp, nm = pad_nodes(np.asarray(xs, np.float32), self._n_cap)
            vp, _ = pad_nodes(np.asarray(vs, np.float32), self._n_cap)
            hp, _ = pad_nodes(np.asarray(hs, np.float32), self._n_cap)
            sp, rp, em = pad_edges(snd, rcv, self._e_cap, xs)
            cols["x"].append(xp)
            cols["v"].append(vp)
            cols["h"].append(hp)
            cols["x_target"].append(xp)
            cols["senders"].append(sp)
            cols["receivers"].append(rp)
            cols["node_mask"].append(nm)
            cols["edge_mask"].append(em)
        base = {k: np.stack(vv) for k, vv in cols.items()}
        lay = shard_layout_fields(base["senders"], base["receivers"],
                                  base["edge_mask"], self._n_cap)
        lay.pop("lay_window_offsets", None)
        fields = {**base, **lay}
        return {f: np.stack([fields[f]], axis=1)
                for f in ShardedBatch._fields}

    def _install(self, host: dict):
        from repro.distributed.dist_egnn import sharded_batch_to_device

        edge_keys = {k for k in host
                     if k in ("senders", "receivers", "edge_mask")
                     or k.startswith("lay_")}
        self._tel.uploaded(*(host[k] for k in edge_keys), edges=True)
        self._tel.uploaded(*(v for k, v in host.items()
                             if k not in edge_keys))
        return sharded_batch_to_device(host, self.mesh)

    def _build_rebuild(self) -> Callable:
        """Per-shard device rebuild under ``shard_map``: each shard runs
        the cell-list build + banded layout on its frozen local subgraph
        at the pinned (n_cap, e_cap) capacities; the 4-scalar flag vector
        is ``pmax``-reduced so one replicated fetch covers every shard.
        The layout call mirrors ``shard_layout_fields``'s host build
        (``pick_windows`` defaults, ``EDGE_KERNEL_BLOCK_E``, capacity from
        the padded edge count) — bitwise the same ``lay_*`` fields."""
        from repro.core.message_passing import EDGE_KERNEL_BLOCK_E
        from repro.distributed.dist_egnn import GRAPH_AXIS
        from jax.sharding import PartitionSpec as P

        r_build = self.r + self.skin
        e_cap, cell_cap, n_cap = self._e_cap, self._cell_cap, self._n_cap

        def shard_rebuild(x, nm):
            db = device_radius_build(x[0], nm[0], r_build=r_build,
                                     edge_cap=e_cap, cell_cap=cell_cap)
            lay = device_banded_layout(
                db.senders, db.receivers, db.edge_mask, n_nodes=n_cap,
                block_e=EDGE_KERNEL_BLOCK_E)
            flags = jnp.stack([
                (~jnp.isfinite(x).all()).astype(jnp.int32),
                db.overflow.astype(jnp.int32), db.n_edges,
                db.max_occupancy])
            flags = jax.lax.pmax(flags, GRAPH_AXIS)
            return (db.senders[None], db.receivers[None],
                    db.edge_mask[None], lay.senders[None],
                    lay.receivers[None], lay.edge_mask[None],
                    lay.block_rwin[None], lay.block_swin[None], flags)

        mapped = jax.shard_map(
            shard_rebuild, mesh=self.mesh,
            in_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS)),
            out_specs=(P(GRAPH_AXIS),) * 8 + (P(),), check_vma=False)

        def rebuild(x, nm):
            self._tel.rebuild_traces += 1
            return mapped(x, nm)

        return jax.jit(rebuild)

    def _device_rebuild(self, sb, x, step: int):
        """One device-mode rebuild at the frozen assignment: swap the
        per-shard edge + layout operands of ``sb`` in place — only the
        pmax'd flag vector crosses to the host.  A cell/grid overflow on
        any shard adapts the global ``cell_cap`` (the pmax'd flags carry
        the worst shard's occupancy) and re-runs the retraced program on
        the same resident coordinates — no gather, no host rebuild."""
        t0 = time.perf_counter()
        if self._rebuild is None:
            self._rebuild = self._build_rebuild()
        out = self._rebuild(x, sb.node_mask[:, 0])
        f = self._tel.fetch(out[8])
        if f[0]:
            raise FloatingPointError(_DIVERGED_MSG.format(step))
        while f[1]:
            self._cell_overflows += 1
            self._cell_cap = min(self._n_cap,
                                 max(auto_cell_cap(int(f[3])),
                                     self._cell_cap + 1))
            self._rebuild = self._build_rebuild()
            out = self._rebuild(x, sb.node_mask[:, 0])
            f = self._tel.fetch(out[8])
        if int(f[2]) > self._e_cap:
            warn_edge_truncation(int(f[2]), self._e_cap,
                                 "longest-first")
        snd, rcv, em, ls, lr, lm, br, bw = out[:8]
        sb = sb._replace(
            senders=snd[:, None], receivers=rcv[:, None],
            edge_mask=em[:, None], lay_senders=ls[:, None],
            lay_receivers=lr[:, None], lay_edge_mask=lm[:, None],
            lay_block_rwin=br[:, None], lay_block_swin=bw[:, None])
        self._rebuild_s += time.perf_counter() - t0
        return sb

    def _build_chunk(self) -> Callable:
        """One jitted shard_map program: per-shard while_loop with a
        ``pmax``-reduced skin criterion.

        Each shard drops its size-1 local (D, B) leading dims and runs the
        single-device chunk body on its local subgraph, calling the
        registry forward with ``axis_name`` so the per-layer virtual-node
        psums run inside the loop body.  The loop *condition* reduces the
        local masked max displacement² with ``pmax`` — a collective in the
        cond — so the decision to stop is global and uniform: no shard
        can run ahead, and the host only ever reads the final step count.
        Thresholds/references/start/budget are operands, so phase A
        (trigger threshold) and phase B (old + pending references) share
        one trace, exactly like :meth:`RolloutEngine._build_chunk`.
        """
        from repro.distributed.dist_egnn import (GRAPH_AXIS, ShardedBatch,
                                                 _edge_layout, _local_graph)
        from jax.sharding import PartitionSpec as P

        r2 = np.float32(self.r) ** 2
        p = self.drop_rate
        dt = self.dt
        cfg = self.cfg
        use_kernel = bool(getattr(cfg, "use_kernel", False))

        def shard_body(params, sb, x, v, ref_a, ref_b, traj,
                       start, budget, lim_a2, lim_b2):
            sbe = jax.tree.map(lambda a: a[0, 0], sb)  # local D=1, B=1
            nm = sbe.node_mask
            ra, rb = ref_a[0], ref_b[0]

            def gdisp2(xc, ref):
                d2 = jnp.max(jnp.sum((xc - ref) ** 2, axis=-1) * nm)
                return jax.lax.pmax(d2, GRAPH_AXIS)

            def cond(c):
                i, xc, _, _ = c
                return ((i < budget) & (gdisp2(xc, ra) <= lim_a2)
                        & (gdisp2(xc, rb) <= lim_b2))

            def body(c):
                i, xc, vc, traj = c
                keep = _step_edge_masks(xc, sbe.senders, sbe.receivers,
                                        sbe.edge_mask, r2, p)
                g = _local_graph(sbe)._replace(
                    x=xc, v=vc, edge_mask=keep.astype(jnp.float32))
                if use_kernel:
                    lk = _step_edge_masks(xc, sbe.lay_senders,
                                          sbe.lay_receivers,
                                          sbe.lay_edge_mask, r2, p)
                    lay = _edge_layout(sbe._replace(
                        lay_edge_mask=lk.astype(jnp.float32)))
                else:
                    lay = None
                xp = self.apply_full(params, cfg, g, axis_name=GRAPH_AXIS,
                                     edge_layout=lay)[0]
                xp = jnp.where(nm[:, None] > 0, xp, 0.0)
                if self.wrap_box is not None:
                    b = jnp.float32(self.wrap_box)
                    xp = xp - b * jnp.floor(xp / b)
                vn = (xp - xc) / dt
                traj = jax.lax.dynamic_update_slice(
                    traj, xp[None, None], (0, start + i, 0, 0))
                return i + jnp.int32(1), xp, vn, traj

            i, xf, vf, traj = jax.lax.while_loop(
                cond, body, (jnp.int32(0), x[0], v[0], traj))
            return xf[None], vf[None], traj, i[None]

        sb_specs = ShardedBatch(
            *([P(GRAPH_AXIS)] * len(ShardedBatch._fields)))
        mapped = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(P(), sb_specs) + (P(GRAPH_AXIS),) * 5 + (P(),) * 4,
            out_specs=(P(GRAPH_AXIS),) * 4, check_vma=False)

        def chunk(params, sb, x, v, ref_a, ref_b, traj,
                  start, budget, lim_a2, lim_b2):
            self._tel.traces += 1
            return mapped(params, sb, x, v, ref_a, ref_b, traj,
                          start, budget, lim_a2, lim_b2)

        donate = (6,) if jax.default_backend() != "cpu" else ()
        return jax.jit(chunk, donate_argnums=donate)

    def run(self, params, x0, v0, h, n_steps: int, *,
            targets: Optional[np.ndarray] = None,
            traj_capacity: Optional[int] = None) -> RolloutResult:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.data.stream import shared_worker_pool
        from repro.distributed.dist_egnn import GRAPH_AXIS

        n_steps = int(n_steps)
        if n_steps <= 0:
            raise ValueError(f"n_steps must be positive, got {n_steps}")
        x0 = np.asarray(x0)
        if self.wrap_box is not None:
            b = np.float32(self.wrap_box)
            x0 = x0 - b * np.floor(x0 / b)
        n = x0.shape[0]
        if targets is not None:
            targets = np.asarray(targets)
            if targets.shape[0] < n_steps:
                raise ValueError(
                    f"rollout targets cover {targets.shape[0]} steps but "
                    f"n_steps={n_steps}: size n_steps at the call site "
                    f"instead of clamping ground truth")
        self._freeze_assignment(x0)
        tel = self._tel
        base = (tel.d2h, tel.h2d, tel.steady_d2h)
        h_np = np.asarray(h)
        # the first install is host either way: it sizes e_cap and ships
        # the initial state — warmup, not steady rebuild traffic
        sb = self._install(self._host_build(x0, np.asarray(v0), h_np))
        if self.rebuild_mode == "device" and self._cell_cap is None:
            x32 = np.asarray(x0, np.float32)
            self._cell_cap = min(self._n_cap, auto_cell_cap(max(
                (cell_occupancy(x32[idx], self.r + self.skin)
                 for idx in self._idx if idx.size), default=1)))
        base2 = (tel.coord_d2h, tel.edge_h2d, self._rebuild_s,
                 self._cell_overflows)
        x, v = sb.x[:, 0], sb.v[:, 0]  # carried state, (D, n_cap, 3)
        if self._chunk is None:
            self._chunk = self._build_chunk()
        # monotone buffer capacity, same contract as RolloutEngine.run:
        # shorter re-runs reuse the compiled chunk with zero retraces
        self._traj_cap = max(self._traj_cap, n_steps, int(traj_capacity or 0))
        # placed as the chunk returns it, so later calls hit the first trace
        traj = jnp.zeros((self.d, self._traj_cap, self._n_cap, 3),
                         jnp.float32, device=NamedSharding(self.mesh,
                                                           P(GRAPH_AXIS)))

        inf = np.float32(np.inf)
        lim2 = np.float32((0.5 * self.skin) ** 2)
        trig2 = (np.float32((self.rebuild_margin * 0.5 * self.skin) ** 2)
                 if self.async_rebuild else lim2)
        pool = None
        x_ref = x
        pending = None  # (future, x_trigger) during an async build
        done = 0
        chunk_calls = 0
        waits = 0
        rebuild_steps: list[int] = []
        trigger_steps: list[int] = []
        base_traces = tel.traces
        while done < n_steps:
            if pending is None:  # phase A: fresh list, watch the trigger
                refs, lims = (x_ref, x_ref), (trig2, inf)
            else:  # phase B: stale list, bounded by old ref AND trigger ref
                refs, lims = (x_ref, pending[1]), (lim2, lim2)
            x, v, traj, i = self._chunk(
                params, sb, x, v, refs[0], refs[1], traj,
                np.int32(done), np.int32(n_steps - done), lims[0], lims[1])
            chunk_calls += 1
            done += int(tel.fetch(i)[0])  # uniform across shards (pmax cond)
            if done >= n_steps:
                break
            if pending is None:
                trigger_steps.append(done)
                if self.rebuild_mode == "device":
                    sb = self._device_rebuild(sb, x, done)
                    x_ref = x
                    rebuild_steps.append(done)
                    continue
                xg, vg = self._gather(tel.fetch(x, coords=True),
                                      tel.fetch(v, coords=True), n)
                if not np.isfinite(xg).all():
                    raise FloatingPointError(_DIVERGED_MSG.format(done))
                if self.async_rebuild:
                    if pool is None:
                        pool = self._pool or shared_worker_pool()
                    pending = (pool.submit(self._host_build, xg, vg, h_np),
                               x)
                else:
                    t0 = time.perf_counter()
                    sb = self._install(self._host_build(xg, vg, h_np))
                    self._rebuild_s += time.perf_counter() - t0
                    x_ref = x
                    rebuild_steps.append(done)
            else:
                fut, x_trig = pending
                if not fut.done():
                    waits += 1  # budget ran out before the build landed
                t0 = time.perf_counter()
                sb = self._install(fut.result())
                self._rebuild_s += time.perf_counter() - t0
                x_ref = x_trig
                rebuild_steps.append(done)
                pending = None

        traj_np = tel.fetch(traj)[:, :n_steps]  # (D, S, n_cap, 3)
        traj_glob = np.zeros((n_steps, n, 3), np.float32)
        for pi, idx in enumerate(self._idx):
            traj_glob[:, idx] = traj_np[pi, :, :idx.size]
        mse = None
        if targets is not None:
            err = np.sum((traj_glob - targets[:n_steps, :n]) ** 2, axis=-1)
            mse = np.mean(err, axis=-1) / 3.0
        rebuilds = len(rebuild_steps)
        return RolloutResult(
            trajectory=traj_glob, per_step_mse=mse, rebuild_count=rebuilds,
            steps_per_rebuild=n_steps / (rebuilds + 1), n_steps=n_steps,
            rebuild_steps=rebuild_steps, trigger_steps=trigger_steps,
            rebuild_waits=waits, chunk_calls=chunk_calls,
            recompiles=max(0, tel.traces - base_traces
                           - (1 if base_traces == 0 else 0)),
            d2h_bytes=tel.d2h - base[0], h2d_bytes=tel.h2d - base[1],
            steady_state_d2h_bytes=tel.steady_d2h - base[2],
            rebuild_mode=self.rebuild_mode,
            coord_d2h_bytes=tel.coord_d2h - base2[0],
            edge_h2d_bytes=tel.edge_h2d - base2[1],
            cell_overflows=self._cell_overflows - base2[3],
            rebuild_s=self._rebuild_s - base2[2])

    def _gather(self, x_sh: np.ndarray, v_sh: np.ndarray,
                n: int) -> tuple[np.ndarray, np.ndarray]:
        """Sharded (D, n_cap, 3) state → global (n, 3) arrays."""
        xg = np.zeros((n, 3), np.float32)
        vg = np.zeros((n, 3), np.float32)
        for pi, idx in enumerate(self._idx):
            xg[idx] = x_sh[pi, :idx.size]
            vg[idx] = v_sh[pi, :idx.size]
        return xg, vg
