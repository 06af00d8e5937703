"""One pipeline API for single-device and DistEGNN training (DESIGN.md §7).

Before this module the two training paths exposed completely different
surfaces: single-device went ``make_model`` → ``dataset_to_batches`` →
``trainer.fit`` (paying a trace-time banded regroup per jitted program),
while DistEGNN went ``FastEGNNConfig`` → ``partition_sample`` /
``stack_partitions`` → ``build_dist_train_step`` (host layouts, zero
regroups).  :func:`build_pipeline` collapses both onto one factory:

    pipe = build_pipeline("fast_egnn", key, train_cfg=tc, hidden=64, ...)
    tr = pipe.make_batches(data[:n], batch_size, r=r)   # GraphBatch stream
    res = pipe.fit(tr, va)                       # single-device vmap path

    pipe = build_pipeline("fast_egnn", key, mesh=make_gnn_mesh(4), ...)
    tr = pipe.make_batches(data[:n], batch_size, r=r)   # ShardedBatch stream
    res = pipe.fit(tr, va)                       # shard_map DistEGNN path

``make_batches`` returns a re-iterable :class:`~repro.data.stream.BatchStream`
(DESIGN.md §8): ``fit`` consumes one epoch per pass while worker threads
build the next batches behind a bounded queue and the device transfer
double-buffers; ``stream[i]`` / ``len(stream)`` materialize the eager list
for random-access callers.

Either way the batches carry host-precomputed banded-CSR layouts, so with
``use_kernel=True`` the fused Pallas edge kernel dispatches with **zero
trace-time regroups** on both paths — ``pipe.dispatch_report()`` exposes
the trace-time telemetry proving it.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core import message_passing as mp
from repro.models.registry import resolve_model
from repro.training.optim import Adam
from repro.training.trainer import FitResult, TrainConfig

Array = jax.Array

#: max live rollout engines per pipeline — each holds a compiled chunk
#: and a donated device trajectory buffer; LRU-evicted beyond this
ROLLOUT_ENGINE_CACHE = 4


class Pipeline:
    """A model + its training machinery behind one uniform surface.

    Attributes: ``name``, ``cfg``, ``params``, ``apply_full`` (the registry
    apply — ``(params, cfg, g, axis_name=None, edge_layout=None)``),
    ``mesh`` (None ⇒ single-device vmap trainer), ``train_cfg``, ``opt``.

    Methods (identical call shapes on both paths):
      * :meth:`make_batches` — raw samples → layout-carrying batches
        (``GraphBatch`` / ``ShardedBatch``);
      * :meth:`train_step` / :meth:`eval_step` — jitted step functions,
        ``train_step(params, opt_state, batch, key=None)`` →
        ``(params, opt_state, metrics dict)``, ``eval_step(params, batch)``
        → scalar;
      * :meth:`fit` — epochs + validation early stopping (the paper's
        protocol), returns :class:`~repro.training.trainer.FitResult` and
        updates ``self.params`` to the best found;
      * :meth:`predict` — batch-level jitted forward → predicted coords;
      * :meth:`rollout` — recursive prediction via the device-resident
        :class:`~repro.rollout.engine.RolloutEngine` (DESIGN.md §10);
      * :meth:`dispatch_report` — trace-time edge-dispatch telemetry.

    The **PredictFn** is the pipeline's one forward surface, built once in
    ``_build_steps`` alongside the train/eval steps and exposed as
    :attr:`predict_fn`:

      * single-device: ``predict_fn(params, graph(B,·), layout|None)`` →
        ``(B, N, 3)`` — one ``jit(vmap)`` program that handles both
        layout-carrying and legacy (layout-free) batches (a ``None``
        layout is an empty pytree, so both shapes share the call site);
      * mesh: ``predict_fn(params, ShardedBatch)`` → ``(D, B, n_cap, 3)``
        — the jitted ``shard_map`` forward.

    :meth:`predict` is a thin batch-unpacking wrapper over it.
    :meth:`rollout` *composes* the model surface in its while_loop chunk:
    single-device it wraps ``predict_fn`` directly; on a mesh it wraps
    ``apply_full`` in its own ``shard_map`` (the jitted shard_map forward
    cannot nest inside the chunk's shard_map — DESIGN.md §11).
    """

    def __init__(self, name: str, cfg: Any, params: Any, apply_full: Callable,
                 mesh, train_cfg: TrainConfig):
        self.name = name
        self.cfg = cfg
        self.params = params
        self.apply_full = apply_full
        self.mesh = mesh
        self.train_cfg = train_cfg
        self.opt = Adam(lr=train_cfg.lr, weight_decay=train_cfg.weight_decay,
                        grad_clip=train_cfg.grad_clip)
        self._steps = None
        # bounded: each engine pins a compiled chunk + donated trajectory
        # buffer, and serving traffic with varied capacity keys must not
        # accumulate them without limit (DESIGN.md §12)
        from repro.serving.programs import LRUCache
        self._rollout_engines = LRUCache(ROLLOUT_ENGINE_CACHE)

    # ------------------------------------------------------------- batches
    def make_batches(self, samples, batch_size: int, *, r: float = np.inf,
                     drop_rate: float = 0.0, partition: str = "random",
                     shuffle_seed: Optional[int] = None,
                     with_layout: Optional[bool] = None,
                     reshuffle_each_epoch: bool = False,
                     cache_dir: Optional[str] = None,
                     prefetch: Optional[int] = None,
                     num_workers: Optional[int] = None,
                     edge_cap: Optional[int] = None) -> "BatchStream":
        """Raw samples → a :class:`~repro.data.stream.BatchStream` of
        fixed-shape, layout-carrying batches (DESIGN.md §8).

        Single-device streams yield ``GraphBatch``es (stacked host banded
        layout; the trailing partial batch is mask-padded, never dropped).
        Distributed streams yield ``ShardedBatch``es built via per-sample
        ``partition_sample`` (strategy = ``partition``); trailing samples
        short of a full batch are dropped with a warning (the shard_map
        program is fixed-shape and carries no sample mask).

        The stream is re-iterable (``fit`` runs one epoch per pass,
        building batches in background workers behind a bounded queue and
        double-buffering the device transfer) and still supports
        ``len`` / indexing by materializing the eager list on demand.
        ``reshuffle_each_epoch`` keys a fresh sample order per epoch from
        ``(shuffle_seed, epoch)`` — off by default so epochs replay the
        eager order exactly.  ``cache_dir`` persists banded layouts to
        disk, so warm runs skip every layout rebuild.

        ``with_layout`` defaults to this pipeline's ``cfg.use_kernel``:
        only the fused kernel reads the host layout, so layout-free
        configs skip the numpy layout pass and its device arrays.  On the
        mesh path layouts are structural ``ShardedBatch`` fields and
        always built.

        On a *multi-process* mesh pipeline the stream runs process-sharded
        (DESIGN.md §11): each host builds only its own block of graph
        shards and the global ``ShardedBatch`` is assembled from the
        per-process local rows — host memory and layout-build time stay
        flat in the host count.  That mode pins the edge capacity, so
        ``edge_cap`` is required there (and optional everywhere else).
        """
        from repro.data.stream import (DEFAULT_PREFETCH, DEFAULT_WORKERS,
                                       BatchStream)

        if with_layout is None:
            with_layout = bool(getattr(self.cfg, "use_kernel", False))
        return BatchStream(
            samples, batch_size, r=r, drop_rate=drop_rate, edge_cap=edge_cap,
            shuffle_seed=shuffle_seed, with_layout=with_layout,
            reshuffle_each_epoch=reshuffle_each_epoch, cache_dir=cache_dir,
            prefetch=DEFAULT_PREFETCH if prefetch is None else prefetch,
            num_workers=DEFAULT_WORKERS if num_workers is None else num_workers,
            n_shards=None if self.mesh is None else self.mesh.devices.size,
            partition=partition, mesh=self.mesh)

    # --------------------------------------------------------------- steps
    def _build_steps(self):
        if self._steps is not None:
            return self._steps
        tc = self.train_cfg
        if self.mesh is None:
            from repro.training.trainer import build_train_step

            step, ev = build_train_step(self.apply_full, self.cfg, tc,
                                        self.opt)

            def train_step(params, opt_state, batch, key=None):
                if key is None:
                    key = jax.random.PRNGKey(tc.seed)
                return step(params, opt_state, batch, key)

            def _predict_one(params, g, lay):
                if lay is None:
                    return self.apply_full(params, self.cfg, g)[0]
                return self.apply_full(params, self.cfg, g,
                                       edge_layout=lay)[0]

            predict_fn = jax.jit(jax.vmap(_predict_one,
                                          in_axes=(None, 0, 0)))
            self._steps = (train_step, ev, predict_fn)
        else:
            from repro.distributed.dist_egnn import (build_dist_apply,
                                                     build_dist_train_step)

            step, loss_fn = build_dist_train_step(
                self.cfg, self.mesh, self.opt, lam_mmd=tc.lam_mmd,
                mmd_sigma=tc.mmd_sigma)

            def train_step(params, opt_state, batch, key=None):
                params, opt_state, loss = step(params, opt_state, batch)
                return params, opt_state, {"loss": loss}

            dist_apply = build_dist_apply(self.cfg, self.mesh)
            self._steps = (train_step, loss_fn,
                           lambda p, sb: dist_apply(p, sb)[0])
        return self._steps

    @property
    def train_step(self) -> Callable:
        """Jitted ``(params, opt_state, batch, key=None)`` →
        ``(params, opt_state, metrics)`` — metrics always has ``"loss"``."""
        return self._build_steps()[0]

    @property
    def eval_step(self) -> Callable:
        """Jitted ``(params, batch)`` → scalar validation metric (masked
        MSE on the single-device path; the Eq. 18 objective — MSE + λ·MMD
        — on the distributed path, whose loss_fn is the parity anchor)."""
        return self._build_steps()[1]

    # ------------------------------------------------------------- forward
    @property
    def predict_fn(self) -> Callable:
        """The pipeline's one jitted forward program (the **PredictFn** —
        see the class docstring for both paths' signatures).  Built once
        in ``_build_steps``; ``predict`` and ``rollout`` both route
        through it."""
        return self._build_steps()[2]

    def predict(self, params, batch) -> Array:
        """Batch-level jitted forward → predicted coordinates
        ((B, N, 3) single-device / (D, B, n_cap, 3) distributed).  Thin
        batch-unpacking wrapper over :attr:`predict_fn`."""
        if self.mesh is None:
            return self.predict_fn(params, batch.graph,
                                   getattr(batch, "layout", None))
        return self.predict_fn(params, batch)

    def rollout(self, params, state0, n_steps: int, *, r: float,
                skin: float = 0.0, dt: float, drop_rate: float = 0.0,
                targets=None, node_cap: Optional[int] = None,
                edge_cap: Optional[int] = None,
                async_rebuild: Optional[bool] = None,
                partition: str = "random", seed: int = 0,
                traj_capacity: Optional[int] = None,
                wrap_box: Optional[float] = None,
                rebuild_mode: str = "auto"):
        """Recursive prediction: feed the model its own output for
        ``n_steps`` steps, velocities re-estimated by finite differences
        at timestep ``dt`` — the sibling of :meth:`predict` for
        simulation (DESIGN.md §10).

        ``state0`` is ``(x0, v0, h)`` (raw numpy, one scene).  ``r`` /
        ``drop_rate`` are the model's graph semantics — identical to
        training; ``skin`` is an execution knob: the radius graph is
        built once at ``r + skin`` and reused on device until some node
        moves more than ``skin/2``.  ``rebuild_mode`` picks how stale
        lists are rebuilt: ``'device'`` runs the jitted cell-list build
        on the accelerator (no coordinate d2h / edge h2d — DESIGN.md
        §13), ``'host'`` the numpy path, with rebuilds optionally
        running asynchronously on the stream worker pool
        (``async_rebuild``, default on when ``skin > 0``) while the
        still-valid list keeps stepping; the default ``'auto'`` selects
        ``'device'`` whenever eligible (finite ``r``, no explicit async
        request).  Both modes produce bitwise-identical trajectories.  The
        trajectory is independent of ``skin`` (up to float ties at the
        cutoffs); ``skin=0`` rebuilds every step.  ``targets`` (optional
        ground-truth frames, one per step — short arrays raise) adds
        ``per_step_mse``.  On a mesh pipeline the rollout routes through
        the frozen-``partition`` per-shard layouts.  Engines are cached
        in a bounded LRU (``ROLLOUT_ENGINE_CACHE`` keys — size exposed in
        :meth:`dispatch_report`), so repeated calls reuse the jitted
        chunk while varied capacity keys cannot leak device buffers;
        ``traj_capacity`` pre-sizes the trajectory buffer so a short
        warmup run compiles the exact program a longer run dispatches.
        ``wrap_box`` applies periodic boundary conditions (positions
        wrapped into ``[0, wrap_box)^3`` each step, before the velocity
        finite difference) — this bounds the recursion for arbitrarily
        long horizons; without it, a diverging model raises
        ``FloatingPointError`` once coordinates go non-finite.

        Returns a :class:`~repro.rollout.engine.RolloutResult`.
        """
        from repro.rollout.engine import DistRolloutEngine, RolloutEngine

        x0, v0, h = state0
        key = (self.mesh is None, float(r), float(skin), float(dt),
               float(drop_rate), node_cap, edge_cap, async_rebuild,
               partition, seed, wrap_box, rebuild_mode)
        eng = self._rollout_engines.get(key)
        if eng is None:
            if self.mesh is None:
                eng = RolloutEngine(
                    self.predict_fn, r=r, skin=skin, dt=dt,
                    drop_rate=drop_rate, node_cap=node_cap,
                    edge_cap=edge_cap,
                    with_layout=bool(getattr(self.cfg, "use_kernel",
                                             False)),
                    async_rebuild=async_rebuild, wrap_box=wrap_box,
                    rebuild_mode=rebuild_mode)
            else:
                eng = DistRolloutEngine(
                    self.apply_full, self.cfg, self.mesh, r=r,
                    skin=skin, dt=dt, drop_rate=drop_rate,
                    strategy=partition, seed=seed, n_cap=node_cap,
                    e_cap=edge_cap, async_rebuild=async_rebuild,
                    wrap_box=wrap_box, rebuild_mode=rebuild_mode)
            self._rollout_engines.put(key, eng)
        return eng.run(params, x0, v0, h, n_steps, targets=targets,
                       traj_capacity=traj_capacity)

    # ----------------------------------------------------------------- fit
    def fit(self, train_batches, val_batches, verbose: bool = False) -> FitResult:
        """Epochs + validation-based early stopping on either path.

        One stream-consuming loop (``trainer.run_fit`` — DESIGN.md §8) for
        both the single-device and distributed paths: each epoch
        re-iterates ``train_batches`` / ``val_batches``, so eager lists
        and ``BatchStream``s (whose background prefetch overlaps the host
        batch build and H2D with step compute) both work, with per-step
        parity between them on a fixed seed.  Updates ``self.params`` to
        the best validation params and returns the :class:`FitResult`.
        """
        from repro.training.trainer import run_fit

        step, eval_step, _ = self._build_steps()
        res = run_fit(step, eval_step, self.params,
                      self.opt.init(self.params), self.train_cfg,
                      train_batches, val_batches, verbose=verbose)
        self.params = res.params
        return res

    # ----------------------------------------------------------- telemetry
    def dispatch_report(self) -> dict:
        """Snapshot of the trace-time edge-dispatch telemetry
        (``core.message_passing.dispatch_counts``) plus the derived
        ``dispatch_mode`` classification for this pipeline's config.
        Counts accumulate per *trace*: ``mp.reset_dispatch_counts()``
        before building a fresh program to observe its decisions.
        """
        from repro.kernels.runtime import backend_mode

        counts = mp.dispatch_counts()
        use_kernel = bool(getattr(self.cfg, "use_kernel", False))
        return dict(counts=counts, use_kernel=use_kernel,
                    mode=mp.dispatch_mode(counts, use_kernel, backend_mode()),
                    rollout_engine_cache=self._rollout_engines.stats())


def build_pipeline(name: str, key, *, mesh=None,
                   train_cfg: Optional[TrainConfig] = None,
                   **cfg_overrides) -> Pipeline:
    """The single factory behind every training entry point (DESIGN.md §7).

    ``mesh=None`` → the vmap single-device trainer over layout-carrying
    ``GraphBatch``es; ``mesh=Mesh(...)`` (e.g. ``make_gnn_mesh(d)``) → the
    ``shard_map`` DistEGNN path over ``ShardedBatch``es.  ``train_cfg``
    seeds the optimiser and fit protocol (default :class:`TrainConfig`);
    ``**cfg_overrides`` go to the registry's config composition exactly as
    ``make_model``'s did.
    """
    train_cfg = train_cfg if train_cfg is not None else TrainConfig()
    if mesh is not None and name != "fast_egnn":
        raise ValueError(
            f"build_pipeline(mesh=...) implements DistEGNN (Sec. VI), which "
            f"is FastEGNN under graph-partition shard_map — got model "
            f"{name!r}; pass name='fast_egnn' or mesh=None")
    cfg, params, apply_full = resolve_model(name, key, **cfg_overrides)
    if mesh is not None:
        # replicated up front: every chip holds its own copy, none is
        # staged through device 0 on the first step
        from jax.sharding import NamedSharding, PartitionSpec

        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    return Pipeline(name, cfg, params, apply_full, mesh, train_cfg)
