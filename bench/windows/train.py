"""The training window: a cell that trains, timed in scenes a second.

Traffic parameters (``bench/traffic/<mix>.json``): ``pool_scenes``, the
scenes made from the seed, and ``batch``, the scenes a step.

Set-up makes the scene pool and weights from the seed, builds the
program's pipeline (``repro.pipeline.build_pipeline``) with those weights,
and runs the first epoch of its batch stream (``Pipeline.make_batches``,
at the launcher's defaults) through ``Pipeline.train_step``: that compiles
the step and drives the first ``CHECKED_STEPS`` steps, whose losses, first
gradient and parameter change the reference checks afterwards.  The
window then re-iterates the same stream, epoch after epoch, as
``Pipeline.fit`` would, with at most ``IN_FLIGHT`` steps queued on the
device, and ends with a block on the last step.

On a sharded cell the check also runs the program's sharded forward
(``repro.distributed.dist_egnn.build_dist_apply``) once, after the window
and after ``release``: at the initial weights, on the first step's batch
(kept from set-up on the host), for the virtual nodes that each shard
holds after the last layer.

Around the loop's three host calls the window records its own spans
(``jax.profiler.TraceAnnotation``): ``stream_next`` (waiting on the
stream), ``train_step_dispatch`` (enqueueing the step) and ``block``
(waiting on the device).
"""
from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, registry
from bench import scenes as scenes_mod
from bench import weights as weights_mod

#: training steps the reference follows: each step's loss, the first
#: gradient and the parameters' change over them are compared
CHECKED_STEPS = 3
#: steps the loop keeps queued on the device before it blocks on the oldest
IN_FLIGHT = 2
#: the window's host annotations, which the trace's window spans
SPANS = ("stream_next", "train_step_dispatch", "block")

_WIDTH_KEYS = ("n_layers", "hidden", "n_virtual", "s_dim", "h_in",
               "coord_clamp", "velocity", "precision", "overlap_sync")


def step_keys(seed: int, n: int) -> np.ndarray:
    """Per-step PRNG keys (raw threefry words), drawn on the host."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(0, 2 ** 32, size=(n, 2), dtype=np.uint64).astype(
        np.uint32)


def _shapes(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


def build(cfg: dict, traffic: dict, seed: int, mark=lambda name: None):
    """Scene pool, weights, pipeline and its stream for one run."""
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    pool = scenes_mod.scene_pool(seed, traffic["pool_scenes"], cfg)
    mark("scene pool made")
    mesh = None
    if cfg["devices"] > 1:
        from repro.distributed.dist_egnn import make_gnn_mesh

        mesh = make_gnn_mesh(cfg["devices"])
    tc = TrainConfig(lr=cfg["lr"], weight_decay=cfg["weight_decay"],
                     grad_clip=cfg["grad_clip"], lam_mmd=cfg["lam_mmd"],
                     mmd_sigma=cfg["mmd_sigma"],
                     mmd_sample=cfg["mmd_sample"])
    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0), mesh=mesh,
                          train_cfg=tc, use_kernel=True,
                          **{k: cfg[k] for k in _WIDTH_KEYS})
    params = weights_mod.make_weights(cfg, weights_mod.seed_key(seed))
    if _shapes(params) != _shapes(pipe.params):
        raise registry.BenchError(
            "the benchmark's weight layout does not match the program's "
            "parameters")
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    pipe.params = params
    mark("pipeline built, weights placed")
    stream = pipe.make_batches(
        pool, traffic["batch"], r=cfg["r"], edge_cap=cfg["edge_cap"],
        partition=cfg.get("partition") or "random")
    return pool, pipe, stream


class Loop:
    """The training loop the window times: re-iterates the stream epoch
    after epoch and keeps at most ``IN_FLIGHT`` steps queued.  On a sharded
    cell it keeps its first batch (``first``), which the check reads
    again."""

    def __init__(self, pipe, stream, keys: np.ndarray):
        self.step_fn = pipe.train_step
        self.keep_first = pipe.mesh is not None
        self.first = None
        self.stream = stream
        self.keys = keys
        self.it = iter(stream)
        self.queue = deque()
        self.losses = []  # device scalars, one per step
        self.batch_index = []  # position of each step's batch in its epoch
        self._pos = 0

    def next_batch(self):
        with jax.profiler.TraceAnnotation("stream_next"):
            try:
                b = next(self.it)
            except StopIteration:
                self.it = iter(self.stream)
                self._pos = 0
                b = next(self.it)
        if self.keep_first and self.first is None:
            self.first = b
        self.batch_index.append(self._pos)
        self._pos += 1
        return b

    def step(self, params, opt_state):
        batch = self.next_batch()
        i = len(self.losses)
        with jax.profiler.TraceAnnotation("train_step_dispatch"):
            params, opt_state, m = self.step_fn(params, opt_state, batch,
                                                self.keys[i])
        self.losses.append(m["loss"])
        self.queue.append(m["loss"])
        if len(self.queue) > IN_FLIGHT:
            with jax.profiler.TraceAnnotation("block"):
                self.queue.popleft().block_until_ready()
        return params, opt_state

    def drain(self, params):
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready((params, list(self.queue)))
        self.queue.clear()


def reference_batches(cfg: dict, traffic: dict, pool: list, keys, steps: int,
                      scenes_per_step: int | None = None):
    """The scenes and keys of the first ``steps`` steps, as the reference's
    graphs.  The stream keeps the pool's order: step k trains on scenes
    ``k*B .. k*B+B-1`` (mod the pool).  ``scenes_per_step`` keeps only the
    first scenes of each batch (a fault that calibration reads)."""
    ref = registry.reference(cfg["reference"])
    b = traffic["batch"]
    assign = None
    if cfg["devices"] > 1:
        # the launcher's "random" partition: the stream seeds it with the
        # scene's position in its batch, 0 for batches of one
        assign = scenes_mod.random_partition(0, cfg["n_particles"],
                                             cfg["devices"])
    cap = cfg["edge_cap"] * cfg["devices"]
    out = []
    for k in range(steps):
        idx = [(k * b + j) % len(pool) for j in range(scenes_per_step or b)]
        out.append([ref.scene_graph(pool[i], cfg["r"], cap, assign)
                    for i in idx])
    return ref, out, [jnp.asarray(keys[k]) for k in range(steps)]


class Kept(NamedTuple):
    """What the check needs once the program's state is freed: the scene
    pool, step keys, initial weights, what the first steps produced and,
    on a sharded cell, ``first``: the first step's batch on the host, with
    the program's model configuration and mesh (None on one chip)."""
    pool: list
    keys: np.ndarray
    params0: dict
    checked: dict
    first: tuple | None


class Warm:
    """A cell after set-up: the program's pipeline, stream and loop, the
    state the window starts from, and what the first steps produced."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, steps: int,
                 mark=lambda name: None):
        self.pool, self.pipe, self.stream = build(cfg, traffic, seed, mark)
        self.keys = step_keys(seed, 1 << 16)
        self.loop = Loop(self.pipe, self.stream, self.keys)
        self.params0 = _host(self.pipe.params)
        params, opt_state = self.pipe.params, self.pipe.opt.init(
            self.pipe.params)
        m1 = params_k = None
        for i in range(steps):
            params, opt_state = self.loop.step(params, opt_state)
            mark(f"warm-up step {i} dispatched")
            if i == 0:
                m1 = _host(opt_state.m)
            if i == CHECKED_STEPS - 1:
                params_k = _host(params)
        self.loop.drain(params)
        mark("warm-up done")
        self.params, self.opt_state = params, opt_state
        losses = [float(l) for l in self.loop.losses[:CHECKED_STEPS]]
        self.checked = dict(losses=losses, m1=m1, params=params_k)

    def keep(self) -> Kept:
        first = None
        if self.loop.first is not None:
            first = (_host(self.loop.first), self.pipe.cfg, self.pipe.mesh)
        return Kept(self.pool, self.keys, self.params0, self.checked, first)


def program_virtual(first: tuple, params0) -> dict:
    """The virtual nodes after the program's sharded forward at ``params0``
    on the first scene of ``first``'s batch: z (D, C, 3) and s (D, C, S),
    each shard's copy (see ``check.virtual_gap``)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.dist_egnn import build_dist_apply
    from repro.distributed.sharding import sharded_batch_from_process_local

    batch, model_cfg, mesh = first
    params = jax.device_put(params0, NamedSharding(mesh, PartitionSpec()))
    sb = sharded_batch_from_process_local(mesh, batch._asdict())
    _, vs = build_dist_apply(model_cfg, mesh)(params, sb)
    return dict(z=np.asarray(vs.z)[:, 0], s=np.asarray(vs.s)[:, 0])


def program_checked(kept: Kept) -> dict:
    """What the check compares: the first steps' readings, with the
    program's virtual nodes added on a sharded cell."""
    if kept.first is None:
        return kept.checked
    return dict(kept.checked, virtual=program_virtual(kept.first,
                                                      kept.params0))


def reference_gaps(cfg: dict, traffic: dict, pool: list, keys, params0,
                   checked: dict, scenes_per_step: int | None = None,
                   **ref_cfg):
    """The reference's first steps from ``params0`` and the numbers
    comparing ``checked`` with them (``check.gaps``); returns (numbers,
    reference output).  ``ref_cfg`` overrides configuration keys of the
    reference alone (a fault that calibration reads)."""
    ref, batches, rkeys = reference_batches(cfg, traffic, pool, keys,
                                            CHECKED_STEPS, scenes_per_step)
    out = ref.train(params0, batches, rkeys, dict(cfg, **ref_cfg),
                    mode="highest")
    return check.gaps(checked, out, params0), out


def len_epoch(traffic: dict) -> int:
    """Batches in an epoch of the stream (full batches)."""
    return traffic["pool_scenes"] // traffic["batch"]


class Window(NamedTuple):
    """What ``measure`` gives the driver: when the window began, its
    host-clock length, the end-to-end metrics it read, and what the
    per-layer readers need (``bench/trace.py``'s ``Context``)."""
    t_begin: float
    window_s: float
    metrics: dict
    info: dict


class Session:
    """One run of a training cell: set-up on construction (every shape the
    window uses is compiled or read back from the cache), then
    ``measure``, ``release`` and ``check``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 mark=lambda name: None):
        self.cfg, self.traffic = cfg, traffic
        self.warm = Warm(cfg, traffic, seed,
                         max(CHECKED_STEPS, len_epoch(traffic)), mark)

    def measure(self, seconds: float) -> Window:
        w = self.warm
        loop, params, opt_state = w.loop, w.params, w.opt_state
        t_begin = time.perf_counter()
        n_before = len(loop.losses)
        while time.perf_counter() - t_begin < seconds:
            params, opt_state = loop.step(params, opt_state)
        loop.drain(params)
        window_s = time.perf_counter() - t_begin
        w.params, w.opt_state = params, opt_state
        steps = len(loop.losses) - n_before
        losses = np.array([float(l) for l in loop.losses])
        self.attempted = int(len(losses))
        self.failed = int(np.sum(~np.isfinite(losses)))
        metrics = {"train_scenes_per_s": {
            "value": steps * self.traffic["batch"] / window_s,
            "unit": "scenes/s"}}
        info = dict(pool=w.pool, batch_index=loop.batch_index[n_before:],
                    n_batches=len(w.stream), steps=steps)
        return Window(t_begin, window_s, metrics, info)

    def release(self) -> None:
        """Free the program's state; keep what the check needs."""
        self.kept = self.warm.keep()
        del self.warm

    def check(self) -> tuple[dict, str]:
        """The numbers that decide ``correct``, and a line for the log."""
        pool, keys, params0, _, _ = self.kept
        checked = program_checked(self.kept)
        values, ref_out = reference_gaps(self.cfg, self.traffic, pool, keys,
                                         params0, checked)
        return values, (f"program losses {checked['losses']}, reference "
                        f"{ref_out['losses']}")
