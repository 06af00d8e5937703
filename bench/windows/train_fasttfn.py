"""The FastTFN training window: the ``train`` window's loop, check and
metrics, with the program's ``fast_tfn`` model in FastEGNN's place.

Traffic parameters as the ``train`` window's (``pool_scenes``, ``batch``).
Set-up builds ``build_pipeline("fast_tfn", ..., use_kernel=True)`` at the
configuration's widths, with the benchmark's weights made here from the
FastTFN parameter layout (below) by ``weights.py``'s jitted maker, and
warms it up as the ``train`` window does; measuring, releasing and the
check against ``reference/fast_tfn.py`` are the ``train`` window's own.

Before anything is made, set-up asks the program's model for its outputs'
shapes at a tiny graph: a program whose FastTFN returns no virtual state
trains without the MMD term the configuration's ``lam_mmd`` asks for, and
is refused (``registry.BenchError``) within seconds.
"""
from __future__ import annotations

import jax

from bench import registry
from bench import scenes as scenes_mod
from bench import weights as weights_mod

train = registry.window("train")
CHECKED_STEPS, SPANS = train.CHECKED_STEPS, train.SPANS
Loop, Kept, Window = train.Loop, train.Kept, train.Window
step_keys, len_epoch = train.step_keys, train.len_epoch
reference_batches, reference_gaps = train.reference_batches, train.reference_gaps
program_checked = train.program_checked

_WIDTH_KEYS = ("n_layers", "hidden", "n_virtual", "s_dim", "h_in", "n_rbf",
               "rbf_cutoff", "coord_clamp", "precision")


def layout(cfg: dict) -> dict:
    """The FastTFN parameter tree as shapes: per layer the radial network
    ``radial`` ((n_rbf + hidden) -> hidden -> 6), the feature update
    ``h_out`` ((hidden + 2) -> hidden -> hidden) and the plug-in's virtual
    block, whose shapes are FastEGNN's; the embedding and ``s_init``."""
    hid, r = cfg["hidden"], cfg["n_rbf"]
    virtual = weights_mod.layout(cfg)["layers"][0]["virtual"]

    def mlp(sizes):
        return [{"w": (a, b), "b": (b,)} for a, b in zip(sizes, sizes[1:])]

    layer = {"radial": mlp([r + hid, hid, 6]),
             "h_out": mlp([hid + 2, hid, hid]), "virtual": virtual}
    return {"embed": mlp([cfg["h_in"], hid]),
            "s_init": (cfg["n_virtual"], cfg["s_dim"]),
            "layers": [layer for _ in range(cfg["n_layers"])]}


def make_weights(cfg: dict, key: jax.Array):
    """The parameter tree for ``cfg`` from ``key``, in one jitted call of
    ``weights.py``'s maker: Glorot-uniform weights, zero biases, ``s_init``
    0.1 N(0, 1)."""
    shapes, treedef = jax.tree_util.tree_flatten_with_path(
        layout(cfg), is_leaf=weights_mod._is_shape)
    names = [getattr(path[-1], "key", None) for path, _ in shapes]
    kinds = tuple((n if n in ("w", "b") else "s", tuple(shape))
                  for n, (_, shape) in zip(names, shapes))
    return jax.tree_util.tree_unflatten(treedef,
                                        weights_mod._make(key, kinds))


def _require_virtual_state(pipe) -> None:
    """Refuse a program whose model hands the objective no virtual nodes."""
    import jax.numpy as jnp

    from repro.core import message_passing as mp
    from repro.core.graph import make_graph

    g = make_graph(jnp.zeros((4, 3)), feat_dim=pipe.cfg.h_in)
    _, aux = jax.eval_shape(lambda p: pipe.apply_full(p, pipe.cfg, g),
                            pipe.params)
    mp.reset_dispatch_counts()  # the probe's trace is not the step's
    if "virtual" not in aux:
        raise registry.BenchError(
            "the program's fast_tfn returns no virtual state, so its "
            "objective leaves out the MMD term that lam_mmd asks for")


def build(cfg: dict, traffic: dict, seed: int, mark=lambda name: None):
    """Pipeline, scene pool and stream for one run (one chip)."""
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    if cfg["devices"] != 1:
        raise registry.BenchError("the FastTFN window runs on one chip")
    tc = TrainConfig(lr=cfg["lr"], weight_decay=cfg["weight_decay"],
                     grad_clip=cfg["grad_clip"], lam_mmd=cfg["lam_mmd"],
                     mmd_sigma=cfg["mmd_sigma"],
                     mmd_sample=cfg["mmd_sample"])
    pipe = build_pipeline("fast_tfn", jax.random.PRNGKey(0), train_cfg=tc,
                          use_kernel=True,
                          **{k: cfg[k] for k in _WIDTH_KEYS})
    _require_virtual_state(pipe)
    pool = scenes_mod.scene_pool(seed, traffic["pool_scenes"], cfg)
    mark("scene pool made")
    params = make_weights(cfg, weights_mod.seed_key(seed))
    if train._shapes(params) != train._shapes(pipe.params):
        raise registry.BenchError(
            "the benchmark's FastTFN weight layout does not match the "
            "program's parameters")
    pipe.params = params
    mark("pipeline built, weights placed")
    stream = pipe.make_batches(pool, traffic["batch"], r=cfg["r"],
                               edge_cap=cfg["edge_cap"])
    return pool, pipe, stream


class Warm(train.Warm):
    """The ``train`` window's set-up, on the FastTFN pipeline."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, steps: int,
                 mark=lambda name: None):
        self.pool, self.pipe, self.stream = build(cfg, traffic, seed, mark)
        self.keys = step_keys(seed, 1 << 16)
        self.loop = Loop(self.pipe, self.stream, self.keys)
        self.params0 = train._host(self.pipe.params)
        params, opt_state = self.pipe.params, self.pipe.opt.init(
            self.pipe.params)
        m1 = params_k = None
        for i in range(steps):
            params, opt_state = self.loop.step(params, opt_state)
            mark(f"warm-up step {i} dispatched")
            if i == 0:
                m1 = train._host(opt_state.m)
            if i == CHECKED_STEPS - 1:
                params_k = train._host(params)
        self.loop.drain(params)
        mark("warm-up done")
        self.params, self.opt_state = params, opt_state
        losses = [float(v) for v in self.loop.losses[:CHECKED_STEPS]]
        self.checked = dict(losses=losses, m1=m1, params=params_k)


class Session(train.Session):
    """One run of the cell: set-up on construction, then the ``train``
    window's ``measure``, ``release`` and ``check``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 mark=lambda name: None):
        self.cfg, self.traffic = cfg, traffic
        self.warm = Warm(cfg, traffic, seed,
                         max(CHECKED_STEPS, len_epoch(traffic)), mark)

