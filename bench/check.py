"""What decides ``correct``: the program's first training steps against the
plain reference's, by three numbers (four on a sharded cell), each held to
a limit of its own.

* ``loss_gap``: over the first ``LOSS_STEPS`` steps, the largest
  ``|loss - ref| / |ref|``.  The third step's loss is left out: after two
  Adam steps it carries the round-off of gradient elements near zero,
  which Adam's normalisation turns into whole steps of either sign, and it
  swings from seed to seed (see PERF.md); the third step still counts
  through ``update_gap``.
* ``grad_gap``: the first gradient as the optimizer got it (read back from
  its first moment after one step: ``m1 / (1 - beta1)``), by the worst
  leaf: ``| |g| - |g_ref| |`` over the larger of the reference leaf's norm
  and the median leaf's.
* ``update_gap``: the parameters' change over the first steps, by the worst
  leaf, measured the same way.
* ``virtual_gap`` (sharded cells alone): the virtual nodes after the full
  forward of the first step's scene at the initial weights, every shard's
  copy against the reference's: the larger of the worst coordinate gap
  over the RMS distance of the scene's particles from their centre of mass
  and the worst feature gap over the RMS of the reference's features.  It
  sees the exchange between chips left out, which a balanced random
  partition hides from the loss (each shard's own centre of mass and
  virtual-node means lie near the global ones).

Leaves whose reference gradient is below a thousandth of the median leaf's
move by round-off alone and are left out of both leaf numbers (a rule on
the reference, not on names).

Also here, copied from the program's smoke run so that no later change can
move them: the compile log read from ``jax.monitoring`` and the device
memory peak.
"""
from __future__ import annotations

import os

import jax
import numpy as np

BETA1 = 0.9
#: steps whose losses ``loss_gap`` compares
LOSS_STEPS = 2
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone
QUIET_LEAF = 1e-3


def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree.leaves(tree)])


def _worst_leaf_gap(got, want, keep: np.ndarray) -> float:
    g, w = _leaf_norms(got), _leaf_norms(want)
    g, w = g[keep], w[keep]
    floor = np.median(w)
    return float(np.max(np.abs(g - w) / np.maximum(w, floor)))


def step_loss_gaps(prog: dict, ref: dict) -> list:
    """``|loss - ref| / |ref|`` of each of the first steps."""
    steps = len(ref["losses"])
    lp = np.asarray(prog["losses"][:steps], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    return [float(g) if np.isfinite(g) else float("inf")
            for g in np.abs(lp - lr) / np.abs(lr)]


def virtual_gap(prog: dict, ref: dict) -> float:
    """``prog``: z (D, C, 3) and s (D, C, S), each shard's copy of the
    virtual nodes; ``ref``: z (C, 3), s (C, S) and the scene's spread."""
    z_ref, s_ref = (np.asarray(ref[k], np.float64) for k in ("z", "s"))
    z = np.max(np.abs(np.asarray(prog["z"], np.float64) - z_ref))
    s = np.max(np.abs(np.asarray(prog["s"], np.float64) - s_ref))
    z, s = z / ref["spread"], s / np.sqrt(np.mean(s_ref ** 2))
    return float(max(z, s)) if np.isfinite([z, s]).all() else float("inf")


def gaps(prog: dict, ref: dict, params0) -> dict:
    """The three numbers, and ``virtual_gap`` where the reference ran
    sharded.  ``prog``: losses (first steps), m1 (the optimizer's first
    moment after step 1), params (after the last of the first steps) and,
    sharded, virtual (see :func:`virtual_gap`).  ``ref``: what
    ``reference.train`` returns."""
    loss_gap = max(step_loss_gaps(prog, ref)[:LOSS_STEPS])
    g_ref = _leaf_norms(ref["grad1"])
    keep = g_ref >= QUIET_LEAF * np.median(g_ref)
    g_prog = jax.tree.map(lambda m: np.asarray(m, np.float64) / (1 - BETA1),
                          prog["m1"])
    d_prog = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                          - np.asarray(b, np.float64), prog["params"], params0)
    d_ref = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                         - np.asarray(b, np.float64), ref["params"], params0)
    out = dict(loss_gap=loss_gap,
               grad_gap=_worst_leaf_gap(g_prog, ref["grad1"], keep),
               update_gap=_worst_leaf_gap(d_prog, d_ref, keep))
    if "virtual" in ref:
        out["virtual_gap"] = virtual_gap(prog["virtual"], ref["virtual"])
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {value, limit}})."""
    table = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(values[k] <= limits[k] for k in limits)
    return ok, table


class CompileLog:
    """Backend-compile seconds and persistent-cache hits and misses, read
    from ``jax.monitoring`` (a cache hit's compile is the read-back)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return dict(compile_s=self.seconds, compiles=self.compiles,
                    cache_hits=self.hits, cache_misses=self.misses)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0
