"""The benchmark's own scenes: fluid blobs, radius graphs and partitions.

A copy of the program's falling-fluid generator (``data/fluid.py``) and
balanced random partition (``data/partition.py``), kept here so that no
later change to the program can change the traffic it is measured on.
The radius search is the benchmark's own (a k-d tree, not the program's
cell list); its predicate is the program's stated one, ``d² ≤ r²``
evaluated in the coordinates' float32, so both build the same graph.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
from scipy.spatial import cKDTree


class Scene(NamedTuple):
    x0: np.ndarray  # (N, 3) float32 positions
    v0: np.ndarray  # (N, 3) float32 velocities
    h: np.ndarray  # (N, 1) float32 invariant feature (homogeneous fluid: 1)
    x1: np.ndarray  # (N, 3) float32 target: the state dt_frames steps on


def radius_pairs(x: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray]:
    """Directed edges (sender, receiver), i != j, with ``|x_i - x_j|² <= r²``
    evaluated in ``x``'s dtype; receiver-major order."""
    rt = x.dtype.type(r)
    # candidates from a slightly wider float64 search, then the exact
    # predicate in x's own precision
    pairs = cKDTree(x.astype(np.float64)).query_pairs(
        float(r) * (1 + 1e-4), output_type="ndarray")
    if pairs.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    i, j = pairs[:, 0], pairs[:, 1]
    d2 = np.sum((x[i] - x[j]) ** 2, axis=-1)
    keep = d2 <= rt * rt
    i, j = i[keep], j[keep]
    snd = np.concatenate([i, j])
    rcv = np.concatenate([j, i])
    order = np.lexsort((snd, rcv))
    return snd[order].astype(np.int64), rcv[order].astype(np.int64)


def _pressure_accel(x: np.ndarray, r: float, stiffness: float) -> np.ndarray:
    snd, rcv = radius_pairs(x, r)
    acc = np.zeros_like(x)
    if snd.size == 0:
        return acc
    diff = x[rcv] - x[snd]
    d = np.sqrt(np.sum(diff ** 2, axis=-1)) + 1e-9
    mag = stiffness * (1.0 - d / r) ** 2
    np.add.at(acc, rcv, diff / d[:, None] * mag[:, None])
    return acc


def fluid_scene(rng: np.random.Generator, n: int, *, r: float,
                steps: int, box: float, spacing: float, dt: float = 0.005,
                stiffness: float = 20.0, damping: float = 0.02) -> Scene:
    """A fluid blob poured into a cubic box of side ``box`` (a jittered
    lattice of ``spacing`` times r: 0.7 gives about 11.5 neighbours a
    particle), and its state ``steps`` simulator steps later.  Gravity,
    pairwise pressure repulsion, damping, reflecting walls."""
    side = int(np.ceil(n ** (1 / 3)))
    spacing = spacing * r
    grid = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                    -1).reshape(-1, 3)
    blob = side * spacing
    lo = np.clip(0.5 * (box - blob), 0.02 * box, None)
    x = grid[:n] * spacing + np.array([lo, lo, max(lo, 0.5 * box)])
    x = x + rng.normal(0, 0.1 * spacing, x.shape)
    v = np.tile(rng.normal(0, 0.05, (1, 3)), (n, 1))
    x0, v0 = x.copy(), v.copy()
    g = np.array([0.0, 0.0, -1.0])
    for _ in range(steps):
        v = (1.0 - damping) * v + dt * (g + _pressure_accel(x, r, stiffness))
        x = x + dt * v
        for axis in range(3):
            low, high = x[:, axis] < 0.0, x[:, axis] > box
            x[low, axis] = -x[low, axis]
            v[low, axis] = -0.5 * v[low, axis]
            x[high, axis] = 2 * box - x[high, axis]
            v[high, axis] = -0.5 * v[high, axis]
        x = np.clip(x, 0.0, box)
    f32 = np.float32
    return Scene(x0.astype(f32), v0.astype(f32), np.ones((n, 1), f32),
                 x.astype(f32))


def scene_pool(seed: int, n_scenes: int, cfg: dict) -> list[Scene]:
    """``n_scenes`` scenes of the configuration ``cfg`` (``n_particles``,
    ``r``, ``target_steps``, ``box``, ``lattice_spacing``), drawn from one
    generator seeded by ``seed``.  Every seed gives the same sizes: only
    positions and velocities move."""
    rng = np.random.default_rng(seed)
    return [fluid_scene(rng, cfg["n_particles"], r=cfg["r"],
                        steps=cfg["target_steps"], box=cfg["box"],
                        spacing=cfg["lattice_spacing"])
            for _ in range(n_scenes)]


def random_partition(seed: int, n: int, d: int) -> np.ndarray:
    """Balanced random node -> shard assignment (shard sizes differ by at
    most one)."""
    assign = np.arange(n) % d
    np.random.default_rng(seed).shuffle(assign)
    return assign
