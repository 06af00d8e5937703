"""CPU rehearsal of the benchmark at tiny sizes (Pallas kernels interpret).

Each cell's files are read as committed, then shrunk: fewer, smaller
scenes and short windows.  The limits are the cell's own.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny sizes per cell: scenes of a few hundred particles on the CPU
TINY = {
    "water3d.train": dict(cfg=dict(n_particles=300, edge_cap=3072),
                          traffic=dict(pool_scenes=6, batch=2)),
    "fluid113k.dist_train": dict(cfg=dict(n_particles=600, edge_cap=1536),
                                 traffic=dict(pool_scenes=3)),
}


def tiny_cell(name: str) -> dict:
    from bench import registry

    c = registry.cell(name)
    c["config"].update(TINY[name]["cfg"])
    c["traffic"].update(TINY[name]["traffic"])
    c["limits"] = {k: v["limit"] for k, v in c["limits"]["limits"].items()}
    return c


def run_tiny(name: str, seed: int = 2 ** 33 + 11, seconds: float = 0.5,
             **cfg_overrides) -> dict:
    """One run of a shrunk cell through the driver, skipping the chip
    check that ``run.py`` makes."""
    from bench import driver

    c = tiny_cell(name)
    c["config"].update(cfg_overrides)
    return driver.run_cell(c["config"], c["traffic"], c["limits"],
                           seed=seed, seconds=seconds, trace=False,
                           t_start=time.perf_counter())


def result_json(res: dict) -> dict:
    return json.loads(json.dumps(res))
