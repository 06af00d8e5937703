"""Finds each piece of the benchmark by the name ``BENCHMARK.json`` gives it.

* ``bench/configs/<config>.json`` -- a configuration (the file that
  ``BENCHMARK.json`` names); its ``reference`` names the plain reference,
  ``bench/reference/<reference>.py``;
* ``bench/traffic/<traffic>.json`` -- a traffic mix: parameters, and the
  name of the window that reads them;
* ``bench/windows/<window>.py`` -- a window: set-up, the measured loop,
  its end-to-end metrics and the comparison with the reference, for one
  kind of work (``train``);
* ``bench/limits/<workload>.json`` -- the limits that decide ``correct``
  in a cell, with the readings they were set from;
* ``bench/metrics/<metric>.py`` -- one reader per per-layer metric;
* ``bench/peaks.json`` -- the chips' published peaks, by ``device_kind``;
* ``bench/held_back.json`` -- cells in ``BENCHMARK.json``'s form that run
  by hand but are not in the benchmark yet.

A later cell, mix, window, configuration or metric is new files alone.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """The benchmark's own files are missing or inconsistent."""


def _json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}") from None


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def held_back(root: str = ROOT) -> dict:
    """Cells that ``run.py`` and ``calibrate.py`` run by hand but that are
    not in the benchmark yet (PERF.md's Open questions say what each waits
    for), in ``BENCHMARK.json``'s form."""
    path = os.path.join(root, "bench", "held_back.json")
    empty = {k: [] for k in ("configs", "workloads", "end_to_end",
                             "per_layer")}
    return _json(path) if os.path.isfile(path) else empty


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one workload needs: the cell entry, its configuration,
    traffic mix and limits.  ``BENCHMARK.json`` first, then the cells
    held back."""
    for bm in (benchmark(root), held_back(root)):
        work = {w["name"]: w for w in bm["workloads"]}
        if name in work:
            break
    else:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json or "
                         f"bench/held_back.json")
    w = work[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return dict(
        workload=w,
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(BENCH_DIR, "traffic",
                                   w["traffic"] + ".json")),
        limits=_json(os.path.join(BENCH_DIR, "limits", name + ".json")),
        end_to_end=[m for m in bm["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in bm["per_layer"]
                   if name in m.get("workloads", [name])],
    )


@functools.lru_cache(maxsize=None)
def load_module(path: str, name: str):
    """The module at ``path``, loaded once per process (so that its jitted
    functions compile once)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(ctx) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no reader for metric {name!r} at "
                         f"{os.path.relpath(path, ROOT)}")
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def window(name: str, bench_dir: str = BENCH_DIR):
    """The module ``bench/windows/<name>.py``: its ``Session(cfg, traffic,
    seed, mark)`` sets a cell up; then ``measure(seconds)``, ``release()``
    and ``check()`` (see ``windows/train.py``)."""
    path = os.path.join(bench_dir, "windows", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no window {name!r} at "
                         f"{os.path.relpath(path, ROOT)}")
    return load_module(path, "bench_window_" + name)


def reference(name: str):
    return load_module(os.path.join(BENCH_DIR, "reference", name + ".py"),
                       "bench_reference_" + name)


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]
