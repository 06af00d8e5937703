"""The one driver: runs one cell from its configuration and traffic mix.

A traffic mix names its window (``"window"`` in ``bench/traffic/<mix>.json``),
the module ``bench/windows/<window>.py`` that sets the cell up, drives the
program for the measured seconds, reads its end-to-end metrics and, once
the program's state is freed, compares what the timed path produced with
the plain reference.  The driver around it is the same for every cell:
the compile cache and log, the profiler trace of the window and the
per-layer metrics read from it, the memory peak, and the verdict.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import jax

from bench import check, registry
from bench import trace as trace_mod


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def phase(name: str, t_start: float) -> None:
    """Log a set-up phase's end, in seconds since the process started."""
    log(f"[{time.perf_counter() - t_start:8.3f} s] {name}")


def enable_compile_cache() -> str:
    """The program's own fixed cache directory (``.jax_cache/`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program kept:
    set-up then reads each one back on a checkout's later runs."""
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cfg: dict, traffic: dict, limits: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float,
             trace_dir: str | None = None, per_layer=(), peaks=None) -> dict:
    """One run of one cell; returns the result's fields (see ``run.py``).

    ``t_start`` is the process's start on ``time.perf_counter``'s clock;
    ``per_layer``: the per-layer metric entries of BENCHMARK.json to read
    from the trace.  ``trace_dir`` keeps the trace there (it is deleted
    otherwise)."""
    devices = jax.devices()[:cfg["devices"]]
    cache_dir = enable_compile_cache()
    clog = check.CompileLog()
    log(f"devices: {devices}; compile cache {cache_dir} "
        f"({check.cache_entries(cache_dir)} entries)")
    window = registry.window(traffic["window"])
    session = window.Session(
        cfg, traffic, seed, lambda name: phase(name, t_start))
    setup_compile = clog.snapshot()
    log(f"set-up compile: {setup_compile}")
    from repro.core import message_passing as mp

    log(f"dispatch counts (trace-time): {mp.dispatch_counts()}")

    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    win = session.measure(seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = clog.snapshot()["compiles"] - setup_compile["compiles"]
    log(f"window: {win.info.get('steps')} steps in {win.window_s!r} s; "
        f"compiles in the window: {in_window}")
    peak = check.memory_peak_bytes(devices)
    # free the program's state before the reference runs
    session.release()
    gc.collect()

    metrics, breakdown, device_extra = {}, None, {}
    if trace:
        tr = trace_mod.load(tdir, len(devices), window.SPANS)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = trace_mod.Context(trace=tr, cfg=cfg, traffic=traffic,
                                window_s=win.window_s, chips=len(devices),
                                peaks=peaks, **win.info)
        for m in per_layer:
            val = registry.metric_reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s()}
        breakdown = tr.breakdown()
    else:
        metrics["setup_s"] = {"value": win.t_begin - t_start, "unit": "s"}
        metrics.update(win.metrics)

    t_ref = time.perf_counter()
    values, note = session.check()
    ok, table = check.judge(values, limits)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s; {note}")
    d = devices[0]
    return dict(
        correct=bool(ok and session.failed == 0),
        attempted=session.attempted, failed=session.failed, metrics=metrics,
        device=dict(platform=d.platform, kind=d.device_kind,
                    count=len(devices), memory_peak_bytes=peak,
                    **device_extra),
        breakdown=breakdown,
        compile=dict(setup_compile, window_compiles=in_window),
        checks=table)
