"""BENCHMARK.json against the contract, lookup by name, and the command's
refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import registry
from bench.tests.tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return registry.benchmark()


def test_top_level_keys(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    assert bm["command"][1] == "bench/run.py"
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_entry_keys(bm):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bm[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}
    e2e = {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_resolves(bm):
    """Each cell, and each cell held back, finds its configuration, traffic
    mix, limits and every per-layer metric's reader by name, and reports
    setup_s, another end-to-end metric and a per-layer metric.  Every cell
    is held to the three training numbers, and a sharded one to
    virtual_gap besides."""
    held = registry.held_back()
    assert not {w["name"] for w in held["workloads"]} & {
        w["name"] for w in bm["workloads"]}
    for w in bm["workloads"] + held["workloads"]:
        c = registry.cell(w["name"])
        assert c["config"]["devices"] == w["chips"]
        want = {"loss_gap", "grad_gap", "update_gap"}
        if c["config"]["devices"] > 1:
            want.add("virtual_gap")
        assert set(c["limits"]["limits"]) == want
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
        for m in c["per_layer"]:
            assert callable(registry.metric_reader(m["name"]))
        registry.reference(c["config"]["reference"])
        assert hasattr(registry.window(c["traffic"]["window"]), "Session")


def test_a_metric_is_a_new_file_alone(tmp_path):
    """A reader dropped into metrics/ under a new name is found by that
    name, with no other file edited."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"), bench / "metrics")
    (bench / "metrics" / "dummy_share.train.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx.steps else None\n")
    read = registry.metric_reader("dummy_share.train", bench_dir=str(bench))
    assert read(type("Ctx", (), {"steps": 3})()) == 42.0
    with pytest.raises(registry.BenchError):
        registry.metric_reader("no_such_metric", bench_dir=str(bench))


def test_a_window_is_a_new_file_alone(tmp_path):
    """A window dropped into windows/ under a new name is found by that
    name, with no other file edited."""
    (tmp_path / "windows").mkdir()
    (tmp_path / "windows" / "dummy_serve.py").write_text(
        "SPANS = ('request',)\n\nclass Session:\n    pass\n")
    mod = registry.window("dummy_serve", bench_dir=str(tmp_path))
    assert mod.SPANS == ("request",) and hasattr(mod, "Session")
    with pytest.raises(registry.BenchError):
        registry.window("no_such_window", bench_dir=str(tmp_path))


def test_unknown_device_kind_is_an_error():
    assert registry.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(registry.BenchError):
        registry.peaks("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "water3d.train",
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_fails_with_only_its_own_files(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program to
    measure: the command fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_line_schema():
    """The last line carries the contract's keys, the checks last."""
    from bench.run import result_line

    c = registry.cell("water3d.train")
    res = dict(correct=True, attempted=7, failed=0,
               metrics={"setup_s": {"value": 1.5, "unit": "s"},
                        "train_scenes_per_s": {"value": 8.5,
                                               "unit": "scenes/s"}},
               device=dict(platform="tpu", kind="TPU v5 lite", count=1,
                           memory_peak_bytes=1),
               breakdown=None,
               compile=dict(compile_s=2.2, compiles=53, cache_hits=53,
                            cache_misses=0, window_compiles=0),
               checks={"loss_gap": {"value": 1e-6, "limit": 1e-4}})
    line = json.loads(json.dumps(result_line(res, c, trace=False)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compile", "checks"]
    assert set(line["metrics"]) == {"setup_s", "train_scenes_per_s"}
    res["metrics"] = {"train_mfu": {"value": 0.2, "unit": "%"}}
    res["breakdown"] = {"device_ops": [], "idle_gaps": []}
    line = result_line(res, c, trace=True)
    assert list(line)[-3:] == ["breakdown", "compile", "checks"]
    assert set(line["metrics"]) == {"train_mfu"}
