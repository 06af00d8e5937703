"""The trace reduction, on hand-made intervals and on traces recorded on
the chip (``bench/fixtures/*.xplane.pb``, cut down by ``fixtures/trim.py``).

The fixtures' numbers are checked twice: against a plain count over the
raw events (a timeline sampled every 100 ns, sums over matching names), and
against the values the reduction gave when the fixture was recorded."""
import os
import re

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import registry
from bench import trace as T
from bench.tests.tiny import ROOT

SPANS = registry.window("train").SPANS

FIX = os.path.join(ROOT, "bench", "fixtures")


def ev(name, start, end, text=""):
    return T.Event(name, float(start), float(end), text or name)


def test_interval_algebra():
    iv = np.array([[0, 10], [5, 20], [30, 40], [39, 41]], float)
    assert T.union(iv).tolist() == [[0, 20], [30, 41]]
    assert T.length(T.clip(T.union(iv), 10, 35)) == 15
    assert T.subtract(np.array([[0, 100]], float),
                      np.array([[10, 20], [15, 30], [90, 120]], float)) == 70


def test_exposed_collective_and_idle_by_hand():
    spans = [ev("train_step_dispatch", 0, 10), ev("block", 10, 1000)]
    ops = [[ev("%fusion.1", 0, 400), ev("%all-reduce.2", 300, 500),
            ev("%fusion.3", 600, 900)],
           [ev("%fusion.1", 0, 100), ev("%all-reduce.2", 100, 700)]]
    t = T.Trace(ops, spans)
    assert t.window_s() == pytest.approx(1e-6)
    # chip 0 busy 0-500 and 600-900, chip 1 busy 0-700
    assert t.busy_s() == pytest.approx((800 + 700) / 2 / 1e9)
    assert t.exposed_seconds(T.COLLECTIVE, 0) == pytest.approx(100e-9)
    assert t.exposed_seconds(T.COLLECTIVE, 1) == pytest.approx(600e-9)
    gaps = t.breakdown()["idle_gaps"]
    assert gaps[0] == ["block", pytest.approx(100e-9)]


def _raw_device_events(pd, chip):
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{chip}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return []


def _raw_window(pd):
    sp = [(e.start_ns, e.end_ns) for p in pd.planes if p.name.startswith("/host")
          for l in p.lines for e in l.events if e.name in SPANS]
    return min(s for s, _ in sp), max(e for _, e in sp)


def _grid(events, lo, hi, step=100.0):
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    for _, s, e in events:
        a, b = int(max(s - lo, 0) / step), int(min(e - lo, hi - lo) / step)
        grid[a:b] = True
    return grid


def _sampled_busy(events, lo, hi, step=100.0):
    return _grid(events, lo, hi, step).sum() * step / 1e9


def _sampled_exposed(events, lo, hi, step=100.0):
    coll = [e for e in events if re.match(r"%all-reduce", e[0])]
    rest = [e for e in events if not re.match(r"%all-reduce", e[0])]
    grid = _grid(coll, lo, hi, step) & ~_grid(rest, lo, hi, step)
    return grid.sum() * step / 1e9


def _is_raw_edge_kernel(name):
    own = name.split(" = ", 1)[0]
    return bool(re.search(r"edge_pathway(_bwd)?_fused", own)
                or (re.match(r"%closed_call[.\d]*$", own)
                    and "kind=kCustom" in name))


FIXTURES = {
    # file: (chips, device idle %, edge-kernel seconds, exposed collective s)
    # 120 ms of a water3d.train step (one chip): 13 edge-kernel calls
    # inside the per-sample loops
    "water3d_train.xplane.pb": (1, 85.1791847687923, 0.119529079, None),
    # 40 ms of a fluid113k.dist_train step on each of four chips: three
    # edge-kernel calls and 4 all-reduces a chip
    "fluid113k_dist_train.xplane.pb": (4, 72.12518535665595, 0.197045178,
                                       2.2291e-05),
}


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_fixture_reduction(name):
    chips, idle_pct, edge_s, exposed_s = FIXTURES[name]
    path = os.path.join(FIX, name)
    pd = ProfileData.from_file(path)
    t = T.load(path, chips, SPANS)
    lo, hi = _raw_window(pd)
    assert t.window_s() == pytest.approx((hi - lo) / 1e9)
    busy = np.mean([_sampled_busy(_raw_device_events(pd, c), lo, hi)
                    for c in range(chips)])
    assert t.busy_s() == pytest.approx(busy, rel=1e-3)
    idle = registry.metric_reader("device_idle.train")(
        type("Ctx", (), {"trace": t})())
    assert idle == pytest.approx(100 * (1 - busy / ((hi - lo) / 1e9)),
                                 rel=1e-3)
    edge = registry.load_module(
        os.path.join(FIX, "..", "metrics", "edge_kernel_roofline.train.py"),
        "edge_fixture_reader")
    raw_edge = sum(e - s for c in range(chips)
                   for n, s, e in _raw_device_events(pd, c)
                   if _is_raw_edge_kernel(n) and s < hi and e > lo) / 1e9
    got_edge = sum(e.end - e.start for c in range(chips) for e in t.ops(c)
                   if edge.is_edge_kernel(e)) / 1e9
    assert got_edge == pytest.approx(raw_edge)
    assert raw_edge > 0
    if idle_pct is not None:
        assert idle == pytest.approx(idle_pct, rel=1e-9)
        assert got_edge == pytest.approx(edge_s, rel=1e-9)
    exposed = max(t.exposed_seconds(T.COLLECTIVE, c) for c in range(chips))
    raw_exposed = max(_sampled_exposed(_raw_device_events(pd, c), lo, hi)
                      for c in range(chips))
    assert exposed == pytest.approx(raw_exposed, rel=1e-2, abs=1e-6)
    if exposed_s is not None:
        assert raw_exposed > 0
        assert exposed == pytest.approx(exposed_s, rel=1e-9)
