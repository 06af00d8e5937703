"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb`` (``jax.profiler.ProfileData``).  Device
operations are the events on each chip's ``XLA Ops`` line; the window is
the span of the host annotations that the cell's window records (its
``SPANS``; the training window's are ``stream_next``,
``train_step_dispatch`` and ``block``), which the profiler puts on the
same clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: XLA's collective operations (their async halves included)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)


class Event(NamedTuple):
    name: str  # the operation's own name: "%fusion.12", or a span's name
    start: float  # ns
    end: float  # ns
    text: str  # the whole HLO instruction as the trace states it, or ""


def op_event(name: str, start: float, end: float) -> Event:
    """A device operation; the trace names it by its whole HLO text."""
    return Event(name.split(" = ", 1)[0], start, end, name)


def kind(name: str) -> str:
    """An operation's name without its instance number: "%fusion.12" ->
    "fusion"."""
    return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))


def union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of (k, 2) [start, end) intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=float)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    iv = np.stack([np.maximum(intervals[:, 0], lo),
                   np.minimum(intervals[:, 1], hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def length(intervals: np.ndarray) -> float:
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(
        intervals) else 0.0


def subtract(a: np.ndarray, b: np.ndarray) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    total = 0.0
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _intervals(events) -> np.ndarray:
    return np.array([(e.start, e.end) for e in events], dtype=float).reshape(
        -1, 2)


class Trace:
    """Device operations per chip and the host spans of one traced window."""

    def __init__(self, device_ops: list, spans: list):
        self.device_ops = device_ops  # per chip: list[Event]
        self.spans = spans  # list[Event]
        if not spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        self.lo = min(s.start for s in spans)
        self.hi = max(s.end for s in spans)

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, chip: int, pattern: re.Pattern | None = None) -> list:
        evs = [e for e in self.device_ops[chip]
               if e.end > self.lo and e.start < self.hi]
        if pattern is None:
            return evs
        return [e for e in evs if pattern.search(e.name)]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return float(np.mean([
            length(clip(union(_intervals(self.ops(c))), self.lo, self.hi))
            for c in range(len(self.device_ops))])) / 1e9

    def op_seconds(self, pattern: re.Pattern, chip: int | None = None) -> float:
        """Summed device time of the operations matching ``pattern``, on
        one chip or summed over all."""
        chips = range(len(self.device_ops)) if chip is None else [chip]
        return sum(e.end - e.start for c in chips
                   for e in self.ops(c, pattern)) / 1e9

    def exposed_seconds(self, pattern: re.Pattern, chip: int) -> float:
        """Time of the matching operations during which no other operation
        runs on that chip."""
        mine = self.ops(chip, pattern)
        others = [e for e in self.ops(chip) if not pattern.search(e.name)]
        return subtract(clip(_intervals(mine), self.lo, self.hi),
                        _intervals(others)) / 1e9

    def span_seconds(self, name: str) -> float:
        return sum(s.end - s.start for s in self.spans if s.name == name) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The kinds of device operation that took most time (innermost
        operations only, so that a loop and its body count once; seconds a
        chip), and the longest idle gaps of chip 0 by the host span that
        covered their middle."""
        n = len(self.device_ops)
        tot: dict[str, float] = {}
        for c in range(n):
            evs = sorted(self.ops(c), key=lambda e: (e.start, -e.end))
            for i, e in enumerate(evs):
                if i + 1 < len(evs) and evs[i + 1].start < e.end:
                    continue  # it encloses the next operation
                k = kind(e.name)
                tot[k] = tot.get(k, 0.0) + (e.end - e.start) / 1e9 / n
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        busy = clip(union(_intervals(self.ops(0))), self.lo, self.hi)
        edges = [self.lo] + [v for iv in busy for v in iv] + [self.hi]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = 0.5 * (s + e)
                cover = [sp.name for sp in self.spans
                         if sp.start <= mid < sp.end]
                gaps.append((cover[-1] if cover else "none", (e - s) / 1e9))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def from_profile(pd, n_devices: int, spans: tuple) -> Trace:
    """The device operations of chips ``0 .. n_devices-1`` and the host
    annotations named in ``spans``."""
    device = {}
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[int(m.group(1))] = [
                        op_event(e.name, e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.end_ns, "")
                         for e in line.events if e.name in spans]
    missing = [d for d in range(n_devices) if d not in device]
    if missing:
        raise ValueError(f"the trace has no '{OPS_LINE}' line for chips "
                         f"{missing} (planes: {[p.name for p in pd.planes]})")
    return Trace([device[d] for d in range(n_devices)], host)


def load(path: str, n_devices: int, spans: tuple) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(path)), n_devices,
                        spans)


class Context:
    """What a per-layer metric's reader gets: the trace, the cell's
    configuration and traffic, the window's host-clock seconds, the chips
    used and their published peaks, and what the cell's window reports
    about itself (the training window: ``pool``, the scene pool;
    ``batch_index``, the position in its epoch of each step's batch;
    ``n_batches``, the batches of an epoch; ``steps``, the window's steps).
    ``memo`` holds what one reader works out for others to reuse."""

    def __init__(self, *, trace: Trace, cfg: dict, traffic: dict,
                 window_s: float, chips: int, peaks: dict, **info):
        self.trace = trace
        self.cfg = cfg
        self.traffic = traffic
        self.window_s = window_s
        self.chips = chips
        self.peaks = peaks
        self.__dict__.update(info)
        self.memo = {}
