"""Cut a recorded chip trace down to a test fixture.

    python bench/fixtures/trim.py <trace dir> <out.xplane.pb> --start-ms A --ms B

Keeps, from ``--start-ms`` after the first device operation and for
``--ms``, every chip's ``XLA Ops`` events and the training window's host spans,
as recorded (names and times unchanged), in a new ``.xplane.pb``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _quote(s: str) -> str:
    return json.dumps(s)  # a valid text-proto string literal for ASCII


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--start-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    from bench import registry
    from bench import trace as T

    spans = registry.window("train").SPANS

    pd = ProfileData.from_file(T.find_xplane(args.trace))
    planes = []
    t0 = None
    for plane in pd.planes:
        if T.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    evs = list(line.events)
                    if evs:
                        first = min(e.start_ns for e in evs)
                        t0 = first if t0 is None else min(t0, first)
    lo = float(int(t0 + args.start_ms * 1e6))
    hi = lo + args.ms * 1e6
    for plane in pd.planes:
        keep = []
        if T.DEVICE_PLANE.match(plane.name):
            keep = [(line.name, [e for e in line.events
                                 if lo <= e.start_ns < hi])
                    for line in plane.lines if line.name == T.OPS_LINE]
        elif plane.name.startswith("/host"):
            evs = [e for line in plane.lines for e in line.events
                   if e.name in spans and e.end_ns > lo and e.start_ns < hi]
            keep = [("python", evs)] if evs else []
        if keep:
            planes.append((plane.name, keep))
    parts = []
    for pid, (pname, lines) in enumerate(planes, start=1):
        names = {}
        body = []
        for lid, (lname, evs) in enumerate(lines, start=1):
            ev_txt = []
            for e in sorted(evs, key=lambda e: e.start_ns):
                mid = names.setdefault(e.name, len(names) + 1)
                ev_txt.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {round((e.start_ns - lo) * 1000)} "
                    f"duration_ps: {round(e.duration_ns * 1000)} }}")
            body.append(f"lines {{ id: {lid} name: {_quote(lname)} "
                        f"timestamp_ns: {int(lo)} " + " ".join(ev_txt) + " }")
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {_quote(n)} }} }}" for n, i in names.items())
        parts.append(f"planes {{ id: {pid} name: {_quote(pname)} "
                     + " ".join(body) + " " + meta + " }")
    data = ProfileData.text_proto_to_serialized_xspace("\n".join(parts))
    with open(args.out, "wb") as f:
        f.write(data)
    print(f"{args.out}: {len(data)} bytes, planes "
          f"{[p for p, _ in planes]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
