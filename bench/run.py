"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, chips) is looked up by name in
``BENCHMARK.json``.  The run makes its scenes and weights from the seed,
warms up (compiling, or reading back from the compile cache), trains for
``--seconds`` and checks its first steps against the plain reference.
With ``--trace 0`` it reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Progress goes to standard error, ending with each number that
decided ``correct`` beside its limit; the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), ``compile`` (set-up's compile
seconds, compiles and persistent-cache hits and misses: a run that
missed the cache compiled in its set-up), then ``checks``.

It refuses to run, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from bench import registry

    c = registry.cell(args.workload)
    chips = c["workload"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU ({devices}); the benchmark runs only "
              f"on the chip", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = registry.peaks(devices[0].device_kind)

    from bench import driver

    res = driver.run_cell(
        c["config"], c["traffic"],
        {k: v["limit"] for k, v in c["limits"]["limits"].items()},
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, per_layer=c["per_layer"] if args.trace else (),
        peaks=peaks)
    line = result_line(res, c, bool(args.trace))
    for name, v in line["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def result_line(res: dict, c: dict, trace: bool) -> dict:
    """The last line: ``correct``, ``attempted``, ``failed``, ``metrics``
    (the cell's end-to-end metrics, or with ``trace`` the per-layer ones
    its trace gave), ``device``, ``breakdown`` when traced, ``compile``,
    and last the numbers that decided ``correct``, each with its limit."""
    names = [m["name"] for m in (c["per_layer"] if trace else c["end_to_end"])]
    line = {k: res[k] for k in ("correct", "attempted", "failed")}
    line["metrics"] = {k: res["metrics"][k] for k in names
                       if k in res["metrics"]}
    line["device"] = res["device"]
    if trace:
        line["breakdown"] = res["breakdown"]
    line["compile"] = res["compile"]
    line["checks"] = res["checks"]
    return line


if __name__ == "__main__":
    sys.exit(main())
