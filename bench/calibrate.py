"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <name> --seeds 12 --controls 3 \\
        [--faults half_batch,exchange,...] [--out FILE]

In one process, so that the programs compile once: for each of
``--seeds`` seeds, the program's first steps against the reference at the
configuration's precision (the lower readings); for the first
``--controls`` seeds, the control, the reference itself computed with
``Precision.HIGH``'s three bfloat16 passes, put in the program's place (the
upper readings); and the faults asked for, each on those seeds:

* ``half_batch``: the reference trained on the first half of each batch
  (refused for a batch of one scene);
* ``half_shards``: the reference with the loss terms of the first half of
  the shards alone, their mean taken over those shards' nodes (half of a
  sharded scene left out);
* ``own_shard``: the reference stepping on the first shard's share of the
  gradient alone (the all-reduce of the gradient between chips left out);
* ``exchange``: the program with the exchange between chips left out
  (centre of mass and virtual-node sums kept local to each shard);
* ``loss_local``: the program with the loss's sums over shards kept local
  to each shard;
* ``program_bf16``: the program's own bfloat16 kernel path.

Each reading is one JSON line: kind, seed and the three numbers, and on a
sharded cell ``virtual_gap`` beside them (a fault of the reference alone
leaves the forward, and so the virtual nodes, as the reference's own).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def as_checked(ref_out: dict, shards: int = 1) -> dict:
    """A reference run dressed as the program's first steps: on a sharded
    cell its virtual nodes as each of ``shards`` shards would hold them."""
    import jax
    import numpy as np

    from bench.check import BETA1

    out = dict(losses=ref_out["losses"],
               m1=jax.tree.map(lambda g: g * (1 - BETA1), ref_out["grad1"]),
               params=ref_out["params"])
    if "virtual" in ref_out:
        vs = ref_out["virtual"]
        out["virtual"] = {k: np.broadcast_to(vs[k], (shards,) + vs[k].shape)
                          for k in ("z", "s")}
    return out


@contextlib.contextmanager
def exchange_left_out():
    """The program's DistEGNN layers with every cross-shard sum of the
    virtual-node bridge made local (the psums skipped)."""
    from repro.core import virtual_nodes as vn
    from repro.models import fast_egnn as fe

    saved = {k: getattr(fe, k) for k in ("init_virtual_coords",
                                         "masked_com", "masked_com_sums",
                                         "launch_virtual_sums",
                                         "virtual_aggregate_from_sums")}
    fe.init_virtual_coords = lambda x, m, c, axis_name=None: \
        vn.init_virtual_coords(x, m, c, None)
    fe.masked_com = lambda x, m, axis_name=None: vn.masked_com(x, m, None)
    fe.masked_com_sums = lambda x, m, axis_name=None: \
        vn.masked_com_sums(x, m, None)
    fe.launch_virtual_sums = lambda dz, ms, n, axis_name=None: \
        vn.launch_virtual_sums(dz, ms, n, None)
    fe.virtual_aggregate_from_sums = \
        lambda p, vs, dz, ms, n, axis_name=None: \
        vn.virtual_aggregate_from_sums(p, vs, dz, ms, n, None)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(fe, k, v)


@contextlib.contextmanager
def loss_sums_local():
    """The program's DistEGNN loss with its masked-MSE sums over shards
    kept local (each shard's mean over its own nodes)."""
    from repro.distributed import dist_egnn
    from repro.training import losses

    saved = dist_egnn.masked_mse
    dist_egnn.masked_mse = lambda p, t, m, axis_name=None: \
        losses.masked_mse(p, t, m, None)
    try:
        yield
    finally:
        dist_egnn.masked_mse = saved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--base-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import check, driver, registry

    c = registry.cell(args.workload)
    cfg, traffic = c["config"], c["traffic"]
    win = registry.window(traffic["window"])
    faults = [f for f in args.faults.split(",") if f]
    if "half_batch" in faults and traffic["batch"] < 2:
        raise SystemExit("half_batch needs a batch of two scenes or more; "
                         "half of a sharded scene is half_shards")
    driver.enable_compile_cache()
    shards = cfg["devices"]

    def emit(kind, seed, values, **extra):  # writes to ``out``, below
        row = dict(workload=args.workload, kind=kind, seed=seed, **values,
                   **extra)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def program(seed, cfg_run, patch=None):
        with patch() if patch else contextlib.nullcontext():
            kept = win.Warm(cfg_run, traffic, seed, win.CHECKED_STEPS).keep()
            gc.collect()
            checked = win.program_checked(kept)
        return kept.pool, kept.keys, kept.params0, checked

    with (open(args.out, "a") if args.out
          else contextlib.nullcontext()) as out:
        for i in range(args.seeds):
            seed = args.base_seed + 7919 * i
            t0 = time.perf_counter()
            pool, keys, params0, checked = program(seed, cfg)
            t1 = time.perf_counter()
            vals, ref_hi = win.reference_gaps(cfg, traffic, pool, keys,
                                              params0, checked)
            emit("program", seed, vals, losses=checked["losses"],
                 ref_losses=ref_hi["losses"],
                 step_loss_gaps=check.step_loss_gaps(checked, ref_hi),
                 program_s=t1 - t0, reference_s=time.perf_counter() - t1)
            if i >= args.controls:
                continue
            ref_mod = registry.reference(cfg["reference"])
            _, batches, rkeys = win.reference_batches(
                cfg, traffic, pool, keys, win.CHECKED_STEPS)
            high = as_checked(ref_mod.train(params0, batches, rkeys, cfg,
                                            mode="high"), shards)
            emit("control_high", seed, check.gaps(high, ref_hi, params0),
                 step_loss_gaps=check.step_loss_gaps(high, ref_hi))
            for f in faults:
                if f == "half_batch":  # the reference on half of each batch
                    _, hb = win.reference_gaps(
                        cfg, traffic, pool, keys, params0, checked,
                        scenes_per_step=traffic["batch"] // 2)
                    fc = as_checked(hb, shards)
                elif f == "half_shards":
                    _, hs = win.reference_gaps(
                        cfg, traffic, pool, keys, params0, checked,
                        loss_shards=cfg["devices"] // 2)
                    fc = as_checked(hs, shards)
                elif f == "own_shard":
                    _, os_ = win.reference_gaps(
                        cfg, traffic, pool, keys, params0, checked,
                        own_shard=True)
                    fc = as_checked(os_, shards)
                elif f == "exchange":
                    _, _, _, fc = program(seed, cfg, exchange_left_out)
                elif f == "loss_local":
                    _, _, _, fc = program(seed, cfg, loss_sums_local)
                elif f == "program_bf16":
                    _, _, _, fc = program(seed, dict(cfg, precision="bf16"))
                else:
                    raise SystemExit(f"unknown fault {f!r}")
                emit(f, seed, check.gaps(fc, ref_hi, params0),
                     step_loss_gaps=check.step_loss_gaps(fc, ref_hi))
    return 0


if __name__ == "__main__":
    sys.exit(main())
