"""Training launcher: train FastEGNN/DistEGNN on a synthetic dataset —

    python -m repro.launch.train gnn --model fast_egnn --dataset nbody \
        --epochs 50 --n-virtual 3 --drop-rate 0.75 [--devices 4]

Both device counts go through the one pipeline API (DESIGN.md §7):
``build_pipeline(name, key, mesh=...)`` + ``pipe.make_batches`` +
``pipe.fit`` — ``--devices 1`` drives the vmap trainer over
layout-carrying GraphBatches, ``--devices > 1`` drives the shard_map
DistEGNN path over that many of the devices JAX finds (model pinned to
fast_egnn, the paper's Sec. VI architecture) and fails when there are
fewer.  A CPU rehearsal sets
``XLA_FLAGS=--xla_force_host_platform_device_count=D`` itself.
"""
from __future__ import annotations

import argparse


def gnn_main(args):
    import jax
    import numpy as np

    from repro.pipeline import build_pipeline
    from repro.training.checkpoint import save_checkpoint
    from repro.training.trainer import TrainConfig

    from repro.launch.compile_cache import enable_compile_cache

    have = len(jax.devices())
    if have < args.devices:
        # CPU rehearsals get their devices from the caller's XLA_FLAGS
        # (--xla_force_host_platform_device_count); nothing is emulated here
        raise SystemExit(f"--devices {args.devices} needs {args.devices} "
                         f"devices, but JAX found {have}: "
                         f"{jax.devices()}")
    enable_compile_cache()

    if args.dataset == "nbody":
        from repro.data.nbody import generate_nbody_dataset
        data = generate_nbody_dataset(args.n_samples, n_nodes=args.n_nodes)
        r, h_in = np.inf, 1
    elif args.dataset == "fluid":
        from repro.data.fluid import generate_fluid_dataset
        data = generate_fluid_dataset(args.n_samples, n_particles=args.n_nodes)
        r, h_in = 0.035, 1
    else:
        from repro.data.protein import generate_protein_dataset
        data = generate_protein_dataset(args.n_samples, n_res=args.n_nodes)
        r, h_in = 10.0, 4

    n_tr = int(0.8 * len(data))
    model = args.model
    kw = dict(h_in=h_in, n_layers=args.n_layers, hidden=args.hidden)
    mesh = None
    if args.devices > 1:
        from repro.distributed.dist_egnn import make_gnn_mesh

        mesh = make_gnn_mesh(args.devices)
        model = "fast_egnn"  # DistEGNN (Sec. VI) is FastEGNN under shard_map
    if model.startswith("fast_"):
        kw.update(n_virtual=args.n_virtual)
        if model in ("fast_egnn", "fast_schnet", "fast_tfn"):
            kw.update(s_dim=args.hidden)
    if model in ("linear",):
        kw = {}
    if model == "mpnn":
        kw = dict(h_in=h_in, n_layers=args.n_layers, hidden=args.hidden)

    tc = TrainConfig(epochs=args.epochs, lam_mmd=args.lam_mmd,
                     mmd_sigma=args.mmd_sigma, seed=args.seed)
    pipe = build_pipeline(model, jax.random.PRNGKey(args.seed), mesh=mesh,
                          train_cfg=tc, **kw)
    # streaming data plane (DESIGN.md §8): batches build in background
    # workers behind a bounded queue; --layout-cache makes warm runs skip
    # every banded-layout rebuild; --reshuffle varies the epoch order
    bk = dict(r=r, drop_rate=args.drop_rate, partition=args.partition,
              prefetch=args.prefetch, num_workers=args.workers,
              cache_dir=args.layout_cache)
    # reshuffle applies to training only: a reshuffled val stream would
    # re-partition (mesh) / re-batch validation every epoch, adding
    # partitioning noise to the early-stopping metric
    tr = pipe.make_batches(data[:n_tr], args.batch,
                           reshuffle_each_epoch=args.reshuffle,
                           shuffle_seed=args.seed if args.reshuffle else None,
                           **bk)
    va = pipe.make_batches(data[n_tr:], args.batch, **bk)
    res = pipe.fit(tr, va, verbose=True)
    if args.layout_cache:
        from repro.data.layout_cache import cache_stats
        print("layout cache:", cache_stats())
    print(f"best val MSE: {res.best_val:.6f}  wall: {res.wall_time:.1f}s"
          f"  devices: {args.devices}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, res.params,
                        {"model": model, "val_mse": res.best_val})
        print("saved", args.checkpoint)


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    g = sub.add_parser("gnn")
    g.add_argument("--model", default="fast_egnn")
    g.add_argument("--dataset", default="nbody", choices=["nbody", "fluid", "protein"])
    g.add_argument("--n-samples", type=int, default=64)
    g.add_argument("--n-nodes", type=int, default=100)
    g.add_argument("--batch", type=int, default=8)
    g.add_argument("--epochs", type=int, default=50)
    g.add_argument("--n-layers", type=int, default=4)
    g.add_argument("--hidden", type=int, default=64)
    g.add_argument("--n-virtual", type=int, default=3)
    g.add_argument("--drop-rate", type=float, default=0.0)
    g.add_argument("--lam-mmd", type=float, default=0.03)
    g.add_argument("--mmd-sigma", type=float, default=1.5)
    g.add_argument("--devices", type=int, default=1)
    g.add_argument("--partition", default="random", choices=["random", "metis"])
    g.add_argument("--checkpoint", default=None)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--layout-cache", default=None, metavar="DIR",
                   help="persist banded-CSR layouts here (warm runs skip "
                        "every layout rebuild — DESIGN.md §8)")
    g.add_argument("--reshuffle", action="store_true",
                   help="reshuffle the training sample order every epoch "
                        "(epoch-keyed rng; off = reproduce the eager order)")
    g.add_argument("--prefetch", type=int, default=2,
                   help="host batches buffered ahead of the training step")
    g.add_argument("--workers", type=int, default=4,
                   help="background batch-build threads")
    args = ap.parse_args()
    gnn_main(args)


if __name__ == "__main__":
    main()
