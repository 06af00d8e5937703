"""DistEGNN end-to-end: partition a fluid graph over 4 devices, train with
psum-synchronised virtual nodes, verify the distributed forward matches the
single-device model exactly.

On a four-chip host:
    PYTHONPATH=src python examples/distributed_fluid.py
On the CPU, with four emulated devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/distributed_fluid.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import make_graph
from repro.data.fluid import generate_fluid_dataset
from repro.data.partition import partition_sample
from repro.distributed.dist_egnn import (build_dist_apply,
                                         build_dist_train_step, make_gnn_mesh,
                                         stack_partitions)
from repro.models.fast_egnn import (FastEGNNConfig, fast_egnn_apply,
                                    init_fast_egnn)
from repro.training.optim import Adam

N_DEV = 4


def main():
    print(f"devices: {jax.devices()}")
    if len(jax.devices()) < N_DEV:
        raise SystemExit(f"needs {N_DEV} devices, JAX found "
                         f"{len(jax.devices())} (see the module docstring)")
    mesh = make_gnn_mesh(N_DEV)
    data = generate_fluid_dataset(8, n_particles=400)
    pgs = [[partition_sample(s.x0, s.v0, s.h, s.x1, d=N_DEV, r=0.05, seed=j)
            for j, s in enumerate(data[i : i + 4])] for i in (0, 4)]
    batches = [stack_partitions(p, mesh) for p in pgs]
    print(f"partitioned: {batches[0].x.shape} per-shard edges "
          f"{float(batches[0].edge_mask.sum(-1).mean()):.0f}")

    cfg = FastEGNNConfig(n_layers=3, hidden=32, h_in=1, n_virtual=3, s_dim=32)
    params = init_fast_egnn(jax.random.PRNGKey(0), cfg)

    # 1. consistency: distributed == single-device on the same (union) graph
    x_pred, vs = build_dist_apply(cfg, mesh)(params, batches[0])
    pg = pgs[0][0]
    xs, vv, hh, snd, rcv, off = [], [], [], [], [], 0
    for d in range(N_DEV):
        nm = pg.node_mask[d] > 0
        n_d = int(nm.sum())
        xs.append(pg.x[d][:n_d]); vv.append(pg.v[d][:n_d]); hh.append(pg.h[d][:n_d])
        em = pg.edge_mask[d] > 0
        snd.append(pg.senders[d][em] + off); rcv.append(pg.receivers[d][em] + off)
        off += n_d
    g = make_graph(np.concatenate(xs), np.concatenate(vv), np.concatenate(hh),
                   np.concatenate(snd), np.concatenate(rcv))
    x_ref, _, _ = fast_egnn_apply(params, cfg, g)
    x_dist = np.concatenate([np.asarray(x_pred[d, 0])[pg.node_mask[d] > 0]
                             for d in range(N_DEV)])
    print(f"dist vs single-device max err: {np.abs(x_dist - np.asarray(x_ref)).max():.2e}")
    print(f"virtual state synced across shards: "
          f"{float(jnp.max(jnp.abs(vs.z - vs.z[0:1]))):.2e}")

    # 2. distributed training (Alg. 1)
    opt = Adam(lr=5e-4)
    step, loss_fn = build_dist_train_step(cfg, mesh, opt, lam_mmd=0.01)
    st = opt.init(params)
    print(f"initial loss: {float(loss_fn(params, batches[0])):.6f}")
    for epoch in range(10):
        for b in batches:
            params, st, loss = step(params, st, b)
        print(f"epoch {epoch}: loss {float(loss):.6f}")


if __name__ == "__main__":
    main()
