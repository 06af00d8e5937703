"""Model operations of a FastEGNN / DistEGNN training step, counted from the
algorithm, and the graph sizes they are counted on.

Forward, per scene (or per shard of a scene): the embedding, and per layer
the edge pathway (``edge_message``), the virtual pathway
(``virtual_message``), the node MLPs ``phi_v`` (hidden -> hidden -> 1) and
``phi_h`` (3 hidden -> hidden -> hidden), the centre of mass, the virtual
global message, the coordinate update, and per channel ``phi_s``
((s_dim + hidden) -> hidden -> s_dim); then the loss.  A training step is
three forwards: the backward counts twice, and nothing recomputed counts.
"""
from __future__ import annotations

import numpy as np

from bench import scenes
from bench.work import edge_message, virtual_message

dense = edge_message.dense


def forward_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    h, s, c = cfg["hidden"], cfg["s_dim"], cfg["n_virtual"]
    per_layer = (
        edge_message.forward_flops(n_nodes, n_edges, h)
        + virtual_message.forward_flops(n_nodes, h, s, c)
        + n_nodes * (dense(h, h) + dense(h, 1)  # phi_v
                     + dense(3 * h, h) + dense(h, h)  # phi_h
                     + 3  # centre of mass
                     + 3 + 3 + 3 + 3 * 2 + 3)  # dx sums, clamp, v gate, x
        + c * (dense(s + h, h) + dense(h, s))  # phi_s
        + c * c * 3 * 2)  # virtual global message
    loss = n_nodes * 3 * 3
    return n_nodes * dense(cfg["h_in"], h) + cfg["n_layers"] * per_layer + loss


def train_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    return 3 * forward_flops(cfg, n_nodes, n_edges)


def shard_sizes(ctx) -> list:
    """Per batch of an epoch, per scene in it, per shard: (nodes, real
    edges), from the benchmark's own radius graphs of the pool (the local
    graphs under the launcher's random partition on several chips)."""
    if "shard_sizes" in ctx.memo:
        return ctx.memo["shard_sizes"]
    cfg, b = ctx.cfg, ctx.traffic["batch"]
    d = cfg["devices"]
    assign = (scenes.random_partition(0, cfg["n_particles"], d)
              if d > 1 else np.zeros(cfg["n_particles"], np.int64))
    per_scene = []
    for sc in ctx.pool:
        snd, rcv = scenes.radius_pairs(sc.x0, cfg["r"])
        local = assign[snd] == assign[rcv]
        per_scene.append([(int(np.sum(assign == k)),
                           int(np.sum(local & (assign[rcv] == k))))
                          for k in range(d)])
    out = [[per_scene[i * b + j] for j in range(b)]
           for i in range(ctx.n_batches)]
    ctx.memo["shard_sizes"] = out
    return out


def window_sizes(ctx):
    """(nodes, edges) of every scene-shard trained in the window."""
    sizes = shard_sizes(ctx)
    for pos in ctx.batch_index:
        for scene in sizes[pos]:
            yield from scene
