"""Model operations of a FastTFN training step, counted from the
algorithm (``reference/fast_tfn.py``).

Forward, per scene: the embedding, and per layer the edge pathway
(``tfn_edge``), the plug-in's virtual pathway (``virtual_message``), the
feature update ``h_out`` ((hidden + 2) -> hidden -> hidden), the centre of
mass, the virtual global message, the coordinate update (the norm bound of
the virtual term and the two sums), and per channel ``phi_s``
((s_dim + hidden) -> hidden -> s_dim); then the loss.  A training step is
three forwards: the backward counts twice, and nothing recomputed counts.
"""
from __future__ import annotations

from bench.work import tfn_edge, virtual_message
from bench.work.edge_message import dense


def forward_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    h, s, c = cfg["hidden"], cfg["s_dim"], cfg["n_virtual"]
    per_layer = (
        tfn_edge.forward_flops(n_nodes, n_edges, h, cfg["n_rbf"])
        + virtual_message.forward_flops(n_nodes, h, s, c)
        + n_nodes * (dense(h + 2, h) + dense(h, h)  # h_out
                     + 3  # centre of mass
                     + 3 * 2 + 3  # norm bound of the virtual term
                     + 3 + 3)  # x + dx_edges + dx_virtual
        + c * (dense(s + h, h) + dense(h, s))  # phi_s
        + c * c * 3 * 2)  # virtual global message
    loss = n_nodes * 3 * 3
    return n_nodes * dense(cfg["h_in"], h) + cfg["n_layers"] * per_layer + loss


def train_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    return 3 * forward_flops(cfg, n_nodes, n_edges)
