"""Operations and compulsory bytes of the real-real edge pathway (Eq. 3 and
the real terms of Eqs. 6-7), counted from the algorithm.

Per real edge j -> i and layer:

* the message MLP ``phi1``: (2 hidden + 1) -> hidden -> hidden;
* the gate MLP ``phi_xr``: hidden -> hidden -> 1;
* the edge vector and its squared length, the gated vector, and the sums
  of message, gated vector and degree into the receiver;

and per node the division of the sums by the degree.  A dense layer of
``a -> b`` is ``2ab`` operations plus ``b`` for its bias.  Activations are
not counted.  The backward pass counts twice the forward, as for the dense
layers that dominate.  Nothing here depends on how an implementation lays
the edges out: no one-hot products, no padding slots, no band geometry.

Bytes are the compulsory traffic in float32 (int32 endpoints): forward,
x and h read once, both endpoints of every edge and the weights read, dx,
mh and the degree written; backward, the same reads plus the cotangents of
dx and mh and the degree, and the gradients of x, h and the weights
written.
"""
from __future__ import annotations

F32 = 4


def dense(a: int, b: int, bias: bool = True) -> int:
    return 2 * a * b + (b if bias else 0)


def weight_count(hidden: int) -> int:
    h = hidden
    return (2 * h + 1) * h + h + h * h + h + h * h + h + h


def forward_flops(n_nodes: int, n_edges: int, hidden: int) -> int:
    h = hidden
    per_edge = (dense(2 * h + 1, h) + dense(h, h)  # phi1
                + dense(h, h) + dense(h, 1, bias=False)  # phi_xr
                + 3 + 5  # edge vector, squared length
                + 3  # gated vector
                + h + 3 + 1)  # sums into the receiver
    per_node = h + 3 + 1  # degree mean
    return n_edges * per_edge + n_nodes * per_node


def forward_bytes(n_nodes: int, n_edges: int, hidden: int) -> int:
    reads = n_nodes * (3 + hidden) + 2 * n_edges + weight_count(hidden)
    writes = n_nodes * (3 + hidden + 1)
    return F32 * (reads + writes)


def backward_flops(n_nodes: int, n_edges: int, hidden: int) -> int:
    return 2 * forward_flops(n_nodes, n_edges, hidden)


def backward_bytes(n_nodes: int, n_edges: int, hidden: int) -> int:
    reads = (n_nodes * (3 + hidden) + 2 * n_edges + weight_count(hidden)
             + n_nodes * (3 + hidden + 1))
    writes = n_nodes * (3 + hidden) + weight_count(hidden)
    return F32 * (reads + writes)
