"""Operations and compulsory bytes of the FastTFN edge pathway (the
single-channel TFN's Cartesian paths of degree 2 at most, arXiv:1802.08219
as the program states it), counted from the algorithm.

Per real edge j -> i and layer:

* the edge vector, its length and direction: 3 + 5 + 2 + 3;
* the radial basis: 4 a basis function (difference, square, scale, exp);
* the radial network: (n_rbf + hidden) -> hidden -> 6;
* the paths: the dot product r̂·v_j (5), ``w0 v_j`` (3), ``w1 r̂`` (3), the
  cross product (9) and ``w2`` times it (3), ``(r̂ r̂ᵀ - I/3) v_j`` (9) and
  ``w3`` times it (3), the type-1 sum (9) and ``w5 r̂·v_j`` (1);
* the sums of the type-1 (3) and type-0 (2) messages and the degree (1)
  into the receiver;

and per node the division of the sums by the degree (3 + 2 + 1).  A dense
layer of ``a -> b`` is ``2ab`` operations plus ``b`` for its bias;
activations and the clip are not counted; the backward pass counts twice
the forward.  Nothing here depends on how an implementation lays the edges
out, nor on how it splits the radial network's first layer.

Bytes are the compulsory float32 traffic (int32 endpoints): forward, x, v
and h read once, both endpoints of every edge and the weights read, the
type-1 and type-0 means and the degree written; backward, the same reads
plus the cotangents of both means and the degree, and the gradients of x,
v, h and the weights written.
"""
from __future__ import annotations

from bench.work.edge_message import F32, dense

PATHS = 6  # path weights the radial network emits


def weight_count(hidden: int, n_rbf: int) -> int:
    return (n_rbf + hidden) * hidden + hidden + hidden * PATHS + PATHS


def forward_flops(n_nodes: int, n_edges: int, hidden: int, n_rbf: int) -> int:
    per_edge = (3 + 5 + 2 + 3  # edge vector, length, direction
                + 4 * n_rbf  # radial basis
                + dense(n_rbf + hidden, hidden) + dense(hidden, PATHS)
                + 5 + 3 + 3 + 9 + 3 + 9 + 3 + 9 + 1  # the paths
                + 3 + 2 + 1)  # sums into the receiver
    return n_edges * per_edge + n_nodes * (3 + 2 + 1)


def forward_bytes(n_nodes: int, n_edges: int, hidden: int, n_rbf: int) -> int:
    reads = (n_nodes * (3 + 3 + hidden) + 2 * n_edges
             + weight_count(hidden, n_rbf))
    writes = n_nodes * (3 + 2 + 1)
    return F32 * (reads + writes)


def backward_flops(n_nodes: int, n_edges: int, hidden: int,
                   n_rbf: int) -> int:
    return 2 * forward_flops(n_nodes, n_edges, hidden, n_rbf)


def backward_bytes(n_nodes: int, n_edges: int, hidden: int,
                   n_rbf: int) -> int:
    reads = (n_nodes * (3 + 3 + hidden) + 2 * n_edges
             + weight_count(hidden, n_rbf) + n_nodes * (3 + 2 + 1))
    writes = n_nodes * (3 + 3 + hidden) + weight_count(hidden, n_rbf)
    return F32 * (reads + writes)
