"""Operations and compulsory bytes of the real-virtual pathway (Eq. 5 and
the virtual terms of Eqs. 6-9), counted from the algorithm.

Per real node i, virtual channel c and layer:

* the message MLP ``phi2_c``: (hidden + s_dim + 1 + C) -> hidden -> hidden
  over ``[h_i, s_c, |x_i - z_c|², mv[:, c]]``;
* the gates ``phi_xv_c`` and ``phi_z_c``: hidden -> hidden -> 1 each;
* the vector ``x_i - z_c`` and its squared length, the two gated vectors,
  and the sums: the channel means into node i, and the node sums into
  channel c.

A dense layer of ``a -> b`` is ``2ab`` operations plus ``b`` for its bias.
Activations are not counted; the backward pass counts twice the forward.
Bytes are the compulsory float32 traffic: forward, x, h and the node mask
read, the weights and the virtual state read, dx and mh written with the
channel sums; backward, the same reads plus the cotangents of dx and mh,
and the gradients of x, h, z and the weights written.
"""
from __future__ import annotations

F32 = 4


def dense(a: int, b: int, bias: bool = True) -> int:
    return 2 * a * b + (b if bias else 0)


def weight_count(hidden: int, s_dim: int, c: int) -> int:
    h = hidden
    phi2 = (h + s_dim + 1 + c) * h + h + h * h + h
    gate = h * h + h + h
    return c * (phi2 + 2 * gate)


def forward_flops(n_nodes: int, hidden: int, s_dim: int, c: int) -> int:
    h = hidden
    per_pair = (dense(h + s_dim + 1 + c, h) + dense(h, h)  # phi2
                + 2 * (dense(h, h) + dense(h, 1, bias=False))  # phi_xv, phi_z
                + 3 + 5  # x - z, squared length
                + 2 * 3  # gated vectors
                + (3 + h) * 2)  # channel means, node sums
    return n_nodes * c * per_pair


def forward_bytes(n_nodes: int, hidden: int, s_dim: int, c: int) -> int:
    reads = (n_nodes * (3 + hidden + 1) + weight_count(hidden, s_dim, c)
             + c * (3 + s_dim + c))
    writes = n_nodes * (3 + hidden) + c * (3 + hidden)
    return F32 * (reads + writes)


def backward_flops(n_nodes: int, hidden: int, s_dim: int, c: int) -> int:
    return 2 * forward_flops(n_nodes, hidden, s_dim, c)


def backward_bytes(n_nodes: int, hidden: int, s_dim: int, c: int) -> int:
    reads = (n_nodes * (3 + hidden + 1) + weight_count(hidden, s_dim, c)
             + c * (3 + s_dim + c) + n_nodes * (3 + hidden)
             + c * (3 + hidden))
    writes = (n_nodes * (3 + hidden) + c * 3
              + weight_count(hidden, s_dim, c))
    return F32 * (reads + writes)
