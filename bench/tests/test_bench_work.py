"""The work functions count the algorithm: hand counts on a small graph,
and the same numbers for two layouts of one graph."""
import numpy as np

from bench.work import edge_message as edge
from bench.work import fast_egnn
from bench.work import virtual_message as virt


def test_edge_counts_by_hand():
    # 5 nodes, 8 edges, hidden 2.  Per edge: phi1 2*5*2+2 = 22 and
    # 2*2*2+2 = 10; gate 2*2*2+2 = 10 and 2*2*1 = 4; edge vector and
    # squared length 8; gated vector 3; sums 2+3+1 = 6  ->  63.
    # Per node: degree mean 2+3+1 = 6.
    assert edge.forward_flops(5, 8, 2) == 8 * 63 + 5 * 6
    assert edge.backward_flops(5, 8, 2) == 2 * (8 * 63 + 5 * 6)
    # weights: phi1 5*2+2 + 2*2+2, gate 2*2+2 + 2*1  ->  26
    assert edge.weight_count(2) == 26
    # forward: read x, h (5*(3+2)), endpoints (2*8), weights (26);
    # write dx, mh, degree (5*6)
    assert edge.forward_bytes(5, 8, 2) == 4 * (25 + 16 + 26 + 30)
    # backward: those reads and the cotangents and degree (5*6); write the
    # gradients of x, h (5*5) and of the weights (26)
    assert edge.backward_bytes(5, 8, 2) == 4 * (25 + 16 + 26 + 30 + 25 + 26)


def test_virtual_counts_by_hand():
    # 4 nodes, hidden 2, s_dim 1, C = 2.  Per node and channel: phi2
    # 2*(2+1+1+2)*2+2 = 26 and 2*2*2+2 = 10; two gates 2*(10+4) = 28;
    # x - z and squared length 8; two gated vectors 6; means and sums
    # 2*(3+2) = 10  ->  88.
    assert virt.forward_flops(4, 2, 1, 2) == 4 * 2 * 88
    assert virt.backward_flops(4, 2, 1, 2) == 2 * 4 * 2 * 88
    # weights per channel: phi2 6*2+2+2*2+2 = 20, gates 2*(2*2+2+2) = 16
    assert virt.weight_count(2, 1, 2) == 2 * 36
    assert virt.forward_bytes(4, 2, 1, 2) == 4 * (
        4 * 6 + 72 + 2 * (3 + 1 + 2) + 4 * 5 + 2 * 5)


def _layout_real_edges(snd, rcv, n, window, swindow, block_e):
    """Real edges as one banded layout of the program holds them."""
    from repro.data.radius_graph import banded_csr_layout

    lay = banded_csr_layout(snd, rcv, n, window=window, swindow=swindow,
                            block_e=block_e)
    return int(lay.edge_mask.sum()), lay.senders.shape[0]


def test_same_work_for_two_layouts_of_one_graph():
    """Two band geometries pad the same graph to different capacities; the
    work is counted from the graph, so it is the same for both."""
    rng = np.random.default_rng(0)
    n, e = 700, 4000
    snd = rng.integers(0, n, e).astype(np.int32)
    rcv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    a = _layout_real_edges(snd, rcv, n, window=128, swindow=256, block_e=128)
    b = _layout_real_edges(snd, rcv, n, window=256, swindow=768, block_e=256)
    assert a[0] == b[0] == e and a[1] != b[1]
    cfg = dict(hidden=64, s_dim=64, n_virtual=3, h_in=1, n_layers=4)
    fa = fast_egnn.train_flops(cfg, n, a[0])
    fb = fast_egnn.train_flops(cfg, n, b[0])
    assert fa == fb
    assert edge.forward_bytes(n, a[0], 64) == edge.forward_bytes(n, b[0], 64)


def test_model_flops_scale():
    """At the water3d cell's sizes a scene's training step is about 3 x 18
    GFLOP, most of it the edge pathway."""
    cfg = dict(hidden=64, s_dim=64, n_virtual=3, h_in=1, n_layers=4)
    total = fast_egnn.train_flops(cfg, 8192, 91_700)
    edge_part = 3 * 4 * edge.forward_flops(8192, 91_700, 64)
    assert 50e9 < total < 60e9
    assert 0.65 < edge_part / total < 0.8
