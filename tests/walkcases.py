"""Layouts with dead blocks, for the tests that the edge kernels' walk of
their live prefix gives what a walk of the whole static grid gives
(``tests/conftest.py``'s ``walks_agree``)."""
import numpy as np

#: 4 receiver x 2 sender windows over 500 nodes, blocks of 32 edges
N, BE = 500, 32
TILING = dict(window=128, swindow=256)
CASES = ("pads", "tail_windows", "all_masked", "no_edges")


def walk_edges(case: str, k: int):
    """Numpy ``(snd, rcv, em)`` of sample ``k`` of a layout the live walk
    must get right: 'pads' (some edges masked, and 150 masked ``0 -> 0``
    pad slots as ``pad_edges`` leaves them), 'tail_windows' (as 'pads',
    but no edge in the last receiver window nor the last sender window:
    the zeroing block and the sender pass's ``visited`` mask),
    'all_masked', 'no_edges'.  Sample k masks a different share of its
    edges, so samples differ in their live counts."""
    from repro.data.radius_graph import sort_edges_by_receiver

    rng = np.random.default_rng(k)
    e, pad = (0, 0) if case == "no_edges" else (800, 150)
    hi = 240 if case == "tail_windows" else N
    snd, rcv = sort_edges_by_receiver(rng.integers(0, hi, e),
                                      rng.integers(0, hi, e))
    em = (rng.random(e) > 0.1 + 0.4 * k).astype(np.float32)
    if case == "all_masked":
        em[:] = 0.0
    z = np.zeros(pad, np.int64)
    return (np.concatenate([snd, z]).astype(np.int32),
            np.concatenate([rcv, z]).astype(np.int32),
            np.concatenate([em, np.zeros(pad, np.float32)]))


def walk_layouts(case: str, n_samples: int):
    """Per-sample edges and host layouts at one shared capacity, and each
    layout's live count against its static block count."""
    from repro.data.radius_graph import banded_csr_layout
    from repro.kernels.edge_message import layout_from_host, live_block_count

    edges = [walk_edges(case, k) for k in range(n_samples)]
    build = lambda s, r, m, **kw: banded_csr_layout(
        s, r, N, edge_mask=m, block_e=BE, **TILING, **kw)
    cap = max(build(*ed).senders.size for ed in edges)
    lays = [layout_from_host(build(*ed, capacity=cap)) for ed in edges]
    live = [int(live_block_count(lay.edge_mask, lay.block_rwin, BE)[0])
            for lay in lays]
    return edges, lays, live, cap // BE
