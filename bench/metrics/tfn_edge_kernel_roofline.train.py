"""Share of its roofline that the fused TFN edge kernel reaches in
training: the least time the chip needs for the TFN edge pathway's
operations and compulsory bytes (``bench/work/tfn_edge.py``, forward and
backward, every layer of every scene the window trained), each call bound
by the larger of operations over the bf16 peak and bytes over HBM
bandwidth, over the kernel's summed device time in the trace, in percent.

The trace names the kernel's passes after their ``pallas_call`` names
(``tfn_edge_fused_fwd``, ``tfn_edge_bwd_fused_recv``,
``tfn_edge_bwd_fused_send``), except inside the per-sample loop that
``vmap`` wraps around a scalar-prefetch Pallas call over a batch of several
scenes, where it names each by its wrapping fusion (``%closed_call.N = ...
kind=kCustom``), as ``edge_kernel_roofline.train`` reads the FastEGNN
kernel.  Where the count of such operations is not a whole multiple of the
layers times the scenes trained, the trace is not what this reader was
written against, and it reads nothing: so also where the pathway ran on
its jnp path.
"""
import re

from bench.work import fast_egnn
from bench.work import tfn_edge as work

NAMED = re.compile(r"tfn_edge_(bwd_)?fused")
WRAPPED = re.compile(r"^%closed_call\b")


def is_tfn_edge_kernel(e) -> bool:
    return bool(NAMED.search(e.name) or (WRAPPED.search(e.name)
                                         and "kind=kCustom" in e.text))


def read(ctx):
    t = ctx.trace
    calls = [e for c in range(len(t.device_ops)) for e in t.ops(c)
             if is_tfn_edge_kernel(e)]
    sizes = list(fast_egnn.window_sizes(ctx))
    cfg = ctx.cfg
    layers = cfg["n_layers"]
    if not calls or len(calls) % (layers * len(sizes)):
        return None
    seconds = sum(e.end - e.start for e in calls) / 1e9
    p, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    h, r = cfg["hidden"], cfg["n_rbf"]
    least = layers * sum(
        max(work.forward_flops(n, e, h, r) / p,
            work.forward_bytes(n, e, h, r) / bw)
        + max(work.backward_flops(n, e, h, r) / p,
              work.backward_bytes(n, e, h, r) / bw)
        for n, e in sizes)
    return 100.0 * least / seconds
