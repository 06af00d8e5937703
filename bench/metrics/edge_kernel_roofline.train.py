"""Share of its roofline that the fused edge kernel reaches in training:
the least time the chip needs for the edge pathway's operations and
compulsory bytes (``bench/work/edge_message.py``, forward and backward,
every layer of every scene-shard the window trained), each call bound by
the larger of operations over the bf16 peak and bytes over HBM bandwidth,
over the kernels' summed device time in the trace, in percent.

The trace names the kernel's calls after their entry points
(``edge_pathway_fused``, ``edge_pathway_bwd_fused``), except inside the
per-sample loop that ``vmap`` wraps around a scalar-prefetch Pallas call
over a batch of several scenes, where it names each by its wrapping fusion
(``%closed_call.N = ... kind=kCustom``).  Where the count of such
operations is not a whole multiple of the layers times the scene-shards
trained, the trace is not what this reader was written against, and it
reads nothing.
"""
import re

from bench.work import edge_message as work
from bench.work import fast_egnn

NAMED = re.compile(r"edge_pathway(_bwd)?_fused")
WRAPPED = re.compile(r"^%closed_call\b")


def is_edge_kernel(e) -> bool:
    return bool(NAMED.search(e.name) or (WRAPPED.search(e.name)
                                         and "kind=kCustom" in e.text))


def read(ctx):
    t = ctx.trace
    calls = [e for c in range(len(t.device_ops)) for e in t.ops(c)
             if is_edge_kernel(e)]
    sizes = list(fast_egnn.window_sizes(ctx))
    layers = ctx.cfg["n_layers"]
    if not calls or len(calls) % (layers * len(sizes)):
        return None
    seconds = sum(e.end - e.start for e in calls) / 1e9
    p, bw, h = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"], ctx.cfg["hidden"]
    least = layers * sum(
        max(work.forward_flops(n, e, h) / p, work.forward_bytes(n, e, h) / bw)
        + max(work.backward_flops(n, e, h) / p, work.backward_bytes(n, e, h) / bw)
        for n, e in sizes)
    return 100.0 * least / seconds
