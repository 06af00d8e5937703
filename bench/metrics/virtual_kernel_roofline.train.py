"""Share of its roofline that the fused virtual-node kernel reaches in
training: the least time the chip needs for the real-virtual pathway's
operations and compulsory bytes (``bench/work/virtual_message.py``,
forward and backward, every layer of every scene-shard the window
trained), each bound by the larger of operations over the bf16 peak and
bytes over HBM bandwidth, over the summed device time of the kernel's
calls (``virtual_pathway_fused``, ``virtual_pathway_bwd_fused``; one call
serves a whole batch) in the trace, in percent."""
import re

from bench.work import fast_egnn
from bench.work import virtual_message as work

KERNEL = re.compile(r"virtual_pathway(_bwd)?_fused")


def read(ctx):
    t = ctx.trace
    seconds = t.op_seconds(KERNEL)
    if not seconds:
        return None
    cfg, p, bw = ctx.cfg, ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    h, s, c = cfg["hidden"], cfg["s_dim"], cfg["n_virtual"]
    least = cfg["n_layers"] * sum(
        max(work.forward_flops(n, h, s, c) / p,
            work.forward_bytes(n, h, s, c) / bw)
        + max(work.backward_flops(n, h, s, c) / p,
              work.backward_bytes(n, h, s, c) / bw)
        for n, _ in fast_egnn.window_sizes(ctx))
    return 100.0 * least / seconds
