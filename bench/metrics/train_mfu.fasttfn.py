"""Model FLOP/s utilization of FastTFN training: the model operations of
the steps the window completed (``bench/work/fast_tfn.py``: forward and
backward, three forwards, nothing recomputed) over window x chips x the
chip's bf16 peak, in percent."""
from bench.work import fast_egnn, fast_tfn


def read(ctx):
    flops = sum(fast_tfn.train_flops(ctx.cfg, n, e)
                for n, e in fast_egnn.window_sizes(ctx))
    if not flops:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
