"""Device time of collective operations during which no other operation
runs on that chip, per step, on the chip where it is largest, in ms.
Nothing where the trace holds no collective."""
from bench.trace import COLLECTIVE


def read(ctx):
    t = ctx.trace
    chips = range(len(t.device_ops))
    if not ctx.steps or not any(t.ops(c, COLLECTIVE) for c in chips):
        return None
    return 1e3 * max(t.exposed_seconds(COLLECTIVE, c) for c in chips) / ctx.steps
