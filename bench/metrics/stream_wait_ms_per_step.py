"""Host time the training loop waits on the batch stream (``next()`` on
``BatchStream``, the benchmark's ``stream_next`` span), per step, in ms."""


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * ctx.trace.span_seconds("stream_next") / ctx.steps
