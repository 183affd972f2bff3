"""Device time per training step in operations that are neither a
convolution nor a collective: tanh, pooling, the loss, the optimizer
update and the micro-shard loop (``models/cnn.py``, ``train/``), from the
device trace, averaged over chips."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced.chips or ctx.steps <= 0:
        return None
    t = ctx.reduced.class_ns("other")
    return t * 1e-6 / ctx.steps if t > 0 else None
