"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals / window), averaged over
chips."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced.chips:
        return None
    w = ctx.reduced.window_ns
    if w <= 0:
        return None
    return (1.0 - ctx.reduced.busy_ns() / w) * 100.0
