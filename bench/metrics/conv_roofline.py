"""The convolution layers' share of their roofline: the least time one
chip needs for its share of every conv layer's forward, weight-gradient
and input-gradient work in a step (the larger of operations over the
peak and bytes over the memory bandwidth, from the family module's
``conv_work``), over the device time of the operations that implement it,
XLA convolutions or Pallas convolution kernels, per step and chip."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced.chips or ctx.steps <= 0:
        return None
    conv_ns = ctx.reduced.class_ns("conv")
    if conv_ns <= 0:
        return None
    flops, nbytes = ctx.family.conv_work(ctx.cfg,
                                         ctx.traffic["batch"] // ctx.chips)
    least_s = max(flops / ctx.peaks["flops"],
                  nbytes / ctx.peaks["hbm_bytes_per_s"])
    return least_s / (conv_ns * 1e-9 / ctx.steps) * 100.0
