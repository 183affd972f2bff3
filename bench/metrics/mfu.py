"""Share of the chips' peak that the whole training step uses: the
operations the forward and backward passes require per image (the family
module's ``train_flops_per_image``), times images trained per second in
the traced window, over the chips' bf16 peak (``bench/peaks.py``)."""


def read(ctx):
    if ctx.images <= 0 or ctx.window_s <= 0:
        return None
    per_image = ctx.family.train_flops_per_image(ctx.cfg)
    rate = per_image * ctx.images / ctx.window_s
    return rate / (ctx.chips * ctx.peaks["flops"]) * 100.0
