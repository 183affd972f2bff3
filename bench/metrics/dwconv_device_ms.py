"""Device time per training step of the depthwise convolutions: the leaf
operations under every block's ``dwconv`` scope, forward and backward
(``models/convnext.py``; ``bench/sublayers.py``), averaged over chips.
It reads the same work whether XLA or a kernel implements it."""

import sublayers


def read(ctx):
    return sublayers.per_step_ms(ctx, "dwconv")
