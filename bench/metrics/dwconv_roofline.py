"""The depthwise convolutions' share of their roofline: the least time of
their forward, weight-gradient and input-gradient work in a step (the
family module's ``dwconv_work``), over ``dwconv_device_ms``
(``bench/sublayers.py``)."""

import sublayers


def read(ctx):
    return sublayers.roofline(ctx, "dwconv", "dwconv_work")
