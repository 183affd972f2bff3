"""The blocks' pointwise pairs' share of their roofline: the least time of
both products' forward, weight-gradient and input-gradient work in a step
(the family module's ``mlp_work``), over the device time of the leaf
operations under every block's ``mlp`` scope (the products, their biases
and the GELU, forward and backward; ``bench/sublayers.py``)."""

import sublayers


def read(ctx):
    return sublayers.roofline(ctx, "mlp", "mlp_work")
