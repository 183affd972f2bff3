"""Device time per training step of the model's forward pass: the leaf
operations the compiled step names as a layer of the model (``conv0``,
``pool1``, ...) or the ``loss``, in the forward direction (the model's
named scopes, ``bench/scopes.py``), averaged over chips."""

import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, scopes.model_pass("fwd"))
