"""Device time per training step of the model's backward pass: the leaf
operations the compiled step names as a layer of the model or the ``loss`` in
the backward direction (``transpose(jvp(<layer>))`` or ``<layer>/bwd``,
``bench/scopes.py``), averaged over chips."""

import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, scopes.model_pass("bwd"))
