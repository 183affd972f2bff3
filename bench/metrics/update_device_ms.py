"""Device time per training step of what the sync strategy does once the
gradients exist: the leaf operations under the ``update`` scope and each
bucket's ``exchange/<bucket>`` (``train/sync.py``, ``train/step.py``;
``bench/scopes.py``), averaged over chips."""

import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, scopes.update)
