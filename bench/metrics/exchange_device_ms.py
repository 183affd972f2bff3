"""Device time per training step of the exchange between workers: the
leaf operations under each bucket's ``exchange/<bucket>`` scope
(``train/sync.py``, ``core/chaos.py``; ``bench/scopes.py``), collectives
and the arithmetic around them, averaged over chips.  Nothing where the
step exchanges nothing."""

import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, scopes.exchange) or None
