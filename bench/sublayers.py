"""Device time and roofline share of one kind of sublayer in every block
of a model, by the program's named scopes (``bench/scopes.py``): the
compiled step names a ConvNeXt block's sublayers ``s{i}b{j}/dwconv``,
``s{i}b{j}/norm`` and ``s{i}b{j}/mlp`` (``src/repro/obs/trace.py``),
forward and backward.  Nothing where the step names no such sublayer."""
from __future__ import annotations

import scopes


def per_step_ms(ctx, sub: str) -> float | None:
    """Device time per step of the leaf operations under every block's
    ``sub`` scope, both directions, averaged over chips."""
    keep = lambda s: s is not None and s[0].endswith(f"/{sub}")
    return scopes.per_step_ms(ctx, keep) or None


def roofline(ctx, sub: str, work: str) -> float | None:
    """The least time one chip needs for its share of the sublayers' work
    in a step (the larger of operations over the peak and bytes over the
    memory bandwidth, from the family module's function ``work``), over
    their device time per step, in percent."""
    count = getattr(ctx.family, work, None)
    ms = per_step_ms(ctx, sub)
    if count is None or ms is None:
        return None
    flops, nbytes = count(ctx.cfg, ctx.traffic["batch"] // ctx.chips)
    least_s = max(flops / ctx.peaks["flops"],
                  nbytes / ctx.peaks["hbm_bytes_per_s"])
    return least_s / (ms * 1e-3) * 100.0
