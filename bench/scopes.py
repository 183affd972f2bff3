"""Device time by the program's named scopes.

The compiled step names its work (``src/repro/obs/trace.py``): each
layer of the model forward and backward (``conv2``/``fwd``,
``conv2``/``bwd``), the ``loss``, the ``update`` and each bucket's
``exchange/<bucket>``.  ``of_hlo`` maps every operation of the step's HLO
text, which a traced run compiles for the trace's classes, to its
``(scope, direction)`` with the program's own parser; the run hands the
map to the readers as ``ctx.scopes``.  The functions below sum the reduced
trace's leaf operations by scope, as ``devtrace.Reduced.class_ns`` sums
them by class: the union of their intervals per chip, averaged over
chips.  A program without the naming contract, or a trace whose operations
the compiled step does not name, gives None.
"""
from __future__ import annotations

import devtrace

#: at most this share of leaf time may fall on operations the HLO lacks
UNKNOWN_SHARE = 0.01


def of_hlo(hlo_text: str):
    """{operation name: (scope, direction) or None} of a compiled step's
    HLO text, or None where there is none or the program names no
    scopes."""
    try:
        from repro.obs.trace import hlo_scopes
    except ImportError:
        return None
    return hlo_scopes(hlo_text) if hlo_text else None


def exchange(s) -> bool:
    """Each bucket's exchange between workers."""
    return s is not None and s[0].startswith("exchange/")


def update(s) -> bool:
    """What the sync strategy does once the gradients exist."""
    return s is not None and (s[0] == "update" or exchange(s))


def model_pass(direction: str):
    """Scopes of the model's layers and loss in one direction: every scope
    but the strategy's."""
    return lambda s: s is not None and s[1] == direction and not update(s)


def _leaves(ctx):
    """(reduced trace, scopes) when both can be read, else None."""
    r = getattr(ctx, "reduced", None)
    if r is None or not r.chips or ctx.steps <= 0:
        return None
    scopes = ctx.scopes
    if scopes is None:
        return None
    total = unknown = 0.0
    for d in r.chips:
        for (name, _, s, e), leaf in zip(r.ops[d], r.leaf[d]):
            if leaf:
                total += e - s
                unknown += (e - s) if name not in scopes else 0.0
    if total <= 0 or unknown > UNKNOWN_SHARE * total:
        return None
    return r, scopes


def per_step_ms(ctx, keep) -> float | None:
    """Device time per step of the leaf operations whose scope ``keep``
    accepts, averaged over chips."""
    found = _leaves(ctx)
    if found is None:
        return None
    r, scopes = found
    per_chip = [devtrace.length(devtrace.union(
        (s, e) for (name, _, s, e), leaf in zip(r.ops[d], r.leaf[d])
        if leaf and keep(scopes.get(name)))) for d in r.chips]
    return sum(per_chip) / len(per_chip) * 1e-6 / ctx.steps


def breakdown(ctx, n: int = 10) -> list[list] | None:
    """[``scope/direction``, seconds] of the ``n`` scopes that took most
    leaf time in the window, then ``unscoped``; summed over the window and
    averaged over chips."""
    found = _leaves(ctx)
    if found is None:
        return None
    r, scopes = found
    tot: dict[str, float] = {}
    for d in r.chips:
        for (name, _, s, e), leaf in zip(r.ops[d], r.leaf[d]):
            if leaf:
                sc = scopes.get(name)
                key = f"{sc[0]}/{sc[1]}" if sc else "unscoped"
                tot[key] = tot.get(key, 0.0) + (e - s)
    k = len(r.chips)
    top = sorted(((key, t) for key, t in tot.items() if key != "unscoped"),
                 key=lambda kv: -kv[1])[:n]
    return ([[key, t / k * 1e-9] for key, t in top]
            + [["unscoped", tot.get("unscoped", 0.0) / k * 1e-9]])
