"""The comparison that decides ``correct``: what the timed path produced
in its first superstep against the plain reference's first steps.

Numbers compared (each against a limit from ``bench/checks/<cell>.json``):

* ``loss_gap``: the largest relative gap, over the superstep's steps,
  between the loss the program reported and the reference's.
* ``change_gap``: the worst leaf, over workers, of the gap between the
  norm of the program's weight change over the superstep and the
  reference's, over the larger of that leaf's reference norm and the
  median leaf's.  A leaf whose reference change is under a thousandth of
  the median leaf's is left out (it moves by rounding alone).
* ``stale_gap`` (more than one worker, staleness 1 or more): the same gap
  for the stale exchange terms each worker holds for its next steps,
  which only the exchange between workers fills.

A strategy whose workers stay identical keeps one copy of the weights; it
is compared with every worker of the reference.
"""
from __future__ import annotations

import numpy as np

#: a leaf whose reference norm is under this share of the median leaf's
#: moves by rounding alone and is left out
NEGLIGIBLE = 1e-3


def _leaves(tree: dict, prefix: str = ""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", np.asarray(v, np.float64)


def norm_gap(got: dict, want: dict) -> tuple[float, str]:
    """Worst leaf of |‖got‖ - ‖want‖| / max(‖want‖, median ‖want‖), per
    worker (leading axis); returns (gap, leaf)."""
    got = dict(_leaves(got))
    want = dict(_leaves(want))
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    worst, where = 0.0, ""
    n_workers = next(iter(want.values())).shape[0]
    for w in range(n_workers):
        ref = {k: float(np.linalg.norm(v[w])) for k, v in want.items()}
        med = float(np.median(list(ref.values())))
        for k, r in ref.items():
            if r < NEGLIGIBLE * med:
                continue
            g = float(np.linalg.norm(got[k][min(w, len(got[k]) - 1)]))
            gap = abs(g - r) / max(r, med)
            if not np.isfinite(g):
                gap = float("inf")
            if gap > worst or where == "":
                worst, where = gap, f"worker{w}/{k}"
    return worst, where


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers, each as (value, where)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    rel = np.abs(lp - lr) / np.abs(lr)
    rel[~np.isfinite(lp)] = np.inf
    t = int(np.argmax(rel))
    out = {"loss_gap": (float(rel[t]), f"step{t}")}
    d_prog = _sub(prog["params"], prog["params0"])
    d_ref = _sub(ref["params"], ref["params0"])
    out["change_gap"] = norm_gap(d_prog, d_ref)
    stale = list(_leaves(ref["stale"]))
    if stale and stale[0][1].shape[0] > 1:
        out["stale_gap"] = norm_gap(prog["stale"], ref["stale"])
    return out


def _sub(a: dict, b: dict) -> dict:
    return {k: (_sub(v, b[k]) if isinstance(v, dict)
                else np.asarray(v, np.float64) - np.asarray(b[k],
                                                            np.float64))
            for k, v in a.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "at"}}): every number at or under
    its limit; a number with no limit is an error."""
    unlimited = set(values) - set(limits)
    if unlimited:
        raise ValueError(f"no limit for {sorted(unlimited)}")
    checks = {}
    ok = True
    for name, (v, at) in values.items():
        checks[name] = {"value": v, "limit": limits[name], "at": at}
        ok = ok and bool(v <= limits[name])
    return ok, checks
