#!/usr/bin/env python3
"""Readings that the ConvNeXt cell's limits of ``correct`` are set from,
with faults planted inside the gradient as well as around the step (the
benchmark's own runs never run this).

    python3 bench/convnext_readings.py --seeds 11,12 \
        --what program control:bfloat16 fault:half_batch grad:neg_dw

The float32 reference runs once per seed; each ``--what`` is compared
with it by ``bench/check.py``:

* ``program``, ``fault:<name>``: the timed path's first superstep, as
  ``bench/readings.py`` gives it;
* ``control:<precision>``: the reference in another precision in the
  program's place (``bfloat16``, or ``one_pass``: bfloat16-rounded
  operands and float32 sums in every product, the chip's rounding of the
  program, for a host without one);
* ``grad:<name>``: the program with a fault in its gradient:
  ``zero_dw`` / ``neg_dw`` zero / negate the gradient of block
  ``GRAD_FAULT_BLOCK``'s depthwise weight (its value unchanged),
  ``no_clip`` leaves AdamW's global-norm clip out.

Besides the checked numbers, each line gives ``diff_gap``: the worst leaf
of ||dW_got - dW_ref|| over the larger of ||dW_ref|| and the median
leaf's, which sees the direction of a change where ``change_gap`` sees
only its size.  ``--batch`` makes the batch smaller (the shards kept) and
``--cpu`` runs without a TPU, for readings on a host.  One JSON line per
reading on standard output.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np

import check
import run

CELL = "convnext-t-chaos-xla-1chip"
#: block s2b4, the middle of the third stage, in forward order
GRAD_FAULT_BLOCK = 10


@functools.lru_cache(maxsize=None)
def _program():
    from repro.models import convnext
    from repro.optim import optimizers
    from repro.train import step
    return convnext, optimizers, step, convnext._block


def plant(kind: str, n_blocks: int) -> None:
    """Plant gradient fault ``kind`` in the program until ``unplant``."""
    import jax

    convnext, optimizers, step, block = _program()
    if kind == "no_clip":
        step.adamw = functools.partial(optimizers.adamw, grad_clip=1e30)
        return
    if kind not in ("zero_dw", "neg_dw"):
        raise ValueError(f"unknown gradient fault {kind!r}")
    target = min(GRAD_FAULT_BLOCK, n_blocks - 1)
    calls = [0]

    def faulty(p, x):
        i = calls[0] % n_blocks
        calls[0] += 1
        if i == target:
            w = jax.lax.stop_gradient(p["dw_w"])
            if kind == "neg_dw":
                w = 2 * w - p["dw_w"]
            p = {**p, "dw_w": w}
        return block(p, x)
    convnext._block = faulty


def unplant() -> None:
    convnext, optimizers, step, block = _program()
    convnext._block = block
    step.adamw = optimizers.adamw


def diff_gap(got: dict, ref: dict) -> tuple[float, str]:
    d_got = dict(check._leaves(check._sub(got["params"], got["params0"])))
    d_ref = dict(check._leaves(check._sub(ref["params"], ref["params0"])))
    norms = {k: float(np.linalg.norm(v)) for k, v in d_ref.items()}
    med = float(np.median(list(norms.values())))
    worst, where = 0.0, ""
    for k, v in d_ref.items():
        if norms[k] < check.NEGLIGIBLE * med:
            continue
        gap = float(np.linalg.norm(d_got[k] - v)) / max(norms[k], med)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def got_for(cell: dict, seed: int, data, what: str) -> dict:
    if what.startswith("control:"):
        return run.reference_readings(cell, seed, data,
                                      precision=what.split(":", 1)[1])
    kind, _, name = what.partition(":")
    if kind not in ("program", "fault", "grad"):
        raise ValueError(f"unknown reading {what!r}")
    if kind == "grad":
        plant(name, sum(cell["cfg"]["depths"]))
    try:
        job = run.Job(cell, seed, data,
                      fault=name if kind == "fault" else None)
        got = run.first_superstep(job)
        job.close()
    finally:
        unplant()
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--what", nargs="+", default=["program"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    try:
        run.prepare_env()
        cell = run.load_cell(CELL)
        if args.batch:
            cell["traffic"] = dict(cell["traffic"], batch=args.batch)
        run.use_compile_cache()
        if not args.cpu:
            run.chips_or_fail(cell["chips"])
    except run.Failure as e:
        print(f"[readings] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        data = cell["family"].inputs(cell["cfg"], seed)
        t = time.perf_counter()
        ref = run.reference_readings(cell, seed, data)
        ref_s = time.perf_counter() - t
        for what in args.what:
            t = time.perf_counter()
            got = got_for(cell, seed, data, what)
            values = check.readings(got, ref)
            print(json.dumps({
                "workload": CELL, "seed": seed, "what": what,
                "batch": cell["traffic"]["batch"],
                "values": {k: v for k, (v, _) in values.items()},
                "at": {k: a for k, (_, a) in values.items()},
                "diff_gap": diff_gap(got, ref),
                "losses": np.asarray(got["losses"]).ravel().tolist(),
                "reference_losses": np.asarray(ref["losses"]).tolist(),
                "seconds": time.perf_counter() - t,
                "reference_s": ref_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
