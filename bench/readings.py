#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, many seeds in one
process (the benchmark's own runs never run this).

    python3 bench/readings.py --workload large-chaos-xla-1chip \
        --seeds 11,12,13 --what program control:int8 fault:half_batch

For each seed and each ``--what``, the numbers ``bench/check.py``
compares, against the float32 reference:

* ``program``: the timed path's first superstep, as a benchmark run
  drives it (no window);
* ``control:<precision>``: the reference itself in a lower precision
  (``bfloat16`` throughout, or ``int8`` products), put in the program's
  place;
* ``fault:<name>``: the timed path with a fault planted under it
  (``unchanged``, ``half_batch``, ``altered_loss``, ``no_exchange``);
* ``reference:no_exchange``: the reference with the exchange between
  workers left out, in the program's place.

One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def one(cell: dict, seed: int, what: str) -> dict:
    import check

    data = cell["family"].inputs(cell["cfg"], seed)
    t = time.perf_counter()
    if what == "program" or what.startswith("fault:"):
        fault = None if what == "program" else what.split(":", 1)[1]
        job = run.Job(cell, seed, data, fault=fault)
        got = run.first_superstep(job)
        job.close()
        del job
    elif what.startswith("control:"):
        got = run.reference_readings(cell, seed, data,
                                     precision=what.split(":", 1)[1])
    elif what == "reference:no_exchange":
        got = run.reference_readings(cell, seed, data, exchange=False)
    else:
        raise ValueError(f"unknown reading {what!r}")
    ref = run.reference_readings(cell, seed, data)
    values = check.readings(got, ref)
    return {"workload": cell["name"], "seed": seed, "what": what,
            "values": {k: v for k, (v, _) in values.items()},
            "at": {k: a for k, (_, a) in values.items()},
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--what", nargs="+", default=["program"])
    args = ap.parse_args(argv)
    try:
        run.prepare_env()
        cell = run.load_cell(args.workload)
        run.use_compile_cache()
        run.chips_or_fail(cell["chips"])
    except run.Failure as e:
        print(f"[readings] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        for what in args.what:
            print(json.dumps(one(cell, seed, what)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
