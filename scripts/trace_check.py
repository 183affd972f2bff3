#!/usr/bin/env python
"""Validate an obs trace.json (DESIGN.md §11) — the CI artifact check.

Three layers, each optional flags deeper than the last:

1. **Format** (always): the file is Chrome-trace JSON Perfetto can load —
   a ``traceEvents`` list whose entries carry ph/ts/pid/tid, with process
   and thread name metadata for every referenced track.
2. **Structure** (``--steps/--superstep/--workers``): the driver emitted
   ``steps / superstep`` superstep spans, and every (bucket, worker) pair
   carries exactly ``steps`` ``exchange/<bucket>`` spans — one per
   optimizer step, for every bucket the layerwise schedule exchanges.
3. **Injected latency** (``--check-waits``): the run's own record of
   the latency it injected agrees with the trace — every ``exchange``
   window (issue to gate end) covers its injected ``delay_ms``, and the
   summed ``exchange_wait`` durations agree with the sleeps the gates
   computed (``slept_ms``) within ``--tolerance``.

    python scripts/trace_check.py trace.json --steps 8 --superstep 2 \
        --workers 4 --check-waits --tolerance 0.25
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def fail(msg: str) -> None:
    print(f"[trace-check] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--steps", type=int, default=None,
                    help="optimizer steps the traced run executed")
    ap.add_argument("--superstep", type=int, default=1)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--check-waits", action="store_true",
                    help="check the exchange spans against the latency the "
                         "run injected (needs --collective-delay > 0)")
    ap.add_argument("--tolerance", type=float, default=0.25)
    args = ap.parse_args()

    # 1. format
    with open(args.trace) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        fail("no traceEvents list")
    tracks = set()
    named_procs, named_threads = set(), set()
    for ev in events:
        if "ph" not in ev or "pid" not in ev:
            fail(f"event missing ph/pid: {ev}")
        if ev["ph"] == "M":
            if ev["name"] == "process_name":
                named_procs.add(ev["pid"])
            elif ev["name"] == "thread_name":
                named_threads.add((ev["pid"], ev["tid"]))
            continue
        if "ts" not in ev:
            fail(f"event missing ts: {ev}")
        tracks.add((ev["pid"], ev.get("tid", 0)))
    for pid, tid in tracks:
        if pid not in named_procs:
            fail(f"pid {pid} has no process_name metadata")
        if (pid, tid) not in named_threads:
            fail(f"track {(pid, tid)} has no thread_name metadata")
    spans = [ev for ev in events if ev["ph"] == "X"]
    print(f"[trace-check] {len(events)} events, {len(spans)} spans, "
          f"{len(tracks)} named tracks")

    # 2. structure
    supersteps = [ev for ev in spans if ev["name"] == "superstep"]
    exchange = defaultdict(list)    # (bucket, worker) -> spans
    waits = defaultdict(list)       # worker -> slept durations (us)
    for ev in spans:
        if ev["name"].startswith("exchange/"):
            a = ev.get("args", {})
            exchange[(a.get("bucket"), a.get("worker"))].append(ev)
        elif ev["name"].startswith("exchange_wait/"):
            waits[ev.get("args", {}).get("worker")].append(ev["dur"])
    buckets = sorted({b for b, _ in exchange})
    workers = sorted({w for _, w in exchange})
    print(f"[trace-check] {len(supersteps)} superstep spans; buckets="
          f"{buckets} workers={workers}")
    if args.steps is not None:
        want = args.steps // args.superstep
        if len(supersteps) != want:
            fail(f"expected {want} superstep spans "
                 f"(steps={args.steps}/K={args.superstep}), "
                 f"got {len(supersteps)}")
        if not exchange:
            fail("no exchange/<bucket> spans in trace")
        if args.workers is not None and len(workers) != args.workers:
            fail(f"expected exchange spans from {args.workers} workers, "
                 f"got {len(workers)}: {workers}")
        for (b, w), evs in sorted(exchange.items()):
            if len(evs) != args.steps:
                fail(f"bucket {b!r} worker {w}: {len(evs)} exchange "
                     f"spans, expected one per step ({args.steps})")
        print(f"[trace-check] every bucket x worker has exactly "
              f"{args.steps} exchange spans "
              f"({len(buckets)} buckets x {len(workers)} workers)")

    # 3. injected latency vs the trace
    if args.check_waits:
        windows = [ev for ev in spans if ev["name"].startswith("exchange/")]
        gates = [ev for ev in spans
                 if ev["name"].startswith("exchange_wait/")]
        short = [ev for ev in windows
                 if ev["dur"] < ev["args"]["delay_ms"] * 1e3
                 * (1 - args.tolerance)]
        if short:
            fail(f"{len(short)} exchange window(s) shorter than their "
                 f"injected delay, e.g. {short[0]}")
        waited = sum(ev["dur"] for ev in gates)
        slept = sum(ev["args"]["slept_ms"] * 1e3 for ev in gates)
        if slept <= 0:
            fail("no gate slept: was --collective-delay > 0?")
        err = abs(waited - slept) / slept
        print(f"[trace-check] gate waits {waited:.0f}us vs computed sleeps "
              f"{slept:.0f}us (rel err {err:.1%}, tolerance "
              f"{args.tolerance:.0%})")
        if err > args.tolerance:
            fail(f"traced gate waits disagree with the run's own sleeps "
                 f"beyond {args.tolerance:.0%}")
    print("[trace-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
