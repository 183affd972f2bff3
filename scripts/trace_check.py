#!/usr/bin/env python
"""Validate an obs trace.json (DESIGN.md §11) — the CI artifact check.

Two layers, the second optional:

1. **Format** (always): the file is Chrome-trace JSON Perfetto can load —
   a ``traceEvents`` list whose entries carry ph/ts/pid/tid, with process
   and thread name metadata for every referenced track.
2. **Structure** (``--steps/--superstep``): the training loop emitted one
   ``superstep`` span per K-step dispatch, each holding its ``dispatch``
   and ``loss_readback`` spans on the same track, and the feed's
   ``feed/wait`` spans are there.

Per-bucket exchange time is not in trace.json: it is device time, read
from the profiler's trace through the ``exchange/<bucket>`` scopes.

    python scripts/trace_check.py trace.json --steps 8 --superstep 2
"""
from __future__ import annotations

import argparse
import json
import sys


def fail(msg: str) -> None:
    print(f"[trace-check] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("--steps", type=int, default=None,
                    help="optimizer steps the traced run executed")
    ap.add_argument("--superstep", type=int, default=1)
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
    print(f"[trace-check] {len(supersteps)} superstep spans")
    if args.steps is not None:
        want = -(-args.steps // args.superstep)
        if len(supersteps) != want:
            fail(f"expected {want} superstep spans "
                 f"(steps={args.steps}/K={args.superstep}), "
                 f"got {len(supersteps)}")
        def holds(outer, ev):   # within 1 us: timestamps are floats
            return ((ev["pid"], ev["tid"]) == (outer["pid"], outer["tid"])
                    and outer["ts"] - 1 <= ev["ts"]
                    and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"]
                    + 1)

        for child in ("dispatch", "loss_readback"):
            inner = [ev for ev in spans if ev["name"] == child]
            for sup in supersteps:
                if not any(holds(sup, ev) for ev in inner):
                    fail(f"superstep at {sup['ts']:.0f}us holds no "
                         f"{child!r} span")
        if not any(ev["name"] == "feed/wait" for ev in spans):
            fail("no feed/wait span in trace")
        print("[trace-check] every superstep span holds its dispatch and "
              "loss_readback spans")
    print("[trace-check] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
