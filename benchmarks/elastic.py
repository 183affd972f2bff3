"""Elastic-resize latency study (DESIGN.md §7): what does a membership
change cost, and does throughput recover?

For each transition of a 4 -> 2 -> 4 worker schedule (shrink, grow) the
``ResizeController`` re-slots the live state and rebuilds the compiled
superstep; this module measures

  - resize latency (seconds from membership event to first new-mesh
    dispatch being possible, as reported by ``ResizeOutcome.latency_s``,
    plus the first post-resize superstep separately — that one carries the
    recompile);
  - steady-state steps/sec before and after the transition;

and additionally times the checkpoint-restore rung (the same 4 -> 2
transition forced through rung 2 with an injected resize poison) so the
ladder's two recovery paths are directly comparable.

Prints one JSON document {"runs": [...]} to stdout; progress lines go to
stderr.  ``benchmarks/run.py --only elastic`` calls ``run`` in its own
process; on the CPU the caller's environment supplies forced host devices
(same harness note as benchmarks/scaling.py: forced host devices share one
CPU, so steps/sec validates the path and the overhead trend, not
real-hardware scaling).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.elastic [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

BATCH = 8
SUPERSTEP = 2
LOGICAL_SHARDS = 8


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _steps_per_s(fn, state, pipe, mesh, worker, start, n_supersteps):
    from repro.launch.train import put_worker_sharded
    s = start
    # two untimed dispatches: the first pays compile, the second the
    # donated-buffer re-trace (same warmup the watchdog applies)
    for _ in range(2):
        state, _ = fn(state, put_worker_sharded(pipe, s, SUPERSTEP, mesh,
                                                worker))
        s += SUPERSTEP
    t0 = time.perf_counter()
    for _ in range(n_supersteps):
        batch = put_worker_sharded(pipe, s, SUPERSTEP, mesh, worker)
        state, m = fn(state, batch)
        s += SUPERSTEP
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return state, s, (n_supersteps * SUPERSTEP) / dt


def run(quick: bool = False) -> dict:
    n_meas = 3 if quick else 10

    import repro.configs as C
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.chaos import SyncConfig
    from repro.core.types import WorkerConfig
    from repro.launch.elastic import ResizeController
    from repro.launch.faults import FaultPlan
    from repro.launch.mesh import make_host_mesh
    from repro.train.step import (init_worker_state, make_optimizer,
                                  make_worker_superstep)
    from benchmarks.scaling import build_worker_cell, require_devices

    require_devices(4)
    cfg = C.get("chaos-small")
    sync = SyncConfig("bsp", axis_name="workers")
    opt = make_optimizer(cfg, total_steps=512)
    runs = []

    def transitions(schedule, label, fault=None, ckpt_dir=None):
        worker, mesh, pipe, fn, state, _ = build_worker_cell(
            cfg, sync, schedule[0], opt, batch=BATCH)
        ctl = ResizeController(cfg, sync, opt, worker, mesh, fault=fault)
        if ckpt_dir:
            ctl.ckpt_mgr = CheckpointManager(ckpt_dir)
        s = 0
        state, s, sps = _steps_per_s(fn, state, pipe, mesh, worker, s, n_meas)
        for target in schedule[1:]:
            if ctl.ckpt_mgr is not None:
                ctl.ckpt_mgr.save(s, state)
            before = sps
            _log(f"[elastic-bench] {label}: {ctl.worker.workers} -> "
                 f"{target} at step {s} ({before:.1f} steps/s before)")
            state, new_fn, out = ctl.resize(state, target, s)
            if new_fn is None:
                _log(f"[elastic-bench] {label}: resize degraded: "
                     f"{out.detail}")
                runs.append({**out.as_dict(), "label": label,
                             "steps_per_s_before": before,
                             "steps_per_s_after": float("nan"),
                             "first_superstep_s": float("nan")})
                continue
            fn = new_fn
            if out.restart_step is not None:
                s = out.restart_step
            # the first post-resize dispatch pays the recompile — report it
            # apart from both the re-slot latency and steady-state rate
            from repro.launch.train import put_worker_sharded
            t0 = time.perf_counter()
            state, m = fn(state, put_worker_sharded(
                pipe, s, SUPERSTEP, ctl.mesh, ctl.worker))
            jax.block_until_ready(m["loss"])
            first = time.perf_counter() - t0
            s += SUPERSTEP
            state, s, sps = _steps_per_s(fn, state, pipe, ctl.mesh,
                                         ctl.worker, s, n_meas)
            runs.append({**out.as_dict(), "label": label,
                         "steps_per_s_before": before,
                         "steps_per_s_after": sps,
                         "first_superstep_s": first})
            _log(f"[elastic-bench] {label}: {out.path} in "
                 f"{out.latency_s * 1e3:.0f}ms, first superstep "
                 f"{first * 1e3:.0f}ms, {sps:.1f} steps/s after")

    # the in-memory rung: shrink then grow back
    transitions([4, 2, 4], "in-memory")
    # the checkpoint-restore rung: same shrink forced off rung 1
    with tempfile.TemporaryDirectory() as d:
        transitions([4, 2], "ckpt-restore",
                    fault=FaultPlan.from_spec("resizefail@0"), ckpt_dir=d)

    return {"runs": runs, "batch": BATCH, "superstep": SUPERSTEP,
            "logical_shards": LOGICAL_SHARDS}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    # stdout carries one JSON document, but the ResizeController and
    # CheckpointManager narrate to stdout — route them to stderr
    payload_out, sys.stdout = sys.stdout, sys.stderr
    try:
        doc = run(args.quick)
    finally:
        sys.stdout = payload_out
    print(json.dumps(doc), flush=True)


if __name__ == "__main__":
    main()
