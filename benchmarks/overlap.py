"""Overlap study: is the per-bucket exchange hidden behind backward compute?

The overlap harness (DESIGN.md §8) injects a deterministic per-byte latency
into every explicit worker-mesh collective (``SyncConfig.collective_delay_
ns_per_byte``, modelling an interconnect of bandwidth 1/delay) and measures
the layerwise bsp+SGD worker path under BOTH bucket-exchange schedules:

``collect``     gradients come stacked out of the per-shard ``lax.map``,
                then each bucket's ``gathered_shard_mean`` runs
                *synchronously* inside the update walk — the full
                bytes × delay charge lands on the critical path.
``interleave``  each bucket's gather is issued the moment that layer's
                gradient is produced during backprop (the shard tape); its
                deadline is slept off only where the exchanged gradient is
                consumed, so the remaining backward compute eats into the
                charge — the paper's compute/communication overlap.

Per cell the module reports the measured exchange cost (``us_per_step`` at
delay d minus the same schedule's delay-0 cell) and, for the blocking
schedule, the roofline-model prediction (``core/roofline.py::
parse_collectives`` effective bytes × delay) parsed from the compiled
superstep HLO — the cross-check that the injection charges exactly the
bytes the collective analysis says move.

Grid: Table-2 nets × workers ∈ {1, 2, 4} × delay ∈ {0} ∪ DELAYS ×
both schedules, layerwise bsp + plain SGD (the paper's update rule).
Prints one JSON document (stdout); progress goes to stderr.  Must run with
enough visible devices; ``benchmarks/run.py --only overlap`` calls ``run``
in its own process, and on the CPU the caller's environment supplies
forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.overlap [--quick]

NOTE on the single-core host: forced host devices share one CPU, so a
*busy* collective could never show an overlap win here.  The injection is
deadline-based (``core/chaos.py``): the deadline is stamped at the
collective's issue point and only the REMAINDER is slept at the consumer,
so latency hidden behind compute shows up as a shorter residual sleep —
wall-clock-accurate overlap measurement without parallel hardware.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BATCH = 8          # global batch (fixed logical_shards=8 micro-shards)
SUPERSTEP = 4      # K steps per dispatch

#: injected interconnect latencies, ns/byte (1/bandwidth: 50 ns/B ~ 20 GB/s,
#: 400 ns/B ~ 2.5 GB/s — a slow cluster link).  The interleaved schedule's
#: gates absorb each other's sleeps, so its added wall-clock tends to the
#: LARGEST bucket's charge while the blocking schedule pays the SUM of
#: charges; the win therefore grows linearly with delay and must clear the
#: tape's re-linearisation overhead (~15 ms/step on the forced-host mesh),
#: which at 50 ns/B it does not on the smallest net — both regimes are in
#: the grid on purpose.
DELAYS = [50.0, 400.0]
QUICK_DELAYS = [400.0]


def collective_bytes(super_fn, state, batch) -> float:
    """Roofline-model effective collective bytes per STEP: parse the
    compiled superstep HLO (the scan body holds each per-step collective
    once) with the same ``parse_collectives`` the roofline analysis uses —
    all-gathers count result bytes, all-reduces 2x."""
    from repro.core.roofline import parse_collectives

    hlo = super_fn.lower(state, batch).compile().as_text()
    return parse_collectives(hlo).effective_bytes


def measure(net: str, n_workers: int, interleave: bool, delay: float,
            measured_supersteps: int, want_bytes: bool = False) -> dict:
    import repro.configs as C
    from repro.core.chaos import SyncConfig
    from repro.launch.train import put_worker_sharded
    from repro.train.step import make_optimizer

    from benchmarks.scaling import build_worker_cell, timed_supersteps

    cfg = C.get(net)
    sync = SyncConfig("bsp", layerwise=True, axis_name="workers",
                      collective_delay_ns_per_byte=delay,
                      interleave=interleave)
    opt = make_optimizer(cfg, total_steps=4096)
    worker, mesh, pipe, super_fn, state, _ = build_worker_cell(
        cfg, sync, n_workers, opt)
    eff_bytes = None
    if want_bytes:
        eff_bytes = collective_bytes(
            super_fn, state, put_worker_sharded(pipe, 0, SUPERSTEP, mesh,
                                                worker))
    state, _, us_per_step = timed_supersteps(
        super_fn, state, pipe, mesh, worker, measured_supersteps)
    return {
        "net": net, "workers": n_workers,
        "schedule": "interleave" if interleave else "collect",
        "delay_ns_per_byte": delay,
        "superstep": SUPERSTEP, "batch": BATCH,
        "logical_shards": worker.logical_shards,
        "us_per_step": us_per_step, "steps_per_s": 1e6 / us_per_step,
        "measured_steps": measured_supersteps * SUPERSTEP,
        "collective_bytes_per_step": eff_bytes,
    }


def run(quick: bool = False) -> dict:
    if quick:
        nets = ["chaos-small"]
        worker_counts = [1, 2]
        delays = QUICK_DELAYS
        net_measured = {"chaos-small": 3}
    else:
        nets = ["chaos-small", "chaos-medium", "chaos-large"]
        worker_counts = [1, 2, 4]
        delays = DELAYS
        # chaos-small's win margin at the top delay is a few ms/step, so it
        # gets the most measured supersteps to stay above host noise
        net_measured = {"chaos-small": 6, "chaos-medium": 2,
                        "chaos-large": 2}

    from benchmarks.scaling import require_devices
    require_devices(max(worker_counts))

    runs = []
    for net in nets:
        for n in worker_counts:
            eff = None
            for interleave in (False, True):
                # the delay-0 cell is the schedule's compute baseline; the
                # blocking schedule's cell also yields the compiled-HLO
                # collective bytes for the roofline cross-check column
                # (reused for the interleaved rows — same collectives, only
                # the issue order moves)
                base = measure(net, n, interleave, 0.0, net_measured[net],
                               want_bytes=not interleave)
                got = base.pop("collective_bytes_per_step")
                eff = got if got is not None else eff
                base["exchange_us"] = 0.0
                base["collective_bytes_per_step"] = eff
                runs.append(base)
                sched = base["schedule"]
                print(f"# {net} N={n} {sched} delay=0: "
                      f"{base['us_per_step']:.0f} us/step "
                      f"(collective_bytes={eff})",
                      file=sys.stderr, flush=True)
                for d in delays:
                    r = measure(net, n, interleave, d, net_measured[net])
                    r.pop("collective_bytes_per_step")
                    r["collective_bytes_per_step"] = eff
                    r["exchange_us"] = r["us_per_step"] - base["us_per_step"]
                    # roofline prediction of the *blocking* exchange cost:
                    # effective bytes × delay (ns -> us); the interleaved
                    # schedule should come in UNDER it by the hidden part
                    r["predicted_exchange_us"] = (
                        eff * d * 1e-3 if eff is not None else None)
                    runs.append(r)
                    print(f"# {net} N={n} {sched} delay={d:.0f}: "
                          f"{r['us_per_step']:.0f} us/step "
                          f"exchange={r['exchange_us']:.0f}us "
                          f"predicted={r['predicted_exchange_us']}",
                          file=sys.stderr, flush=True)
    return {"runs": runs}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: chaos-small, workers {1,2}, one delay")
    args = ap.parse_args()
    json.dump(run(args.quick), sys.stdout)
    print(flush=True)


if __name__ == "__main__":
    main()
