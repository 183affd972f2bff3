"""Fault-tolerant superstep training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b-smoke \
        --steps 200 --sync chaos --superstep 8 --ckpt-dir /tmp/ckpt \
        [--batch 8 --seq 256]

Features (framework-scale runtime, DESIGN.md §3):
  - SUPERSTEP execution: K steps run inside one compiled ``lax.scan``
    dispatch with full TrainState donation; the host syncs on metrics once
    per K steps (loss comes back as a (K,)-vector) instead of once per
    step — the per-step dispatch + host-roundtrip overhead amortizes 1/K;
  - on-device prefetch: a double-buffered background feed builds the NEXT
    superstep's stacked (K, B, ...) batch and ships it to the device while
    the current superstep computes;
  - data routing by family: CNN archs (the paper's Table-2 nets) feed from
    ``ImagePipeline`` in the paper's shared-queue mode (each batch lane
    takes every B-th sample of a per-epoch permutation — no static split),
    token archs from ``TokenPipeline``;
  - checkpoint/restart: atomic keep-N checkpoints, auto-resume from latest,
    deterministic data pipeline keyed by step (resume == replay, any K);
  - pluggable sync strategies (train/sync.py registry: bsp | chaos |
    localsgd; --staleness picks chaos' τ, --layerwise the paper's
    per-layer update rule) — every strategy threads its sync state
    through the scan carry;
  - WORKER MESH (--workers N, DESIGN.md §4): the superstep scan runs inside
    shard_map over a 1-D worker mesh (the paper's Phi threads); each worker
    consumes its contiguous shard of the shared-queue batch and the sync
    mode's collectives ride the named worker axis.  bsp/chaos updates are
    bit-exact for ANY worker count dividing --logical-shards, so their
    checkpoints are worker-count-invariant (resume on fewer/more workers);
  - straggler watchdog: per-superstep wall-time z-score detection with a
    bounded flag log and a window matched to superstep granularity;
  - elastic re-meshing: on restore, arrays are placed under the *current*
    mesh's shardings, so a job can come back on fewer/more chips;
  - preemption simulation via --die-at-step (used by the fault-tolerance
    integration test); checkpoints, logs, and the simulated death all land
    on superstep boundaries.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import queue
import signal
import statistics
import sys
import threading
import time
from collections import deque

import jax
import numpy as np

import repro.configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.core.chaos import SyncConfig
from repro.core.types import WorkerConfig
from repro.data.pipeline import ImagePipeline, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.elastic import ResizeController
from repro.launch.faults import FaultPlan
from repro.launch.mesh import make_host_mesh
from repro.obs import JsonlSink, MetricsBus, Tracer
from repro.obs import trace as obs_trace
from repro.train.step import (init_train_state, init_worker_state,
                              make_optimizer, make_superstep,
                              make_worker_superstep)
from repro.train.sync import get_strategy, sync_modes

#: synthetic-MNIST pool size for CNN runs (offline container, DESIGN.md §6)
CNN_DATASET_SIZE = 4096


class StragglerWatchdog:
    """Flags supersteps slower than mean + z*std over a sliding window.

    The window adapts to superstep granularity — one observation covers K
    steps, so the window shrinks to keep a roughly constant ~200-step
    horizon (min 8 observations) — and ``flagged`` is a bounded deque so a
    long-running job cannot leak memory through its own diagnostics.

    The first ``warmup`` observations are discarded entirely: they carry
    jit-compile time (and the first donated-buffer re-trace, so TWO of
    them), which would both poison the window's variance (a multi-second
    outlier hides any real straggler for the window's whole lifetime) and
    be flagged as a phantom straggler itself.  The driver builds a FRESH
    watchdog after an elastic resize for the same reason — a new mesh
    recompiles and retimes.

    Every observation (including warmup — a 5-second compile is exactly
    what you want visible on the timeline) is exported to the obs layer
    when one is attached: a ``watchdog/superstep_s`` gauge + histogram on
    the metrics bus, a Perfetto counter track on the tracer — so a stall
    shows up in the trace BEFORE any eviction fires, not only as its
    after-the-fact ResizeOutcome row.
    """

    def __init__(self, window: int | None = None, z: float = 3.0,
                 superstep: int = 1, max_flags: int = 64, warmup: int = 2,
                 bus: MetricsBus | None = None, tracer: Tracer | None = None):
        if window is None:
            window = max(8, 200 // max(superstep, 1))
        self.times: deque = deque(maxlen=window)
        self.window = window
        self.z = z
        self.flagged: deque = deque(maxlen=max_flags)
        self.warmup = warmup
        self.bus = bus
        self.tracer = tracer

    def observe(self, step: int, dt: float) -> bool:
        """Record one superstep wall time; True when it was flagged as a
        straggler (the driver's --evict-stragglers feeds this verdict to
        the elastic ResizeController as a membership event)."""
        if self.bus is not None:
            self.bus.gauge("watchdog/superstep_s", dt)
            self.bus.observe("watchdog/superstep_s", dt)
            self.bus.series("watchdog/superstep_s", step, dt)
        if self.tracer is not None:
            self.tracer.counter("watchdog/superstep_s", dt)
        if self.warmup > 0:
            self.warmup -= 1
            return False
        straggled = False
        # need a filled-enough window before z-scoring; never require more
        # samples than the window can hold (large K shrinks it below 10)
        if len(self.times) >= min(10, self.times.maxlen):
            mu = statistics.fmean(self.times)
            sd = statistics.pstdev(self.times) or 1e-9
            if dt > mu + self.z * sd:
                straggled = True
                self.flagged.append((step, dt, mu))
                if self.bus is not None:
                    self.bus.event("straggler", step=step, dt_s=dt,
                                   mean_s=mu)
                if self.tracer is not None:
                    self.tracer.instant("straggler", step=step, dt_s=dt,
                                        mean_s=mu)
                print(f"[watchdog] superstep ending at {step} straggled: "
                      f"{dt * 1e3:.1f}ms vs mean {mu * 1e3:.1f}ms",
                      flush=True)
        self.times.append(dt)
        return straggled


def make_pipeline(cfg, batch: int, seq: int, seed: int = 0):
    """Data pipeline for the arch family: CNN -> ImagePipeline with the
    paper's shared-queue worker semantics; everything else -> TokenPipeline."""
    if cfg.family == "cnn":
        from repro.data.mnist import make_dataset
        imgs, labels = make_dataset(CNN_DATASET_SIZE, seed=seed)
        return ImagePipeline(imgs, labels, batch=batch, seed=seed,
                             sample_mode="queue")
    return TokenPipeline(cfg.vocab_size, batch, seq, seed=seed)


def put_worker_sharded(pipe, start: int, k: int, mesh, worker: WorkerConfig):
    """Assemble the global stacked (K, B, ...) superstep batch worker-shard
    by worker-shard: worker w's device receives exactly
    ``pipe.worker_superstep_at(start, k, N, w)`` (its contiguous lanes of
    the shared queue), and the shards are stitched into one global array
    sharded P(None, workers) over the batch dim — in a real multi-host run
    each host would build only its own shard."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.data.pipeline import worker_slice

    n = worker.workers
    # build the global stacked batch ONCE and slice per worker (slicing is
    # what worker_superstep_at does; rebuilding it N times would put O(N)
    # redundant host work on the prefetch hot path)
    stacked = pipe.superstep_at(start, k)
    b = next(iter(stacked.values())).shape[1]
    shards = [worker_slice(stacked, b, n, w) for w in range(n)]
    sharding = NamedSharding(mesh, P(None, worker.axis))
    devices = list(mesh.devices.flat)
    out = {}
    for key in shards[0]:
        arrs = [jax.device_put(s[key], d) for s, d in zip(shards, devices)]
        shp = shards[0][key].shape
        gshape = (shp[0], shp[1] * n) + shp[2:]
        out[key] = jax.make_array_from_single_device_arrays(
            gshape, sharding, arrs)
    return out


class PrefetchFeed:
    """Double-buffered async host->device feed.

    A daemon thread walks the superstep schedule, builds each stacked
    (K, B, ...) batch on the host, and ``jax.device_put``s it while the
    main thread's current superstep is still computing; queue depth 2 is
    classic double buffering (one in flight, one ready).  ``put`` overrides
    the host->device transfer (the worker route shards each superstep
    batch over the worker mesh, ``put_worker_sharded``).
    """

    def __init__(self, pipe, chunks, depth: int = 2, put=None):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._error: BaseException | None = None
        self._stopped = False
        self._put = put or (lambda p, s, k: jax.device_put(
            p.superstep_at(s, k)))
        self._thread = threading.Thread(
            target=self._produce, args=(pipe, list(chunks)), daemon=True)
        self._thread.start()

    def _produce(self, pipe, chunks):
        try:
            for start, k in chunks:
                if self._stopped:
                    return
                batch = self._put(pipe, start, k)
                self._q.put((start, k, batch))
        except BaseException as e:  # surface in the consumer, never hang it
            self._error = e
        finally:
            self._q.put(None)

    def stop(self):
        """Abandon the feed mid-schedule (elastic resize rebuilds it for
        the new mesh): drain the queue so a producer blocked in ``put``
        wakes up, sees the flag, and exits."""
        self._stopped = True
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is None:
                if self._error is not None:
                    raise RuntimeError("prefetch feed failed") from self._error
                return
            yield item


def superstep_schedule(start: int, steps: int, k: int):
    """[(chunk_start, chunk_len)] covering [start, steps) in K-step chunks
    (the final chunk may be shorter)."""
    return [(s, min(k, steps - s)) for s in range(start, steps, max(k, 1))]


def train(arch: str, steps: int, sync_mode: str = "bsp", batch: int = 8,
          seq: int = 256, ckpt_dir: str | None = None,
          ckpt_every: int = 50, die_at_step: int | None = None,
          base_lr: float = 3e-4, compress: bool = False,
          log_every: int = 10, smoke: bool = True, superstep: int = 1,
          use_kernel: bool = False, workers: int | None = None,
          logical_shards: int = 8, staleness: int = 1,
          layerwise: bool = False, optim: str = "auto",
          ring_dtype: str | None = None, inject: str | None = None,
          inject_seed: int = 0, metrics_out: str | None = None,
          evict_stragglers: bool = False, readmit_after: int | None = None,
          collective_delay: float = 0.0, interleave: bool = False,
          micro_batches: int | None = None,
          layer_chunk: int | None = None, trace_out: str | None = None,
          metrics_interval: int = 0, metrics_bus: MetricsBus | None = None):
    if superstep < 1:
        raise ValueError(f"superstep must be >= 1, got {superstep}")
    # -- observability (DESIGN.md §11) ------------------------------------
    # The bus is ALWAYS present (it replaced the ad-hoc loss_map / metrics
    # dict — per-step cost is one dict store); the tracer only when asked.
    # set_tracer BEFORE building any step function: the worker-mesh bucket
    # paths consult the global at build time, so with no tracer the
    # compiled graphs are byte-identical to a no-obs build.
    bus = metrics_bus if metrics_bus is not None else MetricsBus()
    if bus.sink is None and metrics_interval > 0 and metrics_out:
        bus.sink = JsonlSink(metrics_out + ".jsonl")
    tracer = Tracer("train") if trace_out else None
    prev_tracer = obs_trace.set_tracer(tracer) if tracer else None
    try:
        return _train(arch, steps, sync_mode, batch, seq, ckpt_dir,
                      ckpt_every, die_at_step, base_lr, compress, log_every,
                      smoke, superstep, use_kernel, workers, logical_shards,
                      staleness, layerwise, optim, ring_dtype, inject,
                      inject_seed, metrics_out, evict_stragglers,
                      readmit_after, collective_delay, interleave,
                      micro_batches, layer_chunk, metrics_interval, bus,
                      tracer)
    finally:
        if tracer is not None:
            obs_trace.set_tracer(prev_tracer)
            tracer.write(trace_out)
        bus.close()


def _train(arch, steps, sync_mode, batch, seq, ckpt_dir, ckpt_every,
           die_at_step, base_lr, compress, log_every, smoke, superstep,
           use_kernel, workers, logical_shards, staleness, layerwise, optim,
           ring_dtype, inject, inject_seed, metrics_out, evict_stragglers,
           readmit_after, collective_delay, interleave, micro_batches,
           layer_chunk, metrics_interval, bus, tracer):
    plan = FaultPlan.from_spec(inject, seed=inject_seed)
    cfg = C.smoke(arch) if smoke else C.get(arch)
    if use_kernel:
        cfg = dataclasses.replace(cfg, use_kernel=True)
    if micro_batches is not None:
        cfg = dataclasses.replace(cfg, micro_batches=micro_batches)
    if layer_chunk is not None:
        cfg = dataclasses.replace(cfg, layer_chunk=layer_chunk)
    optimizer = make_optimizer(cfg, base_lr=base_lr, total_steps=steps,
                               kind=optim)
    put = None
    controller = None
    if workers is not None:
        # CHAOS worker-mesh route (DESIGN.md §4): the superstep scan runs
        # inside shard_map over a 1-D worker mesh; each worker consumes its
        # contiguous shard of the shared-queue batch, and the strategy's
        # collectives thread over the named worker axis.  N=1 runs the SAME
        # code path, so semantics never depend on how many devices back it.
        worker = WorkerConfig(workers=workers, logical_shards=logical_shards)
        worker.validate_batch(batch)
        mesh = make_host_mesh(workers)
        sync = SyncConfig(mode=sync_mode, compress=compress,
                          axis_name=worker.axis, staleness=staleness,
                          layerwise=layerwise, ring_dtype=ring_dtype,
                          collective_delay_ns_per_byte=collective_delay,
                          interleave=interleave)
        super_fn = make_worker_superstep(cfg, sync, worker, mesh, optimizer)
        state = init_worker_state(cfg, jax.random.key(0), sync, worker,
                                  optimizer)
        put = lambda p, s, k: put_worker_sharded(p, s, k, mesh, worker)
        controller = ResizeController(cfg, sync, optimizer, worker, mesh,
                                      fault=plan, readmit_after=readmit_after)
        try:  # SIGUSR1 = the scheduler's preemption warning: shed a worker
            signal.signal(signal.SIGUSR1, lambda *_: controller.request(
                controller.worker.workers - 1, "SIGUSR1 preemption warning"))
        except ValueError:
            pass  # not the main thread (in-process harness) — skip the hook
        print(f"[train] worker mesh: {workers} worker(s) x "
              f"{worker.shards_per_worker} shard(s), sync={sync_mode} "
              f"({get_strategy(sync).checkpoint_layout()})", flush=True)
    else:
        if plan is not None and any(e.kind == "kill" for e in plan.events):
            print("[train] NOTE: --inject kill@... is a worker-membership "
                  "event; without --workers there is no mesh to resize, so "
                  "kill events are ignored on this route", flush=True)
        sync = SyncConfig(mode=sync_mode, compress=compress,
                          staleness=staleness, layerwise=layerwise,
                          ring_dtype=ring_dtype,
                          collective_delay_ns_per_byte=collective_delay,
                          interleave=interleave)
        # K=1 is a length-1 scan: every run dispatches through the same scan
        # body, so mixing K across runs/resumes cannot change the numerics
        super_fn = jax.jit(make_superstep(cfg, sync, optimizer),
                           donate_argnums=(0,))
        state = init_train_state(cfg, jax.random.key(0), sync, optimizer)
    pipe = make_pipeline(cfg, batch, seq)

    start = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep_n=3, fault=plan)
        if controller is not None:
            controller.ckpt_mgr = mgr  # the resize ladder's restore rung
        if mgr.latest_step() is not None:
            state, start = mgr.restore(state)
            print(f"[train] resumed from step {start}", flush=True)

    watchdog = StragglerWatchdog(superstep=superstep, bus=bus, tracer=tracer)
    # losses live on the bus as a step-keyed series: an elastic
    # ckpt-restore rung may REPLAY a few steps, and replayed entries
    # overwrite their originals (bit-exactly for worker-count-invariant
    # strategies) instead of duplicating
    saved_at = None
    next_start = start
    faults_seen = 0
    work_s, work_steps = 0.0, 0
    while next_start < steps:
        feed = PrefetchFeed(pipe,
                            superstep_schedule(next_start, steps, superstep),
                            put=put)
        resize_request = None
        for s0, k, dev_batch in feed:
            t0 = time.time()
            if tracer is not None:
                with tracer.span("superstep", step_start=s0, k=k):
                    state, metrics = super_fn(state, dev_batch)
                    # ONE host sync per K steps: the (K,) loss vector —
                    # inside the span so it covers device time, not just
                    # the async dispatch
                    loss_vec = np.asarray(metrics["loss"])
            else:
                state, metrics = super_fn(state, dev_batch)
                loss_vec = np.asarray(metrics["loss"])
            end = s0 + k
            for t in range(s0, end):
                bus.series("train/loss", t, float(loss_vec[t - s0]))
            if plan is not None:
                plan.stall(end)  # inside the watchdog's timed window
            dt = time.time() - t0
            straggled = watchdog.observe(end, dt)
            work_s += dt
            work_steps += k
            bus.gauge("train/steps_per_s", work_steps / max(work_s, 1e-9))
            bus.gauge("train/loss", float(loss_vec[-1]))
            if plan is not None and len(plan.log) > faults_seen:
                for f in plan.log[faults_seen:]:
                    bus.event("fault", **f)
                    if tracer is not None:
                        tracer.instant("fault", **f)
                faults_seen = len(plan.log)
            if metrics_interval > 0 and (
                    end // metrics_interval > s0 // metrics_interval):
                if bus.sink is not None:
                    bus.flush(end)
                else:
                    print(f"[obs] step {end} "
                          + json.dumps(bus.summary()["gauges"]), flush=True)
            for t in range(s0, end):
                if t % log_every == 0:
                    print(f"[train {arch} sync={sync_mode}] step {t} "
                          f"loss={loss_vec[t - s0]:.4f}", flush=True)
            if mgr and end // ckpt_every > s0 // ckpt_every:
                with obs_trace.span("checkpoint", step=end):
                    mgr.save(end, state, blocking=False)
                saved_at = end
            if die_at_step is not None and end >= die_at_step:
                if mgr:
                    mgr.wait()
                print(f"[train] simulated preemption at step {end}",
                      flush=True)
                sys.exit(17)
            next_start = end
            # membership-change events apply at superstep boundaries: the
            # in-flight superstep is already drained here (DESIGN.md §7)
            if controller is not None and end < steps:
                if plan is not None:
                    target = plan.membership_event(
                        end, controller.worker.workers)
                    if target is not None:
                        controller.request(target, "injected worker-kill")
                if evict_stragglers and straggled:
                    controller.request(
                        controller.worker.workers - 1,
                        f"straggler verdict at step {end}")
                controller.observe_boundary(straggled)
                resize_request = controller.take_pending()
                if resize_request is not None:
                    break
        if resize_request is None:
            break
        feed.stop()
        if mgr:
            mgr.wait()  # never race an async save with the restore rung
        target, reason = resize_request
        with obs_trace.span("resize", target=target, reason=reason,
                            at_step=next_start):
            state, new_super_fn, outcome = controller.resize(
                state, target, next_start, reason=reason)
        bus.event("resize", **outcome.as_dict())
        bus.gauge("train/workers", controller.worker.workers)
        if new_super_fn is not None:
            super_fn = new_super_fn
            put = (lambda p, s, k, m=controller.mesh, w=controller.worker:
                   put_worker_sharded(p, s, k, m, w))
            # new mesh => recompile + new timing regime: stale window stats
            # would flag the first post-resize superstep as a straggler
            watchdog = StragglerWatchdog(superstep=superstep, bus=bus,
                                         tracer=tracer)
        if outcome.restart_step is not None:
            next_start = outcome.restart_step  # replay from the checkpoint

    losses = bus.series_sorted("train/loss")
    if mgr:
        if saved_at == steps:
            mgr.wait()
        else:
            with obs_trace.span("checkpoint", step=steps):
                mgr.save(steps, state, blocking=True)
    if plan is not None and len(plan.log) > faults_seen:
        for f in plan.log[faults_seen:]:
            bus.event("fault", **f)
    if metrics_out:
        bus.write_metrics_out(metrics_out, arch=arch, sync=sync_mode,
                              steps=steps,
                              workers_final=(controller.worker.workers
                                             if controller else None))
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--sync", default="bsp", choices=sync_modes(),
                    help="synchronization strategy (train/sync.py registry)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="staleness tau: chaos counts steps (0 degenerates "
                         "exactly to bsp — bit-exact, same checkpoints); "
                         "localsgd counts boundaries (0 = the blocking "
                         "K-step average, >=1 the tau-ring stale "
                         "corrections, DESIGN.md section 8)")
    ap.add_argument("--layerwise", action="store_true",
                    help="per-bucket non-instant updates during backprop "
                         "(paper update rule via the ParamBuckets tape; "
                         "any family/optimizer, composes with --workers "
                         "and --compress)")
    ap.add_argument("--optim", default="auto",
                    choices=["auto", "sgd", "momentum", "adamw"],
                    help="optimizer override (auto = family default: CNN "
                         "-> the paper's plain SGD, else adamw)")
    ap.add_argument("--ring-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="chaos staleness-ring slot dtype (default: param "
                         "dtype); bfloat16 halves the tau x params ring "
                         "memory via the compression cast")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--superstep", type=int, default=1,
                    help="steps per compiled scan dispatch (K)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route the CNN hot path through the Pallas kernels")
    ap.add_argument("--workers", type=int, default=None,
                    help="CHAOS worker-mesh route: N worker instances over "
                         "a 1-D device mesh (needs N visible devices; on the "
                         "CPU force host devices with XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N)")
    ap.add_argument("--logical-shards", type=int, default=8,
                    help="fixed micro-shard count of the global batch on "
                         "the worker route; any --workers dividing it "
                         "computes bit-identical bsp/chaos updates")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--die-at-step", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--inject", default=None,
                    help="deterministic fault-injection spec "
                         "(launch/faults.py), e.g. "
                         "'kill@6:to=3,torn@8,io@restore:times=2'")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="seed for the fault plan's randomness (unspecified "
                         "torn fractions)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a JSON artifact with the per-step loss "
                         "sequence, resize outcomes, and fired faults "
                         "(CI / test assertions; composed by the obs "
                         "metrics bus, DESIGN.md §11)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace/Perfetto trace.json (+ "
                         ".jsonl) with superstep/checkpoint/resize spans "
                         "and, on the layerwise worker mesh, per-bucket "
                         "exchange issue/gate spans (DESIGN.md §11)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="emit a metrics-bus snapshot every N steps — to "
                         "<metrics-out>.jsonl when --metrics-out is set, "
                         "else to stdout; 0 disables")
    ap.add_argument("--evict-stragglers", action="store_true",
                    help="feed straggler-watchdog verdicts to the elastic "
                         "resize controller (shed one worker per verdict)")
    ap.add_argument("--readmit-after", type=int, default=None,
                    help="re-admit a straggler-evicted worker after this "
                         "many consecutive clean supersteps (probation "
                         "window; a straggle during probation resets it)")
    ap.add_argument("--collective-delay", type=float, default=0.0,
                    help="overlap harness (DESIGN.md §8): inject this many "
                         "nanoseconds of latency per byte into every "
                         "explicit worker-mesh collective; 0 leaves the "
                         "compiled graph untouched")
    ap.add_argument("--interleave", action="store_true",
                    help="layerwise worker mesh: fire each bucket's "
                         "exchange during backprop the moment that layer's "
                         "gradient is produced (DESIGN.md §8) instead of "
                         "collect-then-walk; ~1-ulp vs the batched pin")
    ap.add_argument("--micro-batches", type=int, default=None,
                    help="override the arch's micro-batch accumulation "
                         "count (single-instance route; composes with "
                         "--layerwise via the bucket-granular accumulator)")
    ap.add_argument("--layer-chunk", type=int, default=None,
                    help="LM layer-stack chunk size (DESIGN.md §10): split "
                         "the scanned layer stack into n_layers/c per-chunk "
                         "param buckets so --layerwise/--interleave exchange "
                         "at chunk granularity; 0 keeps the single-stack "
                         "scan layout, must divide n_layers")
    args = ap.parse_args()
    use_compile_cache()
    _, losses = train(args.arch, args.steps, args.sync, args.batch, args.seq,
                      args.ckpt_dir, args.ckpt_every, args.die_at_step,
                      args.lr, args.compress, smoke=not args.full_config,
                      superstep=args.superstep, use_kernel=args.use_kernel,
                      workers=args.workers,
                      logical_shards=args.logical_shards,
                      staleness=args.staleness, layerwise=args.layerwise,
                      optim=args.optim, ring_dtype=args.ring_dtype,
                      inject=args.inject, inject_seed=args.inject_seed,
                      metrics_out=args.metrics_out,
                      evict_stragglers=args.evict_stragglers,
                      readmit_after=args.readmit_after,
                      collective_delay=args.collective_delay,
                      interleave=args.interleave,
                      micro_batches=args.micro_batches,
                      layer_chunk=args.layer_chunk,
                      trace_out=args.trace_out,
                      metrics_interval=args.metrics_interval)
    print(f"[train] done: first-10 mean {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")


if __name__ == "__main__":
    main()
