"""Measured CHAOS worker-scaling study (the paper's Result 3 / Tables 7-9).

Runs the worker-mesh superstep path end-to-end for the three Table-2 nets
x sync modes x worker counts x Pallas kernels on/off, and prints one JSON
document (stdout) with steps/sec per cell; progress goes to stderr.

MUST run with enough visible devices for the largest worker count
(``make_host_mesh(n)`` takes the first n devices).  ``benchmarks/run.py
--only scaling`` calls ``run`` in its own process; on the CPU the caller's
environment supplies forced host devices, e.g.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.scaling [--quick]

NOTE on absolute numbers: forced host devices all share the same CPU, so
measured "speedup" here validates the *harness and semantics* (and the
overhead trend); the paper-shaped scaling curve comes from real parallel
hardware, which this same code path targets unchanged.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

BATCH = 8          # global batch, fixed across worker counts (strong scaling)
SUPERSTEP = 4      # K steps per dispatch
DATASET = 512
LOCAL_STEPS = 4    # localsgd boundary

# dense-LM column (DESIGN.md §10): per-shard batch 1 at seq 512 — long
# enough that the tuned Pallas flash forward beats the jnp blockwise path
# (the kernel's win is quadratic-in-T score traffic; below ~512 the
# interpret-mode launch overhead eats it)
LM_SEQ = 512
LM_BATCH = 4
LM_SHARDS = 4      # logical shards (so any worker count dividing 4 works)
LM_WORKERS = [1, 2, 4]
LM_MODES = ("bsp", "chaos")


def build_worker_cell(cfg, sync, n_workers: int, opt, *,
                      dataset: int = DATASET, batch: int = BATCH,
                      logical_shards: int | None = None, seq: int = LM_SEQ):
    """Shared benchmark-cell setup for the worker-mesh studies (this module
    and ``benchmarks/staleness.py``): worker config + mesh + pipeline +
    compiled worker superstep + initial state.  The pipeline dispatches on
    the config family: CNNs get the shared-queue image pipeline (and the
    eval arrays back), token families the deterministic synthetic-bigram
    ``TokenPipeline`` (eval batches are re-derived from it, so the last
    return is ``None``)."""
    from repro.core.types import WorkerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.train.step import init_worker_state, make_worker_superstep

    worker = WorkerConfig(workers=n_workers,
                          logical_shards=logical_shards or 8)
    worker.validate_batch(batch)
    mesh = make_host_mesh(n_workers)
    super_fn = make_worker_superstep(cfg, sync, worker, mesh, opt)
    if cfg.family == "cnn":
        from repro.data.mnist import make_dataset
        from repro.data.pipeline import ImagePipeline
        imgs, labels = make_dataset(dataset, seed=0)
        pipe = ImagePipeline(imgs, labels, batch=batch,
                             sample_mode="queue")
        eval_data = (imgs, labels)
    else:
        from repro.data.pipeline import TokenPipeline
        pipe = TokenPipeline(cfg.vocab_size, batch, seq)
        eval_data = None
    state = init_worker_state(cfg, jax.random.key(0), sync, worker, opt)
    return worker, mesh, pipe, super_fn, state, eval_data


def timed_supersteps(super_fn, state, pipe, mesh, worker, n_supersteps: int,
                     k: int = SUPERSTEP, warmup: int = 2):
    """Run ``n_supersteps + warmup`` supersteps (the first ``warmup``
    untimed) and return ``(state, last_metrics, us_per_step)``.

    ``warmup`` defaults to 2, not 1: the first dispatch compiles, but on
    the forced-host-device mesh the SECOND dispatch still pays one-time
    work (donated-buffer layout + XLA:CPU's deferred first-execution
    passes) and lands 4-5x above steady state.  Timing it poisons short
    cells badly enough to invert real orderings — the Pallas flash cells
    compile longer, so with warmup=1 kernel-on measured SLOWER per step
    than kernel-off even though its steady-state step is faster.

    Host batch build + device placement happen OUTSIDE the timed window:
    the driver's PrefetchFeed overlaps them with the previous superstep's
    compute, so timing them here would bias speedups against higher worker
    counts (the serialized host work doesn't shrink with N).  Each timed
    window is one dispatch + ONE host sync on the (K,) loss vector."""
    from repro.launch.train import put_worker_sharded

    batches = [put_worker_sharded(pipe, i * k, k, mesh, worker)
               for i in range(n_supersteps + warmup)]
    measured_steps, elapsed, metrics = 0, 0.0, None
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, metrics = super_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        if i >= warmup:
            elapsed += dt
            measured_steps += k
    return state, metrics, elapsed / measured_steps * 1e6


def measure(net: str, mode: str, n_workers: int, use_kernel: bool,
            measured_supersteps: int) -> dict:
    import repro.configs as C
    from repro.core.chaos import SyncConfig
    from repro.train.step import make_optimizer

    cfg = C.get(net)
    lm = cfg.family != "cnn"
    if use_kernel:
        cfg = dataclasses.replace(cfg, use_kernel=True)
    # staleness picks chaos' τ (1 = the paper's default) but ALSO localsgd's
    # τ-ring depth since the overlap PR; these rows measure the classic
    # blocking boundary average, so pin localsgd to τ=0 explicitly
    sync = SyncConfig(mode, local_steps=LOCAL_STEPS, axis_name="workers",
                      staleness=0 if mode == "localsgd" else 1)
    opt = make_optimizer(cfg, total_steps=4096)
    batch = LM_BATCH if lm else BATCH
    worker, mesh, pipe, super_fn, state, _ = build_worker_cell(
        cfg, sync, n_workers, opt, batch=batch,
        logical_shards=LM_SHARDS if lm else None)
    state, metrics, us_per_step = timed_supersteps(
        super_fn, state, pipe, mesh, worker, measured_supersteps)
    loss = float(np.asarray(metrics["loss"])[-1])
    r = {
        "net": net, "mode": mode, "workers": n_workers,
        "use_kernel": use_kernel, "superstep": SUPERSTEP, "batch": batch,
        "logical_shards": worker.logical_shards,
        "us_per_step": us_per_step, "steps_per_s": 1e6 / us_per_step,
        "measured_steps": measured_supersteps * SUPERSTEP,
        "final_loss": loss,
    }
    if lm:
        from repro.core.perf_model import dense_lm_ops
        ops = dense_lm_ops(cfg, LM_SEQ)
        r.update(seq=LM_SEQ, lm_fprop=ops["fprop"], lm_bprop=ops["bprop"])
    return r


def require_devices(need: int) -> None:
    """Fail a grid that needs more devices than are visible, with advice."""
    from repro.launch.mesh import cpu_device_hint
    devs = jax.devices()
    if need > len(devs):
        raise RuntimeError(
            f"this grid needs {need} devices, {len(devs)} "
            f"{devs[0].platform} device(s) are visible"
            + cpu_device_hint(need))


def run(quick: bool = False, modes=("bsp", "chaos", "localsgd"),
        nets=None) -> dict:
    """Measure the grid; ``nets`` keeps a subset of the nets."""
    if quick:
        nets_all = ["chaos-small", "lm-bench"]
        worker_counts = {"chaos-small": [1, 4], "lm-bench": [2]}
        kernel_modes = {"chaos-small": [False], "lm-bench": [False, True]}
        lm_modes = ("chaos",)
    else:
        nets_all = ["chaos-small", "chaos-medium", "chaos-large", "lm-bench"]
        worker_counts = {net: [1, 2, 4, 8] for net in nets_all}
        worker_counts["lm-bench"] = list(LM_WORKERS)
        kernel_modes = {net: [False, True] for net in nets_all}
        lm_modes = LM_MODES
    if nets:
        nets_all = [n for n in nets_all if n in set(nets)]
    nets = nets_all
    # measured supersteps per cell, scaled to per-step cost (the K-step
    # superstep amortization already smooths dispatch noise)
    net_measured = {"chaos-small": 4, "chaos-medium": 2, "chaos-large": 1,
                    "lm-bench": 4}

    require_devices(max(max(worker_counts[n]) for n in nets))

    if any(True in kernel_modes[n] for n in nets):
        # populate the per-shard autotune keys (batch/logical_shards = 1)
        # the sharded kernel path looks up at EVERY worker count (the
        # worker route always runs kernels at per-shard batch, N=1 included)
        import repro.configs as C
        from repro.core.types import WorkerConfig
        from repro.kernels import autotune as AT
        from repro.kernels.ops import _interpret
        for net in nets:
            if True not in kernel_modes[net]:
                continue
            cfg = C.get(net)
            if cfg.family == "cnn":
                shard_batch = BATCH // WorkerConfig().logical_shards
                print(f"# tuning per-shard kernels for {net} "
                      f"(batch {shard_batch})", file=sys.stderr, flush=True)
                AT.tune_cnn_net(cfg, shard_batch, iters=1,
                                interpret=_interpret())
            else:
                shard_batch = LM_BATCH // LM_SHARDS
                print(f"# tuning per-shard flash attention for {net} "
                      f"(batch {shard_batch}, seq {LM_SEQ})",
                      file=sys.stderr, flush=True)
                AT.tune_lm_attention(cfg, shard_batch, LM_SEQ, iters=1,
                                     interpret=_interpret())

    runs = []
    for net in nets:
        for use_kernel in kernel_modes[net]:
            for mode in modes:
                if net == "lm-bench" and mode not in lm_modes:
                    continue
                for n in worker_counts[net]:
                    m = 1 if quick else net_measured[net]
                    if use_kernel and net != "lm-bench":
                        # interpret-mode CNN kernels are 10-100x the XLA
                        # step; the flash LM step is cheap — keep its full
                        # measured window (short windows are noise-bound)
                        m = min(m, 2)
                    r = measure(net, mode, n, use_kernel, m)
                    runs.append(r)
                    print(f"# {net} {mode} kernel={int(use_kernel)} "
                          f"N={n}: {r['steps_per_s']:.2f} steps/s",
                          file=sys.stderr, flush=True)
    return {"runs": runs}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: chaos-small workers {1,4} kernels off, "
                         "plus one lm-bench chaos cell (kernel on + off), "
                         "one measured superstep per cell")
    ap.add_argument("--modes", default="bsp,chaos,localsgd",
                    help="comma-separated sync-mode subset — re-measure "
                         "only some BENCH_scaling rows (e.g. --modes chaos "
                         "after a sync-engine change), then merge the "
                         "stdout JSON into the artifact with "
                         "benchmarks/merge_scaling.py")
    ap.add_argument("--nets", default=None,
                    help="comma-separated net subset (e.g. --nets lm-bench "
                         "to add/refresh only the dense-LM column, merged "
                         "with benchmarks/merge_scaling.py)")
    args = ap.parse_args()
    modes = tuple(m for m in args.modes.split(",") if m)
    nets = [n for n in args.nets.split(",") if n] if args.nets else None
    json.dump(run(args.quick, modes, nets), sys.stdout)
    print(flush=True)


if __name__ == "__main__":
    main()
