"""Staleness-τ convergence + throughput study (paper Result 1-2 / Tables
4-6 analogue): does CHAOS's asynchrony degrade accuracy?

Runs the worker-mesh superstep path for the three Table-2 nets × chaos
staleness τ ∈ {0, 1, 2, 4} × workers ∈ {1, 4, 8}, training each cell for a
fixed number of steps and recording BOTH steps/sec and the final error
over the whole dataset — the paper's claim is that accuracy is not
significantly degraded by asynchronous (arbitrary-order, stale) weight
updates, so the artifact holds the error delta vs the τ=0 (≡ bsp) cell
next to the throughput, plus the Listing-2 performance-model speedup
prediction for the same worker count.

The **layerwise column** (``layerwise: true`` rows, τ ∈ {0, 1} × every
worker count): the same cells through the ParamBuckets per-bucket exchange
path (``--layerwise``) — each bucket runs its own ``gathered_shard_mean``
and update in reverse-production order instead of one stacked whole-tree
reduction, the paper's per-layer exchange granularity.  ``run.py`` attaches
``speedup_vs_batched`` (layerwise vs its batched twin) so the
per-layer-exchange overlap is a first-class column; layerwise τ=0 bsp is
bit-exact to batched bsp, so its error column doubles as a correctness
check.

τ=0 resolves to the bsp strategy object itself (train/sync.py), so its
cells ARE the synchronous baseline.  Must run with enough visible devices
for the largest worker count; ``benchmarks/run.py --only staleness``
calls ``run`` in its own process, and on the CPU the caller's environment
supplies forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.staleness [--quick]

NOTE on absolute numbers: forced host devices share one CPU, so measured
throughput validates the harness + overhead trend (the τ>0 cells drop the
blocking exchange from the update's critical path; the wall-clock benefit
needs real parallel hardware, which runs this code path unchanged).  The
ERROR columns are hardware-independent and are the paper-fidelity payload.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 8          # global batch, fixed across worker counts (the cell
                   # setup itself lives in benchmarks/scaling.py)
SUPERSTEP = 4      # K steps per dispatch
EVAL_BATCH = 64

#: per-net (train_steps, constant lr): the paper's 1e-3 + decay schedule
#: barely moves these synthetic-MNIST runs inside a benchmark-sized step
#: budget, leaving the error at chance where τ effects are invisible — so
#: each net trains with a constant lr chosen so the τ=0 (synchronous)
#: baseline converges well below chance, and ONLY τ varies across a row.
#: Probed so τ=4 stays stable (delayed-SGD stability degrades with lr·τ).
TRAIN_STEPS = {"chaos-small": 256, "chaos-medium": 192, "chaos-large": 160,
               "lm-bench": 64}
TRAIN_LR = {"chaos-small": 0.05, "chaos-medium": 0.05, "chaos-large": 0.01,
            "lm-bench": 0.5}

# dense-LM eval set: deterministic TokenPipeline batches at a seed disjoint
# from the training stream (seed 0)
LM_EVAL_BATCHES = 8
LM_EVAL_BATCH = 16


def _final_error_tokens(cfg, params) -> dict:
    """Held-out next-token error rate + loss for the dense-LM cells: the
    synthetic-bigram token pipeline is a pure function of (seed, step), so
    seed-1 batches are a fixed eval set the training stream never saw."""
    from repro.data.pipeline import TokenPipeline
    from repro.models import lm
    from benchmarks.scaling import LM_SEQ

    pipe = TokenPipeline(cfg.vocab_size, LM_EVAL_BATCH, LM_SEQ, seed=1)

    @jax.jit
    def eval_batch(p, batch):
        loss, _ = lm.loss_fn(p, batch, cfg)
        logits, _ = lm.forward(p, batch["tokens"], cfg)
        pred = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        err = jnp.mean((pred != batch["labels"]).astype(jnp.float32))
        return loss, err

    errs, losses = [], []
    for step in range(LM_EVAL_BATCHES):
        batch = jax.tree.map(jnp.asarray, pipe.batch_at(step))
        loss, err = eval_batch(params, batch)
        errs.append(float(err))
        losses.append(float(loss))
    return {"final_error": float(np.mean(errs)),
            "final_loss": float(np.mean(losses))}


def final_error(cfg, state, eval_data, stacked: bool) -> dict:
    """Error rate over the whole eval set at the trained weights (workers'
    mean for worker-stacked states — the shared-trajectory view).  CNN
    cells evaluate the dataset arrays returned by ``build_worker_cell``;
    token cells re-derive a held-out eval stream from the deterministic
    pipeline (``eval_data`` is None there)."""
    from repro.models.api import get_ops

    params = jax.tree.map(np.asarray, state["params"])
    if stacked:
        params = jax.tree.map(lambda x: x.mean(axis=0), params)
    if cfg.family != "cnn":
        return _final_error_tokens(cfg, params)
    imgs, labels = eval_data
    ops = get_ops(cfg)
    loss_fn = jax.jit(ops.loss)
    errs, losses = [], []
    for lo in range(0, len(imgs), EVAL_BATCH):
        batch = {"images": imgs[lo:lo + EVAL_BATCH],
                 "labels": labels[lo:lo + EVAL_BATCH]}
        loss, m = loss_fn(params, batch)
        errs.append(float(m["error_rate"]))
        losses.append(float(loss))
    return {"final_error": float(np.mean(errs)),
            "final_loss": float(np.mean(losses))}


def run_cell(net: str, tau: int, n_workers: int, train_steps: int,
             lr: float, layerwise: bool = False) -> dict:
    import repro.configs as C
    from repro.core.chaos import SyncConfig
    from repro.optim import sgd
    from repro.train.sync import get_strategy

    import benchmarks.scaling as S
    from benchmarks.scaling import build_worker_cell, timed_supersteps

    cfg = C.get(net)
    lm = cfg.family != "cnn"
    sync = SyncConfig("chaos", staleness=tau, axis_name="workers",
                      layerwise=layerwise)
    stacked = get_strategy(sync).stacked_state
    opt = sgd(lambda s: lr)
    batch = S.LM_BATCH if lm else BATCH
    worker, mesh, pipe, super_fn, state, eval_data = build_worker_cell(
        cfg, sync, n_workers, opt, batch=batch,
        logical_shards=S.LM_SHARDS if lm else None)
    # the whole training run is the timed window (minus the two warm-up
    # dispatches — compile + first donated execution), so steps/sec and
    # the convergence payload come from the same cell
    state, _, us_per_step = timed_supersteps(
        super_fn, state, pipe, mesh, worker, train_steps // SUPERSTEP - 2)
    cell = {
        "net": net, "tau": tau, "workers": n_workers,
        "layerwise": layerwise,
        "superstep": SUPERSTEP, "batch": batch,
        "logical_shards": worker.logical_shards,
        "train_steps": train_steps, "lr": lr, "stacked_state": stacked,
        "us_per_step": us_per_step, "steps_per_s": 1e6 / us_per_step,
    }
    if lm:
        from repro.core.perf_model import dense_lm_ops
        ops = dense_lm_ops(cfg, S.LM_SEQ)
        cell.update(seq=S.LM_SEQ, lm_fprop=ops["fprop"],
                    lm_bprop=ops["bprop"])
    cell.update(final_error(cfg, state, eval_data, stacked))
    return cell


def run(quick: bool = False, nets=None) -> dict:
    """Measure the grid; ``nets`` keeps a subset of the nets."""
    keep = set(nets) if nets else None
    if quick:
        nets = ["chaos-small", "chaos-medium", "lm-bench"]
        net_taus = {"chaos-small": [0, 2], "chaos-medium": [0, 2],
                    "lm-bench": [0, 1]}
        net_workers = {"chaos-small": [4], "chaos-medium": [4],
                       "lm-bench": [2]}
        train_steps = {"chaos-small": 64, "chaos-medium": 32,
                       "lm-bench": 32}
        # CI layerwise cells: one CNN per-bucket-exchange point plus the
        # LM chunked-stack tau=0 cell (bit-identical to its batched twin)
        layerwise_cells = {("chaos-small", 0, 4), ("lm-bench", 0, 2)}
    else:
        cnn_nets = ["chaos-small", "chaos-medium", "chaos-large"]
        nets = cnn_nets + ["lm-bench"]
        # dense-LM cells keep tau in {0, 1}: the error-delta payload needs
        # tau=0 (sync baseline) and the paper-default tau=1; worker counts
        # must divide the LM logical-shard count (4)
        net_taus = {n: [0, 1, 2, 4] for n in cnn_nets}
        net_taus["lm-bench"] = [0, 1]
        net_workers = {n: [1, 4, 8] for n in cnn_nets}
        net_workers["lm-bench"] = [1, 2, 4]
        train_steps = dict(TRAIN_STEPS)
        # the layerwise column (per-bucket exchange + update during
        # backprop): τ ∈ {0, 1} are the canonical overlap cells — bsp-exact
        # per-bucket collectives and stale per-bucket chaos — measured at
        # every worker count next to their batched twins
        layerwise_cells = {(net, tau, n) for net in nets for tau in (0, 1)
                           for n in net_workers[net]}
    if keep:
        nets = [n for n in nets if n in keep]
    from benchmarks.scaling import require_devices
    require_devices(max(max(net_workers[n]) for n in nets))

    runs = []
    for net in nets:
        for n in net_workers[net]:
            for tau in net_taus[net]:
                for layerwise in (False, True):
                    if layerwise and (net, tau, n) not in layerwise_cells:
                        continue
                    r = run_cell(net, tau, n, train_steps[net],
                                 TRAIN_LR[net], layerwise=layerwise)
                    runs.append(r)
                    print(f"# {net} tau={tau} N={n} "
                          f"lw={int(layerwise)}: "
                          f"{r['steps_per_s']:.2f} steps/s "
                          f"err={r['final_error']:.4f}",
                          file=sys.stderr, flush=True)
    return {"runs": runs}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: chaos-small + chaos-medium tau {0,2} at "
                         "4 workers, plus lm-bench tau {0,1} at 2 workers "
                         "(with the layerwise tau=0 bit-identity cell), "
                         "short training")
    ap.add_argument("--nets", default=None,
                    help="comma-separated net subset (e.g. --nets lm-bench "
                         "to add/refresh only the dense-LM column, merged "
                         "with benchmarks/merge_staleness.py)")
    args = ap.parse_args()
    nets = [n for n in args.nets.split(",") if n] if args.nets else None
    json.dump(run(args.quick, nets), sys.stdout)
    print(flush=True)


if __name__ == "__main__":
    main()
