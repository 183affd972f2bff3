"""Train/serve step builders: the glue between models, the SyncStrategy
engine, optimizers, and sharding.

``make_train_step(cfg, sync)``  -> (step_fn, TrainState helpers)
``make_superstep(cfg, sync)``   -> K steps per dispatch via lax.scan over a
                                   stacked (K, B, ...) batch (DESIGN.md §3)
``make_serve_step(cfg)``        -> decode step over a KV/state cache

Synchronization behaviour (bsp / chaos(τ) / localsgd / anything registered
later) is fully delegated to ``train/sync.py``: this module builds the
execution-path ``StepContext`` (how gradients are produced and reduced) and
the strategy supplies the step body — there are no per-mode branches here
(DESIGN.md §5).

Named scopes (``obs/trace.py``): everything after the gradients exist runs
under ``update``, and each bucket's exchange under ``exchange/{bucket}``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.chaos import (SyncConfig, delay_gate, delay_start,
                              gathered_shard_mean)
from repro.core.schedule import make_lr_fn
from repro.core.types import ArchConfig, WorkerConfig
from repro.models import layers as ML
from repro.models.api import get_ops
from repro.optim import adamw, sgd
from repro.train.sync import StepContext, get_strategy


def make_optimizer(cfg: ArchConfig, base_lr: float = 3e-4,
                   total_steps: int = 10_000, kind: str = "auto"):
    """``kind``: "auto" (family default: CNN -> the paper's plain SGD,
    everything else -> adamw), or an explicit "sgd" / "momentum" /
    "adamw" override (driver ``--optim``)."""
    lr_fn = make_lr_fn(cfg.lr_schedule,
                       base_lr=1e-3 if cfg.family == "cnn" else base_lr,
                       steps_per_epoch=max(total_steps // 70, 1),
                       total_steps=total_steps)
    if kind == "auto":
        kind = "sgd" if cfg.family == "cnn" else "adamw"
    if kind == "sgd":
        return sgd(lr_fn)  # paper: plain SGD + decay schedule
    if kind == "momentum":
        return sgd(lr_fn, momentum=0.9)
    if kind == "adamw":
        return adamw(lr_fn, moment_dtype=cfg.opt_moment_dtype)
    raise ValueError(
        f"unknown optimizer kind {kind!r}; choose auto|sgd|momentum|adamw")


def init_train_state(cfg: ArchConfig, key, sync: SyncConfig,
                     optimizer=None, abstract: bool = False):
    ops = get_ops(cfg)
    optimizer = optimizer or make_optimizer(cfg)
    strat = get_strategy(sync)
    if abstract:
        params = jax.eval_shape(ops.init, key)
    else:
        params = ops.init(key)
    opt_state = (jax.eval_shape(optimizer.init, params) if abstract
                 else optimizer.init(params))
    sync_state = (jax.eval_shape(strat.init_state, params)
                  if abstract else strat.init_state(params))
    return {"params": params, "opt": opt_state, "sync": sync_state,
            "step": (jax.ShapeDtypeStruct((), jnp.int32) if abstract
                     else jnp.zeros((), jnp.int32))}


def state_specs(cfg: ArchConfig, sync: SyncConfig, optimizer=None):
    """Logical PartitionSpec tree matching init_train_state's output."""
    ops = get_ops(cfg)
    pspecs = ops.param_specs()
    optimizer = optimizer or make_optimizer(cfg)
    strat = get_strategy(sync)

    # optimizer state mirrors param sharding (one params-shaped tree per
    # top-level key: adamw {m, v}, sgd-momentum {mu}); the sync strategy
    # owns its own state layout (chaos' ring is τ separate params-shaped
    # slot trees, each sharded exactly like params)
    abstract = jax.eval_shape(ops.init, jax.random.key(0))
    opt_abs = jax.eval_shape(optimizer.init, abstract)
    opt_specs = {k: pspecs for k in opt_abs} if isinstance(opt_abs, dict) else {}
    return {"params": pspecs, "opt": opt_specs,
            "sync": strat.state_specs(pspecs), "step": P()}


def _make_grad_fn(cfg: ArchConfig, ops):
    """(params, batch) -> (loss, metrics, grads), with optional
    microbatching (gradient accumulation): the global batch is split into
    cfg.micro_batches slices processed sequentially — activation memory
    scales 1/n_micro."""
    def grad_fn(params, batch):
        n_micro = max(cfg.micro_batches, 1)
        if n_micro == 1:
            (l, m), g = jax.value_and_grad(ops.loss, has_aux=True)(params,
                                                                   batch)
            return l, m, g

        def split(x):
            return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        mb = jax.tree.map(split, batch)

        def one(b):
            (l, m), g = jax.value_and_grad(ops.loss, has_aux=True)(params, b)
            g = jax.tree.map(lambda t: t.astype(jnp.float32), g)
            return (l, m), g

        from repro.models import layers as MLY
        if MLY.UNROLL_ATTN:  # dry-run: unrolled for honest cost accounting
            (l, m), g = one(jax.tree.map(lambda x: x[0], mb))
            for i in range(1, n_micro):
                (li, mi), gi = one(jax.tree.map(lambda x, i=i: x[i], mb))
                l = l + li
                m = jax.tree.map(jnp.add, m, mi)
                g = jax.tree.map(jnp.add, g, gi)
        else:
            def body(carry, b):
                l, m, g = carry
                (li, mi), gi = one(b)
                return (l + li, jax.tree.map(jnp.add, m, mi),
                        jax.tree.map(jnp.add, g, gi)), None
            (l0, m0), g0 = one(jax.tree.map(lambda x: x[0], mb))
            (l, m, g), _ = jax.lax.scan(
                body, (l0, m0, g0), jax.tree.map(lambda x: x[1:], mb))
        inv = 1.0 / n_micro
        return (l * inv, jax.tree.map(lambda t: t * inv, m),
                jax.tree.map(lambda t: t * inv, g))

    return grad_fn


def make_train_step(cfg: ArchConfig, sync: SyncConfig, optimizer=None):
    """Returns step(state, batch) -> (new_state, metrics).

    The step body comes from the registered SyncStrategy; this builder only
    supplies the single-instance StepContext (implicit-SPMD reductions are
    identities).  ``sync.layerwise`` routes through the per-layer
    non-instant-update path instead (CNN + stateless SGD, DESIGN.md §5).
    """
    ops = get_ops(cfg)
    optimizer = optimizer or make_optimizer(cfg)
    strat = get_strategy(sync)
    if sync.layerwise:
        return _make_bucket_step(cfg, sync, strat, ops, optimizer)
    ctx = StepContext(optimizer=optimizer, grad_fn=_make_grad_fn(cfg, ops))

    def step(state, batch):
        return strat.step(ctx, state, batch)

    return step


def _apply_bucket(optimizer, bucket, params, g_b, opt_state, step):
    """One bucket's optimizer update with sliced state: returns
    ``(new_params_b, new_opt_state)`` — ``apply_raw`` is strictly per-leaf,
    so bucket-by-bucket application is bit-identical to one whole-tree
    apply given the same (pre-transformed) gradients."""
    st_b = optimizer.slice_state(opt_state, bucket.keys)
    new_p_b, new_st = optimizer.apply_raw(bucket.view(params), g_b, st_b,
                                          step)
    return new_p_b, optimizer.merge_state(opt_state, bucket.keys, new_st)


def _exchange(exchange_bucket, bucket, g_b):
    """One bucket's exchange under its ``exchange/{bucket}`` scope."""
    with jax.named_scope(f"exchange/{bucket.name}"):
        return exchange_bucket(bucket, g_b)


def _exchange_by_bucket(reduce, spec):
    """``reduce`` of a params-shaped tree taken bucket by bucket, each
    under its ``exchange/{bucket}`` scope (the non-layerwise strategies
    exchange the whole gradient tree in one call); any other tree — one
    bucket's slice, the step's metrics — is reduced whole.  ``reduce``
    works leaf by leaf, so the split changes no value."""
    keys = {k for b in spec for k in b.keys}

    def combine(tree):
        if not isinstance(tree, dict) or set(tree) != keys:
            return reduce(tree)
        out = {}
        for b in spec:
            out.update(_exchange(lambda _, t: reduce(t), b, b.view(tree)))
        return out
    return combine


def _bucket_walk(spec, optimizer, exchange_bucket, params, opt_state, grads,
                 step):
    """Collect-then-walk flavour of the bucket tape (reverse-production
    order): exchange then update each bucket.  Used where all bucket
    gradients exist before the walk — the worker mesh (per-shard gradients
    come stacked out of ``lax.map``) and optimizers with a global
    ``pre_apply`` transform (adamw's clip needs the whole exchanged tree).
    Per-bucket exchange + update chaining is preserved either way."""
    new_params = dict(params)
    opt = opt_state
    if optimizer.pre_apply is None:
        for bucket in reversed(spec):
            g_ex = _exchange(exchange_bucket, bucket, bucket.view(grads))
            new_p_b, opt = _apply_bucket(optimizer, bucket, new_params,
                                         g_ex, opt, step)
            new_params.update(new_p_b)
        return new_params, opt
    exchanged = {}
    for bucket in reversed(spec):
        exchanged.update(_exchange(exchange_bucket, bucket,
                                   bucket.view(grads)))
    exchanged = optimizer.pre_apply(exchanged)
    for bucket in reversed(spec):
        new_p_b, opt = _apply_bucket(optimizer, bucket, new_params,
                                     bucket.view(exchanged), opt, step)
        new_params.update(new_p_b)
    return new_params, opt


def _make_bucket_step(cfg: ArchConfig, sync: SyncConfig, strat, ops,
                      optimizer):
    """Per-bucket non-instant updates during backprop (paper §3: dW_l is
    applied the moment layer l's gradient is produced, in reverse
    production order) — any model family via its ``bucket_spec()`` (the
    CNN's walk is chained to each layer's VJP gradient production, through
    both the XLA and Pallas-kernel paths), any optimizer via per-bucket
    state slicing, and it composes with the superstep scan unchanged.

    ``cfg.micro_batches > 1`` composes via the bucket-granular accumulator:
    per-bucket gradients accumulate across the micro-shards (the shared
    ``_make_grad_fn`` scan — bucket slices of one whole-tree accumulation),
    then every bucket exchanges ONCE per step on its accumulated mean and
    the per-bucket updates walk in the same reverse-production order.  A
    per-bucket update cannot fire mid-accumulation (later micro-shards'
    gradients would not exist yet), so the tape degrades to the
    collect-then-walk schedule — numerics identical to the batched
    micro-batch step bucket-by-bucket."""
    spec = ops.bucket_spec()
    ctx = StepContext(optimizer=optimizer)
    n_micro = max(cfg.micro_batches, 1)
    acc_grad_fn = _make_grad_fn(cfg, ops) if n_micro > 1 else None

    def step(state, batch):
        with jax.named_scope("update"):
            exchange_bucket, finish = strat.bucket_exchange(
                ctx, state["sync"], state["step"])
        if n_micro > 1:
            loss, metrics, grads = acc_grad_fn(state["params"], batch)
            with jax.named_scope("update"):
                new_params, new_opt = _bucket_walk(
                    spec, optimizer, exchange_bucket, state["params"],
                    state["opt"], grads, state["step"])
        elif optimizer.pre_apply is None:
            # true tape: each bucket's exchange + update fires inside the
            # backward walk, the moment that bucket's gradient is produced
            opt_box = [state["opt"]]

            def on_bucket(bucket, p_b, g_b):
                del p_b  # the walk's running params are in new_params
                with jax.named_scope("update"):
                    g_ex = _exchange(exchange_bucket, bucket, g_b)
                    new_p_b, opt_box[0] = _apply_bucket(
                        optimizer, bucket, state["params"], g_ex,
                        opt_box[0], state["step"])
                return new_p_b

            loss, metrics, new_params, grads = ops.loss_and_grads(
                state["params"], batch, tape=on_bucket)
            new_opt = opt_box[0]
        else:
            # globally-coupled optimizer (adamw's whole-tree clip): produce
            # the tape gradients, exchange per bucket, transform once, then
            # walk the per-bucket updates in the same reverse order
            loss, metrics, grads = ops.loss_and_grads(state["params"],
                                                      batch)
            with jax.named_scope("update"):
                new_params, new_opt = _bucket_walk(
                    spec, optimizer, exchange_bucket, state["params"],
                    state["opt"], grads, state["step"])
        with jax.named_scope("update"):
            new_sync = finish(grads)
            new_params, new_sync = strat.boundary(ctx, new_params, new_sync,
                                                  state["step"])
        new_state = {"params": new_params, "opt": new_opt,
                     "sync": new_sync, "step": state["step"] + 1}
        return new_state, {**metrics, "loss": loss}

    return step


def make_superstep(cfg: ArchConfig, sync: SyncConfig, optimizer=None):
    """Returns superstep(state, batches) -> (new_state, metrics).

    ``batches`` is a stacked (K, B, ...) pytree (``pipeline.superstep_at``);
    the K constituent steps run inside ONE compiled ``jax.lax.scan``, so the
    host dispatches (and syncs on metrics) once per K steps instead of once
    per step.  The whole TrainState — params, optimizer moments, the sync
    strategy's buffers (chaos ring / compression residual), and the step
    counter that drives the LR schedule and localsgd boundary — is the scan
    carry, so every registered strategy composes unchanged and the result
    is bit-identical to K individual dispatches (tests/test_superstep.py).
    Metrics come back stacked (K,).

    jit with ``donate_argnums=(0,)``: the TrainState is donated so a
    superstep is update-in-place at the HBM level.
    """
    step = make_train_step(cfg, sync, optimizer)

    def superstep(state, batches):
        return jax.lax.scan(step, state, batches)

    return superstep


def make_worker_train_step(cfg: ArchConfig, sync: SyncConfig,
                           worker: WorkerConfig, optimizer=None):
    """Per-worker step body for shard_map execution over the worker mesh.

    Runs on each worker's local slice of the global batch (B/N examples,
    contiguous in global batch order).  The local slice is processed as
    ``worker.shards_per_worker`` fixed-size micro-shards via ``lax.map``
    (identical per-shard shapes for every worker count), and the strategy's
    collectives thread over ``worker.axis`` through the StepContext
    reducers:

      combine     - the worker-count-invariant gathered shard mean
                    (all_gather + ONE fixed-shape sum over logical_shards)
      local_mean  - mean over this worker's own micro-shards
      local_frac  - this worker's additive term of the global mean
                    (local shard sum / logical_shards)
    """
    ops = get_ops(cfg)
    optimizer = optimizer or make_optimizer(cfg)
    if cfg.micro_batches > 1:
        raise NotImplementedError(
            "cfg.micro_batches is not consulted on the worker-mesh path — "
            "the logical-shard decomposition IS the microbatching here "
            "(per-shard batch = B / logical_shards); raise "
            "WorkerConfig.logical_shards to shrink per-shard activation "
            "memory instead")
    if sync.axis_name != worker.axis:
        sync = dataclasses.replace(sync, axis_name=worker.axis)
    strat = get_strategy(sync)
    N, S, axis = worker.workers, worker.logical_shards, worker.axis
    s_local = worker.shards_per_worker

    def shard_grads(params, batch):
        """(losses, metrics, grads), each stacked (S/N, ...) over this
        worker's micro-shards.  Per-shard shapes are independent of N, so
        per-shard values are bit-identical for every worker count."""
        def one(b):
            (l, m), g = jax.value_and_grad(ops.loss, has_aux=True)(params, b)
            g = jax.tree.map(lambda t: t.astype(jnp.float32), g)
            return l, m, g
        shards = jax.tree.map(
            lambda x: x.reshape((s_local, x.shape[0] // s_local)
                                + x.shape[1:]), batch)
        return jax.lax.map(one, shards)

    # local reductions accumulate in f32 like gathered_shard_mean (identity
    # for the uncompressed f32 path; with per-shard bf16 compression the
    # stacks arrive bf16 and must not sum in bf16)
    delay = sync.collective_delay_ns_per_byte
    spec = ops.bucket_spec()
    ctx = StepContext(
        optimizer=optimizer, grad_fn=shard_grads,
        # blocking delay injection (the synchronous-exchange model) lives
        # here, at the gather; delay == 0 leaves the graph untouched
        combine=_exchange_by_bucket(
            lambda t: gathered_shard_mean(t, axis, N, S,
                                          delay_ns_per_byte=delay), spec),
        local_mean=lambda t: jax.tree.map(
            lambda x: jnp.sum(x.astype(jnp.float32), 0) / s_local, t),
        # sum * (1/S), NOT sum / S: gathered_shard_mean multiplies by the
        # reciprocal, and the hogwild own/remote decomposition must use the
        # same arithmetic so remote_now == 0 exactly when all shards are
        # local (N=1 chaos == bsp for ANY logical_shards, not just pow2)
        local_frac=lambda t: jax.tree.map(
            lambda x: jnp.sum(x.astype(jnp.float32), 0) * (1.0 / S), t),
        explicit_workers=True, axis=axis, n_workers=N)

    if sync.layerwise:
        # interleaved schedule (DESIGN.md §8): fire each bucket's exchange
        # collective the moment that layer's stacked gradient is produced
        # during backprop, via the model's shard tape.  Needs a per-leaf
        # optimizer (no whole-tree pre_apply — adamw's clip must see every
        # exchanged bucket first); otherwise, and for families without a
        # shard tape, fall back to collect-then-walk.  The tape restructures
        # the backward into per-layer map bodies, which XLA:CPU canonicalises
        # differently from the whole-chain body — gradients agree with
        # collect-then-walk only to ~1 ulp, which is why interleave is
        # opt-in and the bit-exactness pins ride the collect schedule.
        interleave = (sync.interleave and ops.shard_bucket_grads is not None
                      and optimizer.pre_apply is None)
        if interleave:
            # the interleaved walk places its own start/gate delay pairs, so
            # its combine must not also blocking-inject
            ctx_i = dataclasses.replace(
                ctx, combine=lambda t: gathered_shard_mean(t, axis, N, S))
            # static per-bucket gather cost: result bytes = logical_shards ×
            # per-shard gradient bytes (bf16 on the compressed wire)
            itemsize = 2 if sync.compress else 4
            abstract = ops.abstract_params()
            bucket_ms = {
                b.name: S * sum(l.size * itemsize for l in
                                jax.tree.leaves(b.view(abstract)))
                * delay * 1e-6 for b in spec}
            inject = delay > 0 and N > 1 and strat.bucket_exchange_gathers

            def bucket_step(state, batch):
                with jax.named_scope("update"):
                    exchange_bucket, finish = strat.bucket_exchange(
                        ctx_i, state["sync"], state["step"])
                shards = jax.tree.map(
                    lambda x: x.reshape((s_local, x.shape[0] // s_local)
                                        + x.shape[1:]), batch)
                exchanged = {}

                def on_bucket(bucket, g_b):
                    with jax.named_scope(f"exchange/{bucket.name}"):
                        g_ex = exchange_bucket(bucket, g_b)
                        # deadline stamped when this bucket's gradient
                        # exists = the collective's issue point,
                        # mid-backward
                        tok = (delay_start(g_b, bucket_ms[bucket.name])
                               if inject else None)
                    exchanged[bucket.name] = (g_ex, tok)
                    return tok

                losses, metrics, grads = ops.shard_bucket_grads(
                    state["params"], shards, on_bucket)
                with jax.named_scope("update"):
                    # gates anchor on the LAST-produced gradient: each
                    # bucket sleeps only what remains of its deadline after
                    # the rest of the backward walk ran — latency hidden
                    # behind compute
                    anchor = grads[spec[0].name]
                    new_params = dict(state["params"])
                    new_opt = state["opt"]
                    for bucket in reversed(spec):
                        g_ex, tok = exchanged[bucket.name]
                        if tok is not None:
                            g_ex = delay_gate(g_ex, tok, anchor)
                        new_p_b, new_opt = _apply_bucket(
                            optimizer, bucket, new_params, g_ex, new_opt,
                            state["step"])
                        new_params.update(new_p_b)
                    new_sync = finish(grads)
                    new_params, new_sync = strat.boundary(
                        ctx_i, new_params, new_sync, state["step"])
                    return strat.finish_step(ctx_i, state, new_params,
                                             new_opt, new_sync, losses,
                                             metrics)

            return bucket_step

        # collect-then-walk: gradients come stacked out of the per-shard
        # lax.map, then every bucket runs its own gathered_shard_mean +
        # update in reverse-production order — finer comm/compute
        # interleave than one stacked whole-tree reduction, same per-leaf
        # arithmetic (bit-exact to the batched update for bsp, any N
        # dividing logical_shards); with delay injection each bucket's
        # gather charge lands synchronously inside the walk (the baseline
        # benchmarks/overlap.py measures the interleaved tape against)
        def bucket_step(state, batch):
            with jax.named_scope("update"):
                exchange_bucket, finish = strat.bucket_exchange(
                    ctx, state["sync"], state["step"])
            losses, metrics, grads = ctx.grad_fn(state["params"], batch)
            with jax.named_scope("update"):
                new_params, new_opt = _bucket_walk(
                    spec, optimizer, exchange_bucket, state["params"],
                    state["opt"], grads, state["step"])
                new_sync = finish(grads)
                new_params, new_sync = strat.boundary(
                    ctx, new_params, new_sync, state["step"])
                return strat.finish_step(ctx, state, new_params, new_opt,
                                         new_sync, losses, metrics)

        return bucket_step

    def step(state, batch):
        return strat.step(ctx, state, batch)

    return step


def init_worker_state(cfg: ArchConfig, key, sync: SyncConfig,
                      worker: WorkerConfig, optimizer=None):
    """TrainState for the worker-mesh route.  Strategies whose workers stay
    provably identical (bsp, chaos τ=0) keep UNSTACKED (mesh-replicated)
    state — byte-for-byte the same checkpoint layout as a single-device
    run, which is what makes those checkpoints worker-count-invariant.
    Strategies whose workers genuinely diverge (localsgd, chaos τ>=1)
    carry a leading (N, ...) worker axis.  Sync-state keys follow the
    strategy's ``worker_sync_layout()``: "worker" leaves get the (N, ...)
    axis, "shard" leaves (the compression residual) a (logical_shards, ...)
    axis — worker-count-invariant like the gradients they correct."""
    from repro.core.chaos import replicate_for_workers

    strat = get_strategy(sync)
    state = init_train_state(cfg, key, sync, optimizer)
    layout = strat.worker_sync_layout()
    sync_state = {
        k: (replicate_for_workers(v, worker.workers)
            if layout.get(k) == "worker"
            else replicate_for_workers(v, worker.logical_shards)
            if layout.get(k) == "shard" else v)
        for k, v in state["sync"].items()}
    if strat.stacked_state:
        state = {k: replicate_for_workers(v, worker.workers)
                 for k, v in state.items() if k != "sync"}
    else:
        state = {k: v for k, v in state.items() if k != "sync"}
    state["sync"] = sync_state
    return state


def resize_worker_state(state, sync: SyncConfig, old_worker: WorkerConfig,
                        new_worker: WorkerConfig):
    """Re-slot a worker-route TrainState across an elastic membership
    change N -> N' at a superstep boundary (DESIGN.md §7), WITHOUT going
    through a checkpoint.

    Strategies with replicated state (bsp, chaos τ=0) pass through
    untouched — the resize is bit-exact because the state never depended on
    the worker count in the first place.  Stacked strategies (localsgd,
    chaos τ>=1) re-slot every (N, ...) leaf — params, optimizer moments,
    the step counter, and the sync state's "worker"-layout keys — via
    ``train/sync.py::reslot_stacked``'s documented shrink/grow rule;
    "shard"-layout sync keys (the compression residual) ride through
    unchanged because ``logical_shards`` is the resize invariant."""
    from repro.train.sync import reslot_stacked

    strat = get_strategy(sync)
    state = dict(state)
    sync_state = state.pop("sync")
    if strat.stacked_state:
        state = {k: jax.tree.map(
                     lambda x: reslot_stacked(x, old_worker.workers,
                                              new_worker.workers), v)
                 for k, v in state.items()}
    state["sync"] = strat.resize_state(sync_state, old_worker, new_worker)
    return state


def make_worker_superstep(cfg: ArchConfig, sync: SyncConfig,
                          worker: WorkerConfig, mesh, optimizer=None):
    """Superstep over the worker mesh: the K-step ``lax.scan`` runs INSIDE
    ``shard_map`` over ``mesh``'s 1-D worker axis, so per-step collectives
    (gradient exchange / boundary averages) stay on-device across all K
    steps and the host still dispatches once per superstep.

    Call with the GLOBAL stacked (K, B, ...) batch; shard_map splits axis 1
    over workers (worker w's slice == ``pipeline.worker_superstep_at(step,
    k, N, w)``).  State specs follow ``init_worker_state``'s layout — the
    strategy's ``shard_view`` (replicated or worker-stacked).  Metrics are
    replicated (K,) vectors.  jit'd with the TrainState donated.
    """
    step = make_worker_train_step(cfg, sync, worker, optimizer)
    strat = get_strategy(sync)
    stacked = strat.stacked_state
    layout = strat.worker_sync_layout()

    def _map_sync(sync_state, fn):
        # "worker" keys squeeze/restack their leading worker axis at the
        # shard_map boundary; "shard" keys (the per-micro-shard compression
        # residual) arrive as this worker's (s_local, ...) slice and pass
        # through — the per-shard stacking IS the in-step layout
        return {k: (jax.tree.map(fn, v) if layout.get(k) == "worker" else v)
                for k, v in sync_state.items()}

    def superstep(state, batches):
        state = dict(state)
        sync_state = state.pop("sync")
        if stacked:
            state = jax.tree.map(lambda x: x[0], state)
        state["sync"] = _map_sync(sync_state, lambda x: x[0])
        state, metrics = jax.lax.scan(step, state, batches)
        state = dict(state)
        sync_state = state.pop("sync")
        if stacked:
            state = jax.tree.map(lambda x: x[None], state)
        state["sync"] = _map_sync(sync_state, lambda x: x[None])
        return state, metrics

    base = strat.shard_view(worker)
    sync_spec = {k: (P() if v == "replicated" else P(worker.axis))
                 for k, v in layout.items()}
    state_spec = {"params": base, "opt": base, "step": base,
                  "sync": sync_spec}
    fn = jax.shard_map(superstep, mesh=mesh,
                       in_specs=(state_spec, P(None, worker.axis)),
                       out_specs=(state_spec, P()),
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def make_serve_step(cfg: ArchConfig):
    ops = get_ops(cfg)

    def serve_step(params, cache, tokens, cache_len):
        logits, new_cache = ops.decode(params, cache, tokens, cache_len)
        next_tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), new_cache
    return serve_step
