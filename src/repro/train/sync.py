"""Pluggable synchronization-strategy engine (DESIGN.md §5, §6).

Every gradient-synchronization mode — how workers' gradients are combined,
when parameter updates happen relative to backprop, what extra state rides
the superstep scan carry, and how that state is laid out over the worker
mesh — is one ``SyncStrategy`` subclass registered here by name.  The step
builders in ``train/step.py`` and the driver in ``launch/train.py`` are
strategy-agnostic: they build a ``StepContext`` describing the execution
path (single-instance pjit vs explicit worker mesh) and delegate the whole
step body to the strategy.  There are NO per-mode branches outside this
module.

Protocol (one strategy instance per ``SyncConfig``):

``init_state(params)``      sync buffers carried in ``TrainState["sync"]``
``state_specs(pspecs)``     logical PartitionSpecs matching ``init_state``
``stacked_state``           worker-mesh layout: ``False`` = workers provably
                            identical, state mesh-replicated (worker-count-
                            invariant checkpoints); ``True`` = per-worker
                            state with a leading ``(N, ...)`` axis
``worker_sync_layout()``    per-top-level-sync-key worker-mesh layout:
                            ``"worker"`` (leading (N, ...) axis),
                            ``"shard"`` (leading (logical_shards, ...) axis
                            — worker-count-invariant; the compression
                            residual), or ``"replicated"``
``shard_view(worker)``      the shard_map PartitionSpec implied by the above
``checkpoint_layout()``     human-readable layout contract for tooling
``resize_state(sync_state, old_worker, new_worker)``  re-slot the sync
                            state across an elastic membership change
                            N -> N' at a superstep boundary (DESIGN.md
                            §7): replicated and shard-stacked keys pass
                            through unchanged (``logical_shards`` is the
                            resize invariant), worker-stacked keys are
                            re-slotted by ``reslot_stacked``'s documented
                            shrink/grow rule
``combine_grads`` is supplied BY the execution path via ``StepContext``
                            (identity under implicit SPMD, the fixed-shape
                            gathered shard mean on the worker mesh)
``step(ctx, state, batch)`` the full train-step body (apply_update included)
``boundary(ctx, params, sync_state, step) -> (params, sync_state)``
                            end-of-step parameter hook (localsgd's K-step
                            average / τ-ring stale correction; identity
                            elsewhere)
``finish_step(ctx, state, new_params, new_opt, new_sync, losses, metrics)``
                            packs the step result: metric reduction
                            (``workers_identical`` strategies reduce with
                            the same fixed-shape mean as the gradients so
                            logged losses are worker-count-invariant;
                            diverging strategies local-mean + pmean) and
                            TrainState assembly.  Step builders that
                            compose their own step bodies (the worker-mesh
                            layerwise bucket walk) end with this hook.
``bucket_exchange(ctx, sync_state, step)``  the per-bucket exchange hook
                            for the layerwise (non-instant per-bucket
                            updates during backprop) path: returns
                            ``(exchange_bucket, finish)`` where
                            ``exchange_bucket(bucket, grads_b)`` — called
                            in reverse-production order the moment bucket
                            b's gradient exists — returns the gradient
                            bucket the optimizer should apply, and
                            ``finish(grads)`` returns the new sync state.
                            Compression slices its error-feedback residual
                            per bucket; chaos reads/writes its ring per
                            bucket; on the worker mesh every bucket runs
                            its OWN ``gathered_shard_mean`` (finer
                            comm/compute overlap than one stacked
                            reduction).

Registered strategies:

``bsp``       paper strategy B: combined fresh gradients gate every update.
``chaos``     staleness-τ controlled Hogwild (``SyncConfig.staleness``):
              * τ=0 resolves to THE ``bsp`` strategy object itself —
                bit-exactness to bsp is by construction, not by test luck;
              * worker mesh, τ>=1: each worker applies its own gradient
                contribution instantly and peers' contributions τ steps
                late (ring buffer of remote terms; workers genuinely
                diverge — the paper's arbitrary-order weight updates);
              * pjit path, τ>=1: the whole globally-reduced gradient is
                applied τ steps late (the reduction gates only the step
                output, overlapping with compute); τ=1 reproduces the
                historical staleness-1 exchange unchanged.
``localsgd``  paper strategy-C flavour: purely local updates, parameters
              averaged over workers every ``local_steps`` steps.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.chaos import (SyncConfig, compress_grads, delay_gate,
                              delay_start, localsgd_average, tree_bytes,
                              zeros_like_f32)

STRATEGIES: dict[str, type] = {}


def register(cls):
    STRATEGIES[cls.name] = cls
    return cls


def sync_modes() -> list[str]:
    """Registered mode names (drives the CLI choices in launch/train.py)."""
    return sorted(STRATEGIES)


def get_strategy(sync: SyncConfig) -> "SyncStrategy":
    try:
        cls = STRATEGIES[sync.mode]
    except KeyError:
        raise ValueError(
            f"unknown sync mode {sync.mode!r}; registered strategies: "
            f"{', '.join(sync_modes())}") from None
    return cls(sync).resolve()


def _identity(tree):
    return tree


# ---------------------------------------------------------------------------
# elastic re-slot rule (DESIGN.md §7): how a worker-stacked (N, ...) leaf
# maps onto N' slots when the worker mesh resizes at a superstep boundary.
#   N' == N                pass through (bit-exact)
#   N  == g·N' (shrink)    new worker j <- MEAN of old workers
#                          [j·g, (j+1)·g)  — the same operation localsgd's
#                          boundary applies anyway, and it collapses chaos'
#                          O(lr·τ) transient divergence onto the group mean
#   N' == g·N  (grow)      new workers [j·g, (j+1)·g) <- COPY of old worker
#                          j (each old worker seeds g fresh slots)
#   otherwise              every new worker <- the global mean over all old
#                          workers (the fully collapsed fallback)
# Means accumulate in f32 and cast back to the leaf dtype, mirroring
# ``gathered_shard_mean``'s convention.  Replicated state never passes
# through here (bsp / chaos τ=0 resizes are bit-exact by construction);
# for stacked strategies the result is defined-but-different — pinned by
# tests/test_elastic_resize.py.
# ---------------------------------------------------------------------------
def reslot_stacked(x, n_old: int, n_new: int):
    x = jnp.asarray(x)
    if x.ndim < 1 or x.shape[0] != n_old:
        raise ValueError(
            f"reslot_stacked expects a leading ({n_old}, ...) worker axis, "
            f"got shape {tuple(x.shape)}")
    if n_new == n_old:
        return x
    if n_old % n_new == 0:
        g = n_old // n_new
        grouped = x.reshape((n_new, g) + x.shape[1:])
        return jnp.mean(grouped.astype(jnp.float32), axis=1).astype(x.dtype)
    if n_new % n_old == 0:
        return jnp.repeat(x, n_new // n_old, axis=0)
    m = jnp.mean(x.astype(jnp.float32), axis=0).astype(x.dtype)
    return jnp.broadcast_to(m[None], (n_new,) + x.shape[1:])


@dataclasses.dataclass(frozen=True)
class StepContext:
    """Execution-path plumbing handed to a strategy.

    The SAME strategy classes serve both the single-instance pjit path and
    the explicit worker-mesh path; what differs is how gradients are
    produced and reduced, and that difference lives here:

    ``grad_fn(params, batch) -> (losses, metrics, grads)`` — pjit path:
      scalar loss + one gradient tree; worker path: ``(s_local, ...)``
      stacks of per-micro-shard losses/metrics/gradients.
    ``combine``     local grads -> the GLOBAL mean over all shards/workers
                    (identity under implicit SPMD; the worker-count-
                    invariant gathered shard mean on the worker mesh).
    ``local_mean``  local grads -> the mean over THIS worker's data only.
    ``local_frac``  local grads -> this worker's additive term of the
                    global mean (local shard sum / total shard count).
    """
    optimizer: object
    grad_fn: Optional[Callable] = None
    combine: Callable = _identity
    local_mean: Callable = _identity
    local_frac: Callable = _identity
    explicit_workers: bool = False
    axis: Optional[str] = None
    n_workers: int = 1


# ---------------------------------------------------------------------------
# staleness ring buffer: τ params-shaped trees {"h0".."h{τ-1}"}; the slot
# for step t holds the exchange produced at t, read back at t + τ (slot
# index t % τ).  Slots are whole params-shaped trees selected with
# whole-leaf jnp.where — NOT one (τ, ...)-stacked leaf with dynamic
# gather/scatter, which changes XLA:CPU's fusion of the surrounding
# gradient computation between scan trip counts and breaks the
# K-grouping bit-exactness contract by 1 ulp (tests/test_sync_strategies
# pins scan-vs-individual bit-exactness for τ ∈ {2, 4}).  τ=1 degenerates
# to exactly the historical single prev-grad buffer.  ``dtype`` overrides
# the slot dtype (``SyncConfig.ring_dtype``: a bf16 ring halves the
# τ × params ring memory; writes quantise, reads upcast).
# ---------------------------------------------------------------------------
def init_ring(params, tau: int, dtype=None) -> dict:
    return {f"h{i}": jax.tree.map(
        lambda p: jnp.zeros(p.shape, dtype or p.dtype), params)
        for i in range(tau)}


def ring_read(hist, step, tau: int):
    idx = step % tau
    out = hist["h0"]
    for i in range(1, tau):
        out = jax.tree.map(lambda a, b, i=i: jnp.where(idx == i, b, a),
                           out, hist[f"h{i}"])
    return out


def ring_write(hist, step, tau: int, val):
    if tau == 1:  # the single slot is always overwritten — no select, so
        # τ=1 compiles to exactly the historical prev-grad graph
        return {"h0": jax.tree.map(lambda h, v: v.astype(h.dtype),
                                   hist["h0"], val)}
    idx = step % tau
    return {f"h{i}": jax.tree.map(
        lambda h, v, i=i: jnp.where(idx == i, v.astype(h.dtype), h),
        hist[f"h{i}"], val) for i in range(tau)}


@register
class BspStrategy:
    """Bulk-synchronous (paper strategy B): the combined fresh gradient is
    on the critical path of every update; workers stay provably identical,
    so worker-mesh state is replicated and checkpoints are worker-count-
    invariant."""

    name = "bsp"
    stacked_state = False     # worker mesh: state replicated
    workers_identical = True  # metrics reduce with the same fixed-shape mean
    #: whether the per-bucket exchange runs a mesh collective (drives the
    #: interleaved schedule's per-bucket delay injection — localsgd's
    #: exchange is purely local, so it must not be charged gather latency)
    bucket_exchange_gathers = True

    def __init__(self, sync: SyncConfig):
        self.sync = sync

    def resolve(self) -> "SyncStrategy":
        return self

    # -- state ---------------------------------------------------------
    def init_state(self, params) -> dict:
        if self.sync.compress:
            return {"residual": zeros_like_f32(params)}
        return {}

    def state_specs(self, pspecs) -> dict:
        if self.sync.compress:
            return {"residual": pspecs}
        return {}

    def worker_sync_layout(self) -> dict:
        """Worker-mesh layout per top-level sync-state key.  The
        compression residual is SHARD-stacked (leading (logical_shards, ...)
        axis, each worker holding its contiguous slice): quantisation error
        is carried per micro-shard, so the whole compressed exchange — and
        its checkpointed residual — is bit-identical for every worker count
        dividing logical_shards, exactly like the gradients themselves."""
        return {"residual": "shard"} if self.sync.compress else {}

    def shard_view(self, worker) -> P:
        return P(worker.axis) if self.stacked_state else P()

    def checkpoint_layout(self) -> str:
        return ("worker-stacked (leading (N, ...) axis; checkpoints pin "
                "the worker count)" if self.stacked_state else
                "replicated (worker-count-invariant checkpoints)")

    def resize_state(self, sync_state, old_worker, new_worker) -> dict:
        """Re-slot this strategy's sync state across an elastic membership
        change N -> N' (DESIGN.md §7).  The rule is driven entirely by
        ``worker_sync_layout()``: "worker" keys (chaos' staleness ring,
        localsgd has none beyond params/opt) re-slot their leading (N, ...)
        axis via ``reslot_stacked``; "shard" keys (the compression
        residual, stacked over ``logical_shards``) and replicated keys pass
        through unchanged — ``logical_shards`` is the resize invariant, so
        shard-stacked state stays bit-exact across any N -> N'."""
        if new_worker.logical_shards != old_worker.logical_shards:
            raise ValueError(
                "elastic resize must keep logical_shards fixed (it is the "
                f"bit-exactness anchor), got {old_worker.logical_shards} -> "
                f"{new_worker.logical_shards}")
        layout = self.worker_sync_layout()
        return {k: (jax.tree.map(
                        lambda x: reslot_stacked(x, old_worker.workers,
                                                 new_worker.workers), v)
                    if layout.get(k) == "worker" else v)
                for k, v in sync_state.items()}

    # -- shared pieces --------------------------------------------------
    def _maybe_compress(self, ctx: StepContext, grads, sync_state):
        """bf16-quantise the exchanged gradients with error feedback.  On
        the worker mesh the quantised values stay bf16 so the all_gather
        moves half the bytes (``gathered_shard_mean`` upcasts before its
        fixed-shape sum); on the pjit path they are upcast immediately —
        the collective is implicit there, and downstream arithmetic
        (optimizer pre-transforms) historically ran in f32."""
        new_sync = dict(sync_state)
        if self.sync.compress:
            grads, new_sync["residual"] = compress_grads(
                grads, sync_state["residual"])
            if not ctx.explicit_workers:
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return grads, new_sync

    def finish_step(self, ctx: StepContext, state, new_params, new_opt,
                new_sync, losses, metrics):
        packed = {**metrics, "loss": losses}
        if self.workers_identical:
            # same fixed-shape reduction as the gradients: the logged loss
            # is bit-identical across worker counts too
            packed = ctx.combine(packed)
        else:
            packed = ctx.local_mean(packed)
            if ctx.axis is not None and ctx.n_workers > 1:
                packed = jax.lax.pmean(packed, ctx.axis)
        new_state = {"params": new_params, "opt": new_opt, "sync": new_sync,
                     "step": state["step"] + 1}
        return new_state, packed

    def _reduce(self, ctx: StepContext, grads):
        return ctx.combine(grads)

    def _ring_dtype(self):
        return (jnp.dtype(self.sync.ring_dtype)
                if self.sync.ring_dtype else None)

    def boundary(self, ctx: StepContext, params, sync_state, step):
        """K-boundary hook, after the optimizer applied this step's update.
        Returns ``(params, sync_state)`` — strategies whose boundary carries
        state (localsgd's τ-ring of stale corrections) thread it here."""
        return params, sync_state

    # -- the step body ---------------------------------------------------
    def step(self, ctx: StepContext, state, batch):
        losses, metrics, grads = ctx.grad_fn(state["params"], batch)
        with jax.named_scope("update"):
            grads, new_sync = self._maybe_compress(ctx, grads,
                                                   state["sync"])
            g = self._reduce(ctx, grads)
            new_params, new_opt = ctx.optimizer.apply(
                state["params"], g, state["opt"], state["step"])
            new_params, new_sync = self.boundary(ctx, new_params, new_sync,
                                                 state["step"])
            return self.finish_step(ctx, state, new_params, new_opt,
                                    new_sync, losses, metrics)

    # -- per-bucket exchange (the layerwise path, DESIGN.md §6) ----------
    def bucket_exchange(self, ctx: StepContext, sync_state, step):
        """Returns ``(exchange_bucket, finish)``: ``exchange_bucket(bucket,
        grads_b)`` is called in reverse-production order the moment bucket
        b's gradient exists and returns the exchanged gradient bucket the
        optimizer should apply — each bucket runs its own reduction, so on
        the worker mesh the per-bucket ``gathered_shard_mean`` collectives
        interleave with the per-bucket updates instead of gating on one
        stacked whole-tree reduction.  ``finish(grads)`` (full fresh-
        gradient tree) returns the new sync state — compression residual
        slices accumulate per bucket."""
        residual_out: dict = {}

        def exchange_bucket(bucket, g_b):
            g_b = self._compress_bucket(ctx, bucket, g_b, sync_state,
                                        residual_out)
            return self._reduce(ctx, g_b)

        def finish(grads):
            del grads
            return self._merge_residual(sync_state, residual_out)

        return exchange_bucket, finish

    def _compress_bucket(self, ctx: StepContext, bucket, g_b, sync_state,
                         residual_out):
        if not self.sync.compress:
            return g_b
        res_b = bucket.view(sync_state["residual"])
        g_b, new_res = compress_grads(g_b, res_b)
        residual_out.update(new_res)
        if not ctx.explicit_workers:
            g_b = jax.tree.map(lambda g: g.astype(jnp.float32), g_b)
        return g_b

    def _merge_residual(self, sync_state, residual_out):
        new_sync = dict(sync_state)
        if residual_out:
            new_sync["residual"] = {**sync_state["residual"], **residual_out}
        return new_sync


@register
class LocalSGDStrategy(BspStrategy):
    """Paper strategy-C flavour: purely local gradients; parameters averaged
    over the worker axis every ``local_steps`` steps (workers diverge
    between boundaries, so worker-mesh state is per-worker stacked).

    τ-ring boundary (DESIGN.md §8): here ``SyncConfig.staleness`` counts
    *boundaries*, not steps.  τ=0 is the blocking K-boundary average —
    the historical ``localsgd_average`` code path verbatim, so it is
    bit-exact to the pre-ring implementation by construction (no ring
    state exists at τ=0; checkpoints are unchanged).  τ>=1 replaces the
    blocking pmean with a τ-deep ring of stale *corrections*: at boundary
    m each replica computes ``pmean(params) - params``, writes it into
    ring slot m % τ, and applies the correction written at boundary m-τ
    (zero for the first τ boundaries).  The pmean therefore gates only
    the ring write — a step OUTPUT — never the boundary's own parameter
    update, so the collective overlaps with the next K·τ local steps.
    Corrections sum to zero across workers at write time, so the worker
    MEAN evolves exactly as if no averaging happened — τ-staleness only
    perturbs each replica's pull toward that shared mean trajectory.

    With delay injection (``collective_delay_ns_per_byte`` > 0) a
    per-slot deadline token rides the sync state: the all-reduce's
    2×param-bytes charge is stamped at boundary m and slept off when the
    slot is read back at boundary m+τ — after K·τ local steps of compute
    the remainder is ~0, which is the measurable overlap win
    (benchmarks/overlap.py) vs τ=0's full synchronous charge."""

    name = "localsgd"
    stacked_state = True
    workers_identical = False
    bucket_exchange_gathers = False  # per-bucket reduce is purely local

    def _tau(self) -> int:
        return self.sync.staleness

    def _has_tokens(self) -> bool:
        return (self._tau() >= 1
                and self.sync.collective_delay_ns_per_byte > 0)

    def init_state(self, params) -> dict:
        st = super().init_state(params)
        if self._tau() >= 1:
            st["lsring"] = init_ring(params, self._tau(), self._ring_dtype())
            if self._has_tokens():
                # zero deadlines are already in the past -> first reads
                # sleep nothing (matches the zero corrections they gate)
                st["lstok"] = jnp.zeros((self._tau(),), jnp.float32)
        return st

    def state_specs(self, pspecs) -> dict:
        st = super().state_specs(pspecs)
        if self._tau() >= 1:
            st["lsring"] = {f"h{i}": pspecs for i in range(self._tau())}
            if self._has_tokens():
                st["lstok"] = P()
        return st

    def worker_sync_layout(self) -> dict:
        layout = super().worker_sync_layout()
        if self._tau() >= 1:
            layout["lsring"] = "worker"
            if self._has_tokens():
                layout["lstok"] = "worker"
        return layout

    def _reduce(self, ctx: StepContext, grads):
        return ctx.local_mean(grads)

    def boundary(self, ctx: StepContext, params, sync_state, step):
        sync = self.sync
        tau = self._tau()
        delay = sync.collective_delay_ns_per_byte
        if tau == 0:
            return (localsgd_average(sync, params, step,
                                     delay_ns_per_byte=delay), sync_state)
        do_avg = ((step + 1) % sync.local_steps) == 0
        # 0-based boundary index; only meaningful when do_avg (clamped so
        # the ring arithmetic stays valid off-boundary, where every write
        # and apply is select-disabled anyway)
        m = jnp.maximum((step + 1) // sync.local_steps - 1, 0)
        ring = sync_state["lsring"]
        new_sync = dict(sync_state)
        gated = "lstok" in sync_state and sync.axis_name is not None
        stale = ring_read(ring, m, tau)
        if gated:
            # sleep whatever remains of the deadline stamped τ boundaries
            # ago — K·τ local steps of compute have already eaten into it
            stale = delay_gate(stale, sync_state["lstok"][m % tau], params)
        new_params = jax.tree.map(
            lambda p, s: jnp.where(do_avg, p + s.astype(p.dtype), p),
            params, stale)
        if sync.axis_name is not None:
            avg = jax.tree.map(
                lambda p: jax.lax.pmean(p, sync.axis_name), new_params)
        else:
            avg = new_params  # single instance: correction is exactly zero
        corr = jax.tree.map(lambda a, p: a - p, avg, new_params)
        written = ring_write(ring, m, tau, corr)
        new_sync["lsring"] = jax.tree.map(
            lambda w, h: jnp.where(do_avg, w, h), written, ring)
        if gated:
            ms = 2.0 * tree_bytes(params) * delay * 1e-6  # all-reduce: 2×
            tok = delay_start(corr, jnp.where(do_avg, ms, 0.0))
            new_sync["lstok"] = sync_state["lstok"].at[m % tau].set(
                jnp.where(do_avg, tok, sync_state["lstok"][m % tau]))
        return new_params, new_sync


@register
class ChaosStrategy(BspStrategy):
    """Staleness-τ controlled Hogwild (the paper's CHAOS proper).

    τ = ``SyncConfig.staleness``.  τ=0 never reaches this class —
    ``resolve()`` hands back a ``BspStrategy``, so chaos(τ=0) IS bsp (state
    layout, checkpoints, and arithmetic identical by construction).

    τ>=1, worker mesh (``ctx.explicit_workers``): each worker computes
    gradients at its OWN current weights and applies, in the same step, its
    own additive term of the global mean plus the τ-step-stale remote terms
    from the ring buffer — local updates are instant, peers' updates are
    non-instant and fold in without a barrier, in arbitrary order across
    workers.  Workers genuinely diverge (transiently, by O(lr·τ) per the
    delayed-SGD analysis), so state is worker-stacked.

    τ>=1, pjit path: one logical instance — "peers" are the implicit
    cross-replica reduction, so the whole combined gradient is applied τ
    steps late and the reduction gates only the step output (overlappable).
    τ=1 is the historical staleness-1 delayed exchange, bit-for-bit.
    """

    name = "chaos"
    stacked_state = True       # τ>=1 worker mesh: workers diverge
    workers_identical = False

    def resolve(self) -> "SyncStrategy":
        if self.sync.staleness == 0:
            return BspStrategy(self.sync)
        return self

    def init_state(self, params) -> dict:
        # ring slots default to param dtype: gradients are produced in
        # param dtype anyway and a τ-deep f32 copy of a large model would
        # be the dominant sync-state cost; ``ring_dtype="bfloat16"``
        # (reusing the compression cast) halves even that
        st = {"hist": init_ring(params, self.sync.staleness,
                                self._ring_dtype())}
        if self.sync.compress:
            st["residual"] = zeros_like_f32(params)
        return st

    def state_specs(self, pspecs) -> dict:
        # each ring slot is params-shaped, so it shards exactly like params
        st = {"hist": {f"h{i}": pspecs
                       for i in range(self.sync.staleness)}}
        if self.sync.compress:
            st["residual"] = pspecs
        return st

    def worker_sync_layout(self) -> dict:
        layout = {"hist": "worker"}
        if self.sync.compress:
            layout["residual"] = "shard"
        return layout

    def step(self, ctx: StepContext, state, batch):
        if ctx.explicit_workers:
            return self._hogwild_step(ctx, state, batch)
        return self._delayed_step(ctx, state, batch)

    def _delayed_step(self, ctx: StepContext, state, batch):
        """pjit path: 1) update with the τ-step-stale globally-reduced
        gradient (available immediately, no blocking collective); 2) fresh
        gradients at the new params -> ring slot t, read back at t+τ; their
        reduction gates only the step OUTPUT (overlappable)."""
        tau = self.sync.staleness
        hist = state["sync"]["hist"]
        with jax.named_scope("update"):
            stale = ring_read(hist, state["step"], tau)
            new_params, new_opt = ctx.optimizer.apply(
                state["params"], stale, state["opt"], state["step"])
        losses, metrics, grads = ctx.grad_fn(new_params, batch)
        with jax.named_scope("update"):
            grads, new_sync = self._maybe_compress(ctx, grads,
                                                   state["sync"])
            new_sync["hist"] = ring_write(hist, state["step"], tau,
                                          ctx.combine(grads))
            return self.finish_step(ctx, state, new_params, new_opt,
                                    new_sync, losses, metrics)

    def _hogwild_step(self, ctx: StepContext, state, batch):
        """Worker mesh: own term instant + remote terms τ steps stale.
        With compression the per-shard quantised gradients feed BOTH the
        instant own term and the gathered exchange, so the error-feedback
        residual stays worker-count-invariant (shard-stacked)."""
        tau = self.sync.staleness
        hist = state["sync"]["hist"]
        losses, metrics, grads = ctx.grad_fn(state["params"], batch)
        with jax.named_scope("update"):
            grads, new_sync = self._maybe_compress(ctx, grads,
                                                   state["sync"])
            own = ctx.local_frac(grads)
            stale_remote = ring_read(hist, state["step"], tau)
            g = jax.tree.map(lambda o, s: o + s.astype(jnp.float32),
                             own, stale_remote)
            new_params, new_opt = ctx.optimizer.apply(
                state["params"], g, state["opt"], state["step"])
            # this step's remote term: the all_gather'd global mean minus
            # the own term — it gates only the ring write (the step
            # output), never this step's update
            remote_now = jax.tree.map(lambda a, o: a - o,
                                      ctx.combine(grads), own)
            new_sync["hist"] = ring_write(hist, state["step"], tau,
                                          remote_now)
            return self.finish_step(ctx, state, new_params, new_opt,
                                    new_sync, losses, metrics)

    def bucket_exchange(self, ctx: StepContext, sync_state, step):
        """Layerwise chaos (paper §3 order): the forward pass runs at the
        pre-update weights; during backprop each bucket's update applies,
        the moment that bucket's fresh gradient exists, the τ-step-stale
        exchange — plus, on the worker mesh, the worker's own instant term
        (the hogwild decomposition, per bucket) — and the fresh exchange
        terms enter the ring for step t+τ bucket by bucket.  (The
        non-layerwise pjit chaos instead evaluates gradients at the
        post-update weights — the overlap-friendly SPMD ordering; both are
        staleness-τ members of the same family, DESIGN.md §5.)"""
        tau = self.sync.staleness
        stale = ring_read(sync_state["hist"], step, tau)
        residual_out: dict = {}
        fresh: dict = {}

        def exchange_bucket(bucket, g_b):
            g_b = self._compress_bucket(ctx, bucket, g_b, sync_state,
                                        residual_out)
            stale_b = bucket.view(stale)
            if ctx.explicit_workers:
                own = ctx.local_frac(g_b)
                fresh.update(jax.tree.map(
                    lambda a, o: a - o, ctx.combine(g_b), own))
                return jax.tree.map(
                    lambda o, s: o + s.astype(jnp.float32), own, stale_b)
            fresh.update(ctx.combine(g_b))
            return stale_b

        def finish(grads):
            del grads
            new_sync = self._merge_residual(sync_state, residual_out)
            new_sync["hist"] = ring_write(sync_state["hist"], step, tau,
                                          fresh)
            return new_sync

        return exchange_bucket, finish


SyncStrategy = BspStrategy  # protocol root: every strategy subclasses it
