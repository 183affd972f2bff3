"""CHAOS gradient-synchronization strategies (the paper's core contribution,
adapted to SPMD — see DESIGN.md §2 for the Xeon-Phi -> TPU mapping).

Three modes, all usable by any architecture in the zoo:

``bsp``     Bulk-synchronous SGD (paper strategy B, per-minibatch): the
            gradient all-reduce sits on the critical path of every step.

``chaos``   Controlled-Hogwild: **staleness-τ exchange** (``SyncConfig.
            staleness``; semantics in ``train/sync.py``, DESIGN.md §5).
            On the worker-mesh path each worker applies its OWN gradient
            contribution instantly every step and folds peers' contributions
            in τ steps late (a τ-deep ring buffer) — the paper's "non-instant
            updates of weight parameters without significant delay" +
            "implicit synchronization in arbitrary order".  On the pjit path
            (one logical instance; peers are the implicit cross-replica
            reduction) the whole exchange is delayed τ steps,
                w_{t+1} = w_t - lr * mean_i g_i(w_{t-τ}-trajectory)
            so the reduction gates only the step *output* and XLA's
            latency-hiding scheduler overlaps it with backprop compute.
            τ=0 degenerates exactly to ``bsp`` (same strategy object).

``localsgd``  Paper strategy-C flavour: per-replica instances train on their
            own weights for K steps, then parameters are averaged.  This
            preserves CHAOS's "local updates are instant" property exactly
            (each worker trains on its freshest local weights) at the price
            of K-step weight divergence.  Implemented with an explicit
            replica axis via shard_map (replicas must fit per-device); the
            pjit train-step path runs the K-boundary average through
            ``localsgd_average`` (identity under plain jit, pmean over
            ``SyncConfig.axis_name`` under shard_map), keyed off the
            scan-carried step counter, so the mode composes with the
            superstep scan (DESIGN.md §3).

All modes keep the *semantics deterministic* — unlike racy shared-memory
Hogwild, the same run reproduces bit-exactly, which is how we check the
paper's Result 4 (accuracy parity) rigorously.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    mode: str = "bsp"            # any name in train/sync.py's registry
    local_steps: int = 8         # K for localsgd
    compress: bool = False       # bf16 gradient exchange w/ error feedback
    #: named mesh axis for the pjit-path localsgd parameter average; None
    #: (plain jit / single replica) makes the average an identity, but the
    #: K-step counter carry and the where-select still execute, so the
    #: superstep scan carry is exercised identically on 1 or N replicas.
    axis_name: Optional[str] = None
    #: chaos staleness τ, in steps: peers' gradient contributions fold into
    #: the update up to τ steps late (a τ-deep ring buffer in the scan
    #: carry).  τ=0 degenerates EXACTLY to bsp — the registry resolves
    #: chaos(τ=0) to the bsp strategy object, so bit-exactness is by
    #: construction (train/sync.py).  τ=1 on the pjit path reproduces the
    #: historical staleness-1 delayed exchange unchanged.
    staleness: int = 1
    #: per-bucket non-instant updates during backprop (the paper's §3 rule:
    #: apply dW_l as soon as layer l's gradient is produced, in reverse
    #: production order inside the step) — any model family via its
    #: ``bucket_spec()`` (CNN gets the true per-layer VJP tape), any
    #: optimizer via per-bucket state slicing, both execution paths
    #: (DESIGN.md §6).
    layerwise: bool = False
    #: dtype of the chaos(τ>=1) staleness-ring slots; ``None`` = param
    #: dtype.  ``"bfloat16"`` reuses the compression cast to halve the
    #: τ × params ring memory (exchange values are quantised on write and
    #: upcast to float32 on apply — the error is O(1 ulp bf16) per applied
    #: exchange, NOT accumulated: each slot is overwritten, not re-added).
    ring_dtype: Optional[str] = None
    #: overlap harness (DESIGN.md §8): injected per-byte latency, in
    #: nanoseconds/byte, charged to every *explicit* collective on the
    #: worker mesh (the ``all_gather`` in ``gathered_shard_mean``, the
    #: ``pmean`` in ``localsgd_average`` / the τ-ring boundary).  0.0 (the
    #: default) inserts NOTHING into the compiled graph, so every
    #: bit-exactness pin is untouched.  >0 models an interconnect of
    #: bandwidth 1/delay via deadline-sampling callbacks: the deadline is
    #: stamped when the collective's operand is ready and a gate sleeps
    #: only the *remainder* at the point the result is consumed — so on
    #: single-core CI, latency hidden behind compute shows up as a shorter
    #: residual sleep, independent of XLA thunk concurrency.
    collective_delay_ns_per_byte: float = 0.0
    #: layerwise worker-mesh schedule: fire each bucket's exchange the
    #: moment that layer's gradient is produced during backprop (the
    #: interleaved shard tape, DESIGN.md §8) instead of collecting the full
    #: stacked gradient tree first and then walking buckets.  Off by
    #: default: restructuring the backward into per-layer ``lax.map``
    #: bodies changes which canonical form XLA:CPU picks for each dw
    #: conv/matmul, so the tape's gradients agree with the batched path
    #: only to ~1 ulp (losses stay bit-equal) — the default keeps the
    #: collect-then-walk schedule that IS bit-exact to batched (the
    #: layerwise pins).  The interleaved schedule carries its own pins
    #: (run-to-run determinism, worker-count invariance, allclose vs
    #: collect) and is what ``benchmarks/overlap.py`` measures.  Ignored
    #: (falls back to collect-then-walk) when the model has no shard tape
    #: or the optimizer needs a whole-tree ``pre_apply`` (adamw's
    #: global-norm clip).
    interleave: bool = False

    def __post_init__(self):
        if self.staleness < 0:
            raise ValueError(
                f"staleness must be >= 0, got {self.staleness}")
        if self.collective_delay_ns_per_byte < 0:
            raise ValueError(
                "collective_delay_ns_per_byte must be >= 0, got "
                f"{self.collective_delay_ns_per_byte}")
        if self.ring_dtype is not None:
            jnp.dtype(self.ring_dtype)  # fail fast on an unknown dtype name


def zeros_like_f32(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


# ---------------------------------------------------------------------------
# Collective-latency injection (overlap harness, DESIGN.md §8).
#
# Forced host devices share one CPU core, so a busy-loop "slow collective"
# cannot show an overlap win.  Instead each injected collective is a
# *deadline pair*: a ``start`` callback samples ``now + bytes*delay`` when
# the collective's operand is ready (= issue time), and a ``gate`` callback
# at the consumer sleeps only the remainder.  Compute executed between issue
# and consume eats into the deadline, so hidden latency is measured by wall
# clock rather than by thunk concurrency.  Both callbacks return values that
# are folded into live data (a where-select tie and an add-exact-zero), so
# XLA cannot dead-code-eliminate or reorder them past their anchors; neither
# changes any value, and with delay == 0 none of this is ever inserted.
# ---------------------------------------------------------------------------
_EPOCH = time.monotonic()


def _now_ms() -> np.float32:
    return np.float32((time.monotonic() - _EPOCH) * 1e3)


def _start_cb(_anchor, delay_ms):
    return np.float32(float(_now_ms()) + float(delay_ms))


def _gate_cb(deadline, _anchor):
    rem = (float(deadline) - float(_now_ms())) * 1e-3
    if rem > 0:
        time.sleep(rem)
    return np.float32(0.0)


def _first_scalar(tree):
    return jnp.ravel(jax.tree.leaves(tree)[0])[0].astype(jnp.float32)


def tree_bytes(tree) -> int:
    """Static byte count of a (traced or concrete) pytree."""
    return sum(l.size * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def delay_start(anchor_tree, delay_ms):
    """Sample the deadline ``now + delay_ms`` (ms, f32 token) the moment the
    first leaf of ``anchor_tree`` is available.  ``delay_ms`` may be traced
    (e.g. scaled to zero off a localsgd boundary)."""
    return jax.pure_callback(
        _start_cb, jax.ShapeDtypeStruct((), np.float32),
        _first_scalar(anchor_tree), jnp.asarray(delay_ms, jnp.float32))


def delay_gate(tree, token, anchor_tree):
    """Sleep until ``token``'s deadline once ``anchor_tree`` is available,
    then pass ``tree`` through unchanged.  The gate's (always 0.0) output is
    added to the first leaf so the sleep cannot be eliminated; values are
    untouched (x + 0.0 == x)."""
    z = jax.pure_callback(
        _gate_cb, jax.ShapeDtypeStruct((), np.float32),
        token, _first_scalar(anchor_tree))
    leaves, treedef = jax.tree.flatten(tree)
    leaves = [leaves[0] + z.astype(leaves[0].dtype)] + leaves[1:]
    return jax.tree.unflatten(treedef, leaves)


def delay_tie(tree, token):
    """Order-tie: make ``tree`` depend on ``token`` without changing values
    (the select is never taken — tokens are finite).  Used to pin a start
    callback into the backward walk so XLA cannot hoist it to the end."""
    if token is None:
        return tree
    return jax.tree.map(
        lambda x: jnp.where(token < jnp.inf, x, jnp.zeros_like(x)), tree)


def inject_blocking_delay(tree, n_bytes, delay_ns_per_byte, scale=None):
    """Model a *synchronous* collective: deadline sampled when ``tree`` is
    ready, gate immediately after — the full ``n_bytes * delay`` charge lands
    on the critical path.  ``scale`` (traced, optional) multiplies the delay
    (e.g. 0 off a localsgd boundary)."""
    ms = n_bytes * delay_ns_per_byte * 1e-6
    if scale is not None:
        ms = jnp.asarray(ms, jnp.float32) * scale
    token = delay_start(tree, ms)
    return delay_gate(tree, token, tree)


# ---------------------------------------------------------------------------
# pjit path (production): synchronization behaviour lives in the pluggable
# strategy registry (train/sync.py); this wrapper is kept as the stable
# public name for sync-state construction.
# ---------------------------------------------------------------------------
def init_sync_state(sync: SyncConfig, params):
    from repro.train.sync import get_strategy  # local: avoid import cycle
    return get_strategy(sync).init_state(params)


def localsgd_average(sync: SyncConfig, params, step,
                     delay_ns_per_byte: float = 0.0):
    """Paper strategy-C boundary: every ``local_steps``-th step the replicas'
    parameters are averaged over ``sync.axis_name``.  The boundary derives
    from the (scan-carried, checkpointed) step counter — same arithmetic as
    the shard_map worker path — so no extra sync state is needed.  Under
    plain jit (axis_name=None, e.g. single logical device or implicit SPMD)
    the average is the identity but the select still runs.  Returns the new
    params.

    ``delay_ns_per_byte`` > 0 (overlap harness) charges the all-reduce
    2 × param-bytes synchronously, scaled to zero off the boundary — this is
    the blocking baseline the τ-ring boundary (train/sync.py) hides."""
    do_avg = ((step + 1) % sync.local_steps) == 0
    if sync.axis_name is not None:
        avg = jax.tree.map(lambda p: jax.lax.pmean(p, sync.axis_name), params)
        if delay_ns_per_byte > 0:
            # all-reduce effective bytes = 2 × tree bytes (roofline.py's
            # parse_collectives convention)
            avg = inject_blocking_delay(
                avg, 2 * tree_bytes(params), delay_ns_per_byte,
                scale=do_avg.astype(jnp.float32))
    else:
        avg = params
    return jax.tree.map(lambda p, a: jnp.where(do_avg, a, p), params, avg)


def compress_grads(grads, residual):
    """bf16 gradient exchange with float32 error feedback.

    The reduced tensor is bf16 (halves collective bytes vs f32); the
    quantisation error is carried and re-injected next step, so the long-run
    gradient sum is unbiased.
    """
    def one(g, r):
        acc = g.astype(jnp.float32) + r
        q = acc.astype(jnp.bfloat16)
        return q, acc - q.astype(jnp.float32)
    flat = jax.tree.map(one, grads, residual)
    q = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
    r = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
    return q, r


# ---------------------------------------------------------------------------
# shard_map path (per-replica instances; used by the CNN reproduction and
# localsgd).  Explicit collectives -> we control exactly when workers
# synchronize, mirroring the paper's worker model.
# ---------------------------------------------------------------------------
def gathered_shard_mean(tree, axis_name: str, n_workers: int,
                        n_shards: int, delay_ns_per_byte: float = 0.0):
    """Worker-count-invariant mean of stacked per-shard gradients.

    ``tree`` leaves are ``(n_shards / n_workers, ...)`` stacks of this
    worker's micro-shard gradients.  Instead of ``pmean`` (whose reduction
    tree depends on the worker count), every worker ``all_gather``s the
    full ``(n_shards, ...)`` stack — deterministically concatenated in
    axis-index order, which is exactly global shard order because worker w
    owns the contiguous shard range [w*S/N, (w+1)*S/N) — and then reduces
    it with one FIXED-shape ``sum`` over ``n_shards``.  The floating-point
    reduction is therefore identical for every N dividing ``n_shards``,
    which is what makes bsp/chaos updates (and their checkpoints) bit-exact
    across worker counts (tests/test_worker_scaling.py).

    ``delay_ns_per_byte`` > 0 (overlap harness) charges the gather its
    result bytes *synchronously* right here — the collect-then-walk /
    non-layerwise baseline.  The interleaved layerwise schedule instead
    passes 0 and places its own start/gate pair around the backward walk
    (train/step.py), so the same bytes land off the critical path."""
    if n_workers > 1:
        # gather in the *native* dtype: with per-shard bf16 compression the
        # collective moves half the bytes, and the fixed-shape reduction
        # below upcasts before summing
        tree = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_name, axis=0, tiled=True),
            tree)
        if delay_ns_per_byte > 0:
            # all-gather effective bytes = result bytes (roofline convention)
            tree = inject_blocking_delay(
                tree, tree_bytes(tree), delay_ns_per_byte)
    inv = 1.0 / n_shards
    # accumulate in f32 regardless of wire dtype (identity for f32 inputs,
    # so the uncompressed path's bit-exactness contract is untouched)
    return jax.tree.map(
        lambda x: jnp.sum(x.astype(jnp.float32), axis=0) * inv, tree)


def replicate_for_workers(tree, n: int):
    """Stack `n` copies along a leading replica axis."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                        tree)


def make_worker_step(loss_fn: Callable, lr_fn: Callable, sync: SyncConfig,
                     axis_name: str = "workers"):
    """LEGACY research harness — the production worker route is
    ``train/step.py::make_worker_superstep`` (superstep scan inside
    shard_map, optimizer/LR-schedule aware, worker-count-invariant bsp).
    Kept because its chaos flavour is the OTHER point in the staleness
    design space: local gradient applied instantly + remote gradients one
    step late, vs the production path's fully-stale global exchange
    (w_{t+1} = w_t - lr * mean_i g_i(w_{t-1})).  Exercised by
    tests/test_chaos.py for semantics comparison only.

    state = {params, prev_grad?, step}; each worker holds its OWN params
    (replica axis sharded over `axis_name`).  Sync behaviour:
      bsp      - psum every step, workers stay identical
      chaos    - apply own grad now + others' grads one step late
      localsgd - local SGD; average params every K steps
    """

    def step(state, batch):
        params = state["params"]
        lr = lr_fn(state["step"])
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        n = jax.lax.psum(1, axis_name)

        if sync.mode == "bsp":
            g = jax.lax.pmean(grads, axis_name)
            new_params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
            new_state = {**state, "params": new_params}
        elif sync.mode == "chaos":
            # Controlled Hogwild: local gradient lands instantly; remote
            # gradients arrive one step late (non-instant, no barrier on the
            # fresh local contribution).
            prev = state["prev_grad"]
            remote_stale = jax.tree.map(
                lambda s, sl: (jax.lax.psum(s, axis_name) - sl) / n,
                prev, prev)
            new_params = jax.tree.map(
                lambda p, gl, rs: p - lr * (gl / n + rs),
                params, grads, remote_stale)
            new_state = {**state, "params": new_params, "prev_grad": grads}
        elif sync.mode == "localsgd":
            local = jax.tree.map(lambda p, gg: p - lr * gg, params, grads)
            do_avg = (state["step"] + 1) % sync.local_steps == 0
            avg = jax.lax.pmean(local, axis_name)
            new_params = jax.tree.map(
                lambda l, a: jnp.where(do_avg, a, l), local, avg)
            new_state = {**state, "params": new_params}
        else:
            raise ValueError(sync.mode)
        new_state["step"] = state["step"] + 1
        metrics = {**metrics, "loss": loss}
        metrics = jax.lax.pmean(metrics, axis_name)
        return new_state, metrics

    return step


def worker_train_fn(loss_fn, lr_fn, sync: SyncConfig, mesh,
                    axis_name: str = "workers"):
    """Wrap the worker step in shard_map over a 1-D worker mesh.

    state trees carry a leading replica axis sharded over `axis_name`;
    batches carry a leading worker axis likewise.
    """
    from jax.sharding import PartitionSpec as P

    inner = make_worker_step(loss_fn, lr_fn, sync, axis_name)

    def whole(state, batch):
        def body(state_l, batch_l):
            state_l = jax.tree.map(lambda x: x[0], state_l)
            batch_l = jax.tree.map(lambda x: x[0], batch_l)
            new_state, metrics = inner(state_l, batch_l)
            return (jax.tree.map(lambda x: x[None], new_state), metrics)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P()),
        )(state, batch)

    return jax.jit(whole)
