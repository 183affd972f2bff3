"""Unified model API: dispatches on ``cfg.family``.

    ops = get_ops(cfg)
    params = ops.init(jax.random.key(0))          # real arrays
    specs  = ops.param_specs()                     # logical PartitionSpec tree
    loss, metrics = ops.loss(params, batch)
    spec   = ops.bucket_spec()                     # ordered ParamBuckets
    loss, metrics, grads = ops.loss_and_grads(params, batch)
    loss, metrics, new_params, grads = ops.loss_and_grads(
        params, batch, tape=on_bucket)             # reverse-production tape
    cache  = ops.init_cache(batch_size, max_seq)   # decode families
    logits, cache = ops.decode(params, cache, tokens, cache_len)
    logits, cache = ops.prefill(params, cache, tokens, lengths, cache_len)

ParamBuckets (DESIGN.md §6): ``bucket_spec()`` partitions the param tree
into ordered, disjoint per-layer buckets — the granularity at which the
sync engine exchanges gradients, compression slices its error-feedback
residual, and optimizers slice their state.  ``loss_and_grads``'s tape mode
calls ``tape(bucket, params_b, grads_b) -> new_params_b | None`` once per
bucket in **reverse-production order**: the CNN family chains each call to
that layer's VJP gradient production (the paper's §3 per-layer non-instant
update); every other family computes the whole gradient once and walks the
buckets in reverse order (same exchange/update granularity, coarser
production chaining — their scanned layer stacks are single leaves).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.types import ArchConfig, ParamBucket
from repro.models import layers as L


@dataclasses.dataclass
class ModelOps:
    cfg: ArchConfig
    init: Callable
    param_specs: Callable
    abstract_params: Callable
    loss: Callable
    bucket_spec: Callable = None
    loss_and_grads: Callable = None
    init_cache: Optional[Callable] = None
    abstract_cache: Optional[Callable] = None
    cache_specs: Optional[Callable] = None
    decode: Optional[Callable] = None
    #: batched prefill (DESIGN.md §9): whole right-padded prompts in one
    #: dispatch.  ``prefill(params, cache, tokens, lengths, cache_len)`` —
    #: ``tokens`` (B, T), ``lengths`` (B,) true prompt lengths; row i's
    #: next-token logits live at position lengths[i]-1.
    prefill: Optional[Callable] = None
    forward: Optional[Callable] = None
    #: worker-mesh interleaved tape (DESIGN.md §8), families that have one:
    #: ``shard_bucket_grads(params, shards, on_bucket) -> (losses, metrics,
    #: grads)`` over a stacked (s, b, ...) micro-shard batch, firing
    #: ``on_bucket(bucket, grads_b_stacked) -> token | None`` the moment
    #: each layer's stacked gradient is produced during backprop.
    shard_bucket_grads: Optional[Callable] = None


def _mod(cfg: ArchConfig):
    if cfg.family in ("dense", "mla", "moe", "vlm"):
        from repro.models import lm
        return lm
    if cfg.family == "hybrid":
        from repro.models import mamba2
        return mamba2
    if cfg.family == "ssm":
        from repro.models import rwkv6
        return rwkv6
    if cfg.family == "encdec":
        from repro.models import encdec
        return encdec
    if cfg.family == "cnn":
        from repro.models import cnn
        return cnn
    if cfg.family == "convnext":
        from repro.models import convnext
        return convnext
    raise ValueError(cfg.family)


def default_bucket_spec(abstract_params: dict) -> tuple:
    """Fallback ParamBuckets: one bucket per top-level param-tree key, in
    the model's construction order (an exact disjoint cover by
    construction)."""
    return tuple(ParamBucket(name=k, keys=(k,), index=i)
                 for i, k in enumerate(abstract_params))


def validate_bucket_spec(spec, abstract_params: dict) -> None:
    """Raise unless ``spec`` is an ordered exact disjoint cover of the
    param tree's top-level keys."""
    seen: list = []
    for b in spec:
        for k in b.keys:
            if k in seen:
                raise ValueError(
                    f"bucket {b.name!r} overlaps: key {k!r} already owned")
            if k not in abstract_params:
                raise ValueError(
                    f"bucket {b.name!r} names unknown param key {k!r}")
            seen.append(k)
    missing = set(abstract_params) - set(seen)
    if missing:
        raise ValueError(
            f"bucket_spec misses param keys {sorted(missing)}: buckets must "
            f"exactly cover the param tree")
    if [b.index for b in spec] != list(range(len(spec))):
        raise ValueError("bucket indices must be 0..n-1 in production order")


def get_ops(cfg: ArchConfig) -> ModelOps:
    mod = _mod(cfg)
    dtype = jnp.dtype(cfg.param_dtype)

    def init(key):
        return mod.build_params(cfg, L.InitFactory(key, dtype))

    def param_specs():
        return mod.build_params(cfg, L.SpecFactory())

    def abstract_params():
        return mod.build_params(cfg, L.ShapeFactory(dtype))

    def bucket_spec():
        if hasattr(mod, "bucket_spec"):
            return mod.bucket_spec(cfg)
        return default_bucket_spec(abstract_params())

    def loss_and_grads(params, batch, tape=None):
        """(loss, metrics, grads) — or, with ``tape``, the reverse-
        production bucket walk: ``tape(bucket, params_b, grads_b) ->
        new_params_b | None`` and a 4-tuple return (loss, metrics,
        new_params, grads).  CNN routes the tape through the per-layer VJP
        walk so each bucket's call is chained to that layer's gradient
        production."""
        if tape is not None and hasattr(mod, "loss_and_bucket_grads"):
            return mod.loss_and_bucket_grads(params, batch, cfg, tape)
        (loss, metrics), grads = jax.value_and_grad(
            lambda p, b: mod.loss_fn(p, b, cfg), has_aux=True)(params, batch)
        if tape is None:
            return loss, metrics, grads
        new_params = dict(params)
        for bucket in reversed(bucket_spec()):
            out = tape(bucket, bucket.view(params), bucket.view(grads))
            if out is not None:
                new_params.update(out)
        return loss, metrics, new_params, grads

    ops = ModelOps(
        cfg=cfg, init=init, param_specs=param_specs,
        abstract_params=abstract_params,
        loss=lambda params, batch: mod.loss_fn(params, batch, cfg),
        bucket_spec=bucket_spec, loss_and_grads=loss_and_grads,
        forward=getattr(mod, "forward", None) and (
            lambda params, *a, **k: mod.forward(params, *a, cfg=cfg, **k)
            if cfg.family != "cnn" else mod.forward(params, *a, cfg, **k)),
    )
    if hasattr(mod, "loss_and_shard_bucket_grads"):
        ops.shard_bucket_grads = (
            lambda params, shards, on_bucket:
            mod.loss_and_shard_bucket_grads(params, shards, cfg, on_bucket))
    if hasattr(mod, "init_cache"):
        cache_dtype = jnp.dtype("bfloat16")
        ops.init_cache = lambda b, s: mod.init_cache(
            cfg, b, s, L.InitFactory(jax.random.key(0), cache_dtype))
        ops.abstract_cache = lambda b, s: mod.init_cache(
            cfg, b, s, L.ShapeFactory(cache_dtype))
        ops.cache_specs = lambda b, s: mod.init_cache(
            cfg, b, s, L.SpecFactory())
        ops.decode = (
            lambda params, cache, tokens, cache_len, **kw: mod.decode_step(
                params, cache, tokens, cache_len, cfg, **kw))
    if hasattr(mod, "prefill_step"):
        ops.prefill = (
            lambda params, cache, tokens, lengths, cache_len, **kw:
            mod.prefill_step(params, cache, tokens, lengths, cache_len,
                             cfg, **kw))
    return ops
