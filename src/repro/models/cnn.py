"""The paper's CNNs (Table 2): conv/max-pool/fc stacks for 29x29 MNIST.

Faithful to Cireşan-style nets used in the paper: valid convolutions,
max-pooling, tanh hidden activations, softmax output, MSE-free CE loss,
SGD with the paper's decay schedule (eta0=0.001, x0.9 per epoch).

``use_kernel=True`` (argument, or ``cfg.use_kernel`` when the argument is
left as None) routes the WHOLE hot path through the fused, autotuned
Pallas TPU kernels (`repro.kernels.ops`) — the SIMD-vectorisation
analogue (DESIGN.md §2, §Kernels): one fused conv+bias+tanh launch
forward and one fused dx+dw+db launch backward per conv layer, Pallas
max-pool both ways, one fused matmul+bias(+tanh) launch per FC layer
each way, and a fused softmax-cross-entropy kernel whose backward reuses
the saved dlogits (zero extra launches).

Every layer runs under ``jax.named_scope("{kind}{i}")`` (the names
``bucket_spec`` uses; pool layers too) and the loss under ``"loss"``, on
both paths: the compiled step's HLO names each layer's forward
(``jvp(conv2)/...``) and backward (``transpose(jvp(conv2))/...``, or
``conv2/bwd/...`` in the saved-activation tape) — ``obs/trace.py``'s
naming contract.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.types import ArchConfig, ParamBucket


def _trace_shapes(cfg: ArchConfig):
    """Yield (kind, spec, h, c_in, c_out) per layer; h = output spatial."""
    h = cfg.cnn_input[0]
    c = 1
    out = []
    for spec in cfg.cnn_layers:
        if spec[0] == "conv":
            _, maps, k = spec
            h = h - k + 1
            out.append(("conv", k, h, c, maps))
            c = maps
        elif spec[0] == "pool":
            _, k = spec
            h = h // k
            out.append(("pool", k, h, c, c))
        else:
            _, n = spec
            out.append(("fc", None, n, c * h * h, n))
            h, c = 1, n
    out.append(("fc", None, cfg.n_classes, c * h * h if h > 1 else c,
                cfg.n_classes))
    return out


def param_count(cfg: ArchConfig) -> int:
    n = 0
    for kind, k, _, cin, cout in _trace_shapes(cfg):
        if kind == "conv":
            n += k * k * cin * cout + cout
        elif kind == "fc":
            n += cin * cout + cout
    return n


def build_params(cfg: ArchConfig, f):
    params = {}
    for i, (kind, k, _, cin, cout) in enumerate(_trace_shapes(cfg)):
        if kind == "conv":
            params[f"conv{i}"] = {
                "w": f.array((k, k, cin, cout), None,
                             scale=1.0 / math.sqrt(k * k * cin)),
                "b": f.array((cout,), None, mode="zeros"),
            }
        elif kind == "fc":
            params[f"fc{i}"] = {
                "w": f.array((cin, cout), ("fsdp", None),
                             scale=1.0 / math.sqrt(cin)),
                "b": f.array((cout,), None, mode="zeros"),
            }
    return params


def bucket_spec(cfg: ArchConfig) -> tuple:
    """ParamBuckets (DESIGN.md §6): one bucket per parameterised Table-2
    layer, in forward (production) order — pool layers carry no params and
    therefore no bucket.  The per-layer VJP tape yields these buckets at
    ``index`` descending (reverse-production order, the paper's §3 walk)."""
    buckets = []
    for i, (kind, *_rest) in enumerate(_trace_shapes(cfg)):
        if kind in ("conv", "fc"):
            name = f"{kind}{i}"
            buckets.append(ParamBucket(name=name, keys=(name,),
                                       index=len(buckets)))
    return tuple(buckets)


def _use_kernel(cfg: ArchConfig, use_kernel):
    return cfg.use_kernel if use_kernel is None else use_kernel


def _scoped(fn, scope: str):
    """``fn`` run under a named scope (``obs/trace.py``)."""
    def run(*args):
        with jax.named_scope(scope):
            return fn(*args)
    return run


def forward(params, images, cfg: ArchConfig, use_kernel: bool | None = None):
    """images: (B, H, W, 1) float32 in [0,1].  Returns (B, n_classes) logits."""
    x = images
    uk = _use_kernel(cfg, use_kernel)
    shapes = _trace_shapes(cfg)
    for i, (kind, k, *_) in enumerate(shapes):
        with jax.named_scope(f"{kind}{i}"):
            x = _forward_layer(params, x, i, kind, k, i == len(shapes) - 1,
                               uk)
    return x


def _forward_layer(params, x, i, kind, k, last, uk):
    """Layer ``i`` of ``forward``: the XLA or the Pallas-kernel path."""
    if uk:
        from repro.kernels import ops as kops
    if kind == "conv":
        p = params[f"conv{i}"]
        if uk:
            return kops.conv2d_bias_tanh(x, p["w"], p["b"])
        return jnp.tanh(jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"])
    if kind == "pool":
        if k == 1:
            return x
        if uk:
            return kops.maxpool2d(x, k)
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1), "VALID")
    p = params[f"fc{i}"]
    if x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    if uk:
        return (kops.fc_bias(x, p["w"], p["b"]) if last
                else kops.fc_bias_tanh(x, p["w"], p["b"]))
    x = x @ p["w"] + p["b"]
    return x if last else jnp.tanh(x)


def _layer_fns(cfg: ArchConfig, uk: bool):
    """One closure per Table-2 layer, in forward order: ``(name, fn)`` where
    ``fn(p, x)`` (params-less layers: ``fn(x)``, name None) runs that layer
    through the XLA or Pallas-kernel path under the layer's named scope.
    Shared by the layerwise walk; each runs ``forward``'s own layer body,
    so both paths stay byte-compatible."""
    shapes = _trace_shapes(cfg)
    out = []
    for i, (kind, k, *_) in enumerate(shapes):
        scope = f"{kind}{i}"
        layer = functools.partial(_forward_layer, i=i, kind=kind, k=k,
                                  last=i == len(shapes) - 1, uk=uk)
        if kind == "pool":
            if k > 1:
                out.append((None, _scoped(
                    lambda x, layer=layer: layer(None, x), scope)))
        else:
            out.append((scope, _scoped(
                lambda p, x, layer=layer, scope=scope: layer({scope: p}, x),
                scope)))
    return out


def _layer_bwd_fns(cfg: ArchConfig, uk: bool):
    """Saved-activation backward closure per layer, forward order (matching
    ``_layer_fns``): ``bwd(p, x, y, g) -> (dp, dx)`` for parameterised
    layers, ``bwd(x, y, g) -> dx`` for pool.  ``x``/``y`` are the layer's
    checkpointed input/output activations, so no closure re-runs the
    forward: the kernel path calls the fused backward kernels directly
    (``kernels/ops.py`` saved-activation entry points) and the XLA path
    applies the exact tanh VJP rule ``g * (1 - y*y)`` plus
    ``jax.linear_transpose`` of the linear conv/matmul — the same
    primitives ``jax.vjp`` would emit, minus the primal recompute.  Each
    runs under ``{layer}/bwd``: the same layer's scope, backward."""
    if uk:
        from repro.kernels import ops as kops
    shapes = _trace_shapes(cfg)
    dn = ("NHWC", "HWIO", "NHWC")
    out = []
    for i, (kind, k, _, cin, cout) in enumerate(shapes):
        if kind == "conv":
            if uk:
                # layer 0's input is the images: it takes no dx
                def bwd(p, x, y, g, first=i == 0):
                    dx, dw, db = kops.conv2d_bias_tanh_bwd(
                        x, p["w"], p["b"], y, g, dx=not first)
                    return {"w": dw, "b": db}, dx
            else:
                def bwd(p, x, y, g):
                    g = g * (1.0 - y * y)
                    conv_x = lambda x_: jax.lax.conv_general_dilated(
                        x_, p["w"], (1, 1), "VALID", dimension_numbers=dn)
                    conv_w = lambda w_: jax.lax.conv_general_dilated(
                        x, w_, (1, 1), "VALID", dimension_numbers=dn)
                    (dx,) = jax.linear_transpose(conv_x, x)(g)
                    (dw,) = jax.linear_transpose(conv_w, p["w"])(g)
                    return ({"w": dw.astype(p["w"].dtype),
                             "b": g.sum((0, 1, 2)).astype(p["b"].dtype)},
                            dx.astype(x.dtype))
            out.append(_scoped(bwd, f"conv{i}/bwd"))
        elif kind == "pool":
            if k > 1:
                if uk:
                    bwd = lambda x, y, g, k=k: kops.maxpool2d_vjp_saved(
                        x, y, g, k)
                else:
                    def bwd(x, y, g, k=k):
                        pool = lambda x_: jax.lax.reduce_window(
                            x_, -jnp.inf, jax.lax.max, (1, k, k, 1),
                            (1, k, k, 1), "VALID")
                        _, vjp = jax.vjp(pool, x)
                        (dx,) = vjp(g)
                        return dx
                out.append(_scoped(bwd, f"pool{i}/bwd"))
        else:
            last = i == len(shapes) - 1

            def bwd(p, x, y, g, last=last):
                xf = x.reshape(x.shape[0], -1) if x.ndim > 2 else x
                if uk:
                    if last:
                        dxf, dw, db = kops.fc_bias_bwd(xf, p["w"], p["b"], g)
                    else:
                        dxf, dw, db = kops.fc_bias_tanh_bwd(
                            xf, p["w"], p["b"], y, g)
                else:
                    if not last:
                        g = g * (1.0 - y * y)
                    dw = (xf.T @ g).astype(p["w"].dtype)
                    db = g.sum(0).astype(p["b"].dtype)
                    dxf = (g @ p["w"].T).astype(x.dtype)
                return {"w": dw, "b": db}, dxf.reshape(x.shape)
            out.append(_scoped(bwd, f"fc{i}/bwd"))
    return out


def loss_and_bucket_grads(params, batch, cfg: ArchConfig, tape,
                          use_kernel: bool | None = None):
    """The paper's §3 update rule as a **bucket tape** (DESIGN.md §6):
    non-instant per-bucket weight updates DURING back-propagation.

    Forward runs at the incoming ``params`` recording a per-layer VJP tape;
    the backward walk then visits buckets in reverse-production order and,
    the moment bucket b's gradient is produced, calls
    ``tape(bucket, params_b, grads_b) -> new_params_b`` (``None`` leaves the
    bucket untouched) — so in the compiled graph each bucket's exchange +
    update is chained to that bucket's gradient production, not to a
    whole-tree barrier ("without significant delay").  The same walk drives
    the XLA and the fused Pallas-kernel paths (each layer closure carries
    its own custom-VJP kernels).

    Returns ``(loss, metrics, new_params, grads)`` with ``grads`` the fresh
    float32 per-bucket gradients (for the sync strategy's exchange).
    """
    uk = _use_kernel(cfg, use_kernel)
    x = batch["images"]
    labels = batch["labels"]
    buckets = {b.name: b for b in bucket_spec(cfg)}
    layer_tape = []
    for name, fn in _layer_fns(cfg, uk):
        if name is None:
            x, vjp = jax.vjp(fn, x)
        else:
            x, vjp = jax.vjp(fn, params[name], x)
        layer_tape.append((name, vjp))

    loss, vjp_loss = jax.vjp(lambda lg: _xent(lg, labels, uk), x)
    metrics = {"ce": loss, "error_rate": _error_rate(x, labels),
               "aux": jnp.zeros((), jnp.float32)}

    (dy,) = vjp_loss(jnp.ones((), loss.dtype))
    new_params = dict(params)
    grads = {}
    for name, vjp in reversed(layer_tape):
        if name is None:
            (dy,) = vjp(dy)
            continue
        dp, dy = vjp(dy)
        dp = jax.tree.map(lambda t: t.astype(jnp.float32), dp)
        grads[name] = dp
        out = tape(buckets[name], {name: params[name]}, {name: dp})
        if out is not None:
            new_params.update(out)
    return loss, metrics, new_params, grads


def loss_and_shard_bucket_grads(params, shards, cfg: ArchConfig, on_bucket,
                                use_kernel: bool | None = None):
    """Worker-mesh flavour of the bucket tape (DESIGN.md §8): the per-layer
    backward walk over a stack of micro-shards, firing ``on_bucket`` the
    moment each layer's STACKED gradient exists.

    ``shards`` is the batch pytree with a leading ``(s, b, ...)`` micro-shard
    axis.  Output matches ``lax.map(value_and_grad(loss_fn))`` over that axis
    exactly — ``(losses (s,), metrics {(s,)}, grads {layer: (s, ...) f32})``
    — because every per-shard computation runs through the same per-shard
    ``lax.map`` bodies with the same layer closures (``_layer_fns``); only
    the *schedule* differs: the forward checkpoints each layer's stacked
    input AND output activations (outputs are free — layer i's output is
    layer i+1's input, already live), and the backward consumes the saved
    pair through ``_layer_bwd_fns`` — fused backward kernels fed the saved
    output directly on the kernel path, the exact tanh VJP rule plus
    ``jax.linear_transpose`` on the XLA path — so no layer's forward is
    re-run during the walk (the PR 7 tape re-linearised every layer with
    ``jax.vjp``, ~15 ms/step of recompute on the forced-host mesh) and
    ``on_bucket(bucket, {layer: dp_stacked})`` can issue that bucket's
    exchange collective while the remaining layers' backward is still to
    run.  ``on_bucket`` returns an ordering token (or None); the
    token is tied into the downstream cotangent WITHOUT changing its value
    (``core/chaos.py::delay_tie``), pinning the collective's issue point
    into the backward walk so XLA cannot sink it to the end of the step.
    """
    from repro.core.chaos import delay_tie
    uk = _use_kernel(cfg, use_kernel)
    buckets = {b.name: b for b in bucket_spec(cfg)}
    layers = _layer_fns(cfg, uk)
    labels = shards["labels"]

    xs = shards["images"]
    acts = [xs]  # acts[i] / acts[i+1] = layer i's stacked input / output
    for name, fn in layers:
        if name is None:
            xs = jax.lax.map(fn, xs)
        else:
            xs = jax.lax.map(lambda x, p=params[name], fn=fn: fn(p, x), xs)
        acts.append(xs)

    def loss_and_dy(args):
        logits, lab = args
        loss, vjp_loss = jax.vjp(lambda lg: _xent(lg, lab, uk), logits)
        (dy,) = vjp_loss(jnp.ones((), loss.dtype))
        return loss, _error_rate(logits, lab), dy

    losses, errs, dy = jax.lax.map(loss_and_dy, (xs, labels))
    metrics = {"ce": losses, "error_rate": errs,
               "aux": jnp.zeros_like(losses)}

    grads = {}
    bwds = _layer_bwd_fns(cfg, uk)
    for (name, _fn), bwd, x_in, y_out in zip(
            reversed(layers), reversed(bwds),
            reversed(acts[:-1]), reversed(acts[1:])):
        if name is None:
            dy = jax.lax.map(lambda a, bwd=bwd: bwd(*a), (x_in, y_out, dy))
            continue

        def bwd_layer(args, bwd=bwd, p=params[name]):
            x, y, g = args
            dp, dx = bwd(p, x, y, g)
            return jax.tree.map(lambda t: t.astype(jnp.float32), dp), dx

        dp, dy = jax.lax.map(bwd_layer, (x_in, y_out, dy))
        grads[name] = dp
        dy = delay_tie(dy, on_bucket(buckets[name], {name: dp}))
    return losses, metrics, grads


def _xent(logits, labels, uk: bool):
    """Mean softmax cross-entropy over the batch, in float32, under the
    ``loss`` scope."""
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        if uk:
            from repro.kernels import ops as kops
            return jnp.mean(kops.softmax_xent(logits, labels))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - ll)


def _error_rate(logits, labels):
    with jax.named_scope("loss"):
        wrong = jnp.argmax(logits.astype(jnp.float32), -1) != labels
        return jnp.mean(wrong.astype(jnp.float32))


def loss_fn(params, batch, cfg: ArchConfig, use_kernel: bool | None = None):
    uk = _use_kernel(cfg, use_kernel)
    logits = forward(params, batch["images"], cfg, use_kernel=uk)
    loss = _xent(logits, batch["labels"], uk)
    return loss, {"ce": loss, "error_rate": _error_rate(logits,
                                                        batch["labels"]),
                  "aux": jnp.zeros((), jnp.float32)}
