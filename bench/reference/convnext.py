"""Plain reference of CHAOS training of ConvNeXt (Liu et al. 2022,
arXiv:2201.03545), in jax.numpy.

Imports nothing of the program.  From the seed alone it makes the initial
weights (normal draws, one split of ``jax.random.key(seed)`` per weight in
forward order, scaled by 1/sqrt(fan-in); zero biases and LayerNorm
shifts; LayerNorm scales and layer scales one), takes the order in which
the shared queue hands out images from the Table-2 reference
(``table2_cnn.queue_rows``), and follows the first training steps of the
job the traffic file states:

* each of the ``logical_shards`` micro-shards of a step's batch computes
  its mean softmax cross-entropy and gradient at the weights of the
  worker that owns it (worker w owns the contiguous shards
  [w S/N, (w+1) S/N)), one micro-shard at a time, so that it fits at
  224x224;
* a worker applies, with AdamW, its own shards' share of the global mean
  gradient plus the rest of the global mean from ``staleness`` steps
  before (``sync="chaos"``); with staleness 0, or ``sync="bsp"``, the
  whole global mean;
* AdamW as the configuration's ``optimizer`` states it: the gradient
  scaled to a global norm of at most ``grad_clip``, moments with ``b1``
  and ``b2``, bias correction, ``eps`` outside the square root, decoupled
  weight decay on every leaf, a constant rate;
* the loss a step reports is the mean over all micro-shards.

Layers: images (uint8) scaled to [0, 1] and normalised by the
configuration's channel mean and deviation; the strided stem and
downsample convolutions as patch matrices and one matrix product; the 7x7
depthwise convolution as the sum of its 49 shifted, weighted slices of the
zero-padded input; LayerNorm over channels; GELU written with erf (the
exact form).  Everything is float32, products at ``Precision.HIGHEST``,
unless ``precision="bfloat16"``, which holds the weights, activations,
gradients, moments and updates in bfloat16 (the control), or
``precision="one_pass"``, which gives every product (matrix products and
depthwise taps, forward and both gradients) bfloat16-rounded operands and
float32 sums, as a TPU runs the configuration's float32 products at the
default precision: on a host without a TPU, the size of the program's
rounding on the chip.

Departures from the paper, each also the configuration's: stochastic depth
0; layer scale initialised to 1, not 1e-6; weights N(0, 1/fan-in), not
truncated N(0, 0.02); the program's AdamW settings, not the recipe's; no
EMA, mixup, cutmix, label smoothing or augmentation.
"""
from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STEM, DOWN, KERNEL, RATIO = 4, 2, 7, 4

_spec = importlib.util.spec_from_file_location(
    "table2_reference", os.path.join(os.path.dirname(__file__),
                                     "table2_cnn.py"))
_table2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_table2)
queue_rows = _table2.queue_rows


def init_params(cfg: dict, seed: int) -> dict:
    key = jax.random.key(seed)

    def normal(shape, fan_in):
        nonlocal key
        key, sub = jax.random.split(key)
        return (jax.random.normal(sub, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in)))

    zeros = lambda n: jnp.zeros((n,), jnp.float32)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    dims = cfg["dims"]
    c_in = cfg["input_channels"]
    params = {"stem": {
        "w": normal((STEM, STEM, c_in, dims[0]), STEM * STEM * c_in),
        "b": zeros(dims[0]), "ln_g": ones(dims[0]), "ln_b": zeros(dims[0])}}
    for i, (d, depth) in enumerate(zip(dims, cfg["depths"])):
        if i:
            prev = dims[i - 1]
            params[f"down{i}"] = {
                "ln_g": ones(prev), "ln_b": zeros(prev),
                "w": normal((DOWN, DOWN, prev, d), DOWN * DOWN * prev),
                "b": zeros(d)}
        for j in range(depth):
            params[f"s{i}b{j}"] = {
                "dw_w": normal((KERNEL, KERNEL, 1, d), KERNEL * KERNEL),
                "dw_b": zeros(d), "ln_g": ones(d), "ln_b": zeros(d),
                "w1": normal((d, RATIO * d), d), "b1": zeros(RATIO * d),
                "w2": normal((RATIO * d, d), RATIO * d), "b2": zeros(d),
                "gamma": ones(d)}
    params["head"] = {
        "ln_g": ones(dims[-1]), "ln_b": zeros(dims[-1]),
        "w": normal((dims[-1], cfg["classes"]), dims[-1]),
        "b": zeros(cfg["classes"])}
    return params


def _round(a):
    """``a`` rounded to bfloat16 and held in float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _one_pass_mm(a, b):
    """a @ b from bfloat16-rounded operands with float32 sums, and the same
    for both gradient products: one pass, as a TPU runs float32 products
    at the default precision."""
    return jnp.matmul(_round(a), _round(b), precision=HIGHEST)


def _one_pass_mm_fwd(a, b):
    return _one_pass_mm(a, b), (a, b)


def _one_pass_mm_bwd(res, g):
    a, b = res
    k, n = b.shape
    da = jnp.matmul(_round(g), _round(b).T, precision=HIGHEST)
    db = jnp.matmul(_round(a).reshape(-1, k).T, _round(g).reshape(-1, n),
                    precision=HIGHEST)
    return da, db


_one_pass_mm.defvjp(_one_pass_mm_fwd, _one_pass_mm_bwd)


@jax.custom_vjp
def _one_pass_tap(x, w):
    """One depthwise tap, x * w per channel, from bfloat16-rounded
    operands, and the same for both gradients."""
    return _round(x) * _round(w)


def _one_pass_tap_fwd(x, w):
    return _one_pass_tap(x, w), (x, w)


def _one_pass_tap_bwd(res, g):
    x, w = res
    return (_round(g) * _round(w),
            jnp.sum(_round(x) * _round(g), axis=(0, 1, 2)))


_one_pass_tap.defvjp(_one_pass_tap_fwd, _one_pass_tap_bwd)


def _matmul(precision: str):
    if precision == "float32":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.matmul
    if precision == "one_pass":
        return _one_pass_mm
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _patch_conv(x, w, b, k, mm):
    """A k x k stride-k convolution: non-overlapping patches, one matrix
    product with the (k k c_in, c_out) weight matrix."""
    n, h, wd, c = x.shape
    ho, wo = h // k, wd // k
    p = x[:, :ho * k, :wo * k].reshape(n, ho, k, wo, k, c)
    p = p.transpose(0, 1, 3, 2, 4, 5).reshape(n, ho, wo, k * k * c)
    return mm(p, w.reshape(k * k * c, -1)) + b


def _depthwise(x, w, b, precision: str):
    """7x7 depthwise convolution, padding 3: the sum of the 49 shifted
    slices of the padded input, each times its tap's per-channel weight."""
    pad = KERNEL // 2
    _, h, wd, _ = x.shape
    tap = _one_pass_tap if precision == "one_pass" else jnp.multiply
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    y = sum(tap(xp[:, i:i + h, j:j + wd, :], w[i, j, 0])
            for i in range(KERNEL) for j in range(KERNEL))
    return y + b


def forward(params, images, cfg: dict, precision: str = "float32"):
    """Logits (B, classes) of uint8 images (B, H, W, 3)."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    mm = _matmul(precision)
    eps = cfg["layer_norm_eps"]
    mean = jnp.asarray(cfg["input_mean"], dt)
    std = jnp.asarray(cfg["input_std"], dt)
    x = (images.astype(dt) / 255.0 - mean) / std
    p = params["stem"]
    x = _layer_norm(_patch_conv(x, p["w"], p["b"], STEM, mm), p["ln_g"],
                    p["ln_b"], eps)
    for i, depth in enumerate(cfg["depths"]):
        if i:
            p = params[f"down{i}"]
            x = _patch_conv(_layer_norm(x, p["ln_g"], p["ln_b"], eps),
                            p["w"], p["b"], DOWN, mm)
        for j in range(depth):
            p = params[f"s{i}b{j}"]
            y = _depthwise(x, p["dw_w"], p["dw_b"], precision)
            y = _layer_norm(y, p["ln_g"], p["ln_b"], eps)
            y = mm(y, p["w1"]) + p["b1"]
            y = 0.5 * y * (1.0 + jax.lax.erf(y / math.sqrt(2.0)))
            y = mm(y, p["w2"]) + p["b2"]
            x = x + p["gamma"] * y
    p = params["head"]
    x = _layer_norm(jnp.mean(x, axis=(1, 2)), p["ln_g"], p["ln_b"], eps)
    return mm(x, p["w"]) + p["b"]


def loss(params, images, labels, cfg: dict, precision: str = "float32"):
    logits = forward(params, images, cfg, precision).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def adamw(opt: dict, params, grads, m, v, step: int):
    """One AdamW step as the configuration states it; returns (params, m,
    v)."""
    leaves = jax.tree.leaves(grads)
    dt = leaves[0].dtype
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves) + 1e-12)
    scale = jnp.minimum(1.0, opt["grad_clip"] / norm).astype(dt)
    b1, b2, t = opt["b1"], opt["b2"], step + 1
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g * scale, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g * scale),
                     v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - opt["lr"] * ((m_ / bc1)
                                           / (jnp.sqrt(v_ / bc2)
                                              + opt["eps"])
                                           + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v


def follow(cfg: dict, traffic: dict, seed: int, images, labels, steps: int,
           precision: str = "float32", exchange: bool = True):
    """Follow the first ``steps`` steps.  Returns host arrays: ``losses``
    (steps,), and per worker (leading axis N) the initial weights
    ``params0``, the weights ``params``, and the stale terms ``stale``
    (``{"h<i>": tree}``, one per staleness slot, the slot a step reads
    being its index modulo the staleness).  ``exchange=False`` leaves the
    exchange between workers out (each worker sees only its own
    shards)."""
    if traffic["sync"] == "chaos":
        tau = traffic["staleness"]
    elif traffic["sync"] == "bsp":
        tau = 0
    else:
        raise ValueError(f"no reference for sync {traffic['sync']!r}")
    opt = cfg["optimizer"]
    if opt["kind"] != "adamw" or opt["schedule"] != "constant":
        raise ValueError(f"no reference for optimizer {opt}")
    n_img, batch = len(images), traffic["batch"]
    S, N = traffic["logical_shards"], traffic["workers"]
    per_shard = batch // S
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(p, x, y, cfg, precision)))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw(opt, p, g, m, v, t))
    p0 = jax.tree.map(lambda a: a.astype(dtype), init_params(cfg, seed))
    zeros = jax.tree.map(jnp.zeros_like, p0)
    params = [p0] * N
    moments = [(zeros, zeros)] * N
    rings = [[zeros] * tau for _ in range(N)]
    losses = []
    for t in range(steps):
        rows = queue_rows(n_img, batch, seed, t)
        x_all, y_all = images[rows], labels[rows]
        shard_l, shard_g = [], []
        for s in range(S):
            w = s // (S // N)
            sl = slice(s * per_shard, (s + 1) * per_shard)
            l, g = grad(params[w], jnp.asarray(x_all[sl]),
                        jnp.asarray(y_all[sl]))
            shard_l.append(l)
            shard_g.append(g)
        mean = lambda gs: jax.tree.map(lambda *g: sum(g) * (1.0 / S), *gs)
        total = mean(shard_g)
        for w in range(N):
            own = mean(shard_g[w * (S // N):(w + 1) * (S // N)])
            if tau == 0:
                g = total if exchange else own
            else:
                g = jax.tree.map(jnp.add, own, rings[w][t % tau])
                rings[w][t % tau] = (jax.tree.map(jnp.subtract, total, own)
                                     if exchange else zeros)
            params[w], m, v = step_fn(params[w], g, *moments[w], t)
            moments[w] = (m, v)
        losses.append(float(sum(shard_l) / S))
    stack = lambda trees: jax.tree.map(
        lambda *a: np.stack([np.asarray(x, np.float32) for x in a]), *trees)
    return {"losses": np.asarray(losses, np.float64),
            "params0": stack([p0] * N), "params": stack(params),
            "stale": {f"h{i}": stack([r[i] for r in rings])
                      for i in range(tau)}}
