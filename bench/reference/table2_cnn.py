"""Plain reference of CHAOS training of a Table-2 CNN, in jax.numpy.

Imports nothing of the program.  From the seed alone it makes the initial
weights (normal draws, one split of ``jax.random.key(seed)`` per weight in
forward order, scaled by 1/sqrt(fan-in); zero biases), the order in which
the shared queue hands out images (one permutation per epoch from
``SeedSequence([seed, epoch])``), and then follows the first training
steps of the job the traffic file states:

* each of the ``logical_shards`` micro-shards of a step's batch computes
  its mean softmax cross-entropy and gradient at the weights of the
  worker that owns it (worker w owns the contiguous shards
  [w S/N, (w+1) S/N));
* a worker applies, with SGD at the paper's rate (eta0, times ``decay``
  per epoch), its own shards' share of the global mean gradient at once,
  plus the rest of the global mean from ``staleness`` steps before
  (``sync="chaos"``); with staleness 0, or ``sync="bsp"``, the whole
  global mean at once;
* the loss a step reports is the mean over all micro-shards.

Layers: valid stride-1 convolution as im2col and one matrix product,
bias, tanh; non-overlapping max-pool; fully connected with tanh, and a
last linear layer.  Everything is float32, products at
``Precision.HIGHEST``, unless ``precision`` names a lower one, which is how
the controls are computed: ``"bfloat16"`` holds the weights, activations,
gradients and updates in bfloat16; ``"int8"`` keeps float32 but quantises
both operands of every product symmetrically per tensor to 8 bits, forward
and backward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _shapes(cfg: dict):
    """(name, kind, k, h_in, c_in, h_out, c_out) per layer, forward order;
    names index the layer list as the program's parameter tree does."""
    h, c = cfg["input_hw"], 1
    out = []
    specs = list(cfg["layers"]) + [["out", cfg["classes"]]]
    for i, spec in enumerate(specs):
        if spec[0] == "conv":
            _, maps, k = spec
            out.append((f"conv{i}", "conv", k, h, c, h - k + 1, maps))
            h, c = h - k + 1, maps
        elif spec[0] == "pool":
            k = spec[1]
            out.append((None, "pool", k, h, c, h // k, c))
            h = h // k
        else:
            units = spec[1]
            out.append((f"fc{i}", "fc", None, h, h * h * c, 1, units))
            h, c = 1, units
    return out


def init_params(cfg: dict, seed: int) -> dict:
    key = jax.random.key(seed)
    params = {}
    for name, kind, k, _, c_in, _, c_out in _shapes(cfg):
        if name is None:
            continue
        shape = (k, k, c_in, c_out) if kind == "conv" else (c_in, c_out)
        fan_in = k * k * c_in if kind == "conv" else c_in
        key, sub = jax.random.split(key)
        params[name] = {
            "w": jax.random.normal(sub, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in)),
            "b": jnp.zeros((c_out,), jnp.float32)}
    return params


def queue_rows(n: int, batch: int, seed: int, step: int) -> np.ndarray:
    """Indices of the images of step ``step``: the contiguous chunk
    [step B, (step+1) B) of the concatenated per-epoch permutations."""
    epoch, off = divmod(step * batch, n)
    rows = []
    while len(rows) < batch:
        perm = np.random.default_rng(
            np.random.SeedSequence([seed, epoch])).permutation(n)
        take = min(batch - len(rows), n - off)
        rows.extend(perm[off:off + take])
        epoch, off = epoch + 1, 0
    return np.asarray(rows)


def _quantise(a):
    scale = jnp.max(jnp.abs(a)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(a / scale) * scale


def _matmul(precision: str):
    """a @ b in ``precision``: float32 at HIGHEST; bfloat16 operands as
    they come; or both operands quantised to int8, forward and backward."""
    if precision == "float32":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if precision == "bfloat16":
        return jnp.matmul
    if precision != "int8":
        raise ValueError(f"unknown precision {precision!r}")
    mm = lambda a, b: jnp.matmul(_quantise(a), _quantise(b),
                                 precision=HIGHEST)

    @jax.custom_vjp
    def f(a, b):
        return mm(a, b)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return mm(g, b.T), mm(a.T, g)

    f.defvjp(fwd, bwd)
    return f


def forward(params, images, cfg: dict, precision: str = "float32"):
    """Logits (B, classes) of images (B, H, W, 1)."""
    mm = _matmul(precision)
    x = images
    for name, kind, k, _, c_in, h_out, c_out in _shapes(cfg):
        if kind == "conv":
            b = x.shape[0]
            cols = jnp.stack([x[:, i:i + h_out, j:j + h_out, :]
                              for i in range(k) for j in range(k)], axis=3)
            cols = cols.reshape(b * h_out * h_out, k * k * c_in)
            w = params[name]["w"].reshape(k * k * c_in, c_out)
            y = mm(cols, w).reshape(b, h_out, h_out, c_out)
            x = jnp.tanh(y + params[name]["b"])
        elif kind == "pool":
            if k > 1:
                b, _, _, c = x.shape
                x = x[:, :h_out * k, :h_out * k, :].reshape(
                    b, h_out, k, h_out, k, c).max(axis=(2, 4))
        else:
            x = mm(x.reshape(x.shape[0], -1), params[name]["w"]) \
                + params[name]["b"]
            if name != _shapes(cfg)[-1][0]:
                x = jnp.tanh(x)
    return x


def loss(params, images, labels, cfg: dict, precision: str = "float32"):
    logits = forward(params, images, cfg, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def follow(cfg: dict, traffic: dict, seed: int, images, labels, steps: int,
           precision: str = "float32", exchange: bool = True):
    """Follow the first ``steps`` steps.  Returns host arrays: ``losses``
    (steps,), and per worker (leading axis N) the initial weights
    ``params0``, the weights ``params``, and the stale terms ``stale``
    (``{"h<i>": tree}``, one per staleness slot, the slot a step reads
    being its index modulo the staleness).  ``exchange=False`` leaves the
    exchange between workers out (a planted fault: each worker sees only
    its own shards)."""
    if traffic["sync"] == "chaos":
        tau = traffic["staleness"]
    elif traffic["sync"] == "bsp":
        tau = 0
    else:
        raise ValueError(f"no reference for sync {traffic['sync']!r}")
    n_img, batch = len(images), traffic["batch"]
    S, N = traffic["logical_shards"], traffic["workers"]
    per_shard = batch // S
    spe = max(n_img // batch, 1)
    lr0, decay = cfg["lr"], cfg["lr_decay"]
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(p, x, y, cfg, precision)))
    p0 = jax.tree.map(lambda a: a.astype(dtype), init_params(cfg, seed))
    zeros = jax.tree.map(jnp.zeros_like, p0)
    params = [p0] * N
    rings = [[zeros] * tau for _ in range(N)]
    losses = []
    for t in range(steps):
        rows = queue_rows(n_img, batch, seed, t)
        x_all, y_all = images[rows], labels[rows]
        shard_l, shard_g = [], []
        for s in range(S):
            w = s // (S // N)
            sl = slice(s * per_shard, (s + 1) * per_shard)
            l, g = grad(params[w], jnp.asarray(x_all[sl], dtype),
                        jnp.asarray(y_all[sl]))
            shard_l.append(l)
            shard_g.append(g)
        mean = lambda gs: jax.tree.map(lambda *g: sum(g) * (1.0 / S), *gs)
        total = mean(shard_g)
        lr = lr0 * decay ** (t // spe)
        for w in range(N):
            own = mean(shard_g[w * (S // N):(w + 1) * (S // N)])
            if tau == 0:
                g = total if exchange else own
            else:
                g = jax.tree.map(jnp.add, own, rings[w][t % tau])
                rings[w][t % tau] = (jax.tree.map(jnp.subtract, total, own)
                                     if exchange else zeros)
            params[w] = jax.tree.map(lambda p, d: p - lr * d, params[w], g)
        losses.append(float(sum(shard_l) / S))
    stack = lambda trees: jax.tree.map(
        lambda *a: np.stack([np.asarray(x, np.float32) for x in a]), *trees)
    return {"losses": np.asarray(losses, np.float64),
            "params0": stack([p0] * N), "params": stack(params),
            "stale": {f"h{i}": stack([r[i] for r in rings])
                      for i in range(tau)}}
