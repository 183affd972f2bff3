"""ConvNeXt (Liu et al., "A ConvNet for the 2020s", arXiv:2201.03545).

Images arrive as uint8 (B, H, W, 3) and are normalised on the device with
the ImageNet channel mean and deviation.  Then:

* ``stem``: a 4x4 stride-4 convolution to ``dims[0]`` channels, then
  LayerNorm over channels;
* stage i of ``depths[i]`` blocks at ``dims[i]`` channels, each stage after
  the first preceded by ``down{i}``: LayerNorm, then a 2x2 stride-2
  convolution;
* block ``s{i}b{j}``: 7x7 depthwise convolution (padding 3, with bias),
  LayerNorm, Linear d -> 4d, exact (erf) GELU, Linear 4d -> d, a
  per-channel layer scale gamma, and the residual add;
* ``head``: global average pool, LayerNorm, Linear to ``n_classes``.

Every LayerNorm has eps 1e-6.  Weights are drawn N(0, 1/fan-in) by the
program's initialiser, biases and LayerNorm shifts are zero, LayerNorm
scales and gamma are one (the paper initialises gamma to 1e-6, which with
random weights leaves every block's branch a millionth of the residual).

Named scopes (``obs/trace.py``): ``stem``, ``down{i}``, each block
``s{i}b{j}`` with its sublayers ``dwconv``, ``norm`` and ``mlp`` (the
pointwise pair and its GELU; the layer scale and residual are in the
block's own scope), ``head`` and ``loss``.  The parameter tree's top-level
keys, and so the ParamBuckets, are the same names but ``loss``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.types import ArchConfig, ParamBucket
from repro.models.layers import layer_norm

KERNEL = 7
MLP_RATIO = 4
LN_EPS = 1e-6
STEM_PATCH = 4
DOWN_PATCH = 2
IN_CHANNELS = 3
#: ImageNet channel statistics of images scaled to [0, 1]
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)

_DN = ("NHWC", "HWIO", "NHWC")


def _stages(cfg: ArchConfig):
    """(stage, dim, depth) in forward order."""
    return list(zip(range(len(cfg.convnext_dims)), cfg.convnext_dims,
                    cfg.convnext_depths))


def param_count(cfg: ArchConfig) -> int:
    d0 = cfg.convnext_dims[0]
    n = STEM_PATCH ** 2 * IN_CHANNELS * d0 + 3 * d0
    prev = d0
    for i, d, depth in _stages(cfg):
        if i:
            n += DOWN_PATCH ** 2 * prev * d + d + 2 * prev
        block = (KERNEL ** 2 * d + d + 2 * d
                 + 2 * MLP_RATIO * d * d + MLP_RATIO * d + d + d)
        n += depth * block
        prev = d
    return n + 2 * prev + prev * cfg.n_classes + cfg.n_classes


def build_params(cfg: ArchConfig, f):
    zeros = lambda n: f.array((n,), None, mode="zeros")
    ones = lambda n: f.array((n,), None, mode="ones")
    d0 = cfg.convnext_dims[0]
    fan = STEM_PATCH ** 2 * IN_CHANNELS
    params = {"stem": {
        "w": f.array((STEM_PATCH, STEM_PATCH, IN_CHANNELS, d0), None,
                     scale=1.0 / math.sqrt(fan)),
        "b": zeros(d0), "ln_g": ones(d0), "ln_b": zeros(d0)}}
    prev = d0
    for i, d, depth in _stages(cfg):
        if i:
            fan = DOWN_PATCH ** 2 * prev
            params[f"down{i}"] = {
                "ln_g": ones(prev), "ln_b": zeros(prev),
                "w": f.array((DOWN_PATCH, DOWN_PATCH, prev, d), None,
                             scale=1.0 / math.sqrt(fan)),
                "b": zeros(d)}
        h = MLP_RATIO * d
        for j in range(depth):
            params[f"s{i}b{j}"] = {
                "dw_w": f.array((KERNEL, KERNEL, 1, d), None,
                                scale=1.0 / KERNEL),
                "dw_b": zeros(d), "ln_g": ones(d), "ln_b": zeros(d),
                "w1": f.array((d, h), None, scale=1.0 / math.sqrt(d)),
                "b1": zeros(h),
                "w2": f.array((h, d), None, scale=1.0 / math.sqrt(h)),
                "b2": zeros(d), "gamma": ones(d)}
        prev = d
    params["head"] = {
        "ln_g": ones(prev), "ln_b": zeros(prev),
        "w": f.array((prev, cfg.n_classes), None,
                     scale=1.0 / math.sqrt(prev)),
        "b": zeros(cfg.n_classes)}
    return params


def bucket_spec(cfg: ArchConfig) -> tuple:
    """ParamBuckets (DESIGN.md §6): the stem, each downsample, each block
    and the head, in forward (production) order."""
    names = ["stem"]
    for i, _, depth in _stages(cfg):
        if i:
            names.append(f"down{i}")
        names += [f"s{i}b{j}" for j in range(depth)]
    names.append("head")
    return tuple(ParamBucket(name=n, keys=(n,), index=i)
                 for i, n in enumerate(names))


def _conv(x, w, stride: int, padding="VALID", groups: int = 1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding, dimension_numbers=_DN,
        feature_group_count=groups)


def _norm(x, p):
    return layer_norm(x, p["ln_g"], p["ln_b"], eps=LN_EPS)


def _block(p, x):
    d = x.shape[-1]
    with jax.named_scope("dwconv"):
        pad = KERNEL // 2
        y = _conv(x, p["dw_w"], 1, ((pad, pad), (pad, pad)), groups=d)
        y = y + p["dw_b"]
    with jax.named_scope("norm"):
        y = _norm(y, p)
    with jax.named_scope("mlp"):
        y = jax.nn.gelu(y @ p["w1"] + p["b1"], approximate=False)
        y = y @ p["w2"] + p["b2"]
    return x + p["gamma"] * y


def forward(params, images, cfg: ArchConfig):
    """images: (B, H, W, 3) uint8.  Returns (B, n_classes) logits."""
    with jax.named_scope("stem"):
        x = images.astype(jnp.float32) * (1.0 / 255.0)
        x = (x - jnp.asarray(MEAN)) / jnp.asarray(STD)
        p = params["stem"]
        x = _norm(_conv(x, p["w"], STEM_PATCH) + p["b"], p)
    for i, _, depth in _stages(cfg):
        if i:
            with jax.named_scope(f"down{i}"):
                p = params[f"down{i}"]
                x = _conv(_norm(x, p), p["w"], DOWN_PATCH) + p["b"]
        for j in range(depth):
            with jax.named_scope(f"s{i}b{j}"):
                x = _block(params[f"s{i}b{j}"], x)
    with jax.named_scope("head"):
        p = params["head"]
        x = _norm(jnp.mean(x, axis=(1, 2)), p)
        return x @ p["w"] + p["b"]


def loss_fn(params, batch, cfg: ArchConfig):
    """Mean softmax cross-entropy over the batch, under the ``loss``
    scope, and the error rate."""
    logits = forward(params, batch["images"], cfg)
    labels = batch["labels"]
    with jax.named_scope("loss"):
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        loss = jnp.mean(lse - picked)
        err = jnp.mean((jnp.argmax(logits, -1) != labels).astype(jnp.float32))
    return loss, {"ce": loss, "error_rate": err,
                  "aux": jnp.zeros((), jnp.float32)}
