"""The ConvNeXt family (Liu et al. 2022, arXiv:2201.03545): what the harness
knows of a configuration whose file says ``"family": "convnext"``.

The functions ``bench/run.py`` calls are those of every family (see
``bench/families/table2_cnn.py``): ``arch``, ``inputs``, ``pipeline``,
``total_steps``, ``train_flops_per_image``, ``conv_work`` and
``conv_weight_shapes``.  The readers of this family's own metrics call
``dwconv_work`` and ``mlp_work``.

A configuration states ``input_hw`` (square RGB images), ``classes``,
``dims`` and ``depths`` (channels and blocks of each stage).  The stem is a
4x4 stride-4 convolution, each later stage starts with a 2x2 stride-2
downsample, and a block is a 7x7 depthwise convolution, LayerNorm, and the
pointwise pair d -> 4d -> d; the head is a global average pool, LayerNorm
and a linear layer to ``classes``.  A multiply-accumulate is two
operations; bytes are float32 and count each pass's inputs read and
output written once: x, w -> y; x, dy -> dw; dy, w -> dx.

Which operations the chip runs as convolutions with a window, and so what
``devtrace.hlo_classes`` classes ``conv`` (read from the compiled step for
a v5e): the stem, the downsamples and the depthwise convolutions, and also
every pointwise matrix product, which XLA writes on 4-D activations as a
1x1-window convolution; each in both directions (forward, weight gradient,
input gradient; the stem takes no input gradient).  The head's product on
pooled 2-D activations is not one.  ``conv_work`` counts exactly these.

The inputs are made here from the seed, not by the program's data module,
so that the inputs of every benchmark run stay fixed whatever a later
change does to the program: uniform noise in [0, 128) plus a tint in
[0, 128) per class and channel, uint8.
"""
from __future__ import annotations

import numpy as np

F32 = 4
STEM, DOWN, KERNEL, MLP_RATIO = 4, 2, 7, 4


def arch(cfg: dict, traffic: dict):
    """The program's ConvNeXt at the file's sizes; raises ``ValueError``
    where the program has no such model, or its parameter count differs
    from the file's layers."""
    import dataclasses

    import repro.configs as C

    try:
        a = C.get(cfg["arch"])
    except ModuleNotFoundError:
        raise ValueError(f"the program has no {cfg['arch']!r} in its "
                         f"registry") from None
    if a.family != "convnext":
        raise ValueError(f"the program's {cfg['arch']} is of family "
                         f"{a.family!r}, not convnext")
    if traffic["use_kernel"]:
        raise ValueError("the program has no kernel path for convnext")
    if a.param_dtype != cfg["dtype"]:
        raise ValueError(f"the program holds {cfg['arch']} in "
                         f"{a.param_dtype}; the configuration states "
                         f"{cfg['dtype']}")
    hw = cfg["input_hw"]
    a = dataclasses.replace(a, convnext_dims=tuple(cfg["dims"]),
                            convnext_depths=tuple(cfg["depths"]),
                            cnn_input=(hw, hw), n_classes=cfg["classes"])
    if a.param_count() != param_count(cfg):
        raise ValueError(f"the program's {cfg['arch']} has "
                         f"{a.param_count()} parameters at the "
                         f"configuration's sizes; its layers have "
                         f"{param_count(cfg)}")
    return a


def inputs(cfg: dict, seed: int):
    """(images (n, hw, hw, 3) uint8, labels (n,) int32) for the
    configuration's ``train_images``."""
    n, hw, classes = cfg["train_images"], cfg["input_hw"], cfg["classes"]
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=n).astype(np.int32)
    tint = rng.integers(0, 128, size=(classes, 3), dtype=np.uint8)
    noise = np.frombuffer(rng.bytes(n * hw * hw * 3), np.uint8)
    images = (noise >> 1).reshape(n, hw, hw, 3)
    images += tint[labels][:, None, None, :]
    return images, labels


def pipeline(cfg: dict, traffic: dict, seed: int, data):
    """The shared queue of ``data`` the program's ``_train`` feeds from."""
    from repro.data.pipeline import ImagePipeline

    images, labels = data
    return ImagePipeline(images, labels, batch=traffic["batch"], seed=seed,
                         sample_mode="queue")


def total_steps(cfg: dict, traffic: dict) -> int:
    """``epochs`` passes over the synthetic images."""
    return cfg["epochs"] * max(cfg["train_images"] // traffic["batch"], 1)


def layer_shapes(cfg: dict) -> list[dict]:
    """One dict per layer with weights, in forward order: ``kind`` (stem,
    down, dwconv, pointwise, head), the window ``k``, and the input and
    output sizes of one image."""
    dims, h = cfg["dims"], cfg["input_hw"] // STEM
    out = [{"kind": "stem", "k": STEM, "h_in": cfg["input_hw"], "c_in": 3,
            "h_out": h, "c_out": dims[0]}]
    for i, (d, depth) in enumerate(zip(dims, cfg["depths"])):
        if i:
            out.append({"kind": "down", "k": DOWN, "h_in": h,
                        "c_in": dims[i - 1], "h_out": h // DOWN,
                        "c_out": d})
            h //= DOWN
        for _ in range(depth):
            out.append({"kind": "dwconv", "k": KERNEL, "h_in": h,
                        "c_in": d, "h_out": h, "c_out": d})
            for c_in, c_out in ((d, MLP_RATIO * d), (MLP_RATIO * d, d)):
                out.append({"kind": "pointwise", "k": 1, "h_in": h,
                            "c_in": c_in, "h_out": h, "c_out": c_out})
    out.append({"kind": "head", "k": 1, "h_in": 1, "c_in": dims[-1],
                "h_out": 1, "c_out": cfg["classes"]})
    return out


def weight_size(layer: dict) -> int:
    k2 = layer["k"] ** 2
    if layer["kind"] == "dwconv":
        return k2 * layer["c_in"]
    return k2 * layer["c_in"] * layer["c_out"]


def layer_macs(layer: dict) -> int:
    """Multiply-accumulates of one layer's forward pass for one image."""
    return layer["h_out"] ** 2 * weight_size(layer)


def forward_macs(cfg: dict) -> int:
    return sum(layer_macs(l) for l in layer_shapes(cfg))


def param_count(cfg: dict) -> int:
    """Weights and biases of every layer, the LayerNorms (stem, each
    downsample, each block, head) and each block's layer scale."""
    n = 0
    for l in layer_shapes(cfg):
        n += weight_size(l) + l["c_out"]
        if l["kind"] in ("stem", "dwconv"):
            n += 2 * l["c_out"]
        elif l["kind"] in ("down", "head"):
            n += 2 * l["c_in"]
    n += sum(d * depth for d, depth in zip(cfg["dims"], cfg["depths"]))
    return n


def train_flops_per_image(cfg: dict) -> int:
    """Operations a training step requires per image: the forward pass,
    the weight gradients, and the input gradients of every layer but the
    stem (the image needs none).  LayerNorm, GELU and the pools are not
    counted; nothing recomputed is."""
    macs = sum(layer_macs(l) * (2 if l["kind"] == "stem" else 3)
               for l in layer_shapes(cfg))
    return 2 * macs


def _work(layers, batch: int) -> tuple[int, int]:
    """(operations, bytes) of the layers' forward, weight-gradient and
    input-gradient passes over ``batch`` images (no input gradient for
    the stem)."""
    flops = nbytes = 0
    for l in layers:
        m = layer_macs(l) * batch
        x = l["h_in"] ** 2 * l["c_in"] * batch * F32
        y = l["h_out"] ** 2 * l["c_out"] * batch * F32
        w = weight_size(l) * F32
        flops += 2 * m * 2
        nbytes += (x + w + y) + (x + y + w)
        if l["kind"] != "stem":
            flops += 2 * m
            nbytes += y + w + x
    return flops, nbytes


def conv_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) over ``batch`` images of every layer the chip
    runs as a convolution with a window: all but the head."""
    return _work([l for l in layer_shapes(cfg) if l["kind"] != "head"],
                 batch)


def dwconv_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) of the depthwise convolutions."""
    return _work([l for l in layer_shapes(cfg) if l["kind"] == "dwconv"],
                 batch)


def mlp_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) of the blocks' pointwise pairs."""
    return _work([l for l in layer_shapes(cfg)
                  if l["kind"] == "pointwise"], batch)


def conv_weight_shapes(cfg: dict) -> list[tuple[int, int, int, int]]:
    """``(k, k, c_in, c_out)`` of the stem, each downsample and each
    stage's depthwise convolution (``(7, 7, 1, d)``), which mark a Pallas
    kernel for them in the compiled step."""
    shapes = []
    for l in layer_shapes(cfg):
        if l["kind"] in ("stem", "down"):
            shape = (l["k"], l["k"], l["c_in"], l["c_out"])
        elif l["kind"] == "dwconv":
            shape = (l["k"], l["k"], 1, l["c_out"])
        else:
            continue
        if shape not in shapes:
            shapes.append(shape)
    return shapes
