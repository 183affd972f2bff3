"""The Table-2 CNN family (Viebke et al. 2017): what the harness knows of a
configuration whose file says ``"family": "table2_cnn"``.

``bench/run.py`` loads a family module by the name the configuration file
gives, as it loads references and metric readers, and reaches the model
only through these functions:

* ``arch(cfg, traffic)``: the program's model configuration for the file
  and the traffic's kernel path, checked against the file; raises
  ``ValueError`` naming what differs.
* ``inputs(cfg, seed)``: the training set made from the seed, a tuple of
  host arrays (here images and labels); the reference is handed the same
  tuple.
* ``pipeline(cfg, traffic, seed, data)``: the program's pipeline that the
  feed draws supersteps from.
* ``total_steps(cfg, traffic)``: the length of the optimizer's schedule.
* ``train_flops_per_image(cfg)``: operations a training step requires per
  image (``mfu``).
* ``conv_work(cfg, batch)``: operations and bytes of the convolution
  layers over ``batch`` images (``conv_roofline``).
* ``conv_weight_shapes(cfg)``: the conv layers' weight shapes, which mark
  a Pallas conv kernel in the compiled step (``devtrace.hlo_classes``).

A configuration states the sizes of a Table-2 CNN: ``input_hw`` (square
images of one channel), ``classes`` and ``layers``: ``["conv", maps,
kernel]``, ``["pool", kernel]`` or ``["fc", units]``; an output layer of
``classes`` units follows the last.  Convolutions are valid with stride 1,
pools are non-overlapping.  A multiply-accumulate is two operations.

The inputs are a copy of the synthetic-MNIST renderer the program ships
(29x29 digit glyphs from a 7x5 stroke font with affine jitter and noise),
kept here so that the inputs of every benchmark run stay fixed whatever a
later change does to the program's own data module.
"""
from __future__ import annotations

import numpy as np

F32 = 4
#: the side of the images ``render`` makes
RENDER_HW = 29


def arch(cfg: dict, traffic: dict):
    import dataclasses

    import repro.configs as C

    a = C.get(cfg["arch"])
    layers = [list(l) for l in a.cnn_layers]
    if layers != cfg["layers"] or a.n_classes != cfg["classes"]:
        raise ValueError(f"the program's {cfg['arch']} ({layers}) is not "
                         f"the configuration's ({cfg['layers']})")
    hw = cfg["input_hw"]
    if tuple(a.cnn_input) != (hw, hw) or hw != RENDER_HW:
        raise ValueError(f"{cfg['arch']} takes {a.cnn_input} images; the "
                         f"configuration states {cfg['input_hw']} and the "
                         f"renderer makes {RENDER_HW}")
    if traffic["use_kernel"]:
        a = dataclasses.replace(a, use_kernel=True)
    return a


def inputs(cfg: dict, seed: int):
    """(images (n, 29, 29, 1) float32 in [0, 1], labels (n,) int32) for the
    configuration's ``train_images``."""
    return render(cfg["train_images"], seed)


def pipeline(cfg: dict, traffic: dict, seed: int, data):
    """The shared queue of ``data`` the program's ``_train`` feeds from."""
    from repro.data.pipeline import ImagePipeline

    images, labels = data
    return ImagePipeline(images, labels, batch=traffic["batch"], seed=seed,
                         sample_mode="queue")


def total_steps(cfg: dict, traffic: dict) -> int:
    """``epochs`` passes over the rendered images (the paper trains 70
    epochs, the rate falling by 0.9 each)."""
    return cfg["epochs"] * max(cfg["train_images"] // traffic["batch"], 1)


# 7x5 bitmap font for digits 0-9
_FONT = {
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00010", "00100", "01000", "11111"],
    3: ["01110", "10001", "00001", "00110", "00001", "10001", "01110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}

_GLYPHS = np.stack([
    np.array([[int(c) for c in row] for row in _FONT[d]], np.float32)
    for d in range(10)])  # (10, 7, 5)


def _render_one(digit: int, rng: np.random.Generator) -> np.ndarray:
    g = _GLYPHS[digit]
    # upsample 7x5 -> 21x15 and place on 28x28 with jitter
    img = np.kron(g, np.ones((3, 3), np.float32))
    canvas = np.zeros((28, 28), np.float32)
    oy = 3 + rng.integers(-2, 3)
    ox = 6 + rng.integers(-3, 4)
    # shear: shift rows by up to +-2 px progressively
    shear = rng.uniform(-0.12, 0.12)
    out = np.zeros_like(img)
    for r in range(img.shape[0]):
        shift = int(round(shear * (r - img.shape[0] / 2)))
        out[r] = np.roll(img[r], shift)
    h, w = out.shape
    canvas[oy:oy + h, ox:ox + w] = out
    # stroke-weight variation + blur-ish noise
    canvas = np.clip(canvas * rng.uniform(0.75, 1.0), 0, 1)
    canvas += rng.normal(0, 0.08, canvas.shape).astype(np.float32)
    return np.clip(canvas, 0.0, 1.0)


def render(n: int, seed: int):
    """(images (n, 29, 29, 1) float32 in [0, 1], labels (n,) int32)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    images = np.zeros((n, 29, 29, 1), np.float32)
    for i in range(n):
        images[i, :28, :28, 0] = _render_one(int(labels[i]), rng)
    return images, labels


def layer_shapes(cfg: dict) -> list[dict]:
    """One dict per parameterised or pooling layer, in forward order, with
    the input and output sizes of one image."""
    h, c = cfg["input_hw"], 1
    out = []
    for spec in cfg["layers"]:
        kind = spec[0]
        if kind == "conv":
            _, maps, k = spec
            ho = h - k + 1
            out.append({"kind": "conv", "k": k, "h_in": h, "c_in": c,
                        "h_out": ho, "c_out": maps})
            h, c = ho, maps
        elif kind == "pool":
            _, k = spec
            out.append({"kind": "pool", "k": k, "h_in": h, "c_in": c,
                        "h_out": h // k, "c_out": c})
            h = h // k
        elif kind == "fc":
            _, units = spec
            out.append({"kind": "fc", "n_in": h * h * c, "n_out": units})
            h, c = 1, units
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    out.append({"kind": "fc", "n_in": h * h * c, "n_out": cfg["classes"]})
    return out


def layer_macs(layer: dict) -> int:
    """Multiply-accumulates of one layer's forward pass for one image."""
    if layer["kind"] == "conv":
        return (layer["h_out"] ** 2 * layer["k"] ** 2 * layer["c_in"]
                * layer["c_out"])
    if layer["kind"] == "fc":
        return layer["n_in"] * layer["n_out"]
    return 0


def forward_macs(cfg: dict) -> int:
    return sum(layer_macs(l) for l in layer_shapes(cfg))


def param_count(cfg: dict) -> int:
    n = 0
    for l in layer_shapes(cfg):
        if l["kind"] == "conv":
            n += l["k"] ** 2 * l["c_in"] * l["c_out"] + l["c_out"]
        elif l["kind"] == "fc":
            n += l["n_in"] * l["n_out"] + l["n_out"]
    return n


def train_flops_per_image(cfg: dict) -> int:
    """Operations a training step requires per image: the forward pass,
    the weight gradients, and the input gradients of every layer but the
    first (the image needs none).  Nothing recomputed is counted."""
    layers = [l for l in layer_shapes(cfg) if l["kind"] != "pool"]
    macs = 0
    for i, l in enumerate(layers):
        m = layer_macs(l)
        macs += 2 * m if i == 0 else 3 * m
    return 2 * macs


def conv_work(cfg: dict, batch: int) -> tuple[int, int]:
    """(operations, bytes) of every convolution layer's forward, weight
    gradient and input gradient (not for the first layer) over ``batch``
    images, in float32.  Bytes count each pass's inputs read and output
    written once: x, w -> y; x, dy -> dw; dy, w -> dx."""
    flops = nbytes = 0
    for i, l in enumerate(layer_shapes(cfg)):
        if l["kind"] != "conv":
            continue
        m = layer_macs(l) * batch
        x = l["h_in"] ** 2 * l["c_in"] * batch * F32
        y = l["h_out"] ** 2 * l["c_out"] * batch * F32
        w = l["k"] ** 2 * l["c_in"] * l["c_out"] * F32
        flops += 2 * m * 2                  # forward + weight gradient
        nbytes += (x + w + y) + (x + y + w)
        if i > 0:                           # the image needs no gradient
            flops += 2 * m
            nbytes += y + w + x
    return flops, nbytes


def conv_weight_shapes(cfg: dict) -> list[tuple[int, int, int, int]]:
    """``(k, k, c_in, c_out)`` of each conv layer, in forward order."""
    return [(l["k"], l["k"], l["c_in"], l["c_out"])
            for l in layer_shapes(cfg) if l["kind"] == "conv"]
