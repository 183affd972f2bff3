"""Operations and bytes of a Table-2 CNN, computed from its layer list.

The layer list is the configuration file's ``layers``: ``["conv", maps,
kernel]``, ``["pool", kernel]`` or ``["fc", units]``, after a
``input_hw x input_hw x 1`` image; an output layer of ``classes`` units
follows the last.  Convolutions are valid with stride 1, pools are
non-overlapping.  A multiply-accumulate is two operations.
"""
from __future__ import annotations

F32 = 4


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
