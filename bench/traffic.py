"""The traffic generator: the images and labels a training cell feeds.

A traffic mix is a JSON file under ``bench/traffic/``: the training job's
parameters (sync mode, staleness, global batch, logical shards, steps per
superstep, workers, kernel path).  This module reads it and renders the
configuration's ``train_images`` images from the run's seed.

``render`` is a copy of the synthetic-MNIST renderer the program ships
(29x29 digit glyphs from a 7x5 stroke font with affine jitter and noise),
kept here so that the inputs of every benchmark run stay fixed whatever a
later change does to the program's own data module.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: keys every traffic file states
KEYS = ("sync", "staleness", "batch", "logical_shards", "superstep",
        "workers", "use_kernel")

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


def load(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    missing = [k for k in KEYS if k not in spec]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    if spec["batch"] % spec["logical_shards"]:
        raise ValueError(f"traffic {name!r}: logical_shards must divide "
                         f"the batch")
    if spec["logical_shards"] % spec["workers"]:
        raise ValueError(f"traffic {name!r}: workers must divide "
                         f"logical_shards")
    return spec


def steps_per_epoch(cfg: dict, spec: dict) -> int:
    """Steps that cover the rendered images once."""
    return max(cfg["train_images"] // spec["batch"], 1)


def total_steps(cfg: dict, spec: dict) -> int:
    """The schedule's length: ``epochs`` passes over the rendered images
    (the paper trains 70 epochs, the rate falling by 0.9 each)."""
    return cfg["epochs"] * steps_per_epoch(cfg, spec)


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
