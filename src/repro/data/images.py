"""Seeded synthetic images at a real dataset's shape and dtype.

``make_images(n, hw, classes, seed)`` gives ``n`` uint8 RGB images of
``hw`` x ``hw`` and their labels: uniform noise in [0, 128) plus a tint in
[0, 128) per class and channel, so the class is learnable from the mean
colour.  Made in bulk from one generator, about a second for 2,048
images of 224x224.
"""
from __future__ import annotations

import numpy as np


def make_images(n: int, hw: int, classes: int, seed: int = 0):
    """(images (n, hw, hw, 3) uint8, labels (n,) int32)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=n).astype(np.int32)
    tint = rng.integers(0, 128, size=(classes, 3), dtype=np.uint8)
    noise = np.frombuffer(rng.bytes(n * hw * hw * 3), np.uint8)
    images = (noise >> 1).reshape(n, hw, hw, 3)
    images += tint[labels][:, None, None, :]
    return images, labels
