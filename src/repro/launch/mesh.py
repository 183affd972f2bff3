"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (TPU v5e pod); multi-pod adds a
leading pure-DP "pod" axis (2 x 256 = 512 chips).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the worker step and the elastic
    restore place arrays with ``with_sharding_constraint`` and gather over
    the worker axis, which Explicit axes (the default) reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(n: int = None, axis: str = "workers"):
    """1-D mesh over available (possibly forced-host) devices, for the
    CHAOS worker-model runs and tests."""
    devs = jax.devices()
    n = n or len(devs)
    if n > len(devs):
        raise ValueError(
            f"requested a {n}-worker mesh but only {len(devs)} "
            f"{devs[0].platform} device(s) are visible"
            + cpu_device_hint(n))
    return auto_mesh((n,), (axis,), devices=devs[:n])


def cpu_device_hint(n: int) -> str:
    """How to get ``n`` devices — advice that only applies on the CPU."""
    if jax.default_backend() != "cpu":
        return ""
    return (f"; set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"in the environment BEFORE jax initialises to force {n} host "
            f"devices")
