"""Pallas max-pool kernels so the CNN hot path (conv -> tanh -> pool) runs
end-to-end through Pallas in both directions (DESIGN.md §Kernels).

VALID pooling with stride == window (the paper's nets): output spatial dims
floor to ``H // k``; trailing rows/cols that don't fill a window are cropped
(forward) and receive zero gradient (backward), matching
``lax.reduce_window``.

Tie semantics in the backward: XLA's select-and-scatter routes the whole
gradient to the first maximum; this kernel splits it evenly across tied
maxima.  Both are valid subgradients.  They agree whenever the window max
is unique — true almost surely for well-scaled conv+tanh activations, but
NOT when tanh saturates (fp32 tanh returns exactly +/-1.0 for |z| >~ 8.6,
so saturated windows do tie); expect a bounded gradient divergence from
the XLA path in that regime, not an error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv2d import _divisor_block, record_launch


def _maxpool_fwd_kernel(x_ref, o_ref, *, k: int, Ho: int, Wo: int):
    x = x_ref[...]                       # (bb, H, W, C)
    bb, C = x.shape[0], x.shape[3]
    xc = x[:, :Ho * k, :Wo * k, :].reshape(bb, Ho, k, Wo, k, C)
    o_ref[...] = jnp.max(xc, axis=(2, 4)).astype(o_ref.dtype)


def maxpool2d_fwd(x, k: int, *, batch_block: int = 8,
                  interpret: bool):
    B, H, W, C = x.shape
    Ho, Wo = H // k, W // k
    bb = _divisor_block(B, batch_block)
    record_launch("maxpool2d_fwd")
    return pl.pallas_call(
        functools.partial(_maxpool_fwd_kernel, k=k, Ho=Ho, Wo=Wo),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, H, W, C), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((bb, Ho, Wo, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, C), x.dtype),
        interpret=interpret,
    )(x)


def _maxpool_bwd_kernel(x_ref, y_ref, dy_ref, dx_ref, *, k: int, Ho: int,
                        Wo: int):
    x = x_ref[...]                       # (bb, H, W, C)
    bb, H, W, C = x.shape
    xc = x[:, :Ho * k, :Wo * k, :].reshape(bb, Ho, k, Wo, k, C)
    y = y_ref[...][:, :, None, :, None, :]        # (bb, Ho, 1, Wo, 1, C)
    mask = (xc == y).astype(jnp.float32)
    ties = jnp.sum(mask, axis=(2, 4), keepdims=True)
    dxc = mask * (dy_ref[...][:, :, None, :, None, :].astype(jnp.float32)
                  / ties)
    dxc = dxc.reshape(bb, Ho * k, Wo * k, C)
    dx_ref[...] = jnp.pad(
        dxc, ((0, 0), (0, H - Ho * k), (0, W - Wo * k), (0, 0))
    ).astype(dx_ref.dtype)


def maxpool2d_bwd(x, y, dy, k: int, *, batch_block: int = 8,
                  interpret: bool):
    """dx for maxpool2d_fwd; one pallas_call, gradient split across ties."""
    B, H, W, C = x.shape
    Ho, Wo = H // k, W // k
    bb = _divisor_block(B, batch_block)
    record_launch("maxpool2d_bwd")
    return pl.pallas_call(
        functools.partial(_maxpool_bwd_kernel, k=k, Ho=Ho, Wo=Wo),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, H, W, C), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((bb, Ho, Wo, C), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((bb, Ho, Wo, C), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, H, W, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
        interpret=interpret,
    )(x, y, dy)
