"""Pallas kernels for the FC + softmax tail of the paper's CNNs.

The conv trunk got the tiling/fusion/autotune treatment in the first
kernel pass (Table 5: conv backprop is 88% of step time); the FC layers
and the softmax output are the remaining hot fraction, and Krizhevsky's
"one weird trick" (arXiv:1404.5997) argues they deserve their own
treatment.  Three kernels:

``fc_fwd``           y = act(x @ w + b) in one launch — the matmul runs on
                     the MXU with an fp32 accumulator, the bias + tanh
                     epilogue stays in-register.

``fc_bwd_fused``     dx, dw AND db from one launch (the dtanh factor fused
                     when the forward activations are supplied): dz shares
                     one VMEM residency for all three products; dw/db
                     accumulate across batch-grid steps in fp32 scratch,
                     the same sequential-grid pattern as the conv backward.

``softmax_xent_fwd`` per-sample CE loss and dlogits (softmax - onehot)
                     from one pass over the logits: the backward of the
                     loss costs zero extra launches (dlogits is saved as
                     the residual).

Grids are (batch-block × dout-block) forward and (batch-block,) backward;
block sizes come from ``kernels/autotune.py`` like the conv kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv2d import _divisor_block, record_launch


# ---------------------------------------------------------------------------
# Forward: fused matmul + bias + tanh epilogue
# ---------------------------------------------------------------------------
def _fc_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, activation: str | None):
    x = x_ref[...]                       # (bb, Din)
    w = w_ref[...]                       # (Din, db)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    acc += b_ref[...].astype(jnp.float32)          # (1, db)
    if activation == "tanh":
        acc = jnp.tanh(acc)
    o_ref[...] = acc.astype(o_ref.dtype)


def fc_fwd(x, w, bias=None, *, activation: str | None = None,
           batch_block: int = 8, dout_block: int | None = None,
           interpret: bool):
    """act(x @ w + b); x: (B, Din), w: (Din, Dout), b: (Dout,) -> (B, Dout).

    Grid is (B/bb, Dout/db); each step holds an x row block, a w column
    block, and the fp32 accumulator for its output tile in VMEM.
    """
    B, Din = x.shape
    _, Dout = w.shape
    bb = _divisor_block(B, batch_block)
    db = _divisor_block(Dout, dout_block)
    b2 = (jnp.zeros((Dout,), x.dtype) if bias is None else bias).reshape(
        1, Dout)
    record_launch("fc_fwd")
    return pl.pallas_call(
        functools.partial(_fc_fwd_kernel, activation=activation),
        grid=(B // bb, Dout // db),
        in_specs=[
            pl.BlockSpec((bb, Din), lambda i, j: (i, 0)),
            pl.BlockSpec((Din, db), lambda i, j: (0, j)),
            pl.BlockSpec((1, db), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, db), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Dout), x.dtype),
        interpret=interpret,
    )(x, w, b2)


# ---------------------------------------------------------------------------
# Fused backward: dx + dw + db (+ dtanh) from ONE launch
# ---------------------------------------------------------------------------
def _fc_bwd_body(x, dz, w, dx_ref, dw_ref, db_ref, dw_acc, db_acc):
    """``x``: (bb, Din), ``dz``: (bb, Dout) fp32 (dtanh already applied
    when fusing), ``w``: (Din, Dout)."""
    first = pl.program_id(0) == 0
    last = pl.program_id(0) == pl.num_programs(0) - 1

    @pl.when(first)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    dx_ref[...] = jnp.dot(dz, w.T.astype(jnp.float32),
                          preferred_element_type=jnp.float32
                          ).astype(dx_ref.dtype)
    dw_acc[...] += jnp.dot(x.T.astype(jnp.float32), dz,
                           preferred_element_type=jnp.float32)
    db_acc[...] += jnp.sum(dz, axis=0, keepdims=True)

    @pl.when(last)
    def _flush():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)
        db_ref[...] = db_acc[...].astype(db_ref.dtype)


def _fc_bwd_kernel(x_ref, dy_ref, w_ref, dx_ref, dw_ref, db_ref,
                   dw_acc, db_acc):
    _fc_bwd_body(x_ref[...], dy_ref[...].astype(jnp.float32), w_ref[...],
                 dx_ref, dw_ref, db_ref, dw_acc, db_acc)


def _fc_bwd_tanh_kernel(x_ref, dy_ref, y_ref, w_ref, dx_ref, dw_ref, db_ref,
                        dw_acc, db_acc):
    y = y_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * (1.0 - y * y)
    _fc_bwd_body(x_ref[...], dz, w_ref[...], dx_ref, dw_ref, db_ref,
                 dw_acc, db_acc)


def fc_bwd_fused(x, dy, w, y=None, *, batch_block: int = 8,
                 interpret: bool):
    """One pallas_call -> (dx, dw, db) for the fused FC layer.

    ``y`` (the forward tanh output) fuses the dtanh factor in-kernel; with
    ``y=None`` the upstream gradient is used as-is (linear output layer).
    Grid is (B/bb,); dw/db accumulate across batch blocks in fp32 scratch.
    """
    B, Din = x.shape
    _, Dout = w.shape
    bb = _divisor_block(B, batch_block)
    in_specs = [
        pl.BlockSpec((bb, Din), lambda b: (b, 0)),
        pl.BlockSpec((bb, Dout), lambda b: (b, 0)),
    ]
    inputs = [x, dy]
    if y is not None:
        in_specs.append(pl.BlockSpec((bb, Dout), lambda b: (b, 0)))
        inputs.append(y)
        kern = _fc_bwd_tanh_kernel
    else:
        kern = _fc_bwd_kernel
    in_specs.append(pl.BlockSpec((Din, Dout), lambda b: (0, 0)))
    inputs.append(w)
    record_launch("fc_bwd_fused")
    dx, dw, db = pl.pallas_call(
        kern,
        grid=(B // bb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, Din), lambda b: (b, 0)),
            pl.BlockSpec((Din, Dout), lambda b: (0, 0)),
            pl.BlockSpec((1, Dout), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Din), x.dtype),
            jax.ShapeDtypeStruct((Din, Dout), jnp.float32),
            jax.ShapeDtypeStruct((1, Dout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Din, Dout), jnp.float32),
            pltpu.VMEM((1, Dout), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return dx, dw, db.reshape(Dout)


# ---------------------------------------------------------------------------
# Fused softmax + cross-entropy: (loss, dlogits) in one pass
# ---------------------------------------------------------------------------
def _softmax_xent_kernel(l_ref, lab_ref, loss_ref, dl_ref):
    l = l_ref[...].astype(jnp.float32)             # (bb, C)
    lab = lab_ref[...]                             # (bb, 1) int32
    m = jnp.max(l, axis=1, keepdims=True)
    e = jnp.exp(l - m)
    s = jnp.sum(e, axis=1, keepdims=True)
    lse = jnp.log(s) + m
    classes = jax.lax.broadcasted_iota(jnp.int32, l.shape, 1)
    onehot = (classes == lab).astype(jnp.float32)
    ll = jnp.sum(l * onehot, axis=1, keepdims=True)
    loss_ref[...] = (lse - ll).astype(loss_ref.dtype)
    dl_ref[...] = (e / s - onehot).astype(dl_ref.dtype)


def softmax_xent_fwd(logits, labels, *, batch_block: int = 8,
                     interpret: bool):
    """Per-sample CE loss and its logits gradient from one launch.

    logits: (B, C), labels: (B,) int -> (loss (B,), dlogits (B, C) where
    dlogits = softmax(logits) - onehot(labels), i.e. d loss_i / d logits_i).
    """
    B, C = logits.shape
    bb = _divisor_block(B, batch_block)
    lab2 = labels.reshape(B, 1).astype(jnp.int32)
    record_launch("softmax_xent")
    loss, dl = pl.pallas_call(
        _softmax_xent_kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, C), lambda b: (b, 0)),
            pl.BlockSpec((bb, 1), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda b: (b, 0)),
            pl.BlockSpec((bb, C), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, C), logits.dtype),
        ],
        interpret=interpret,
    )(logits, lab2)
    return loss.reshape(B), dl
