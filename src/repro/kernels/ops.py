"""jit'd public wrappers for the Pallas kernels, with custom VJPs so the
training path (the paper's hot-spot: conv backprop, Table 5) also runs
through Pallas — and through the autotuner's block configs (DESIGN.md
§Kernels).

Per conv layer per train step this issues exactly TWO pallas_call launches:
one fused forward (conv + bias + tanh) and one fused backward (dx + dw + db
from a single pass, dtanh folded in), down from three with the split
fwd/dx/dw kernels.

The kernels run interpreted (their bodies executed as jnp ops) only on the
CPU backend, where the tests run; on any other backend they are compiled.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import autotune as AT
from repro.kernels import conv2d as K
from repro.kernels import fc as FC
from repro.kernels import pool as P


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _fwd_cfg(x, w, variant="plain"):
    return AT.get_conv_fwd_config(x.shape, w.shape, x.dtype,
                                  interpret=_interpret(), variant=variant)


def _bwd_cfg(x, w, variant="plain"):
    return AT.get_conv_bwd_config(x.shape, w.shape, x.dtype,
                                  interpret=_interpret(), variant=variant)


# ---------------------------------------------------------------------------
# Plain valid conv (no epilogue) — kept for callers that fuse nothing
# ---------------------------------------------------------------------------
@jax.custom_vjp
def conv2d_valid(x, w):
    """Valid conv, stride 1, NHWC x HWIO -> NHWC.  Pallas forward+backward,
    autotuned block sizes, fused single-launch backward."""
    return K.conv2d_fwd(x, w, interpret=_interpret(), **_fwd_cfg(x, w))


def _cv_fwd(x, w):
    return conv2d_valid(x, w), (x, w)


def _cv_bwd(res, dy):
    x, w = res
    dx, dw, _db = K.conv2d_bwd_fused(x, dy, w, interpret=_interpret(),
                                     **_bwd_cfg(x, w))
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv2d_valid.defvjp(_cv_fwd, _cv_bwd)


# ---------------------------------------------------------------------------
# Fused conv + bias + tanh — the CNN layer op (models/cnn.py hot path)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def conv2d_bias_tanh(x, w, b):
    """tanh(conv2d_valid(x, w) + b) in one forward launch; the backward is
    one launch too (dtanh + dx + dw + db fused)."""
    return K.conv2d_fwd(x, w, b, activation="tanh", interpret=_interpret(),
                        **_fwd_cfg(x, w, "bias_tanh"))


def _cbt_fwd(x, w, b):
    y = conv2d_bias_tanh(x, w, b)
    return y, (x, w, b, y)


def _cbt_bwd(res, dy):
    x, w, b, y = res
    dx, dw, db = K.conv2d_bwd_fused(x, dy, w, y, interpret=_interpret(),
                                    **_bwd_cfg(x, w, "dtanh"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


conv2d_bias_tanh.defvjp(_cbt_fwd, _cbt_bwd)


# ---------------------------------------------------------------------------
# Max pooling (stride == window, VALID) — Pallas both ways
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(1,))
def maxpool2d(x, k: int):
    """Max pool with window k, stride k, VALID; Pallas forward + backward."""
    return P.maxpool2d_fwd(x, k, interpret=_interpret())


def _mp_fwd(x, k):
    y = maxpool2d(x, k)
    return y, (x, y)


def _mp_bwd(k, res, dy):
    x, y = res
    return (P.maxpool2d_bwd(x, y, dy, k, interpret=_interpret()),)


maxpool2d.defvjp(_mp_fwd, _mp_bwd)


# ---------------------------------------------------------------------------
# Fused FC layers (matmul + bias [+ tanh]) — the CNN tail (kernels/fc.py)
# ---------------------------------------------------------------------------
def _fcf_cfg(x, w, variant="plain"):
    return AT.get_fc_fwd_config(x.shape, w.shape, x.dtype,
                                interpret=_interpret(), variant=variant)


def _fcb_cfg(x, w, variant="plain"):
    return AT.get_fc_bwd_config(x.shape, w.shape, x.dtype,
                                interpret=_interpret(), variant=variant)


@jax.custom_vjp
def fc_bias_tanh(x, w, b):
    """tanh(x @ w + b) in one forward launch; one fused backward launch
    (dtanh + dx + dw + db)."""
    return FC.fc_fwd(x, w, b, activation="tanh", interpret=_interpret(),
                     **_fcf_cfg(x, w, "bias_tanh"))


def _fbt_fwd(x, w, b):
    y = fc_bias_tanh(x, w, b)
    return y, (x, w, b, y)


def _fbt_bwd(res, dy):
    x, w, b, y = res
    dx, dw, db = FC.fc_bwd_fused(x, dy, w, y, interpret=_interpret(),
                                 **_fcb_cfg(x, w, "dtanh"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


fc_bias_tanh.defvjp(_fbt_fwd, _fbt_bwd)


@jax.custom_vjp
def fc_bias(x, w, b):
    """x @ w + b (linear output layer) — fused forward, fused backward."""
    return FC.fc_fwd(x, w, b, activation=None, interpret=_interpret(),
                     **_fcf_cfg(x, w, "plain"))


def _fb_fwd(x, w, b):
    return fc_bias(x, w, b), (x, w, b)


def _fb_bwd(res, dy):
    x, w, b = res
    dx, dw, db = FC.fc_bwd_fused(x, dy, w, interpret=_interpret(),
                                 **_fcb_cfg(x, w, "plain"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


fc_bias.defvjp(_fb_fwd, _fb_bwd)


# ---------------------------------------------------------------------------
# Saved-activation backward entry points (models/cnn.py shard tape)
# ---------------------------------------------------------------------------
# The worker-mesh bucket tape checkpoints every layer's output during its
# forward pass, so its backward can call the fused backward kernels
# DIRECTLY with the saved activations instead of re-linearising the layer
# (``jax.vjp`` re-runs the forward to rebuild residuals).  These are the
# exact same kernel launches the custom-VJP wrappers above issue — same
# configs, same casts — so the tape's gradients stay bit-comparable.


def conv2d_bias_tanh_bwd(x, w, b, y, dy):
    """Fused (dx, dw, db) for ``conv2d_bias_tanh`` from the saved output
    ``y`` — one launch, no forward recompute."""
    dx, dw, db = K.conv2d_bwd_fused(x, dy, w, y, interpret=_interpret(),
                                    **_bwd_cfg(x, w, "dtanh"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


def fc_bias_tanh_bwd(x, w, b, y, dy):
    """Fused (dx, dw, db) for ``fc_bias_tanh`` from the saved output."""
    dx, dw, db = FC.fc_bwd_fused(x, dy, w, y, interpret=_interpret(),
                                 **_fcb_cfg(x, w, "dtanh"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


def fc_bias_bwd(x, w, b, dy):
    """Fused (dx, dw, db) for the linear ``fc_bias`` output layer."""
    dx, dw, db = FC.fc_bwd_fused(x, dy, w, interpret=_interpret(),
                                 **_fcb_cfg(x, w, "plain"))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


def maxpool2d_vjp_saved(x, y, dy, k: int):
    """``maxpool2d`` backward from the saved (x, y) pair — the same single
    Pallas launch the custom VJP issues."""
    return P.maxpool2d_bwd(x, y, dy, k, interpret=_interpret())


# ---------------------------------------------------------------------------
# Fused softmax-cross-entropy: per-sample loss, dlogits saved as residual
# so the backward costs ZERO extra launches
# ---------------------------------------------------------------------------
@jax.custom_vjp
def softmax_xent(logits, labels):
    """Per-sample CE loss (B,) for logits (B, C) and int labels (B,)."""
    loss, _ = FC.softmax_xent_fwd(logits, labels, interpret=_interpret())
    return loss


def _sx_fwd(logits, labels):
    loss, dl = FC.softmax_xent_fwd(logits, labels, interpret=_interpret())
    return loss, (dl, labels.shape)


def _sx_bwd(res, g):
    dl, lab_shape = res
    # labels are integer-valued: their cotangent is the symbolic float0 zero
    return (dl * g[:, None].astype(dl.dtype),
            np.zeros(lab_shape, dtype=jax.dtypes.float0))


softmax_xent.defvjp(_sx_fwd, _sx_bwd)
