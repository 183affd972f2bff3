"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked form).

The wkv state-passing recurrence is the compute hot-spot of the
attention-free archs (rwkv6-1.6b), which the flash-attention kernel does
not cover.  Same chunked math as ``models/rwkv6.wkv_chunked`` (the oracle),
but the per-chunk (Q,Q) score tile, the (Q,D) rescale tensors, and the
(D,D) running state all stay in VMEM:

  grid = (B, H, T/Q)      -- chunk index innermost, sequential on TPU
  scratch: S (D, D) f32   -- the recurrence state, carried across chunks
  per chunk:
    seg   = cumsum(log w)                        (Q, D)
    y     = tril(-1)[ (r e^{seg-lw}) (k e^{-seg})^T ] v   intra-chunk
          + ((r*u*k).1) * v                      bonus diagonal
          + (r e^{seg-lw}) S                     inter-chunk
    S     = diag(e^{seg_last}) S + (k e^{seg_last - seg})^T v

Q = D = 64 tiles keep everything MXU-aligned and well under VMEM.
Decay logs are clamped upstream (models/rwkv6._time_mix) so e^{-seg} is
finite in f32 for Q = 64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_sc, *, Q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    r = r_ref[0, 0].astype(jnp.float32)   # (Q, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # (D,)

    lw = jnp.log(jnp.clip(w, 1e-12))
    seg = jnp.cumsum(lw, axis=0)          # (Q, D)
    ri = r * jnp.exp(seg - lw)            # e^{seg_{i-1}}
    kj = k * jnp.exp(-seg)

    att = jax.lax.dot_general(ri, kj, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    att = jnp.where(jj < ii, att, 0.0)    # strictly causal within chunk

    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True)
    y = y + bonus * v
    y = y + jax.lax.dot_general(ri, s_sc[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    wj = jnp.exp(seg[-1][None, :] - seg)  # (Q, D)
    s_new = (s_sc[...] * jnp.exp(seg[-1])[:, None]
             + jax.lax.dot_general(k * wj, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    s_sc[...] = s_new
    o_ref[0, 0] = y.astype(o_ref.dtype)


def wkv6_chunked(r, k, v, w, u, *, chunk: int = 64, interpret: bool):
    """r, k, v, w: (B, T, H, D); w = decay in (0,1); u: (H, D).
    Returns y: (B, T, H, D).  T must be a multiple of `chunk`."""
    B, T, H, D = r.shape
    assert T % chunk == 0, (T, chunk)
    nC = T // chunk

    def bhtd(x):  # (B,T,H,D) -> (B,H,T,D)
        return x.transpose(0, 2, 1, 3)

    kern = functools.partial(_wkv_kernel, Q=chunk)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nC),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, D), lambda b, h, c: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(bhtd(r), bhtd(k), bhtd(v), bhtd(w), u)
    return out.transpose(0, 2, 1, 3)
