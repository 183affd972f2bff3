"""Pallas TPU flash-attention forward kernel.

This is the §Perf hillclimb change for the memory-dominant training/prefill
cells: the pure-jnp blockwise attention (models/layers.py) materialises the
per-block score/prob matrices through HBM (XLA cannot fuse across the two
dots), whereas this kernel keeps them in VMEM.

Structure (the canonical TPU pallas flash pattern):
  grid = (B, Hkv*G, n_q_blocks, n_kv_blocks)   -- sequential on TPU
  scratch (VMEM, persists across the innermost kv iterations):
      m (bq, 1), l (bq, 1), acc (bq, D)
  @pl.when(kv_idx == 0)         -> init scratch
  each step: s = q @ k^T, online-softmax update of (m, l, acc)
  @pl.when(kv_idx == nk - 1)    -> out = acc / l

Block sizes: bq x D and bk x D tiles; with bq = bk = 512 and D = 128 the
working set is ~1.3MB in f32 — comfortably inside a v5e core's VMEM, and
the (bq, bk) score tile feeds the MXU at 128-aligned shapes.

Validated in interpret mode against models.layers.flash_attention /
the naive oracle (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
               acc_sc, *, scale: float, causal: bool, block_q: int,
               block_k: int, n_kv: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_off = off_ref[0, 0]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)      # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)      # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # absolute query position: queries live at cache positions
        # [q_off, q_off + Tq) — the causal frontier of a continued sequence
        # sits at q_off + row, NOT at row (the pre-fix bug: a batched
        # prefill starting mid-cache masked every cached key as "future")
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_sc[...] = (acc_sc[...] * corr
                       + jax.lax.dot_general(
                           p, v, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))
        m_sc[...] = m_new
        l_sc[...] = l_new

    if causal:
        # skip fully-masked kv blocks (block start beyond q block end,
        # measured at the ABSOLUTE query position q_off + row)
        @pl.when(ki * block_k <= q_off + qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == n_kv - 1)
    def _done():
        l = l_sc[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        # log-sum-exp of the (scaled) scores per query row — the training
        # backward's softmax denominator (models/layers.py::_flash_bwd
        # recomputes p = exp(s - lse) per block from it, so the kernel
        # forward needs NO jnp-forward recompute in its VJP)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, causal: bool = True, q_offset=0,
                        softmax_scale=None, block_q: int = 512,
                        block_k: int = 512, interpret: bool,
                        return_lse: bool = False):
    """q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D/Dv) — GQA by head grouping.
    Returns (B, Hq, Tq, Dv); with ``return_lse`` also the per-row
    log-sum-exp (B, Hkv, G, Tq) f32 in the models/layers convention (the
    flash backward's residual).

    ``q_offset`` (python int or traced int32 scalar) is the absolute cache
    position of query row 0: the causal mask admits ``k_pos <= q_offset +
    row``, matching ``models/layers.py::flash_attention``.  It rides into
    the kernel as a (1, 1) SMEM scalar, so a traced offset does not change
    compiled shapes (one program serves every cache position)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)

    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Tq + pad_q) // block_q
    nk = (Tk + pad_k) // block_k

    # expand q to (B, Hkv, G*Tq... ) keep heads explicit: fold G into Q rows
    qf = q.reshape(B, Hkv, G, Tq + pad_q, D)

    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv=nk, seq_len=Tk)

    def one_group(qg):  # qg: (B, Hkv, Tq+pad, D)
        return pl.pallas_call(
            kern,
            grid=(B, Hkv, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1), lambda b, h, i, j: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, Dv),
                             lambda b, h, i, j: (b, h, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, Dv),
                             lambda b, h, i, j: (b, h, i, 0)),
                # a (block_q, 1) column: the TPU tiles the two minor dims
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Hkv, Tq + pad_q, Dv), q.dtype),
                jax.ShapeDtypeStruct((B, Hkv, Tq + pad_q, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
            interpret=interpret,
        )(off, qg, k, v)

    outs = [one_group(qf[:, :, g]) for g in range(G)]
    out = jnp.stack([o for o, _ in outs], axis=2)
    out = out.reshape(B, Hq, Tq + pad_q, Dv)[:, :, :Tq]
    if not return_lse:
        return out
    lse = jnp.stack([l[..., 0] for _, l in outs], axis=2)[:, :, :, :Tq]
    return out, lse


# ---------------------------------------------------------------------------
# Training-grade flash attention: Pallas forward + a real backward
# ---------------------------------------------------------------------------
# The forward launches the kernel above with autotuned (block_q, block_k)
# and also emits the per-row log-sum-exp; the custom VJP saves (q, k, v,
# out, lse) — exactly the jnp flash path's residual set — and the backward
# runs the blockwise flash backward from models/layers.py directly, with
# NO forward recompute.  One shared backward implementation keeps the two
# paths' gradients bit-comparable while the kernel carries the forward.

_BWD_BLOCK_K = 1024  # the jnp backward's kv block (layers.py default)


def _ft_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    qT, kT, vT = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = flash_attention_fwd(qT, kT, vT, causal=causal, q_offset=0,
                                   softmax_scale=scale, block_q=block_q,
                                   block_k=block_k, interpret=interpret,
                                   return_lse=True)
    return out.transpose(0, 2, 1, 3), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_train(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _ft_impl(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _ft_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _ft_impl(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _ft_bwd(causal, scale, block_q, block_k, interpret, res, dout):
    from repro.models import layers as L  # deferred: no import cycle
    q, k, v, out, lse = res
    off = jnp.zeros((), jnp.int32)
    dq, dk, dv, _ = L._flash_bwd(causal, _BWD_BLOCK_K, scale,
                                 (q, k, v, out, lse, off), dout)
    return dq, dk, dv


_flash_train.defvjp(_ft_fwd, _ft_bwd)


def flash_attention_train(q, k, v, *, causal: bool = True,
                          softmax_scale=None):
    """Differentiable Pallas flash attention for the LM *training* forward
    (``ArchConfig.use_kernel``): q, k, v in the models/layers (B, T, H, D)
    convention, GQA by head grouping.  Block sizes come from the autotuner
    (``kernels/autotune.py::get_flash_config``, tuned by ``benchmarks/run.py
    --only kernels``), falling back to the 512x512 baseline."""
    from repro.kernels import autotune as AT
    from repro.kernels.ops import _interpret
    B, Tq, Hq, D = q.shape
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(D))
    interp = _interpret()
    q_shape = (B, Hq, Tq, D)
    k_shape = (B, k.shape[2], k.shape[1], k.shape[3])
    cfg = AT.get_flash_config(q_shape, k_shape, q.dtype, interpret=interp)
    return _flash_train(q, k, v, causal, scale, cfg["block_q"],
                        cfg["block_k"], interp)
