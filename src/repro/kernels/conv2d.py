"""Pallas TPU kernels for the paper's hot-spot: convolutional layers.

Hardware adaptation (DESIGN.md §2): the paper vectorises the conv partial-
derivative/weight-gradient loops with 512-bit SIMD + 64-byte-aligned loads.
On TPU the analogue is MXU matmuls over VMEM-resident tiles: each grid step
keeps a tile of the feature maps in VMEM and reduces the KxK shifted windows
with (bb*rb*Wo, Cin) x (Cin, Cout) dots — an implicit-im2col formulation
(kernel taps unrolled, contraction on the channel dim feeds the systolic
array).

Tiling (DESIGN.md §Kernels): the forward grid is 3-D
(batch-block × output-row-block × Cout-block).  Row blocks read a halo of
``K-1`` extra input rows via element-offset (``pl.Element``) block dims, so
feature maps larger than a single VMEM block (e.g. 64x64) stream through in
row slabs instead of requiring the whole image resident.  Block sizes come
from ``kernels/autotune.py`` (or the caller) and must divide the
corresponding dimension; compiled for a TPU they must also be whole or
(8, 128)-tile multiples in the two minor dims.

Fusion: the forward kernel applies a bias + tanh epilogue in-register, and
``conv2d_bwd_fused`` computes dx, dw AND db from ONE shared pass over the
shifted-window patches (with the dtanh factor fused when the forward
activations are supplied) — per-layer backward launches drop from 2 to 1,
which matters because backprop of the conv layers is 88% of the paper's
total time (Table 5).

dw/db accumulate across grid steps in fp32 VMEM scratch, relying on the
TPU's sequential-grid revisiting semantics (tested explicitly for
``batch_block < B`` in tests/test_kernels.py).

Tap packing: where a layer's K*K*Cin taps fit one 128-lane tile (the input
layer, Cin = 1), the per-tap dots above would contract over Cin lanes of
128.  ``conv2d_packed_fwd`` / ``conv2d_packed_bwd`` instead take an
explicit, transposed patch matrix (``tap_patches``: taps along sublanes,
output pixels along lanes, dense in HBM) and do ONE dot per grid step over
all the taps; the backward computes the input gradient only when asked.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# Launch accounting — lets tests assert how many pallas_call launches a
# train step issues (the fusion win is 3 -> 2 per conv layer).
# ---------------------------------------------------------------------------
_ACTIVE_TRACE = None


def record_launch(name: str) -> None:
    if _ACTIVE_TRACE is not None:
        _ACTIVE_TRACE.append(name)


@contextmanager
def launch_trace():
    """Collect the names of Pallas kernel launches issued inside the block."""
    global _ACTIVE_TRACE
    prev, _ACTIVE_TRACE = _ACTIVE_TRACE, []
    try:
        yield _ACTIVE_TRACE
    finally:
        _ACTIVE_TRACE = prev


#: A TPU vector register is an (8, 128) f32 tile over an array's two minor
#: dims.  A block's minor dims must be whole or tile multiples, and a
#: reshape that folds leading dims into the second-minor dim keeps its
#: layout only when that dim is a multiple of SUBLANES.
SUBLANES, LANES = 8, 128


#: Scoped-VMEM limit of the conv kernels.  Mosaic's default is 16 MiB; the
#: block heuristic (kernels/autotune.py) fills at most 12 MiB of its own
#: estimate, but f32 dots at "highest" precision keep ~2.4x that live
#: (conv2's forward: 22.8 MiB for a 9.6 MiB estimate).  A v5e core has
#: 128 MiB of VMEM.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)


def aligned(n: int) -> int:
    """``n`` rounded up to a whole number of sublane tiles."""
    return -(-n // SUBLANES) * SUBLANES


def _divisor_block(n: int, want: int | None) -> int:
    """Largest block size <= ``want`` that divides ``n``."""
    d = n if want is None else max(1, min(want, n))
    while n % d:
        d -= 1
    return d


# ---------------------------------------------------------------------------
# Forward: tiled (batch x row x Cout) grid with fused bias+tanh epilogue
# ---------------------------------------------------------------------------
def _conv_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, K: int, rb: int, Wo: int,
                     activation: str | None):
    x = x_ref[...]        # (bb, rb+K-1, W, Cin) halo'd row slab in VMEM
    w = w_ref[...]        # (K, K, Cin, cb)
    bb, Cin = x.shape[0], x.shape[3]
    cb = w.shape[3]
    acc = jnp.zeros((bb * rb * Wo, cb), jnp.float32)
    for kh in range(K):           # static unroll: K*K MXU dots
        for kw in range(K):
            patch = x[:, kh:kh + rb, kw:kw + Wo, :].reshape(bb * rb * Wo, Cin)
            acc += jnp.dot(patch, w[kh, kw],
                           preferred_element_type=jnp.float32)
    acc += b_ref[...].reshape(1, cb).astype(jnp.float32)
    if activation == "tanh":
        acc = jnp.tanh(acc)
    o_ref[...] = acc.reshape(bb, rb, Wo, cb).astype(o_ref.dtype)


def conv2d_fwd(x, w, bias=None, *, activation: str | None = None,
               batch_block: int = 8, row_block: int | None = None,
               cout_block: int | None = None, interpret: bool):
    """Valid conv, stride 1, NHWC x HWIO -> NHWC, optional fused bias+tanh.

    Grid is (B/bb, Ho/rb, Cout/cb); the x slab for each row block carries a
    K-1 halo (element-offset indexing), so VMEM holds bb*(rb+K-1)*W*Cin
    elements instead of the whole feature map.  The kernel computes
    ``aligned(Wo)`` output columns from an x zero-padded on the right, so
    every (bb, rb, Wo, C) -> (bb*rb*Wo, C) reshape keeps the (8, 128) tile
    layout; the extra columns are cropped on return.
    """
    B, H, W, Cin = x.shape
    K, _, _, Cout = w.shape
    Ho, Wo = H - K + 1, W - K + 1
    bb = _divisor_block(B, batch_block)
    rb = _divisor_block(Ho, row_block)
    cb = _divisor_block(Cout, cout_block)
    Wa = aligned(Wo)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, Wa - Wo), (0, 0)))
    b2 = (jnp.zeros((Cout,), x.dtype) if bias is None else bias).reshape(
        1, Cout)
    kern = functools.partial(_conv_fwd_kernel, K=K, rb=rb, Wo=Wa,
                             activation=activation)
    record_launch("conv2d_fwd")
    out = pl.pallas_call(
        kern,
        grid=(B // bb, Ho // rb, Cout // cb),
        in_specs=[
            # element offsets: row slabs overlap by the K-1 halo
            pl.BlockSpec((pl.Element(bb), pl.Element(rb + K - 1),
                          pl.Element(Wa + K - 1), pl.Element(Cin)),
                         lambda b, r, c: (b * bb, r * rb, 0, 0)),
            pl.BlockSpec((K, K, Cin, cb), lambda b, r, c: (0, 0, 0, c)),
            pl.BlockSpec((1, cb), lambda b, r, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((bb, rb, Wa, cb),
                               lambda b, r, c: (b, r, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wa, Cout), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="conv2d_fwd" + (f"_{activation}" if activation else ""),
    )(xp, w, b2)
    return out[:, :, :Wo]


# ---------------------------------------------------------------------------
# Fused backward: dx + dw + db from ONE pass over the shifted windows
# ---------------------------------------------------------------------------
def _bwd_body(x, dzp, w, dx_ref, dw_ref, db_ref, dw_acc, db_acc, *,
              K: int, rb: int, W: int, Wo: int):
    """Shared backward pass.  ``x``: (bb, rb+K-1, Wo+K-1, Cin) input slab,
    ``dzp``: (bb, rb+K-1, W+K-1, Cout) zero-padded upstream grad slab
    (already multiplied by dtanh when fusing), ``w``: (K, K, Cin, Cout).

    dx rows [r*rb, r*rb+rb) = correlation of dzp with the flipped taps;
    dw/db accumulate this slab's contribution into fp32 VMEM scratch and
    write out on the last grid step (sequential revisiting semantics).
    """
    bb, Cin = x.shape[0], x.shape[3]
    Cout = dzp.shape[3]
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    last = ((pl.program_id(0) == pl.num_programs(0) - 1) &
            (pl.program_id(1) == pl.num_programs(1) - 1))

    @pl.when(first)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    # dx: full-correlation with flipped taps, same MXU dot structure
    acc = jnp.zeros((bb * rb * W, Cin), jnp.float32)
    for kh in range(K):
        for kw in range(K):
            patch = dzp[:, kh:kh + rb, kw:kw + W, :].reshape(
                bb * rb * W, Cout)
            acc += jnp.dot(patch, w[K - 1 - kh, K - 1 - kw].T,
                           preferred_element_type=jnp.float32)
    dx_ref[...] = acc.reshape(bb, rb, W, Cin).astype(dx_ref.dtype)

    # dw/db: the valid (un-padded) dz rows of this slab are [K-1, K-1+rb);
    # rows past Ho fall in dzp's zero padding and contribute nothing.
    dzf = dzp[:, K - 1:K - 1 + rb, K - 1:K - 1 + Wo, :].reshape(
        bb * rb * Wo, Cout)
    db_acc[...] += jnp.sum(dzf, axis=0, keepdims=True)
    for kh in range(K):
        for kw in range(K):
            patch = x[:, kh:kh + rb, kw:kw + Wo, :].reshape(
                bb * rb * Wo, Cin).astype(jnp.float32)
            dw_acc[kh, kw] += jnp.dot(patch.T, dzf,
                                      preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)
        db_ref[...] = db_acc[...].astype(db_ref.dtype)


def _conv_bwd_kernel(xp_ref, dyp_ref, w_ref, dx_ref, dw_ref, db_ref,
                     dw_acc, db_acc, **kw):
    _bwd_body(xp_ref[...], dyp_ref[...].astype(jnp.float32), w_ref[...],
              dx_ref, dw_ref, db_ref, dw_acc, db_acc, **kw)


def _conv_bwd_tanh_kernel(xp_ref, dyp_ref, yp_ref, w_ref, dx_ref, dw_ref,
                          db_ref, dw_acc, db_acc, **kw):
    # dtanh fusion: dz = dy * (1 - y^2); padded entries stay exactly zero.
    y = yp_ref[...].astype(jnp.float32)
    dzp = dyp_ref[...].astype(jnp.float32) * (1.0 - y * y)
    _bwd_body(xp_ref[...], dzp, w_ref[...], dx_ref, dw_ref, db_ref,
              dw_acc, db_acc, **kw)


def conv2d_bwd_fused(x, dy, w, y=None, *, batch_block: int = 8,
                     row_block: int | None = None, interpret: bool):
    """One pallas_call -> (dx, dw, db) for the valid conv.

    ``y`` (the forward tanh output) fuses the dtanh factor in-kernel; with
    ``y=None`` the upstream gradient is used as-is (plain conv backward).
    Grid is (B/bb, H/rb) over *input* rows; dy (and y) arrive zero-padded by
    K-1 so halo reads, out-of-range output rows, and the width correlation
    all fall out of the padding — no in-kernel masking needed.  As in the
    forward, the kernel works on sublane-aligned widths (``aligned(W)`` dx
    columns, ``aligned(Wo)`` dz columns for dw); the extra dz columns are
    zero padding, so they add nothing to dw/db, and the extra dx columns are
    cropped on return.
    """
    B, H, W, Cin = x.shape
    K, _, _, Cout = w.shape
    Ho, Wo = dy.shape[1], dy.shape[2]
    bb = _divisor_block(B, batch_block)
    rb = _divisor_block(H, row_block)
    pad = K - 1
    Wa, Woa = aligned(W), aligned(Wo)
    dz_pad = ((0, 0), (pad, pad), (pad, Wa - Wo), (0, 0))
    dyp = jnp.pad(dy, dz_pad)
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, Woa - Wo), (0, 0)))
    slab = pl.BlockSpec((pl.Element(bb), pl.Element(rb + pad),
                         pl.Element(Wa + pad), pl.Element(Cout)),
                        lambda b, r: (b * bb, r * rb, 0, 0))
    in_specs = [
        pl.BlockSpec((pl.Element(bb), pl.Element(rb + pad),
                      pl.Element(Woa + pad), pl.Element(Cin)),
                     lambda b, r: (b * bb, r * rb, 0, 0)),
        slab,
    ]
    inputs = [xp, dyp]
    if y is not None:
        in_specs.append(slab)
        inputs.append(jnp.pad(y, dz_pad))
        kern, name = _conv_bwd_tanh_kernel, "conv2d_bwd_tanh"
    else:
        kern, name = _conv_bwd_kernel, "conv2d_bwd"
    in_specs.append(pl.BlockSpec((K, K, Cin, Cout),
                                 lambda b, r: (0, 0, 0, 0)))
    inputs.append(w)
    record_launch("conv2d_bwd_fused")
    dx, dw, db = pl.pallas_call(
        functools.partial(kern, K=K, rb=rb, W=Wa, Wo=Woa),
        grid=(B // bb, H // rb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, rb, Wa, Cin), lambda b, r: (b, r, 0, 0)),
            pl.BlockSpec((K, K, Cin, Cout), lambda b, r: (0, 0, 0, 0)),
            pl.BlockSpec((1, Cout), lambda b, r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Wa, Cin), x.dtype),
            jax.ShapeDtypeStruct((K, K, Cin, Cout), jnp.float32),
            jax.ShapeDtypeStruct((1, Cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((K, K, Cin, Cout), jnp.float32),
            pltpu.VMEM((1, Cout), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(*inputs)
    return dx[:, :, :W], dw, db.reshape(Cout)


# ---------------------------------------------------------------------------
# Split backward kernels — kept as the un-fused baseline (benchmarks compare
# against them) and for callers that only need one of the two gradients.
# ---------------------------------------------------------------------------
def _conv_dx_kernel(dy_ref, w_ref, dx_ref, *, K: int, H: int, W: int):
    """dx = full-correlation of dy with w flipped: implemented as the same
    shifted-window MXU reduction over a zero-padded dy block."""
    dy = dy_ref[...]      # (bb, Ho, Wo, Cout)
    w = w_ref[...]        # (K, K, Cin, Cout)
    bb, Ho, Wo, Cout = dy.shape
    Cin = w.shape[2]
    pad = K - 1
    dyp = jnp.pad(dy, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    acc = jnp.zeros((bb * H * W, Cin), jnp.float32)
    for kh in range(K):
        for kw in range(K):
            patch = dyp[:, kh:kh + H, kw:kw + W, :].reshape(bb * H * W, Cout)
            # flipped taps: w[K-1-kh, K-1-kw] transposed (Cout, Cin)
            acc += jnp.dot(patch, w[K - 1 - kh, K - 1 - kw].T,
                           preferred_element_type=jnp.float32)
    dx_ref[...] = acc.reshape(bb, H, W, Cin).astype(dx_ref.dtype)


def conv2d_dx(dy, w, x_shape, *, batch_block: int = 8,
              interpret: bool):
    B, H, W, Cin = x_shape
    K = w.shape[0]
    Ho, Wo = dy.shape[1], dy.shape[2]
    Cout = dy.shape[3]
    bb = _divisor_block(B, batch_block)
    kern = functools.partial(_conv_dx_kernel, K=K, H=H, W=W)
    record_launch("conv2d_dx")
    return pl.pallas_call(
        kern,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, Ho, Wo, Cout), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((K, K, Cin, Cout), lambda b: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, H, W, Cin), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, Cin), dy.dtype),
        interpret=interpret,
        name="conv2d_dx",
    )(dy, w)


def _conv_dw_kernel(x_ref, dy_ref, dw_ref, acc_ref, *, K: int):
    """Weight gradients — the paper's SIMD-vectorised loop (Listing 1).
    Each grid step accumulates a batch-block's contribution into fp32 VMEM
    scratch: dw[kh,kw] += patch^T @ dy (contraction over batch*spatial on
    the MXU); the scratch flushes to the output on the last step."""
    x = x_ref[...]        # (bb, H, W, Cin)
    dy = dy_ref[...]      # (bb, Ho, Wo, Cout)
    bb, Ho, Wo, Cout = dy.shape
    Cin = x.shape[3]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dyf = dy.reshape(bb * Ho * Wo, Cout).astype(jnp.float32)
    for kh in range(K):
        for kw in range(K):
            patch = x[:, kh:kh + Ho, kw:kw + Wo, :].reshape(
                bb * Ho * Wo, Cin).astype(jnp.float32)
            acc_ref[kh, kw] += jnp.dot(patch.T, dyf,
                                       preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def conv2d_dw(x, dy, w_shape, *, batch_block: int = 8,
              interpret: bool):
    B, H, W, Cin = x.shape
    K, _, _, Cout = w_shape
    Ho, Wo = dy.shape[1], dy.shape[2]
    bb = _divisor_block(B, batch_block)
    kern = functools.partial(_conv_dw_kernel, K=K)
    record_launch("conv2d_dw")
    return pl.pallas_call(
        kern,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, H, W, Cin), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((bb, Ho, Wo, Cout), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((K, K, Cin, Cout), lambda b: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, K, Cin, Cout), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K, Cin, Cout), jnp.float32)],
        interpret=interpret,
        name="conv2d_dw",
    )(x, dy)


# ---------------------------------------------------------------------------
# Tap-packed pair: every tap in one contraction (K*K*Cin <= LANES)
# ---------------------------------------------------------------------------
def packs_taps(w_shape) -> bool:
    """Whether the tap-packed pair serves a conv of weight shape
    ``(K, K, Cin, Cout)``: all K*K*Cin taps fit one lane tile."""
    K, _, Cin, _ = w_shape
    return K * K * Cin <= LANES


def tap_patches(x, K: int):
    """The valid conv's patch matrix, transposed: ``(K*K*Cin, B*Ho*Wa)``.

    Row ``(kh*K + kw)*Cin + c`` (the row order of ``w.reshape(-1, Cout)``),
    column ``(b*Ho + h)*Wa + j`` holds ``x[b, h+kh, j+kw, c]``; each output
    row has ``Wa = aligned(Wo)`` columns, the extra ones read zero padding.
    Built from K*K shifted slices, no convolution op.  Taps run along
    sublanes and pixels along lanes, so the matrix is dense in HBM, where a
    ``(pixels, taps)`` one would fill K*K*Cin of every 128 lanes.
    """
    B, H, W, Cin = x.shape
    Ho, Wo = H - K + 1, W - K + 1
    Wa = aligned(Wo)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, Wa - Wo), (0, 0)))
    taps = jnp.stack([xp[:, kh:kh + Ho, kw:kw + Wa, :]
                      for kh in range(K) for kw in range(K)])
    return taps.transpose(0, 4, 1, 2, 3).reshape(K * K * Cin, B * Ho * Wa)


def _packed_fwd_kernel(p_ref, w_ref, b_ref, o_ref, *, Ho: int, Wa: int,
                       activation: str | None):
    w = w_ref[...]
    w = w.reshape(-1, w.shape[3])                       # (taps, Cout)
    # (taps, rows)^T @ (taps, Cout): one dot over all K*K*Cin taps
    acc = jax.lax.dot_general(p_ref[...], w, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc += b_ref[...].astype(jnp.float32)
    if activation == "tanh":
        acc = jnp.tanh(acc)
    bb, _, Wo, cout = o_ref.shape
    o_ref[...] = acc.reshape(bb, Ho, Wa, cout)[:, :, :Wo].astype(o_ref.dtype)


def conv2d_packed_fwd(x, w, bias=None, *, activation: str | None = None,
                      batch_block: int = 8, interpret: bool):
    """Valid conv, stride 1, NHWC x HWIO -> NHWC, optional fused bias+tanh,
    as one dot per grid step over the tap-packed patch matrix.

    Returns ``(y, patches)``: the patch matrix (``tap_patches``) is what a
    backward needs of x.  The grid runs over batch blocks; a block's
    ``bb*Ho*Wa`` patch columns must fill whole lane tiles or be the batch.
    """
    B, H, W, Cin = x.shape
    K, _, _, Cout = w.shape
    Ho, Wo = H - K + 1, W - K + 1
    Wa = aligned(Wo)
    bb = _divisor_block(B, batch_block)
    patches = tap_patches(x, K)
    b2 = (jnp.zeros((Cout,), x.dtype) if bias is None else bias).reshape(
        1, Cout)
    name = "conv2d_packed_fwd" + (f"_{activation}" if activation else "")
    record_launch(name)
    y = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, Ho=Ho, Wa=Wa,
                          activation=activation),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((patches.shape[0], bb * Ho * Wa), lambda b: (0, b)),
            pl.BlockSpec((K, K, Cin, Cout), lambda b: (0, 0, 0, 0)),
            pl.BlockSpec((1, Cout), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, Ho, Wo, Cout), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Cout), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(patches, w, b2)
    return y, patches


def _packed_bwd_kernel(p_ref, dy_ref, *refs, Ho: int, Wa: int, tanh: bool,
                       dx: bool):
    refs = list(refs)
    y_ref = refs.pop(0) if tanh else None
    w_ref = refs.pop(0) if dx else None
    dp_ref = refs.pop(0) if dx else None
    dw_ref, db_ref, dw_acc, db_acc = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    dz = dy_ref[...].astype(jnp.float32)
    if tanh:
        y = y_ref[...].astype(jnp.float32)
        dz = dz * (1.0 - y * y)
    bb, _, Wo, cout = dz.shape
    if Wa > Wo:   # the patch matrix's padding columns get a zero dz
        dz = jnp.concatenate(
            [dz, jnp.zeros((bb, Ho, Wa - Wo, cout), jnp.float32)], axis=2)
    dz = dz.reshape(bb * Ho * Wa, cout)
    dw_acc[...] += jnp.dot(p_ref[...].astype(jnp.float32), dz,
                           preferred_element_type=jnp.float32)
    db_acc[...] += jnp.sum(dz, axis=0, keepdims=True)
    if dx:   # the patch matrix's gradient, (taps, rows) like the matrix
        w = w_ref[...]
        dp_ref[...] = jax.lax.dot_general(
            w.reshape(-1, cout), dz, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _flush():
        dw_ref[...] = dw_acc[...].reshape(dw_ref.shape).astype(dw_ref.dtype)
        db_ref[...] = db_acc[...].astype(db_ref.dtype)


def conv2d_packed_bwd(patches, dy, w, y=None, *, dx: bool,
                      batch_block: int = 8, interpret: bool):
    """One pallas_call -> (dx, dw, db) for the tap-packed conv, from the
    forward's patch matrix.

    ``y`` (the forward tanh output) fuses the dtanh factor, as in
    ``conv2d_bwd_fused``.  dw = patches @ dz is one dot per grid step.
    With ``dx=False`` the kernel skips the input gradient and returns None
    for it; with ``dx=True`` it writes the patch matrix's gradient
    ``w_packed @ dz^T``, which the transpose of ``tap_patches`` (a
    shift-and-add) folds back into dx.
    """
    B, Ho, Wo, Cout = dy.shape
    K, _, Cin, _ = w.shape
    Wa = aligned(Wo)
    bb = _divisor_block(B, batch_block)
    taps, rows = patches.shape[0], bb * Ho * Wa
    cols = pl.BlockSpec((taps, rows), lambda b: (0, b))
    slab = pl.BlockSpec((bb, Ho, Wo, Cout), lambda b: (b, 0, 0, 0))
    whole_w = pl.BlockSpec((K, K, Cin, Cout), lambda b: (0, 0, 0, 0))
    in_specs, inputs = [cols, slab], [patches, dy]
    if y is not None:
        in_specs.append(slab)
        inputs.append(y)
    out_specs = [whole_w, pl.BlockSpec((1, Cout), lambda b: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((K, K, Cin, Cout), jnp.float32),
                 jax.ShapeDtypeStruct((1, Cout), jnp.float32)]
    if dx:
        in_specs.append(whole_w)
        inputs.append(w)
        out_specs.insert(0, cols)
        out_shape.insert(0, jax.ShapeDtypeStruct(patches.shape,
                                                 jnp.float32))
    name = ("conv2d_packed_bwd" + ("_tanh" if y is not None else "")
            + ("_dx" if dx else ""))
    record_launch(name)
    outs = pl.pallas_call(
        functools.partial(_packed_bwd_kernel, Ho=Ho, Wa=Wa,
                          tanh=y is not None, dx=dx),
        grid=(B // bb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((taps, Cout), jnp.float32),
                        pltpu.VMEM((1, Cout), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(*inputs)
    if not dx:
        dw, db = outs
        return None, dw, db.reshape(Cout)
    dp, dw, db = outs
    x_shape = (B, Ho + K - 1, Wo + K - 1, Cin)
    (dxv,) = jax.linear_transpose(
        lambda x: tap_patches(x, K),
        jax.ShapeDtypeStruct(x_shape, jnp.float32))(dp)
    return dxv.astype(patches.dtype), dw, db.reshape(Cout)
