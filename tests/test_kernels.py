"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests
against the pure-jnp oracle (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels import conv2d as K
from repro.kernels import ops as kops
from repro.kernels import ref

# the paper's actual conv layer shapes (Table 2)
PAPER_SHAPES = [
    (8, 29, 29, 1, 4, 5),      # small conv1
    (8, 13, 13, 5, 5, 10),     # small conv2
    (4, 29, 29, 1, 4, 20),     # medium/large conv1
    (4, 13, 13, 20, 5, 40),    # medium conv2
    (2, 26, 26, 20, 5, 60),    # large conv2
    (2, 11, 11, 60, 6, 100),   # large conv3
]


@pytest.mark.parametrize("B,H,W,Cin,Kk,Cout", PAPER_SHAPES)
def test_conv_fwd_paper_shapes(B, H, W, Cin, Kk, Cout):
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jax.random.normal(k1, (B, H, W, Cin), jnp.float32)
    w = jax.random.normal(k2, (Kk, Kk, Cin, Cout), jnp.float32) * 0.1
    np.testing.assert_allclose(kops.conv2d_valid(x, w),
                               ref.conv2d_valid_ref(x, w),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_dtypes(dtype):
    k1, k2 = jax.random.split(jax.random.key(1))
    x = jax.random.normal(k1, (4, 13, 13, 5), jnp.float32).astype(dtype)
    w = (jax.random.normal(k2, (5, 5, 5, 10), jnp.float32) * 0.1).astype(dtype)
    got = kops.conv2d_valid(x, w).astype(jnp.float32)
    want = ref.conv2d_valid_ref(x.astype(jnp.float32),
                                w.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,W,Cin,Kk,Cout", PAPER_SHAPES[:4])
def test_conv_grads(B, H, W, Cin, Kk, Cout):
    k1, k2 = jax.random.split(jax.random.key(2))
    x = jax.random.normal(k1, (B, H, W, Cin), jnp.float32)
    w = jax.random.normal(k2, (Kk, Kk, Cin, Cout), jnp.float32) * 0.1
    f1 = lambda x, w: jnp.sum(jnp.tanh(kops.conv2d_valid(x, w)))
    f2 = lambda x, w: jnp.sum(jnp.tanh(ref.conv2d_valid_ref(x, w)))
    g1 = jax.grad(f1, (0, 1))(x, w)
    g2 = jax.grad(f2, (0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


@settings(max_examples=25, deadline=None)
@given(
    B=st.integers(1, 6),
    H=st.integers(5, 18),
    Cin=st.integers(1, 8),
    Kk=st.integers(1, 5),
    Cout=st.integers(1, 12),
    bb=st.integers(1, 8),
)
def test_conv_fwd_hypothesis(B, H, Cin, Kk, Cout, bb):
    """Property sweep over arbitrary shapes and batch blockings."""
    if Kk > H:
        return
    k1, k2 = jax.random.split(jax.random.key(B * 1000 + H))
    x = jax.random.normal(k1, (B, H, H, Cin), jnp.float32)
    w = jax.random.normal(k2, (Kk, Kk, Cin, Cout), jnp.float32) * 0.2
    got = K.conv2d_fwd(x, w, batch_block=bb, interpret=True)
    np.testing.assert_allclose(got, ref.conv2d_valid_ref(x, w),
                               atol=2e-4, rtol=2e-4)


def test_dw_kernel_matches_ref():
    k1, k2 = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k1, (6, 13, 13, 5), jnp.float32)
    dy = jax.random.normal(k2, (6, 9, 9, 10), jnp.float32)
    got = K.conv2d_dw(x, dy, (5, 5, 5, 10), interpret=True)
    np.testing.assert_allclose(got, ref.conv2d_dw_ref(x, dy),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# Tiled + fused + autotuned conv pipeline (DESIGN.md §Kernels)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bb", [2, 4, 8])
def test_dw_cross_step_accumulation_regression(bb):
    """_conv_dw_kernel accumulates across grid steps via sequential-grid
    revisiting of its fp32 scratch: with batch_block < B the result must
    still equal the whole-batch XLA reference (interpret path here; the
    non-interpret path runs in test_dw_accumulation_compiled on TPU)."""
    k1, k2 = jax.random.split(jax.random.key(11))
    x = jax.random.normal(k1, (8, 13, 13, 5), jnp.float32)
    dy = jax.random.normal(k2, (8, 9, 9, 10), jnp.float32)
    got = K.conv2d_dw(x, dy, (5, 5, 5, 10), batch_block=bb, interpret=True)
    np.testing.assert_allclose(got, ref.conv2d_dw_ref(x, dy),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="non-interpret Pallas needs a TPU backend")
def test_dw_accumulation_compiled():
    """Same regression through the compiled (non-interpret) path."""
    k1, k2 = jax.random.split(jax.random.key(11))
    x = jax.random.normal(k1, (8, 13, 13, 5), jnp.float32)
    dy = jax.random.normal(k2, (8, 9, 9, 10), jnp.float32)
    got = K.conv2d_dw(x, dy, (5, 5, 5, 10), batch_block=2, interpret=False)
    np.testing.assert_allclose(got, ref.conv2d_dw_ref(x, dy),
                               atol=1e-3, rtol=1e-3)


def test_conv_fwd_row_block_tiling_large_map():
    """64x64 feature map — larger than a single whole-image VMEM block at
    production channel counts — streamed through in halo'd row slabs."""
    k1, k2 = jax.random.split(jax.random.key(21))
    x = jax.random.normal(k1, (2, 64, 64, 3), jnp.float32)
    w = jax.random.normal(k2, (5, 5, 3, 8), jnp.float32) * 0.1
    want = ref.conv2d_valid_ref(x, w)
    for rb, cb in [(15, None), (20, 4), (12, 8), (4, None)]:
        got = K.conv2d_fwd(x, w, batch_block=1, row_block=rb, cout_block=cb,
                           interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4,
                                   err_msg=f"row_block={rb} cout_block={cb}")


def test_conv_bwd_fused_row_block_tiling_large_map():
    k1, k2, k3 = jax.random.split(jax.random.key(22), 3)
    x = jax.random.normal(k1, (2, 64, 64, 3), jnp.float32)
    w = jax.random.normal(k2, (5, 5, 3, 8), jnp.float32) * 0.1
    dy = jax.random.normal(k3, (2, 60, 60, 8), jnp.float32)
    f = lambda x, w: jnp.sum(ref.conv2d_valid_ref(x, w) * dy)
    gx, gw = jax.grad(f, (0, 1))(x, w)
    for rb in (16, 8):
        dx, dw, db = K.conv2d_bwd_fused(x, dy, w, batch_block=2,
                                        row_block=rb, interpret=True)
        np.testing.assert_allclose(dx, gx, atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(dw, gw, atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(db, jnp.sum(dy, (0, 1, 2)),
                                   atol=2e-3, rtol=2e-3)


def test_conv_fused_epilogue_fwd():
    """conv + bias + tanh in one launch == XLA composition."""
    k1, k2, k3 = jax.random.split(jax.random.key(23), 3)
    x = jax.random.normal(k1, (4, 29, 29, 1), jnp.float32)
    w = jax.random.normal(k2, (4, 4, 1, 5), jnp.float32) * 0.2
    b = jax.random.normal(k3, (5,), jnp.float32) * 0.1
    got = K.conv2d_fwd(x, w, b, activation="tanh", row_block=13,
                       interpret=True)
    np.testing.assert_allclose(got, jnp.tanh(ref.conv2d_valid_ref(x, w) + b),
                               atol=1e-4, rtol=1e-4)


# two Table-2 layer shapes for the end-to-end gradient acceptance check
GRAD_E2E_SHAPES = [
    (8, 29, 29, 1, 4, 5),      # small conv1
    (4, 13, 13, 20, 5, 40),    # medium conv2
]


@pytest.mark.parametrize("B,H,W,Cin,Kk,Cout", GRAD_E2E_SHAPES)
def test_grad_e2e_custom_vjp_vs_xla(B, H, W, Cin, Kk, Cout):
    """jax.grad through the kops.conv2d_valid custom VJP (fused Pallas
    backward) must match jax.grad through lax.conv_general_dilated."""
    k1, k2 = jax.random.split(jax.random.key(31))
    x = jax.random.normal(k1, (B, H, W, Cin), jnp.float32)
    w = jax.random.normal(k2, (Kk, Kk, Cin, Cout), jnp.float32) * 0.1
    f1 = lambda x, w: jnp.sum(jnp.cos(kops.conv2d_valid(x, w)))
    f2 = lambda x, w: jnp.sum(jnp.cos(ref.conv2d_valid_ref(x, w)))
    g1 = jax.grad(f1, (0, 1))(x, w)
    g2 = jax.grad(f2, (0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,H,W,Cin,Kk,Cout", GRAD_E2E_SHAPES)
def test_grad_e2e_fused_epilogue_vs_xla(B, H, W, Cin, Kk, Cout):
    """Same check for the fused conv+bias+tanh variant (dtanh folded into
    the single backward launch), including the bias gradient."""
    k1, k2, k3 = jax.random.split(jax.random.key(32), 3)
    x = jax.random.normal(k1, (B, H, W, Cin), jnp.float32)
    w = jax.random.normal(k2, (Kk, Kk, Cin, Cout), jnp.float32) * 0.1
    b = jax.random.normal(k3, (Cout,), jnp.float32) * 0.1
    f1 = lambda x, w, b: jnp.sum(kops.conv2d_bias_tanh(x, w, b))
    f2 = lambda x, w, b: jnp.sum(jnp.tanh(ref.conv2d_valid_ref(x, w) + b))
    g1 = jax.grad(f1, (0, 1, 2))(x, w, b)
    g2 = jax.grad(f2, (0, 1, 2))(x, w, b)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


def test_fused_epilogue_mixed_precision_bias_grad():
    """bf16 activations with an fp32 bias (standard mixed-precision layout):
    the custom VJP must return db in the bias's own dtype."""
    k1, k2, k3 = jax.random.split(jax.random.key(33), 3)
    x = jax.random.normal(k1, (4, 13, 13, 5), jnp.float32).astype(
        jnp.bfloat16)
    w = (jax.random.normal(k2, (5, 5, 5, 10), jnp.float32) * 0.1).astype(
        jnp.bfloat16)
    b = jax.random.normal(k3, (10,), jnp.float32) * 0.1
    grads = jax.grad(lambda x, w, b: jnp.sum(
        kops.conv2d_bias_tanh(x, w, b).astype(jnp.float32)), (0, 1, 2))(
        x, w, b)
    assert grads[0].dtype == jnp.bfloat16
    assert grads[1].dtype == jnp.bfloat16
    assert grads[2].dtype == jnp.float32


def test_conv_launch_count_per_train_step():
    """The fusion acceptance criterion: with use_kernel=True, each conv
    layer of a train step issues exactly 2 Pallas launches (one fused
    forward, one fused backward) — down from 3 (fwd + dx + dw)."""
    import repro.configs as C
    from repro.models import cnn
    from repro.models import layers as L
    cfg = C.get("chaos-small")
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    batch = {"images": jax.random.uniform(jax.random.key(1), (4, 29, 29, 1)),
             "labels": jax.random.randint(jax.random.key(2), (4,), 0, 10)}
    n_conv = sum(1 for s in cfg.cnn_layers if s[0] == "conv")
    with K.launch_trace() as rec:
        jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=True)[0])(
            params)
    assert rec.count("conv2d_fwd") == n_conv
    assert rec.count("conv2d_bwd_fused") == n_conv
    conv_launches = [r for r in rec if r.startswith("conv2d")]
    assert len(conv_launches) == 2 * n_conv, conv_launches


def test_cnn_kernel_grads_match_xla_path():
    """Full train-step gradients via the fused Pallas path == via XLA."""
    import repro.configs as C
    from repro.models import cnn
    from repro.models import layers as L
    cfg = C.get("chaos-small")
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    batch = {"images": jax.random.uniform(jax.random.key(1), (4, 29, 29, 1)),
             "labels": jax.random.randint(jax.random.key(2), (4,), 0, 10)}
    g1 = jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=True)[0])(
        params)
    g2 = jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=False)[0])(
        params)
    flat1, _ = jax.tree_util.tree_flatten(g1)
    flat2, _ = jax.tree_util.tree_flatten(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


def test_maxpool_kernel_matches_xla():
    x = jax.random.normal(jax.random.key(41), (4, 29, 29, 5), jnp.float32)
    for k in (2, 3):
        got = kops.maxpool2d(x, k)
        want = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, k, k, 1), (1, k, k, 1), "VALID")
        np.testing.assert_allclose(got, want)
        g1 = jax.grad(lambda x: jnp.sum(jnp.sin(kops.maxpool2d(x, k))))(x)
        g2 = jax.grad(lambda x: jnp.sum(jnp.sin(jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1),
            "VALID"))))(x)
        np.testing.assert_allclose(g1, g2, atol=1e-5)


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    """tune_conv_fwd persists to the JSON cache, survives a memory-cache
    clear, and never picks a config slower than the heuristic default
    on its own measurements."""
    from repro.kernels import autotune as AT
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    AT.clear_memory_cache()
    k1, k2 = jax.random.split(jax.random.key(51))
    x = jax.random.normal(k1, (8, 13, 13, 5), jnp.float32)
    w = jax.random.normal(k2, (5, 5, 5, 10), jnp.float32) * 0.1
    cfg, rep = AT.tune_conv_fwd(x, w, iters=1, interpret=True)
    assert rep["best_us"] <= rep["baseline_us"]
    AT.clear_memory_cache()
    entry = AT.lookup(rep["key"])
    assert entry is not None and entry["config"] == cfg
    # the tuned config must be numerically identical to the baseline
    got = K.conv2d_fwd(x, w, interpret=True, **cfg)
    np.testing.assert_allclose(got, ref.conv2d_valid_ref(x, w),
                               atol=1e-4, rtol=1e-4)
    AT.clear_memory_cache()


def test_autotune_candidates_respect_vmem_budget():
    from repro.kernels import autotune as AT
    x_shape, w_shape = (8, 64, 64, 32), (5, 5, 32, 128)
    cands = AT.conv_fwd_candidates(x_shape, w_shape)
    # the heuristic default (what an untuned run uses) is always measured
    assert cands[0] == AT.default_conv_fwd(x_shape, w_shape)
    for cfg in cands[1:]:
        assert AT.conv_fwd_vmem_bytes(cfg, x_shape, w_shape) <= \
            AT.VMEM_BUDGET_BYTES


def test_cnn_with_kernel_matches_xla_path():
    """End-to-end: the paper CNN forward via Pallas == via XLA conv."""
    import repro.configs as C
    from repro.models import cnn
    from repro.models import layers as L
    cfg = C.get("chaos-small")
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    x = jax.random.uniform(jax.random.key(1), (4, 29, 29, 1))
    y1 = cnn.forward(params, x, cfg, use_kernel=False)
    y2 = cnn.forward(params, x, cfg, use_kernel=True)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Pallas flash-attention kernel (the §Perf memory-term optimization)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,Hq,Hkv,T,D,Dv,causal,bq,bk", [
    (1, 2, 2, 128, 32, 32, True, 32, 32),
    (2, 4, 2, 96, 16, 16, True, 32, 32),      # GQA + non-dividing T
    (1, 2, 1, 256, 64, 32, False, 64, 128),   # Dv != D, non-causal
    (1, 1, 1, 70, 16, 16, True, 32, 32),      # ragged tail
])
def test_pallas_flash_attention(B, Hq, Hkv, T, D, Dv, causal, bq, bk):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models import layers as L
    ks = jax.random.split(jax.random.key(B * 7 + T), 3)
    q = jax.random.normal(ks[0], (B, Hq, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, Dv), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk,
                              interpret=True)
    # oracle: the validated jnp blockwise implementation (BTHD layout)
    want = L.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=causal
                             ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_flash_attention_dtypes(dtype):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models import layers as L
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32).astype(dtype)
    got = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    want = L.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True
                             ).transpose(0, 2, 1, 3)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# Pallas WKV6 recurrence kernel (attention-free archs' hot spot)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,D,chunk", [
    (2, 192, 3, 16, 64),
    (1, 64, 2, 32, 32),
    (2, 256, 1, 64, 64),   # production tile shape (D=64)
])
def test_pallas_wkv6_kernel(B, T, H, D, chunk):
    from repro.kernels.wkv6 import wkv6_chunked
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(jax.random.key(B * 13 + T), 5)
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D))
    w = jnp.exp(-jnp.exp(jnp.clip(
        jax.random.normal(ks[3], (B, T, H, D)), None, 0.0)))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    got = wkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    want, _ = wkv_chunked(r, k, v, w, u)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_pallas_wkv6_state_continuity():
    """The VMEM-carried state must make chunk boundaries seamless: kernel
    output == naive per-token recurrence across many chunks."""
    from repro.kernels.wkv6 import wkv6_chunked
    from tests.test_numerics import naive_wkv
    B, T, H, D = 1, 128, 2, 8
    ks = jax.random.split(jax.random.key(77), 5)
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D))
    w = jnp.exp(-jnp.exp(jnp.clip(
        jax.random.normal(ks[3], (B, T, H, D)), None, 0.0)))
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    got = wkv6_chunked(r, k, v, w, u, chunk=32, interpret=True)
    want, _ = naive_wkv(r, k, v, w, u)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# q_offset: absolute query position in the flash kernel's causal mask (§9).
# Pre-fix, the kernel assumed q and k both start at position 0, so a batched
# prefill of a CONTINUED sequence (queries at cache positions
# [cache_len, cache_len+Tq)) masked every cached key as "future".
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("offset_kind", ["zero", "cache_len"])
def test_pallas_flash_attention_q_offset(offset_kind):
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models import layers as L
    B, Hq, Hkv, Tq, Tk, D = 2, 4, 2, 16, 64, 32
    offset = 0 if offset_kind == "zero" else Tk - Tq   # append at cache tail
    ks = jax.random.split(jax.random.key(41), 3)
    q = jax.random.normal(ks[0], (B, Hq, Tq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Tk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Tk, D), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=True, q_offset=offset,
                              block_q=16, block_k=32, interpret=True)
    want = L.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True,
                             q_offset=offset).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    if offset:
        # regression vs the pre-fix behaviour: offset must actually admit
        # the cached keys, i.e. differ from running the kernel at offset 0
        at0 = flash_attention_fwd(q, k, v, causal=True, q_offset=0,
                                  block_q=16, block_k=32, interpret=True)
        assert not np.allclose(np.asarray(got), np.asarray(at0))


def test_pallas_flash_attention_q_offset_traced():
    """A traced (jitted scalar) offset must match the python-int program —
    the offset rides in SMEM, so one compiled program serves every cache
    position."""
    from repro.kernels.flash_attention import flash_attention_fwd
    B, H, Tq, Tk, D = 1, 2, 8, 32, 16
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, H, Tq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, Tk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, Tk, D), jnp.float32)
    fn = jax.jit(lambda off: flash_attention_fwd(
        q, k, v, causal=True, q_offset=off, block_q=8, block_k=16,
        interpret=True))
    for off in (0, 13, Tk - Tq):
        np.testing.assert_allclose(
            np.asarray(fn(jnp.int32(off))),
            np.asarray(flash_attention_fwd(q, k, v, causal=True,
                                           q_offset=off, block_q=8,
                                           block_k=16, interpret=True)),
            atol=1e-6, rtol=1e-6)
