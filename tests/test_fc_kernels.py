"""Fused Pallas FC + softmax-CE kernel validation (kernels/fc.py): forward
and ``jax.grad`` parity vs the XLA reference path (plain + mixed precision),
autotune integration, and the whole-train-step launch-count contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import conv2d as CK
from repro.kernels import fc as FK
from repro.kernels import ops as kops

# (B, Din, Dout) — the paper nets' FC shapes plus a lane-unfriendly odd one
FC_SHAPES = [
    (8, 90, 50),     # small: 10 maps * 3x3 -> FC50
    (8, 50, 10),     # small output layer
    (4, 360, 150),   # medium-ish tail
    (6, 37, 11),     # nothing divides nicely
]


@pytest.mark.parametrize("B,Din,Dout", FC_SHAPES)
def test_fc_fwd_matches_xla(B, Din, Dout):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k1, (B, Din), jnp.float32)
    w = jax.random.normal(k2, (Din, Dout), jnp.float32) * 0.1
    b = jax.random.normal(k3, (Dout,), jnp.float32) * 0.1
    np.testing.assert_allclose(kops.fc_bias(x, w, b), x @ w + b,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(kops.fc_bias_tanh(x, w, b),
                               jnp.tanh(x @ w + b), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bb,db", [(1, None), (2, 8), (4, 2), (8, None)])
def test_fc_fwd_block_sweep(bb, db):
    """Any divisor blocking must be numerically identical to whole-array."""
    k1, k2 = jax.random.split(jax.random.key(1))
    x = jax.random.normal(k1, (8, 64), jnp.float32)
    w = jax.random.normal(k2, (64, 16), jnp.float32) * 0.1
    got = FK.fc_fwd(x, w, batch_block=bb, dout_block=db, interpret=True)
    np.testing.assert_allclose(got, x @ w, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,Din,Dout", FC_SHAPES[:2])
def test_fc_grad_parity_vs_xla(B, Din, Dout):
    """jax.grad through the fused custom VJP == grad through plain XLA."""
    k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(k1, (B, Din), jnp.float32)
    w = jax.random.normal(k2, (Din, Dout), jnp.float32) * 0.1
    b = jax.random.normal(k3, (Dout,), jnp.float32) * 0.1
    for fused, ref in [
        (kops.fc_bias_tanh, lambda x, w, b: jnp.tanh(x @ w + b)),
        (kops.fc_bias, lambda x, w, b: x @ w + b),
    ]:
        g1 = jax.grad(lambda *a: jnp.sum(jnp.cos(fused(*a))), (0, 1, 2))(
            x, w, b)
        g2 = jax.grad(lambda *a: jnp.sum(jnp.cos(ref(*a))), (0, 1, 2))(
            x, w, b)
        for a_, b_ in zip(g1, g2):
            np.testing.assert_allclose(a_, b_, atol=1e-4, rtol=1e-4)


def test_fc_bwd_cross_step_accumulation():
    """dw/db accumulate across batch-grid steps in fp32 scratch: with
    batch_block < B the fused backward must equal the whole-batch result
    (the conv-dw regression, FC flavour)."""
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k1, (8, 32), jnp.float32)
    w = jax.random.normal(k2, (32, 12), jnp.float32) * 0.1
    dy = jax.random.normal(k3, (8, 12), jnp.float32)
    want_dw = x.T @ dy
    want_db = dy.sum(0)
    for bb in (1, 2, 4, 8):
        dx, dw, db = FK.fc_bwd_fused(x, dy, w, batch_block=bb,
                                     interpret=True)
        np.testing.assert_allclose(dw, want_dw, atol=1e-4, rtol=1e-4,
                                   err_msg=f"bb={bb}")
        np.testing.assert_allclose(db, want_db, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(dx, dy @ w.T, atol=1e-4, rtol=1e-4)


def test_fc_mixed_precision_dtypes():
    """bf16 activations/weights with an fp32 bias (standard mixed-precision
    layout): fp32 accumulation inside, per-operand dtypes outside."""
    k1, k2, k3 = jax.random.split(jax.random.key(4), 3)
    x = jax.random.normal(k1, (8, 64), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(k2, (64, 16), jnp.float32) * 0.1).astype(
        jnp.bfloat16)
    b = jax.random.normal(k3, (16,), jnp.float32) * 0.1
    y = kops.fc_bias_tanh(x, w, b)
    assert y.dtype == jnp.bfloat16
    want = jnp.tanh(x.astype(jnp.float32) @ w.astype(jnp.float32) + b)
    np.testing.assert_allclose(y.astype(jnp.float32), want, atol=5e-2,
                               rtol=5e-2)
    grads = jax.grad(lambda x, w, b: jnp.sum(
        kops.fc_bias_tanh(x, w, b).astype(jnp.float32)), (0, 1, 2))(x, w, b)
    assert grads[0].dtype == jnp.bfloat16
    assert grads[1].dtype == jnp.bfloat16
    assert grads[2].dtype == jnp.float32
    ref = jax.grad(lambda x, w, b: jnp.sum(jnp.tanh(
        x.astype(jnp.float32) @ w.astype(jnp.float32) + b)), (0, 1, 2))(
        x, w, b)
    for a_, b_ in zip(grads, ref):
        np.testing.assert_allclose(a_.astype(jnp.float32),
                                   b_.astype(jnp.float32), atol=8e-2,
                                   rtol=8e-2)


# ---------------------------------------------------------------------------
# Fused softmax-cross-entropy
# ---------------------------------------------------------------------------
def _xent_ref(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - ll


@pytest.mark.parametrize("B,C", [(8, 10), (4, 33), (16, 10)])
def test_softmax_xent_value_and_grad(B, C):
    k1, k2 = jax.random.split(jax.random.key(5))
    logits = jax.random.normal(k1, (B, C), jnp.float32) * 3.0
    labels = jax.random.randint(k2, (B,), 0, C)
    np.testing.assert_allclose(kops.softmax_xent(logits, labels),
                               _xent_ref(logits, labels), atol=1e-5,
                               rtol=1e-5)
    g1 = jax.grad(lambda l: jnp.mean(kops.softmax_xent(l, labels)))(logits)
    g2 = jax.grad(lambda l: jnp.mean(_xent_ref(l, labels)))(logits)
    np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-5)


def test_softmax_xent_dlogits_is_softmax_minus_onehot():
    k1, k2 = jax.random.split(jax.random.key(6))
    logits = jax.random.normal(k1, (8, 10), jnp.float32)
    labels = jax.random.randint(k2, (8,), 0, 10)
    _, dl = FK.softmax_xent_fwd(logits, labels, interpret=True)
    want = jax.nn.softmax(logits, -1) - jax.nn.one_hot(labels, 10)
    np.testing.assert_allclose(dl, want, atol=1e-5, rtol=1e-5)


def test_softmax_xent_extreme_logits_stable():
    """The in-kernel max-subtraction must keep large logits finite."""
    logits = jnp.array([[1e4, -1e4, 0.0], [500.0, 499.0, -500.0]],
                       jnp.float32)
    labels = jnp.array([0, 1], jnp.int32)
    loss = kops.softmax_xent(logits, labels)
    assert np.isfinite(np.asarray(loss)).all()
    np.testing.assert_allclose(loss, _xent_ref(logits, labels), atol=1e-4)


# ---------------------------------------------------------------------------
# Whole-train-step integration: launch count + grads through the full tail
# ---------------------------------------------------------------------------
def test_full_cnn_step_launch_count_with_fc_kernels():
    """With use_kernel=True a chaos-small train step must hit EXACTLY:
    2 launches per conv layer (fused fwd + fused bwd), 2 per pool layer,
    2 per FC layer, and 1 for softmax-CE (its backward reuses the saved
    dlogits — zero extra launches)."""
    import repro.configs as C
    from repro.models import cnn
    from repro.models import layers as L
    cfg = C.get("chaos-small")
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    batch = {"images": jax.random.uniform(jax.random.key(1), (4, 29, 29, 1)),
             "labels": jax.random.randint(jax.random.key(2), (4,), 0, 10)}
    n_conv = sum(1 for s in cfg.cnn_layers if s[0] == "conv")
    n_pool = sum(1 for s in cfg.cnn_layers if s[0] == "pool")
    n_fc = sum(1 for s in cfg.cnn_layers if s[0] == "fc") + 1  # + output fc
    with CK.launch_trace() as rec:
        jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=True)[0])(
            params)
    assert rec.count("fc_fwd") == n_fc
    assert rec.count("fc_bwd_fused") == n_fc
    assert rec.count("softmax_xent") == 1
    assert rec.count("conv2d_fwd") == n_conv
    assert rec.count("conv2d_bwd_fused") == n_conv
    assert rec.count("maxpool2d_fwd") == n_pool
    assert rec.count("maxpool2d_bwd") == n_pool
    assert len(rec) == 2 * (n_conv + n_pool + n_fc) + 1, rec


def test_full_cnn_grads_kernel_tail_vs_xla_tail():
    """Full train-step gradients with the FC + softmax-CE kernels == the
    XLA path (the conv-only version of this lives in test_kernels.py)."""
    import repro.configs as C
    from repro.models import cnn
    from repro.models import layers as L
    cfg = C.get("chaos-small")
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    batch = {"images": jax.random.uniform(jax.random.key(1), (8, 29, 29, 1)),
             "labels": jax.random.randint(jax.random.key(2), (8,), 0, 10)}
    g1 = jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=True)[0])(
        params)
    g2 = jax.grad(lambda p: cnn.loss_fn(p, batch, cfg, use_kernel=False)[0])(
        params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


def test_fc_autotune_cache_roundtrip(tmp_path, monkeypatch):
    """tune_fc_fwd persists to the JSON cache under the fc_fwd| key, the
    tuned config is never slower than the heuristic default on its own
    measurements, and it is numerically identical to it."""
    from repro.kernels import autotune as AT
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    AT.clear_memory_cache()
    k1, k2 = jax.random.split(jax.random.key(7))
    x = jax.random.normal(k1, (8, 90), jnp.float32)
    w = jax.random.normal(k2, (90, 50), jnp.float32) * 0.1
    cfg, rep = AT.tune_fc_fwd(x, w, iters=1, interpret=True)
    assert rep["key"].startswith("fc_fwd|plain|")
    assert rep["best_us"] <= rep["baseline_us"]
    AT.clear_memory_cache()
    entry = AT.lookup(rep["key"])
    assert entry is not None and entry["config"] == cfg
    got = FK.fc_fwd(x, w, interpret=True, **cfg)
    np.testing.assert_allclose(got, x @ w, atol=1e-5, rtol=1e-5)
    bcfg, brep = AT.tune_fc_bwd(
        x, jax.random.normal(k1, (8, 50), jnp.float32), w, iters=1,
        interpret=True)
    assert brep["best_us"] <= brep["baseline_us"]
    assert AT.lookup(brep["key"])["config"] == bcfg
    AT.clear_memory_cache()


def test_fc_candidates_respect_vmem_budget():
    from repro.kernels import autotune as AT
    x_shape, w_shape = (64, 4096), (4096, 8192)
    cands = AT.fc_fwd_candidates(x_shape, w_shape)
    assert cands[0] == AT.default_fc_fwd(x_shape, w_shape)
    for cfg in cands[1:]:
        assert AT.fc_fwd_vmem_bytes(cfg, x_shape, w_shape) <= \
            AT.VMEM_BUDGET_BYTES
