"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (how every other kernel test runs on the CPU) accepts what
the TPU compiler refuses: blocks that are not whole or (8, 128)-tile
multiples, more VMEM than a kernel may use, layouts Mosaic cannot lower.
These tests compile each kernel of ``chaos-large``'s training step (and the
``lm-bench`` flash forward) for a v5e that is described, not attached, with
the block configs the heuristic picks, and check that the compiled program
holds the kernel (``tpu_custom_call``) under its ``pallas_call`` name; and
which operations of ConvNeXt's step the chip runs as convolutions.
Nothing runs.

The topology is described inside a fixture: only the process that runs
these tests loads the TPU compiler.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.kernels import autotune as AT
from repro.kernels import ops as kops
from repro.models import cnn
from repro.obs.trace import hlo_scopes

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import devtrace  # noqa: E402  (the benchmark's HLO classes)

B = 32                                   # one chaos-large micro-shard
CONVS = {"conv0": ((B, 29, 29, 1), (4, 4, 1, 20)),
         "conv2": ((B, 26, 26, 20), (5, 5, 20, 60)),
         "conv4": ((B, 11, 11, 60), (6, 6, 60, 100))}
#: the kernel each conv layer runs: conv0's 4*4*1 taps fit one lane tile
#: (the tap-packed pair), conv2's 500 and conv4's 2,160 do not
PACKED = {"conv0": True, "conv2": False, "conv4": False}
POOLS = {"pool3": (B, 22, 22, 60), "pool5": (B, 6, 6, 100)}
FCS = {"fc6": (900, 150, True), "fc7": (150, 10, False)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch, tmp_path):
    """The training path's kernel entry points, steered to compile (this
    process's backend is the CPU) with heuristic block configs only."""
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "none.json"))
    AT.clear_memory_cache()
    yield kops
    AT.clear_memory_cache()


def _assert_kernel(fn, *shapes, count=1, names=()):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") >= count, text[:2000]
    for name in names:
        assert f"/{name}/pallas_call" in text, name


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# "highest" makes Mosaic split each f32 dot into bf16 passes, which keeps
# more VMEM live than the default (the kernel-vs-XLA check runs there)
@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("layer", sorted(CONVS))
def test_conv_fwd_compiles(layer, precision, one_chip, compiled_kernels):
    x, w = CONVS[layer]
    S = lambda s: _shape(one_chip, s)
    name = ("conv2d_packed_fwd_tanh" if PACKED[layer]
            else "conv2d_fwd_tanh")
    with jax.default_matmul_precision(precision):
        _assert_kernel(compiled_kernels.conv2d_bias_tanh,
                       S(x), S(w), S(w[3:]), names=[name])


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("layer", sorted(CONVS))
def test_conv_bwd_fused_compiles(layer, precision, one_chip,
                                 compiled_kernels):
    x, w = CONVS[layer]
    ho = x[1] - w[0] + 1
    y = (B, ho, ho, w[3])
    S = lambda s: _shape(one_chip, s)
    k = compiled_kernels
    with jax.default_matmul_precision(precision):
        if not PACKED[layer]:
            _assert_kernel(k.conv2d_bias_tanh_bwd, S(x), S(w), S(w[3:]),
                           S(y), S(y), names=["conv2d_bwd_tanh"])
            return
        # the packed backward with and without the input gradient
        _assert_kernel(k.conv2d_bias_tanh_bwd, S(x), S(w), S(w[3:]),
                       S(y), S(y), names=["conv2d_packed_bwd_tanh_dx"])
        _assert_kernel(lambda *a: k.conv2d_bias_tanh_bwd(*a, dx=False)[1:],
                       S(x), S(w), S(w[3:]), S(y), S(y),
                       names=["conv2d_packed_bwd_tanh"])


@pytest.mark.parametrize("layer", sorted(POOLS))
def test_pool_fwd_bwd_compile(layer, one_chip, compiled_kernels):
    x = POOLS[layer]
    y = (B, x[1] // 2, x[2] // 2, x[3])
    S = lambda s: _shape(one_chip, s)
    _assert_kernel(lambda x: compiled_kernels.maxpool2d(x, 2), S(x),
                   names=["maxpool_fwd"])
    _assert_kernel(lambda x, y, dy: compiled_kernels.maxpool2d_vjp_saved(
        x, y, dy, 2), S(x), S(y), S(y), names=["maxpool_bwd"])


@pytest.mark.parametrize("layer", sorted(FCS))
def test_fc_fwd_bwd_compile(layer, one_chip, compiled_kernels):
    din, dout, hidden = FCS[layer]
    k = compiled_kernels
    S = lambda s: _shape(one_chip, s)
    x, w, b, y = S((B, din)), S((din, dout)), S((dout,)), S((B, dout))
    if hidden:
        _assert_kernel(k.fc_bias_tanh, x, w, b, names=["fc_fwd_tanh"])
        _assert_kernel(k.fc_bias_tanh_bwd, x, w, b, y, y,
                       names=["fc_bwd_tanh"])
    else:
        _assert_kernel(k.fc_bias, x, w, b, names=["fc_fwd"])
        _assert_kernel(k.fc_bias_bwd, x, w, b, y, names=["fc_bwd"])


def test_softmax_xent_compiles(one_chip, compiled_kernels):
    _assert_kernel(compiled_kernels.softmax_xent,
                   _shape(one_chip, (B, 10)),
                   _shape(one_chip, (B,), jnp.int32), names=["softmax_xent"])


def test_flash_train_fwd_compiles(one_chip, compiled_kernels):
    """``flash_attention_train``'s forward at the lm-bench per-shard shape
    (batch 1, T 512, Hq 4, Hkv 2, d_head 16; (B, T, H, D) layout)."""
    from repro.kernels.flash_attention import flash_attention_train
    cfg = C.get("lm-bench")
    T = 512
    q = _shape(one_chip, (1, T, cfg.n_heads, cfg.d_head))
    kv = _shape(one_chip, (1, T, cfg.n_kv_heads, cfg.d_head))
    # one launch per query-head group
    _assert_kernel(flash_attention_train, q, kv, kv,
                   count=cfg.n_heads // cfg.n_kv_heads)


def _worker_superstep(topo, cfg, images, dtype):
    """The jitted chaos τ=1 worker superstep on one described chip (one
    worker, 8 logical shards), compiled for a stacked batch of ``images``
    (K, B, H, W, C)."""
    from repro.core.chaos import SyncConfig
    from repro.core.types import WorkerConfig
    from repro.train.step import (init_worker_state, make_optimizer,
                                  make_worker_superstep)
    from repro.train.sync import get_strategy

    mesh = Mesh(np.array(topo.devices[:1]), ("workers",),
                axis_types=(AxisType.Auto,))
    worker = WorkerConfig(workers=1, logical_shards=8)
    sync = SyncConfig(mode="chaos", axis_name=worker.axis, staleness=1)
    opt = make_optimizer(cfg, total_steps=32)
    strat = get_strategy(sync)
    abstract = jax.eval_shape(lambda: init_worker_state(
        cfg, jax.random.key(0), sync, worker, opt))

    def place(tree, spec):
        return jax.tree.map(lambda a: _shape(NamedSharding(mesh, spec),
                                             a.shape, a.dtype), tree)

    layout = strat.worker_sync_layout()
    state = {k: place(v, strat.shard_view(worker))
             for k, v in abstract.items() if k != "sync"}
    state["sync"] = {k: place(v, P() if layout.get(k, "replicated")
                              == "replicated" else P(worker.axis))
                     for k, v in abstract["sync"].items()}
    data = NamedSharding(mesh, P(None, worker.axis))
    batch = {"images": _shape(data, images, dtype),
             "labels": _shape(data, images[:2], jnp.int32)}
    return make_worker_superstep(cfg, sync, worker, mesh,
                                 opt).lower(state, batch).compile()


def test_worker_superstep_compiles(topo, compiled_kernels):
    """The whole jitted chaos-large worker superstep (sync chaos, τ=1,
    K=8, batch 256 over 8 logical shards, kernels on) for one chip."""
    cfg = dataclasses.replace(C.get("chaos-large"), use_kernel=True)
    compiled = _worker_superstep(topo, cfg, (8, 256, 29, 29, 1),
                                 jnp.float32)
    text = compiled.as_text()
    # 8 forward + 7 backward kernels per step, inside one scan body
    assert text.count("tpu_custom_call") >= 15
    for name in ("conv2d_packed_fwd_tanh", "conv2d_packed_bwd_tanh",
                 "conv2d_fwd_tanh", "conv2d_bwd_tanh", "maxpool_fwd",
                 "maxpool_bwd", "fc_fwd_tanh", "fc_fwd", "fc_bwd_tanh",
                 "fc_bwd", "softmax_xent"):
        assert f"/{name}/pallas_call" in text, name
    # conv0's input is the images: its backward computes no dx
    assert "conv2d_packed_bwd_tanh_dx" not in text
    # every Pallas conv kernel is named by its layer and direction
    scopes = hlo_scopes(text)
    weights = [(k, k, ci, co) for kind, k, _, ci, co in
               cnn._trace_shapes(cfg) if kind == "conv"]
    convs = [n for n, c in devtrace.hlo_classes(text, weights).items()
             if c == "conv"]
    assert convs and all(scopes[n] for n in convs)
    assert {scopes[n] for n in convs} == {
        (f"conv{i}", d) for i in (0, 2, 4) for d in ("fwd", "bwd")}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30


def test_convnext_conv_classes(topo):
    """ConvNeXt's worker superstep (the smoke config, uint8 images) for
    one chip: the operations the benchmark classes ``conv`` (convolutions
    with a window) are the stem, the downsamples, the depthwise
    convolutions and the pointwise products, which XLA writes on 4-D
    activations as 1x1-window convolutions, each forward and backward;
    not the head's product.  ``bench/families/convnext.py::conv_work``
    counts exactly these."""
    cfg = C.smoke("convnext-tiny")
    text = _worker_superstep(topo, cfg, (2, 16, 64, 64, 3),
                             jnp.uint8).as_text()
    scopes = hlo_scopes(text)
    convs = [n for n, c in devtrace.hlo_classes(text).items()
             if c == "conv"]
    assert convs and all(scopes[n] for n in convs)
    got = {(s[0].split("/")[-1] if "/" in s[0] else s[0], s[1])
           for s in (scopes[n] for n in convs)}
    want = {(name, d) for name in ("stem", "down1", "down2", "down3",
                                   "dwconv", "mlp") for d in ("fwd", "bwd")}
    assert got == want
