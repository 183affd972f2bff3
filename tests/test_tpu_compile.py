"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (how every other kernel test runs on the CPU) accepts what
the TPU compiler refuses: blocks that are not whole or (8, 128)-tile
multiples, more VMEM than a kernel may use, layouts Mosaic cannot lower.
These tests compile each kernel of ``chaos-large``'s training step (and the
``lm-bench`` flash forward) for a v5e that is described, not attached, with
the block configs the heuristic picks, and check that the compiled program
holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture: only the process that runs
these tests loads the TPU compiler.
"""
import dataclasses

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

B = 32                                   # one chaos-large micro-shard
CONVS = {"conv0": ((B, 29, 29, 1), (4, 4, 1, 20)),
         "conv2": ((B, 26, 26, 20), (5, 5, 20, 60)),
         "conv4": ((B, 11, 11, 60), (6, 6, 60, 100))}
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


def _assert_kernel(fn, *shapes, count=1):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") >= count, text[:2000]


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# "highest" makes Mosaic split each f32 dot into bf16 passes, which keeps
# more VMEM live than the default (the kernel-vs-XLA check runs there)
@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("layer", sorted(CONVS))
def test_conv_fwd_compiles(layer, precision, one_chip, compiled_kernels):
    x, w = CONVS[layer]
    S = lambda s: _shape(one_chip, s)
    with jax.default_matmul_precision(precision):
        _assert_kernel(compiled_kernels.conv2d_bias_tanh,
                       S(x), S(w), S(w[3:]))


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("layer", sorted(CONVS))
def test_conv_bwd_fused_compiles(layer, precision, one_chip,
                                 compiled_kernels):
    x, w = CONVS[layer]
    ho = x[1] - w[0] + 1
    y = (B, ho, ho, w[3])
    S = lambda s: _shape(one_chip, s)
    with jax.default_matmul_precision(precision):
        _assert_kernel(compiled_kernels.conv2d_bias_tanh_bwd,
                       S(x), S(w), S(w[3:]), S(y), S(y))


@pytest.mark.parametrize("layer", sorted(POOLS))
def test_pool_fwd_bwd_compile(layer, one_chip, compiled_kernels):
    x = POOLS[layer]
    y = (B, x[1] // 2, x[2] // 2, x[3])
    S = lambda s: _shape(one_chip, s)
    _assert_kernel(lambda x: compiled_kernels.maxpool2d(x, 2), S(x))
    _assert_kernel(lambda x, y, dy: compiled_kernels.maxpool2d_vjp_saved(
        x, y, dy, 2), S(x), S(y), S(y))


@pytest.mark.parametrize("layer", sorted(FCS))
def test_fc_fwd_bwd_compile(layer, one_chip, compiled_kernels):
    din, dout, hidden = FCS[layer]
    k = compiled_kernels
    S = lambda s: _shape(one_chip, s)
    x, w, b, y = S((B, din)), S((din, dout)), S((dout,)), S((B, dout))
    if hidden:
        _assert_kernel(k.fc_bias_tanh, x, w, b)
        _assert_kernel(k.fc_bias_tanh_bwd, x, w, b, y, y)
    else:
        _assert_kernel(k.fc_bias, x, w, b)
        _assert_kernel(k.fc_bias_bwd, x, w, b, y)


def test_softmax_xent_compiles(one_chip, compiled_kernels):
    _assert_kernel(compiled_kernels.softmax_xent,
                   _shape(one_chip, (B, 10)),
                   _shape(one_chip, (B,), jnp.int32))


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


def test_worker_superstep_compiles(topo, compiled_kernels):
    """The whole jitted chaos-large worker superstep (sync chaos, τ=1,
    K=8, batch 256 over 8 logical shards, kernels on) for one chip."""
    from repro.core.chaos import SyncConfig
    from repro.core.types import WorkerConfig
    from repro.train.step import (init_worker_state, make_optimizer,
                                  make_worker_superstep)
    from repro.train.sync import get_strategy

    mesh = Mesh(np.array(topo.devices[:1]), ("workers",),
                axis_types=(AxisType.Auto,))
    cfg = dataclasses.replace(C.get("chaos-large"), use_kernel=True)
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
    batch = {"images": _shape(data, (8, 256, 29, 29, 1)),
             "labels": _shape(data, (8, 256), jnp.int32)}
    compiled = make_worker_superstep(cfg, sync, worker, mesh,
                                     opt).lower(state, batch).compile()
    # 8 forward + 7 backward kernels per step, inside one scan body
    assert compiled.as_text().count("tpu_custom_call") >= 15
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30
