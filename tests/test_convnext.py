"""ConvNeXt (``models/convnext.py``) against the plain reference
``bench/reference/convnext.py`` on the CPU at ``smoke_config()`` size:
initial weights, logits, loss and every gradient leaf; one chaos τ=1
superstep on one worker and bsp on two CPU devices through
``make_worker_superstep``, with bsp bit-identical across worker counts;
the parameter count at published widths; uint8 images carried to the
device unchanged; and the training CLI on the smoke config.

Tolerances (float32 throughout on the CPU; the program convolves with
XLA, the reference sums shifted slices and patch products in another
order, so the two differ by float32 rounding alone, ~1e-7 relative per
operation):

* logits 5e-6 of the largest logit (measured 4e-7);
* loss 5e-6 relative (measured 1.5e-7; GELU in its tanh form in place of
  erf moves it 2.6e-5);
* each gradient leaf 2e-5 of its norm (measured 1.6e-6 at worst, the
  first block's depthwise bias; the tanh GELU moves a leaf 1.5e-3);
* after a superstep of AdamW steps, the losses 5e-6 relative, and each
  weight leaf's change 1e-3 of the change's norm (measured 1.7e-4, a
  depthwise weight): AdamW divides by the root of the second moment, so
  an entry whose gradient is near ``eps`` turns its rounding into a
  change of the update itself.  The gradients are pinned above.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core.chaos import SyncConfig
from repro.core.types import WorkerConfig
from repro.data.images import make_images
from repro.data.pipeline import ImagePipeline
from repro.launch.mesh import make_host_mesh
from repro.launch.train import put_worker_sharded
from repro.models import convnext
from repro.models.api import get_ops, validate_bucket_spec
from repro.train.step import (init_worker_state, make_optimizer,
                              make_worker_superstep)

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")
SEED = 2**31 + 17

_spec = importlib.util.spec_from_file_location(
    "convnext_reference", os.path.join(ROOT, "bench", "reference",
                                       "convnext.py"))
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)


def _ref_cfg(cfg):
    """The benchmark's configuration file at the program config's sizes."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "convnext-tiny.json")) as f:
        file = json.load(f)
    return dict(file, dims=list(cfg.convnext_dims),
                depths=list(cfg.convnext_depths),
                input_hw=cfg.cnn_input[0], classes=cfg.n_classes)


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


@pytest.fixture(scope="module")
def cfg():
    return C.smoke("convnext-tiny")


@pytest.fixture(scope="module")
def batch(cfg):
    images, labels = make_images(8, cfg.cnn_input[0], cfg.n_classes, seed=3)
    return {"images": images, "labels": labels}


def test_published_sizes():
    cfg = C.get("convnext-tiny")
    assert cfg.param_count() == 28_589_128
    assert not cfg.has_decoder
    assert cfg.convnext_dims == (96, 192, 384, 768)
    assert cfg.convnext_depths == (3, 3, 9, 3)
    abstract = get_ops(cfg).abstract_params()
    assert sum(a.size for a in jax.tree.leaves(abstract)) == 28_589_128


def test_smoke_config_keeps_every_kind_of_layer(cfg):
    spec = get_ops(cfg).bucket_spec()
    validate_bucket_spec(spec, get_ops(cfg).abstract_params())
    names = [b.name for b in spec]
    assert names == ["stem", "s0b0", "down1", "s1b0", "down2", "s2b0",
                     "s2b1", "down3", "s3b0", "head"]
    assert cfg.param_count() == sum(
        a.size for a in jax.tree.leaves(get_ops(cfg).abstract_params()))


def test_initial_weights_are_the_reference_s(cfg):
    got = get_ops(cfg).init(jax.random.key(SEED))
    want = R.init_params(_ref_cfg(cfg), SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_logits_loss_and_gradients_match_the_reference(cfg, batch):
    params = get_ops(cfg).init(jax.random.key(SEED))
    rc = _ref_cfg(cfg)
    logits = convnext.forward(params, batch["images"], cfg)
    want = R.forward(params, batch["images"], rc)
    assert float(jnp.max(jnp.abs(logits - want))
                 / jnp.max(jnp.abs(want))) < 5e-6
    (loss, _), grads = jax.value_and_grad(
        get_ops(cfg).loss, has_aux=True)(params, batch)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: R.loss(p, batch["images"], batch["labels"], rc))(params)
    assert abs(float(loss) - float(ref_loss)) < 5e-6 * float(ref_loss)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert _rel(g, r) < 2e-5, jax.tree_util.keystr(path)


def _superstep(cfg, mode, workers, k, batch, shards, pipe, seed):
    worker = WorkerConfig(workers=workers, logical_shards=shards)
    mesh = make_host_mesh(workers)
    sync = SyncConfig(mode=mode, axis_name=worker.axis, staleness=1)
    opt = make_optimizer(cfg, total_steps=64)
    fn = make_worker_superstep(cfg, sync, worker, mesh, opt)
    state = init_worker_state(cfg, jax.random.key(seed), sync, worker, opt)
    state, metrics = fn(state, put_worker_sharded(pipe, 0, k, mesh, worker))
    return jax.device_get(state), np.asarray(metrics["loss"])


def _assert_follows_reference(state, losses, ref, stacked):
    np.testing.assert_allclose(losses, ref["losses"], rtol=5e-6)
    params = state["params"]
    if not stacked:
        params = jax.tree.map(lambda x: x[None], params)
    flat = jax.tree_util.tree_leaves_with_path(params)
    for (path, p), r, r0 in zip(flat, jax.tree.leaves(ref["params"]),
                                jax.tree.leaves(ref["params0"])):
        assert _rel(p - r0, r - r0) < 1e-3, jax.tree_util.keystr(path)


def test_chaos_superstep_follows_the_reference(cfg):
    """One worker, chaos τ=1, K=2 steps of 8 images as 4 micro-shards:
    losses and every weight's change as the reference follows them."""
    images, labels = make_images(64, cfg.cnn_input[0], cfg.n_classes,
                                 seed=5)
    pipe = ImagePipeline(images, labels, batch=8, seed=SEED,
                         sample_mode="queue")
    state, losses = _superstep(cfg, "chaos", 1, 2, 8, 4, pipe, SEED)
    traffic = {"sync": "chaos", "staleness": 1, "batch": 8,
               "logical_shards": 4, "workers": 1}
    ref = R.follow(_ref_cfg(cfg), traffic, SEED, images, labels, 2)
    _assert_follows_reference(state, losses, ref, stacked=True)


def test_bsp_on_two_devices_follows_the_reference_bit_exact_across_n():
    """bsp on two forced CPU devices: bit-identical to one worker, and
    both as the reference follows them."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, 'tests')!r})
        import jax, numpy as np
        import test_convnext as T
        import repro.configs as C
        cfg = C.smoke("convnext-tiny")
        images, labels = T.make_images(64, 64, 10, seed=7)
        pipe = T.ImagePipeline(images, labels, batch=8, seed=T.SEED,
                               sample_mode="queue")
        s1, l1 = T._superstep(cfg, "bsp", 1, 2, 8, 4, pipe, T.SEED)
        s2, l2 = T._superstep(cfg, "bsp", 2, 2, 8, 4, pipe, T.SEED)
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(l1, l2)
        traffic = {{"sync": "bsp", "staleness": 0, "batch": 8,
                    "logical_shards": 4, "workers": 2}}
        ref = T.R.follow(T._ref_cfg(cfg), traffic, T.SEED, images, labels,
                         2)
        T._assert_follows_reference(s2, l2, ref, stacked=False)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


def test_uint8_images_reach_the_device_unchanged(cfg):
    images, labels = make_images(16, 32, 10, seed=1)
    pipe = ImagePipeline(images, labels, batch=8, sample_mode="queue")
    worker = WorkerConfig(workers=1, logical_shards=2)
    got = put_worker_sharded(pipe, 0, 2, make_host_mesh(1), worker)
    assert got["images"].dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got["images"]),
                                  pipe.superstep_at(0, 2)["images"])


def test_make_images_is_a_function_of_the_seed():
    a, la = make_images(4, 16, 10, seed=2**31 + 5)
    b, lb = make_images(4, 16, 10, seed=2**31 + 5)
    c, _ = make_images(4, 16, 10, seed=6)
    assert a.dtype == np.uint8 and a.shape == (4, 16, 16, 3)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(la, lb)
    assert not np.array_equal(a, c)


def test_training_cli_runs_the_worker_superstep(tmp_path):
    """``python -m repro.launch.train --arch convnext-tiny`` (the smoke
    config) trains two supersteps on the worker route with finite
    losses."""
    out = tmp_path / "metrics.json"
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "convnext-tiny", "--steps", "4", "--superstep", "2",
           "--workers", "1", "--logical-shards", "2", "--sync", "chaos",
           "--batch", "4", "--metrics-out", str(out)]
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=900)
    assert run.returncode == 0, run.stderr[-4000:]
    assert "worker mesh: 1 worker(s) x 2 shard(s), sync=chaos" in run.stdout
    with open(out) as f:
        losses = json.load(f)["losses"]
    assert len(losses) == 4 and np.all(np.isfinite(losses))
