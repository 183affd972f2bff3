"""The compiled step's named scopes (``obs/trace.py``'s naming contract):
``scope_of`` on op_names, the fusion rule of ``hlo_scopes``, every
Table-2 layer's forward and backward named in the compiled worker
superstep on both paths, every ConvNeXt layer and block sublayer both
ways, each bucket's exchange named on the layerwise worker mesh, kernel
names on every ``pallas_call``, and no host callback in a compiled step
unless latency is injected."""
import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.core.chaos import SyncConfig
from repro.core.types import WorkerConfig
from repro.launch.mesh import make_host_mesh
from repro.models import cnn
from repro.obs.trace import Tracer, hlo_scopes, scope_of, set_tracer
from repro.train.step import (init_worker_state, make_optimizer,
                              make_worker_superstep)

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(ROOT, "bench"))
import devtrace  # noqa: E402  (the benchmark's HLO classes)

CALLBACK = re.compile(r'custom_call_target="[^"]*callback[^"]*"')


@pytest.mark.parametrize("op_name,want", [
    ("jit(superstep)/while/body/closed_call/jvp(conv2)/conv_general_dilated",
     ("conv2", "fwd")),
    ("jit(superstep)/while/body/transpose(jvp(conv2))/conv_general_dilated",
     ("conv2", "bwd")),
    ("jit(s)/while/body/shard_map/conv4/bwd/dot_general", ("conv4", "bwd")),
    ("jit(s)/pool3/bwd/transpose(jvp(pool3))/select_and_scatter_add",
     ("pool3", "bwd")),
    ("jit(s)/jvp(fc6)/conv2d_fwd_tanh/pallas_call", ("fc6", "fwd")),
    ("jvp(fc7)/transpose", ("fc7", "fwd")),
    ("transpose(jvp(loss))/mul;transpose(jvp(loss))/broadcast_in_dim",
     ("loss", "bwd")),
    ("jit(s)/while/body/update/mul", ("update", "fwd")),
    ("jit(s)/update/exchange/conv2/all_gather", ("exchange/conv2", "fwd")),
    ("jit(s)/closed_call/exchange/fc6/reduce_sum", ("exchange/fc6", "fwd")),
    ("jit(s)/while/body/dynamic_update_slice", None),
    ("reduce_sum", None),
    ("jit(conv2d_bias_tanh)/conv2d_fwd_tanh/pallas_call", None),
    # ConvNeXt: layers, and the block sublayers the readers select
    ("jit(s)/while/body/closed_call/jvp(s1b2)/dwconv/conv_general_dilated",
     ("s1b2/dwconv", "fwd")),
    ("jit(s)/while/body/transpose(jvp(s1b2))/dwconv/conv_general_dilated",
     ("s1b2/dwconv", "bwd")),
    ("jit(s)/jvp(s0b0)/mlp/dot_general", ("s0b0/mlp", "fwd")),
    ("transpose(jvp(s0b0))/mlp/dot_general", ("s0b0/mlp", "bwd")),
    ("jit(s)/jvp(s2b8)/norm/rsqrt", ("s2b8/norm", "fwd")),
    ("jit(s)/jvp(s2b8)/add", ("s2b8", "fwd")),
    ("transpose(jvp(s3b2))/mul", ("s3b2", "bwd")),
    ("jit(s)/jvp(s0b0)/dwconvs/add", ("s0b0", "fwd")),
    ("jit(s)/jvp(stem)/conv_general_dilated", ("stem", "fwd")),
    ("jit(s)/transpose(jvp(down2))/conv_general_dilated", ("down2", "bwd")),
    ("jit(s)/jvp(head)/dot_general", ("head", "fwd")),
    ("jit(s)/update/exchange/s1b2/reduce_sum", ("exchange/s1b2", "fwd")),
    ("jit(s)/while/body/norm/add", None),
])
def test_scope_of(op_name, want):
    assert scope_of(op_name) == want


HLO = """\
%fused_computation.1 (param_0: f32[2,8]) -> f32[4,2,8] {
  %param_0 = f32[2,8]{1,0} parameter(0)
  %convolution.3 = f32[2,8]{1,0} convolution(%param_0, %param_0), window={size=3x3}, metadata={op_name="jit(s)/transpose(jvp(conv2))/conv_general_dilated"}
  %tanh.1 = f32[2,8]{1,0} tanh(%convolution.3), metadata={op_name="jit(s)/jvp(conv0)/tanh"}
  ROOT %dynamic-update-slice.2 = f32[4,2,8]{2,1,0} dynamic-update-slice(%tanh.1), metadata={op_name="jit(s)/while/body/dynamic_update_slice"}
}

%fused_computation.2 (param_0: f32[2,8]) -> f32[2,8] {
  %param_0 = f32[2,8]{1,0} parameter(0)
  %multiply.1 = f32[2,8]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(s)/jvp(conv0)/mul"}
  ROOT %subtract.4 = f32[2,8]{1,0} subtract(%multiply.1, %param_0), metadata={op_name="jit(s)/update/sub"}
}

%fused_computation.3 (param_0: f32[2,8]) -> f32[2,8] {
  %param_0 = f32[2,8]{1,0} parameter(0)
  %copy.1 = f32[2,8]{1,0} copy(%param_0)
  %exponential.1 = f32[2,8]{1,0} exponential(%copy.1), metadata={op_name="jit(s)/jvp(loss)/exp"}
  ROOT %add.2 = f32[2,8]{1,0} add(%exponential.1, %param_0)
}

%fused_computation.4 (param_0: f32[2,8]) -> f32[2,8] {
  %param_0 = f32[2,8]{1,0} parameter(0)
  ROOT %fusion.9 = f32[2,8]{1,0} fusion(%param_0), kind=kLoop, calls=%fused_computation.3
}

ENTRY %main.9 (Arg_0.1: f32[2,8]) -> f32[4,2,8] {
  %Arg_0.1 = f32[2,8]{1,0} parameter(0)
  %bitcast_dynamic-update-slice_fusion.8 = f32[4,2,8]{2,1,0} fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(s)/while/body/dynamic_update_slice"}
  %multiply_subtract_fusion = f32[2,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2
  %fusion.10 = f32[2,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.4
  %copy.7 = f32[2,8]{1,0} copy(%Arg_0.1)
  ROOT %conv2d_bwd_tanh.3 = f32[4,2,8]{2,1,0} custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/transpose(jvp(conv4))/conv2d_bwd_tanh/pallas_call"}
}
"""


def test_hlo_scopes_fusion_rule():
    got = hlo_scopes(HLO)
    # root unscoped (the lax.map stack write): the convolution member's
    assert got["bitcast_dynamic-update-slice_fusion.8"] == ("conv2", "bwd")
    # the root's scope wins over a member's
    assert got["multiply_subtract_fusion"] == ("update", "fwd")
    # no root scope, no conv or dot: the first scoped member, through a
    # nested fusion
    assert got["fusion.10"] == ("loss", "fwd")
    assert got["copy.7"] is None
    assert got["conv2d_bwd_tanh.3"] == ("conv4", "bwd")


def _layers(cfg):
    """The Table-2 layers that run: conv, fc, and pools with k > 1."""
    return [(f"{kind}{i}", kind) for i, (kind, k, *_) in
            enumerate(cnn._trace_shapes(cfg)) if kind != "pool" or k > 1]


def _check_layers(cfg, hlo):
    scopes, classes = hlo_scopes(hlo), devtrace.hlo_classes(hlo)
    for name, cls in classes.items():
        if cls == "conv":
            s = scopes[name]
            assert s is not None and re.fullmatch(r"conv\d+", s[0]), name
    seen = set(scopes.values())
    for layer, kind in _layers(cfg):
        for direction in ("fwd", "bwd"):
            assert (layer, direction) in seen, (layer, direction)
    assert ("loss", "fwd") in seen and ("update", "fwd") in seen
    return seen


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernel"])
@pytest.mark.parametrize("arch", ["chaos-medium", "chaos-large"])
def test_worker_superstep_scopes(arch, use_kernel):
    """The default job's compiled worker superstep (chaos τ=1, one worker,
    two micro-shards of two images), built with a tracer installed: every
    convolution is named by its layer and direction, every layer appears
    both ways, and no host callback is in the program."""
    cfg = dataclasses.replace(C.get(arch), use_kernel=use_kernel)
    worker = WorkerConfig(workers=1, logical_shards=2)
    sync = SyncConfig(mode="chaos", axis_name=worker.axis, staleness=1)
    opt = make_optimizer(cfg, total_steps=8)
    prev = set_tracer(Tracer())
    try:
        fn = make_worker_superstep(cfg, sync, worker, make_host_mesh(1), opt)
        state = jax.eval_shape(lambda: init_worker_state(
            cfg, jax.random.key(0), sync, worker, opt))
        batch = {"images": jax.ShapeDtypeStruct((1, 4, 29, 29, 1),
                                                jnp.float32),
                 "labels": jax.ShapeDtypeStruct((1, 4), jnp.int32)}
        hlo = fn.lower(state, batch).compile().as_text()
    finally:
        set_tracer(prev)
    _check_layers(cfg, hlo)
    assert not CALLBACK.search(hlo)


def test_convnext_worker_superstep_scopes():
    """ConvNeXt's compiled worker superstep (chaos τ=1, one worker, two
    micro-shards, the smoke config), built with a tracer installed: every
    layer and every block's sublayers appear both ways, each bucket is a
    layer, and no host callback is in the program."""
    from repro.models import convnext
    cfg = C.smoke("convnext-tiny")
    worker = WorkerConfig(workers=1, logical_shards=2)
    sync = SyncConfig(mode="chaos", axis_name=worker.axis, staleness=1)
    opt = make_optimizer(cfg, total_steps=8)
    prev = set_tracer(Tracer())
    try:
        fn = make_worker_superstep(cfg, sync, worker, make_host_mesh(1), opt)
        state = jax.eval_shape(lambda: init_worker_state(
            cfg, jax.random.key(0), sync, worker, opt))
        batch = {"images": jax.ShapeDtypeStruct((1, 4, 64, 64, 3),
                                                jnp.uint8),
                 "labels": jax.ShapeDtypeStruct((1, 4), jnp.int32)}
        hlo = fn.lower(state, batch).compile().as_text()
    finally:
        set_tracer(prev)
    seen = set(hlo_scopes(hlo).values())
    for bucket in convnext.bucket_spec(cfg):
        subs = (["dwconv", "norm", "mlp"]
                if re.fullmatch(r"s\d+b\d+", bucket.name) else [])
        for name in [bucket.name] + [f"{bucket.name}/{s}" for s in subs]:
            for direction in ("fwd", "bwd"):
                assert (name, direction) in seen, (name, direction)
    assert ("loss", "fwd") in seen and ("update", "fwd") in seen
    assert not CALLBACK.search(hlo)


def test_layerwise_worker_mesh_scopes():
    """Layerwise collect-then-walk and interleave at N=2 on forced host
    devices: each bucket's exchange (its all-gather included) is named
    ``exchange/<bucket>``, and with a tracer installed the program holds
    a host callback only when ``--collective-delay`` injects latency."""
    code = textwrap.dedent("""
        import json, re, sys
        import jax, jax.numpy as jnp
        import repro.configs as C
        from repro.core.chaos import SyncConfig
        from repro.core.types import WorkerConfig
        from repro.launch.mesh import make_host_mesh
        from repro.obs.trace import Tracer, hlo_scopes, set_tracer
        from repro.train.step import (init_worker_state, make_optimizer,
                                      make_worker_superstep)

        set_tracer(Tracer())
        cfg = C.get("chaos-medium")
        worker = WorkerConfig(workers=2, logical_shards=2)
        mesh = make_host_mesh(2)
        opt = make_optimizer(cfg, total_steps=8)
        batch = {"images": jax.ShapeDtypeStruct((1, 4, 29, 29, 1),
                                                jnp.float32),
                 "labels": jax.ShapeDtypeStruct((1, 4), jnp.int32)}
        out = {}
        for interleave in (False, True):
            for delay in (0.0, 400.0):
                sync = SyncConfig(mode="chaos", axis_name=worker.axis,
                                  staleness=1, layerwise=True,
                                  interleave=interleave,
                                  collective_delay_ns_per_byte=delay)
                fn = make_worker_superstep(cfg, sync, worker, mesh, opt)
                state = jax.eval_shape(lambda: init_worker_state(
                    cfg, jax.random.key(0), sync, worker, opt))
                hlo = fn.lower(state, batch).compile().as_text()
                scopes = hlo_scopes(hlo)
                gathers = re.findall(r"%([\\w.\\-]+) = \\S+ all-gather\\(",
                                     hlo)
                out[f"{interleave}-{delay}"] = {
                    "hlo": hlo if delay == 0 else "",
                    "gathers": {n: scopes[n] for n in gathers},
                    "callbacks": len(re.findall(
                        'custom_call_target="[^"]*callback', hlo))}
        print("RESULT " + json.dumps(out))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert run.returncode == 0, run.stderr[-4000:]
    res = json.loads(run.stdout.split("RESULT ", 1)[1])
    cfg = C.get("chaos-medium")
    buckets = {b.name for b in cnn.bucket_spec(cfg)}
    for interleave in (False, True):
        plain, injected = res[f"{interleave}-0.0"], res[f"{interleave}-400.0"]
        seen = _check_layers(cfg, plain["hlo"])
        assert {f"exchange/{b}" for b in buckets} <= {s[0] for s in seen
                                                       if s}
        assert plain["gathers"]
        for name, s in plain["gathers"].items():
            assert s is not None and s[0].startswith("exchange/"), name
        assert plain["callbacks"] == 0
        assert injected["callbacks"] > 0


@pytest.mark.parametrize("name,call", [
    ("conv2d_fwd", lambda K, x, w, dy: K.conv2d_fwd(x, w, interpret=True)),
    ("conv2d_fwd_tanh", lambda K, x, w, dy: K.conv2d_fwd(
        x, w, activation="tanh", interpret=True)),
    ("conv2d_bwd", lambda K, x, w, dy: K.conv2d_bwd_fused(
        x, dy, w, interpret=True)),
    ("conv2d_bwd_tanh", lambda K, x, w, dy: K.conv2d_bwd_fused(
        x, dy, w, dy, interpret=True)),
    ("conv2d_dx", lambda K, x, w, dy: K.conv2d_dx(dy, w, x.shape,
                                                  interpret=True)),
    ("conv2d_dw", lambda K, x, w, dy: K.conv2d_dw(x, dy, w.shape,
                                                  interpret=True)),
    ("conv2d_packed_fwd", lambda K, x, w, dy: K.conv2d_packed_fwd(
        x, w, interpret=True)[0]),
    ("conv2d_packed_fwd_tanh", lambda K, x, w, dy: K.conv2d_packed_fwd(
        x, w, activation="tanh", interpret=True)[0]),
    ("conv2d_packed_bwd", lambda K, x, w, dy: K.conv2d_packed_bwd(
        K.tap_patches(x, 3), dy, w, dx=False, interpret=True)[1:]),
    ("conv2d_packed_bwd_tanh_dx", lambda K, x, w, dy: K.conv2d_packed_bwd(
        K.tap_patches(x, 3), dy, w, dy, dx=True, interpret=True)),
])
def test_conv_kernel_names(name, call):
    """Each conv ``pallas_call`` variant carries its own name into the
    compiled program's op_names."""
    from repro.kernels import conv2d as K
    x, w = jnp.ones((2, 8, 8, 3)), jnp.ones((3, 3, 3, 4))
    dy = jnp.ones((2, 6, 6, 4))
    hlo = jax.jit(lambda *a: call(K, *a)).lower(x, w, dy).compile().as_text()
    assert f"/{name}/" in hlo


@pytest.mark.parametrize("name,call", [
    ("maxpool_fwd", lambda P, x, y: P.maxpool2d_fwd(x, 2, interpret=True)),
    ("maxpool_bwd", lambda P, x, y: P.maxpool2d_bwd(x, y, y, 2,
                                                    interpret=True)),
])
def test_pool_kernel_names(name, call):
    from repro.kernels import pool as P
    x, y = jnp.ones((2, 8, 8, 3)), jnp.ones((2, 4, 4, 3))
    hlo = jax.jit(lambda *a: call(P, *a)).lower(x, y).compile().as_text()
    assert f"/{name}/" in hlo


@pytest.mark.parametrize("name,call", [
    ("fc_fwd", lambda F, x, w, y, lab: F.fc_fwd(x, w, interpret=True)),
    ("fc_fwd_tanh", lambda F, x, w, y, lab: F.fc_fwd(
        x, w, activation="tanh", interpret=True)),
    ("fc_bwd", lambda F, x, w, y, lab: F.fc_bwd_fused(x, y, w,
                                                      interpret=True)),
    ("fc_bwd_tanh", lambda F, x, w, y, lab: F.fc_bwd_fused(
        x, y, w, y, interpret=True)),
    ("softmax_xent", lambda F, x, w, y, lab: F.softmax_xent_fwd(
        y, lab, interpret=True)),
])
def test_fc_kernel_names(name, call):
    from repro.kernels import fc as F
    x, w, y = jnp.ones((8, 16)), jnp.ones((16, 8)), jnp.ones((8, 8))
    lab = jnp.zeros((8,), jnp.int32)
    hlo = jax.jit(lambda *a: call(F, *a)).lower(x, w, y, lab).compile() \
        .as_text()
    assert f"/{name}/" in hlo
