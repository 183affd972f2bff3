#!/usr/bin/env python3
"""Chip smoke test: CHAOS training of ``chaos-large`` on a TPU.

Drives the repo's main path — data-parallel CNN training through
``repro.launch.train.train`` on the worker mesh — at the published Table-2
widths of ``chaos-large`` (C20@4 -> P1 -> C60@5 -> P2 -> C100@6 -> P2 ->
FC150 -> 10, 383,160 parameters) from a random seeded init, and checks what
comes out.  Everything runs in this one process: a chip belongs to one
process at a time.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the four-chip worker mesh only

One chip: (a) the XLA path and (b) the Pallas-kernel path, each a few
supersteps of ``sync="chaos"``, staleness 1, K=8, global batch 256 over 8
logical shards (32 images per micro-shard), on one worker.  Checks: the
backend is a TPU; losses are finite and the last superstep's mean is below
the first's; (b) launched compiled (not interpreted) Pallas kernels; and on
one batch the kernel path's loss, and each layer's output and gradients,
agree with the XLA path's.

Four chips: ``sync="bsp"`` at 4 workers and at 1 worker must give
bit-identical per-step losses (worker-count invariance), ``sync="chaos"``
at 4 workers must give finite losses, and the mesh, the batch shards and
the trained state must span 4 distinct devices.

Earlier lines print information (device kind, compile and step seconds,
deviations); they are not benchmark numbers.  The last line of stdout is
one JSON object, ``{"ok": true, "device": {...}}``.  Any failed check exits
non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "chaos-large"
BATCH = 256            # global batch per step
SHARDS = 8             # logical micro-shards: 32 images each
K = 8                  # steps per superstep dispatch
SUPERSTEPS = 4         # per one-chip phase
SUPERSTEPS_4 = 2       # per four-chip run
#: kernel-vs-XLA agreement on one batch under matmul precision "highest"
#: (see kernel_vs_xla): max |kernel - xla| <= GRAD_RTOL * max |xla| for
#: the loss and every layer's output and gradients.  The chip's tanh alone
#: differs from the host's by ~4.5e-5 of a conv output's max.
GRAD_RTOL = 1e-4
KERNEL_LAUNCHES = {"conv2d_packed_fwd_tanh", "conv2d_packed_bwd_tanh",
                   "conv2d_fwd", "conv2d_bwd_fused", "maxpool2d_fwd",
                   "maxpool2d_bwd", "fc_fwd", "fc_bwd_fused", "softmax_xent"}


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def info(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def run_phase(label: str, **kw):
    """One ``train`` call; returns (state, losses, superstep seconds)."""
    import numpy as np

    from repro.launch.train import train
    from repro.obs import MetricsBus

    bus = MetricsBus()
    steps = kw.pop("supersteps") * K
    t0 = time.perf_counter()
    state, losses = train(ARCH, steps, batch=BATCH, superstep=K,
                          logical_shards=SHARDS, smoke=False, log_every=K,
                          metrics_bus=bus, **kw)
    wall = time.perf_counter() - t0
    losses = np.asarray(losses, np.float32)
    dts = bus.series_sorted("watchdog/superstep_s")
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        fail(f"{label}: expected {steps} finite losses, got {losses}")
    steady = (sum(dts[1:]) / len(dts[1:]) / K) if len(dts) > 1 else None
    info(f"{label}: {wall:.1f}s wall; first superstep (compile + run) "
         f"{dts[0]:.1f}s; steady step "
         + (f"{steady * 1e3:.2f}ms" if steady is not None else "n/a")
         + f"; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return state, losses, dts


def check_falling(label: str, losses) -> None:
    first, last = losses[:K].mean(), losses[-K:].mean()
    info(f"{label}: superstep mean loss {first:.5f} -> {last:.5f}")
    if not last < first:
        fail(f"{label}: loss did not fall ({first} -> {last})")


def _xla_layer(kind: str, k: int, last: bool):
    """One layer of ``models/cnn.py::forward``'s XLA path as f(x, p)."""
    import jax
    import jax.numpy as jnp

    if kind == "conv":
        return lambda x, p: jnp.tanh(jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"])
    if kind == "pool":
        return lambda x, p: jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1), "VALID")
    if last:
        return lambda x, p: x @ p["w"] + p["b"]
    return lambda x, p: jnp.tanh(x @ p["w"] + p["b"])


def _kernel_layer(kind: str, k: int, last: bool, x, p, y, dy):
    """The same layer's forward and fused-backward kernel launches, the
    backward fed the reference output ``y`` (its dtanh factor)."""
    from repro.kernels import ops as kops

    if kind == "conv":
        return (kops.conv2d_bias_tanh(x, p["w"], p["b"]),
                kops.conv2d_bias_tanh_bwd(x, p["w"], p["b"], y, dy))
    if kind == "pool":
        return kops.maxpool2d(x, k), (kops.maxpool2d_vjp_saved(x, y, dy, k),)
    if last:
        return (kops.fc_bias(x, p["w"], p["b"]),
                kops.fc_bias_bwd(x, p["w"], p["b"], dy))
    return (kops.fc_bias_tanh(x, p["w"], p["b"]),
            kops.fc_bias_tanh_bwd(x, p["w"], p["b"], y, dy))


def kernel_vs_xla() -> float:
    """The kernel path against the XLA path on one micro-shard batch, all
    under matmul precision "highest"; returns the worst deviation.

    The loss is compared end to end.  Gradients are compared per layer:
    the XLA path runs on the host (exact f32) and records every layer's
    input, output and upstream gradient; the chip runs that layer's
    forward and fused-backward kernels on the same inputs.  Whole-network
    gradients cannot be compared across backends: the chip's tanh differs
    from the host's by ~2.5e-5, which flips max-pool argmaxes and moved
    conv2's weight gradient by 3.75e-3 of its max on a v5e.
    Nor can both paths run on the chip: XLA:TPU does not finish compiling
    conv0's weight gradient (Cin=1) at "highest" for 32 images, and at 8
    images its conv gradients are off by up to 5e-3 while the kernels'
    match the host to 1e-6."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.configs as C
    from repro.kernels import ops as kops
    from repro.launch.train import make_pipeline
    from repro.models import cnn
    from repro.models import layers as L

    cfg = C.get(ARCH)
    host, chip = jax.devices("cpu")[0], jax.devices()[0]
    params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                 jnp.float32))
    batch = make_pipeline(cfg, BATCH // SHARDS, 0).batch_at(0)
    shapes = cnn._trace_shapes(cfg)
    layers = [(i, kind, k, i == len(shapes) - 1)
              for i, (kind, k, _, _, _) in enumerate(shapes)
              if kind != "pool" or k > 1]

    def rel(got, want):
        got, want = np.asarray(got), np.asarray(want)
        return float(np.max(np.abs(got - want))) / max(
            float(np.max(np.abs(want))), 1e-30)

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        # the XLA path on the host: every layer's (x, p, y, vjp)
        x = jax.device_put(batch["images"], host)
        tape = []
        for i, kind, k, last in layers:
            p = jax.device_put(params.get(f"{kind}{i}", {}), host)
            if kind == "fc":
                x = x.reshape(x.shape[0], -1)
            y, vjp = jax.vjp(_xla_layer(kind, k, last), x, p)
            tape.append((x, p, y, vjp))
            x = y
        labels = jax.device_put(batch["labels"], host)

        def xla_loss(logits):
            lse = jax.nn.logsumexp(logits, axis=-1)
            return jnp.mean(lse - jnp.take_along_axis(
                logits, labels[:, None], axis=-1)[:, 0])

        loss_x, dy = jax.value_and_grad(xla_loss)(x)
        dlogits_x, ref = dy, []
        for x, p, y, vjp in reversed(tape):
            dy = dy.reshape(y.shape)       # fc6 flattens the pooled maps
            dx, dp = vjp(dy)
            ref.append((x, p, y, dy, (dx, dp.get("w"), dp.get("b"))))
            dy = dx
        ref.reverse()

        # the kernel path on the chip: end-to-end loss, then every layer
        loss_k = jax.jit(lambda p, b: cnn.loss_fn(
            p, b, cfg, use_kernel=True)[0])(params, batch)
        kern = jax.jit(lambda ref, logits, labels: (
            [_kernel_layer(kind, k, last, x, p, y, dy)
             for (_, kind, k, last), (x, p, y, dy) in zip(layers, ref)],
            jax.value_and_grad(lambda lg: jnp.mean(
                kops.softmax_xent(lg, labels)))(logits)))
        per_layer, (xent_k, dlogits_k) = jax.block_until_ready(kern(
            jax.device_put([r[:4] for r in ref], chip),
            jax.device_put(tape[-1][2], chip),
            jax.device_put(labels, chip)))
        info(f"kernel vs xla: compiled and ran in "
             f"{time.perf_counter() - t0:.1f}s")

    devs = {"loss (end to end)": rel(loss_k, loss_x),
            "softmax-xent loss": rel(xent_k, loss_x),
            "softmax-xent dlogits": rel(dlogits_k, dlogits_x)}
    for (i, kind, k, _), (y_k, grads_k), (x, _, y, _, grads_x) in zip(
            layers, per_layer, ref):
        devs[f"{kind}{i} fwd"] = rel(y_k, y)
        if kind == "pool":
            # a window with tied maxima is a valid subgradient either way:
            # XLA routes its gradient to one maximum, the kernel splits it
            # evenly (kernels/pool.py).  Compare the unique-max windows
            # element by element and every window's gradient sum.
            B, H, W, C = x.shape
            win = lambda a: np.asarray(a)[:, :H // k * k, :W // k * k] \
                .reshape(B, H // k, k, W // k, k, C)
            at_max = win(x) == np.asarray(y)[:, :, None, :, None, :]
            tied = at_max.sum(axis=(2, 4), keepdims=True) > 1
            unique = ~np.broadcast_to(tied, at_max.shape)
            dx_k, dx_x = win(grads_k[0]), win(grads_x[0])
            info(f"kernel vs xla {kind}{i}: {int(tied.sum())} of "
                 f"{tied.size} windows have tied maxima")
            devs[f"{kind}{i} dx (unique max)"] = rel(dx_k[unique],
                                                     dx_x[unique])
            devs[f"{kind}{i} dx (window sums)"] = rel(dx_k.sum((2, 4)),
                                                      dx_x.sum((2, 4)))
            continue
        for name, g_k, g_x in zip(("dx", "dw", "db"), grads_k, grads_x):
            devs[f"{kind}{i} {name}"] = rel(g_k, g_x)
    for name, dev in devs.items():
        info(f"kernel vs xla {name}: max deviation {dev:.3e} of max |xla|")
    worst = max(devs.values())
    info(f"kernel vs xla: loss {float(loss_k):.7f} vs {float(loss_x):.7f}; "
         f"worst relative deviation {worst:.3e} (tolerance {GRAD_RTOL:g})")
    return worst


def one_chip() -> None:
    from repro.kernels import conv2d
    from repro.kernels import ops as kops

    _, losses, _ = run_phase("(a) xla", sync_mode="chaos", workers=1,
                             staleness=1, supersteps=SUPERSTEPS)
    check_falling("(a) xla", losses)

    if kops._interpret():
        fail("Pallas kernels would run in interpret mode")
    with conv2d.launch_trace() as launches:
        _, losses, _ = run_phase("(b) pallas", sync_mode="chaos", workers=1,
                                 staleness=1, use_kernel=True,
                                 supersteps=SUPERSTEPS)
    check_falling("(b) pallas", losses)
    missing = KERNEL_LAUNCHES - set(launches)
    info(f"(b) pallas: {len(launches)} compiled kernel launches traced "
         f"({', '.join(sorted(set(launches)))}); "
         f"interpret={kops._interpret()}")
    if missing:
        fail(f"(b) issued no {sorted(missing)} launches")

    worst = kernel_vs_xla()
    if not worst <= GRAD_RTOL:
        fail(f"kernel path deviates from the XLA path by {worst:.3e} "
             f"> {GRAD_RTOL:g}")


def four_chips() -> None:
    import jax
    import numpy as np

    import repro.configs as C
    from repro.core.types import WorkerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_pipeline, put_worker_sharded

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    mesh = make_host_mesh(4)
    mesh_devs = set(mesh.devices.flat)
    worker = WorkerConfig(workers=4, logical_shards=SHARDS)
    pipe = make_pipeline(C.get(ARCH), BATCH, 0)
    batch = put_worker_sharded(pipe, 0, K, mesh, worker)
    shard_devs = {s.device for a in batch.values()
                  for s in a.addressable_shards}
    info(f"mesh on {len(mesh_devs)} devices; batch shards on "
         f"{len(shard_devs)}")
    if len(mesh_devs) != 4 or len(shard_devs) != 4:
        fail("the mesh or the batch shards do not span 4 devices")

    state4, l4, _ = run_phase("bsp N=4", sync_mode="bsp", workers=4,
                              supersteps=SUPERSTEPS_4)
    state_devs = {d for leaf in jax.tree.leaves(state4)
                  for d in leaf.sharding.device_set}
    info(f"bsp N=4 state spans {len(state_devs)} devices")
    if len(state_devs) != 4:
        fail("the N=4 train state does not span 4 devices")
    _, l1, _ = run_phase("bsp N=1", sync_mode="bsp", workers=1,
                         supersteps=SUPERSTEPS_4)
    same = l4.view(np.uint32) == l1.view(np.uint32)
    if not same.all():
        t = int(np.argmin(same))
        fail(f"bsp N=4 vs N=1 losses differ first at step {t}: "
             f"{l4[t]!r} vs {l1[t]!r} (|diff| {abs(l4[t] - l1[t]):.3e})")
    info(f"bsp N=4 and N=1: {len(l4)} per-step losses bit-identical")

    run_phase("chaos N=4", sync_mode="chaos", workers=4, staleness=1,
              supersteps=SUPERSTEPS_4)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the worker mesh on "
                         "four chips, and nothing else")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    # heuristic block configs only: no autotune cache from outside the
    # checkout decides what compiles
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.devnull
    # the host backend computes the XLA reference of the kernel check; the
    # chip stays the default backend whatever the list's order
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    info(f"device: {dev.device_kind} x{len(devs)} ({dev.platform})")

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
