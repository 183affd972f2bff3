"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the quantity the
paper's table reports: % time, minutes, speedup, GFLOP/s, ...) and
persists each section's rows to ``BENCH_<section>.json`` (see ``--out``)
so the perf trajectory accumulates across PRs.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]
                                            [--out DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROWS = []


def row(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


# ---------------------------------------------------------------------------
# Table 1 / Table 5 analogue: per-layer time split of the CNN training step
# ---------------------------------------------------------------------------
def bench_layer_times(quick=False):
    import repro.configs as C
    from repro.models import cnn, layers as L

    for arch in (["chaos-small"] if quick else
                 ["chaos-small", "chaos-medium", "chaos-large"]):
        cfg = C.get(arch)
        params = cnn.build_params(cfg, L.InitFactory(jax.random.key(0),
                                                     jnp.float32))
        B = 8
        x = jax.random.uniform(jax.random.key(1), (B, 29, 29, 1))
        y = jax.random.randint(jax.random.key(2), (B,), 0, 10)
        batch = {"images": x, "labels": y}

        full = jax.jit(jax.grad(lambda p: cnn.loss_fn(p, batch, cfg)[0]))
        us_full = _timeit(full, params, n=5)

        # time conv fwd+bwd by differentiating w.r.t. conv params only
        conv_keys = [k for k in params if k.startswith("conv")]
        conv_p = {k: params[k] for k in conv_keys}
        rest = {k: v for k, v in params.items() if k not in conv_keys}
        conv_only = jax.jit(jax.grad(
            lambda cp: cnn.loss_fn({**rest, **cp}, batch, cfg)[0]))
        us_conv = _timeit(conv_only, conv_p, n=5)
        frac = us_conv / us_full * 100
        row(f"layer_times/{arch}/full_step", us_full,
            f"conv_share~{frac:.0f}%_paper_93.7%")


# ---------------------------------------------------------------------------
# Table 8 + Table 9 + Result 3: the paper's performance model
# ---------------------------------------------------------------------------
def bench_perf_model(quick=False):
    from repro.core import perf_model as pm
    t8 = pm.table8()
    for arch in ("small", "medium", "large"):
        for p in (480, 960, 1920, 3840):
            row(f"table8/{arch}/{p}T", 0.0,
                f"pred={t8[arch][p]:.1f}min_paper={pm.PAPER_TABLE8[arch][p]}min")
    for arch in ("small", "medium", "large"):
        row(f"result3/speedup_vs_phi1T/{arch}", 0.0,
            f"{pm.predict_speedup(arch, 244):.1f}x_paper_up_to_103x")
    row("table9/small/240T/70ep", 0.0,
        f"pred={pm.predict_time('small', 240) / 60:.1f}min_paper=8.9min")
    row("table9/small/240T/140ep", 0.0,
        f"pred={pm.predict_time('small', 240, ep=140) / 60:.1f}min_paper=17.6min")


# ---------------------------------------------------------------------------
# Fig 5-9 measured analogue: CHAOS sync-mode step times (single host device;
# the cross-replica benefit is quantified by the roofline collective term)
# ---------------------------------------------------------------------------
def bench_sync_modes(quick=False):
    import repro.configs as C
    from repro.core.chaos import SyncConfig
    from repro.train.step import (init_train_state, make_optimizer,
                                  make_train_step)
    from repro.data.pipeline import ImagePipeline
    from repro.data.mnist import make_dataset

    cfg = C.get("chaos-small")
    imgs, labels = make_dataset(256, seed=0)
    pipe = ImagePipeline(imgs, labels, batch=32)
    batch = pipe.batch_at(0)
    for mode in ("bsp", "chaos", "localsgd"):
        sync = SyncConfig(mode=mode)
        opt = make_optimizer(cfg, total_steps=100)
        step = jax.jit(make_train_step(cfg, sync, opt))
        state = init_train_state(cfg, jax.random.key(0), sync, opt)
        us = _timeit(lambda s, b: step(s, b)[0], state, batch, n=5)
        row(f"sync_step/chaos-small/{mode}", us,
            f"{32 / (us / 1e6):.0f}img_per_s")


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (paper Listing 1: vectorised conv loops) — tuned
# vs the heuristic default block config, per Table-2 net
# ---------------------------------------------------------------------------
# (B, H, W, Cin, K, Cout) conv shapes of the paper's three Table-2 nets
NET_CONV_SHAPES = {
    "small": [(8, 29, 29, 1, 4, 5), (8, 13, 13, 5, 5, 10)],
    "medium": [(8, 29, 29, 1, 4, 20), (8, 13, 13, 20, 5, 40)],
    "large": [(8, 26, 26, 20, 5, 60), (8, 11, 11, 60, 6, 100)],
}


def bench_kernels(quick=False):
    from repro.kernels import autotune as AT
    from repro.kernels import conv2d as CK
    from repro.kernels import ops as kops
    from repro.kernels import ref

    detail = []
    nets = ["small"] if quick else ["small", "medium", "large"]
    iters = 1 if quick else 2
    # match the training path's interpret mode so tuned configs land under
    # the cache key that ops._fwd_cfg/_bwd_cfg actually look up on this host
    interp = kops._interpret()
    for net in nets:
        for (B, H, W, Cin, K, Cout) in NET_CONV_SHAPES[net]:
            x = jax.random.normal(jax.random.key(0), (B, H, W, Cin),
                                  jnp.float32)
            w = jax.random.normal(jax.random.key(1), (K, K, Cin, Cout),
                                  jnp.float32) * 0.1
            dy = jax.random.normal(jax.random.key(2),
                                   (B, H - K + 1, W - K + 1, Cout),
                                   jnp.float32)
            b = jax.random.normal(jax.random.key(3), (Cout,),
                                  jnp.float32) * 0.1
            # tune the fused variants models/cnn.py actually executes
            y = jnp.tanh(ref.conv2d_valid_ref(x, w) + b)
            flops = 2 * B * (H - K + 1) * (W - K + 1) * K * K * Cin * Cout
            cfg, rep = AT.tune_conv_fwd(x, w, b, activation="tanh",
                                        iters=iters, interpret=interp)
            bcfg, brep = AT.tune_conv_bwd(x, dy, w, y, iters=iters,
                                          interpret=interp)
            shp = f"{net}/conv_{H}x{W}x{Cin}_k{K}_{Cout}"
            row(f"kernel/fwd/{shp}/default", rep["baseline_us"],
                f"{flops / rep['baseline_us'] / 1e3:.2f}GFLOPs")
            row(f"kernel/fwd/{shp}/tuned", rep["best_us"],
                f"{rep['baseline_us'] / rep['best_us']:.2f}x_cfg={cfg}")
            row(f"kernel/bwd_fused/{shp}/default", brep["baseline_us"],
                f"vs_tuned_{brep['baseline_us'] / brep['best_us']:.2f}x")
            # best_us <= baseline_us by construction: the heuristic
            # default is always in the measured candidate set
            detail.append({
                "net": net,
                "shape": [B, H, W, Cin, K, Cout],
                "fwd": {"variant": "bias_tanh",
                        "default_us": rep["baseline_us"],
                        "tuned_us": rep["best_us"], "tuned_config": cfg,
                        "candidates": rep["candidates"]},
                "bwd_fused": {"variant": "dtanh",
                              "default_us": brep["baseline_us"],
                              "tuned_us": brep["best_us"],
                              "tuned_config": bcfg},
            })

    # fused vs split backward + Pallas-vs-XLA reference points (large conv2)
    B, H, W, Cin, K, Cout = 8, 26, 26, 20, 5, 60
    x = jax.random.normal(jax.random.key(0), (B, H, W, Cin), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (K, K, Cin, Cout),
                          jnp.float32) * 0.1
    dy = jax.random.normal(jax.random.key(2), (B, 22, 22, Cout), jnp.float32)
    flops = 2 * B * 22 * 22 * K * K * Cin * Cout
    us_p = _timeit(jax.jit(kops.conv2d_valid), x, w, n=3)
    us_x = _timeit(jax.jit(ref.conv2d_valid_ref), x, w, n=3)
    mode = "interp" if interp else "compiled"
    row(f"kernel/conv2d_pallas_{mode}", us_p,
        f"{flops / us_p / 1e3:.2f}GFLOPs")
    row("kernel/conv2d_xla", us_x, f"{flops / us_x / 1e3:.2f}GFLOPs")
    us_fused = _timeit(jax.jit(lambda x, dy, w: CK.conv2d_bwd_fused(
        x, dy, w, interpret=interp)), x, dy, w, n=3)
    us_split = _timeit(jax.jit(lambda x, dy, w: (
        CK.conv2d_dx(dy, w, x.shape, interpret=interp),
        CK.conv2d_dw(x, dy, w.shape, interpret=interp))),
        x, dy, w, n=3)
    row("kernel/conv_bwd_fused_1launch", us_fused,
        f"vs_split_{us_split / us_fused:.2f}x")
    row("kernel/conv_bwd_split_2launch", us_split, "baseline")

    from repro.models import layers as L
    B, T, Hq, Hkv, D = 1, 1024, 8, 2, 64
    q = jax.random.normal(jax.random.key(2), (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(3), (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(4), (B, T, Hkv, D), jnp.float32)
    fl = jax.jit(lambda q, k, v: L.flash_attention(q, k, v, causal=True))
    us_f = _timeit(fl, q, k, v, n=3)
    aflops = 4 * B * Hq * T * T * D / 2
    row("kernel/flash_attention_1k", us_f, f"{aflops / us_f / 1e3:.2f}GFLOPs")

    # training-grade flash attention (DESIGN.md §10): tuned Pallas forward
    # + LSE-saving blockwise backward vs the pure-jnp flash path, at the
    # dense-LM bench net's per-shard training shape — the same cache key
    # ``flash_attention_train`` resolves inside the worker-mesh cells
    flash_detail = None
    if not quick:
        from repro.kernels import flash_attention as FA
        B, T, Hq, Hkv, D = 1, 512, 4, 2, 16  # lm-bench per-shard GQA shape
        qt = jax.random.normal(jax.random.key(5), (B, T, Hq, D), jnp.float32)
        kt = jax.random.normal(jax.random.key(6), (B, T, Hkv, D), jnp.float32)
        vt = jax.random.normal(jax.random.key(7), (B, T, Hkv, D), jnp.float32)
        to_kern = lambda x: x.transpose(0, 2, 1, 3)
        fcfg, frep = AT.tune_flash_attention(
            to_kern(qt), to_kern(kt), to_kern(vt), iters=2,
            interpret=interp)
        row("kernel/flash_fwd_T512/default", frep["baseline_us"],
            "512x512_baseline")
        row("kernel/flash_fwd_T512/tuned", frep["best_us"],
            f"{frep['baseline_us'] / frep['best_us']:.2f}x_cfg={fcfg}")
        grad_j = jax.jit(jax.grad(
            lambda q, k, v: (L.flash_attention(q, k, v,
                                               causal=True) ** 2).mean(),
            argnums=(0, 1, 2)))
        grad_p = jax.jit(jax.grad(
            lambda q, k, v: (FA.flash_attention_train(
                q, k, v, causal=True) ** 2).mean(), argnums=(0, 1, 2)))
        us_j = _timeit(grad_j, qt, kt, vt, n=3, warmup=1)
        us_p = _timeit(grad_p, qt, kt, vt, n=3, warmup=1)
        row("kernel/flash_train_T512/jnp", us_j, "blockwise_jnp_fwd+bwd")
        row("kernel/flash_train_T512/pallas_tuned", us_p,
            f"vs_jnp_{us_j / us_p:.2f}x_lse_saving_bwd")
        flash_detail = {
            "shape_bthd": [B, T, Hq, D], "kv_heads": Hkv,
            "fwd": {"default_us": frep["baseline_us"],
                    "tuned_us": frep["best_us"], "tuned_config": fcfg,
                    "candidates": frep["candidates"]},
            "train_grad": {"jnp_us": us_j, "pallas_tuned_us": us_p,
                           "speedup": us_j / us_p},
        }
    return {"conv_shapes": detail, "flash": flash_detail,
            "autotune_cache": AT.cache_path()}


# ---------------------------------------------------------------------------
# End-to-end training throughput: steps/sec + time-per-epoch (the paper's
# actual deliverable — its speedup curves are epoch times, Tables 8/9) for
# the three Table-2 nets, at superstep K in {1, 8, 32}, Pallas kernels
# on/off.  First end-to-end point on the perf trajectory (BENCH_train.json).
# ---------------------------------------------------------------------------
EPOCH_IMAGES = 60_000  # paper's MNIST train-set size
TRAIN_BATCH = 8


def bench_train(quick=False):
    import dataclasses as DC

    import repro.configs as C
    from repro.core.chaos import SyncConfig
    from repro.data.mnist import make_dataset
    from repro.data.pipeline import ImagePipeline
    from repro.train.step import (init_train_state, make_optimizer,
                                  make_superstep)

    nets = ["chaos-small"] if quick else ["chaos-small", "chaos-medium",
                                          "chaos-large"]
    supersteps = [1, 8, 32]
    imgs, labels = make_dataset(512, seed=0)
    detail = []
    kernel_modes = (False, True)
    for net in nets:
        base_cfg = C.get(net)
        for use_kernel in kernel_modes:
            cfg = DC.replace(base_cfg, use_kernel=use_kernel)
            sync = SyncConfig("bsp")
            opt = make_optimizer(cfg, total_steps=4096)
            super_fn = jax.jit(make_superstep(cfg, sync, opt),
                               donate_argnums=(0,))
            pipe = ImagePipeline(imgs, labels, batch=TRAIN_BATCH,
                                 sample_mode="queue")
            # interpret-mode Pallas is orders slower on CPU: measure fewer
            # steps there (the K-scaling ratio is what matters, not the
            # absolute interpreter floor)
            target = (8 if quick else 16) if use_kernel else 64
            by_k = {}
            for K in supersteps:
                state = init_train_state(cfg, jax.random.key(0), sync, opt)
                step = 0
                measured_steps = 0
                elapsed = 0.0
                while measured_steps < target:
                    # mirror the driver: host batch build + device transfer
                    # + one dispatch + ONE host sync on the (K,) loss vector
                    t0 = time.perf_counter()
                    batch = jax.device_put(pipe.superstep_at(step, K))
                    state, metrics = super_fn(state, batch)
                    np.asarray(metrics["loss"])
                    dt = time.perf_counter() - t0
                    if step > 0:  # first dispatch = compile, not timed
                        elapsed += dt
                        measured_steps += K
                    step += K
                us_per_step = elapsed / measured_steps * 1e6
                sps = 1e6 / us_per_step
                epoch_min = (EPOCH_IMAGES / TRAIN_BATCH) * (us_per_step
                                                            / 1e6) / 60
                by_k[K] = us_per_step
                kind = "kernel" if use_kernel else "xla"
                row(f"train/{net}/{kind}/K{K}", us_per_step,
                    f"{sps:.1f}steps_per_s_epoch~{epoch_min:.2f}min")
                detail.append({
                    "net": net, "use_kernel": use_kernel, "superstep": K,
                    "us_per_step": us_per_step, "steps_per_s": sps,
                    "epoch_min": epoch_min,
                    "batch": TRAIN_BATCH, "measured_steps": measured_steps,
                })
            kind = "kernel" if use_kernel else "xla"
            row(f"train/{net}/{kind}/superstep_speedup", by_k[1],
                f"K32_vs_K1_{by_k[1] / by_k[32]:.2f}x")
    return {"runs": detail, "epoch_images": EPOCH_IMAGES}


# ---------------------------------------------------------------------------
# Result 3 / Tables 7-9 measured analogue: CHAOS worker scaling.  Runs the
# worker-mesh superstep path (shard_map over the visible devices) for the
# three Table-2 nets x 3 sync modes x workers {1,2,4,8} x kernels on/off,
# then puts measured speedup next to the paper's performance-model
# prediction.  The grids run in this process: a chip belongs to one process.
# ---------------------------------------------------------------------------
PAPER_ARCH = {"chaos-small": "small", "chaos-medium": "medium",
              "chaos-large": "large"}


def _model_speedup(r: dict) -> float:
    """Listing-2 predicted speedup for a worker-mesh run row.  Table-2 CNN
    nets map straight onto the paper's op-count tables; other nets (the
    dense-LM column) must carry their own per-sample op counts in the run
    dict (``lm_fprop``/``lm_bprop``, emitted by benchmarks/scaling.py), and
    are registered with the perf model on the fly — rows with neither get
    NaN instead of a KeyError that would void the whole artifact."""
    from repro.core import perf_model as pm

    key = PAPER_ARCH.get(r["net"])
    if key is None:
        if "lm_fprop" not in r:
            return float("nan")
        key = r["net"]
        pm.register_arch(key, fprop=r["lm_fprop"], bprop=r["lm_bprop"])
    return pm.predict_speedup(key, r["workers"])


def bench_scaling(quick=False):
    from benchmarks import scaling
    runs = scaling.run(quick)["runs"]
    base = {(r["net"], r["mode"], r["use_kernel"]): r["steps_per_s"]
            for r in runs if r["workers"] == 1}
    for r in runs:
        b = base.get((r["net"], r["mode"], r["use_kernel"]))
        # nan, not None: a missing N=1 baseline (edited worker sweep,
        # partial run) must not crash the row formatting below and throw
        # away an hours-long measurement
        r["speedup_vs_1"] = r["steps_per_s"] / b if b else float("nan")
        # paper performance-model cross-check: N workers ~ N Phi threads
        r["model_speedup"] = _model_speedup(r)
        kind = "kernel" if r["use_kernel"] else "xla"
        row(f"scaling/{r['net']}/{r['mode']}/{kind}/N{r['workers']}",
            r["us_per_step"],
            f"{r['steps_per_s']:.1f}steps_per_s_speedup="
            f"{r['speedup_vs_1']:.2f}x_model={r['model_speedup']:.2f}x")
    return {"runs": runs, "batch": runs[0]["batch"] if runs else None,
            "superstep": runs[0]["superstep"] if runs else None,
            "note": "forced host devices share one CPU; speedup_vs_1 "
                    "validates the worker path + overhead trend, "
                    "model_speedup is the paper's Listing-2 prediction "
                    "for the same worker count"}


# ---------------------------------------------------------------------------
# Result 1-2 / Tables 4-6 analogue: staleness-τ CHAOS convergence study.
# Runs the worker-mesh chaos(τ) path (τ=0 ≡ bsp by construction) for the
# Table-2 nets × τ × worker counts, recording steps/sec AND final error so
# the paper's "asynchrony does not significantly degrade accuracy" claim is
# measured, with the τ=0 cell as the synchronous baseline and the Listing-2
# model prediction per worker count.
# ---------------------------------------------------------------------------
def bench_staleness(quick=False):
    from benchmarks import staleness
    runs = staleness.run(quick)["runs"]
    # baselines are keyed WITHIN a layerwise flavour (τ=0 layerwise bsp is
    # the layerwise rows' synchronous baseline); speedup_vs_batched then
    # compares each layerwise row against its batched twin — the
    # per-layer-exchange overlap column
    lw = lambda r: bool(r.get("layerwise"))
    base = {(r["net"], r["workers"], lw(r)): r for r in runs
            if r["tau"] == 0}
    base_n1 = {(r["net"], r["tau"], lw(r)): r for r in runs
               if r["workers"] == 1}
    batched = {(r["net"], r["tau"], r["workers"]): r for r in runs
               if not lw(r)}
    for r in runs:
        b = base.get((r["net"], r["workers"], lw(r)))
        b1 = base_n1.get((r["net"], r["tau"], lw(r)))
        tw = batched.get((r["net"], r["tau"], r["workers"]))
        r["speedup_vs_tau0"] = (r["steps_per_s"] / b["steps_per_s"]
                                if b else float("nan"))
        r["speedup_vs_n1"] = (r["steps_per_s"] / b1["steps_per_s"]
                              if b1 else float("nan"))
        r["error_delta_vs_tau0"] = (r["final_error"] - b["final_error"]
                                    if b else float("nan"))
        r["speedup_vs_batched"] = (r["steps_per_s"] / tw["steps_per_s"]
                                   if lw(r) and tw else float("nan"))
        r["model_speedup"] = _model_speedup(r)
        kind = "layerwise" if lw(r) else "batched"
        row(f"staleness/{r['net']}/tau{r['tau']}/N{r['workers']}/{kind}",
            r["us_per_step"],
            f"{r['steps_per_s']:.1f}steps_per_s_err={r['final_error']:.4f}"
            f"_derr={r['error_delta_vs_tau0']:+.4f}"
            f"_speedup_tau0={r['speedup_vs_tau0']:.2f}x")
    return {"runs": runs,
            "note": "tau=0 IS bsp (the chaos strategy resolves to the bsp "
                    "object at staleness 0); error columns are hardware-"
                    "independent; forced host devices share one CPU, so "
                    "steps_per_s validates the harness + overhead trend "
                    "and model_speedup is the paper's Listing-2 prediction "
                    "for the same worker count"}


# ---------------------------------------------------------------------------
# Elastic resize: membership-change latency + throughput recovery
# (DESIGN.md §7; CI's preemption-injection smoke uploads this section)
# ---------------------------------------------------------------------------
def bench_elastic(quick=False):
    from benchmarks import elastic
    runs = elastic.run(quick)["runs"]
    for r in runs:
        row(f"elastic/{r['label']}/{r['from']}to{r['to']}",
            r["latency_s"] * 1e6,
            f"path={r['path']}_first_superstep="
            f"{r['first_superstep_s'] * 1e3:.0f}ms_steps_per_s="
            f"{r['steps_per_s_before']:.1f}->{r['steps_per_s_after']:.1f}")
    return {"runs": runs,
            "note": "latency_s is the re-slot + rebuild cost from "
                    "ResizeOutcome; the first post-resize superstep "
                    "carries the recompile and is reported separately; "
                    "forced host devices share one CPU, so steps_per_s "
                    "validates recovery, not hardware scaling"}


# ---------------------------------------------------------------------------
# Overlap study (DESIGN.md §8): is the layerwise per-bucket exchange hidden
# behind backward compute?  Interleaved (backprop-time bucket collectives)
# vs collect-then-walk under injected per-byte collective latency, with the
# roofline collective-bytes model as the predicted blocking cost.
# ---------------------------------------------------------------------------
def bench_overlap(quick=False):
    from benchmarks import overlap
    runs = overlap.run(quick)["runs"]
    base = {(r["net"], r["workers"], r["schedule"]): r["us_per_step"]
            for r in runs if r["delay_ns_per_byte"] == 0}
    collect = {(r["net"], r["workers"], r["delay_ns_per_byte"]): r
               for r in runs if r["schedule"] == "collect"}
    for r in runs:
        d = r["delay_ns_per_byte"]
        tw = collect.get((r["net"], r["workers"], d))
        # interleaved speedup vs the blocking schedule at the same delay —
        # the overlap win (nan on the collect rows' own delay-0 baselines)
        r["speedup_vs_collect"] = (tw["us_per_step"] / r["us_per_step"]
                                   if tw and r["schedule"] == "interleave"
                                   else float("nan"))
        pred = r.get("predicted_exchange_us")
        # roofline cross-check: measured blocking exchange / predicted
        # bytes-x-delay (meaningful on the collect rows, where the whole
        # charge is synchronous; interleaved rows land BELOW 1 by design)
        r["exchange_vs_roofline"] = (r["exchange_us"] / pred
                                     if pred else float("nan"))
        if r["schedule"] == "interleave" and tw and pred:
            r["hidden_us"] = tw["exchange_us"] - r["exchange_us"]
            r["hidden_frac_of_predicted"] = r["hidden_us"] / pred
        # shard-tape compute overhead: interleave delay-0 vs collect
        # delay-0 isolates what the manual bucket tape costs over the
        # whole-tree value_and_grad (no injected latency on either side).
        # Since the residual-checkpointing change the tape saves every
        # layer's output and replays NO forward — this column records it.
        if r["schedule"] == "interleave" and d == 0 and tw:
            r["tape_overhead_us"] = r["us_per_step"] - tw["us_per_step"]
            r["tape_overhead_frac"] = (r["tape_overhead_us"]
                                       / tw["us_per_step"])
        name = (f"overlap/{r['net']}/N{r['workers']}/{r['schedule']}"
                f"/delay{d:.0f}")
        row(name, r["us_per_step"],
            f"exchange={r['exchange_us']:.0f}us"
            f"_roofline={r['exchange_vs_roofline']:.2f}x"
            f"_vs_collect={r['speedup_vs_collect']:.2f}x")
    return {"runs": runs,
            "note": "layerwise bsp+SGD worker path; exchange_us = "
                    "us_per_step minus the same schedule's delay-0 cell; "
                    "predicted_exchange_us = compiled-HLO effective "
                    "collective bytes x injected delay (core/roofline.py "
                    "convention); interleave hides the charge behind the "
                    "remaining backward walk, collect takes it "
                    "synchronously; tape_overhead_us (delay-0 interleave "
                    "rows) = saved-activation bucket tape vs whole-tree "
                    "value_and_grad at zero injected latency"}


# ---------------------------------------------------------------------------
# Roofline table from the dry-run results (deliverable g summary)
# ---------------------------------------------------------------------------
def bench_roofline(quick=False):
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_results.json")
    if not os.path.exists(path):
        row("roofline/missing", 0.0, "run_repro.launch.dryrun_--all_first")
        return
    with open(path) as f:
        results = json.load(f)
    for r in results:
        if r.get("tier") != "roofline" or r.get("status") != "ok":
            continue
        rl = r["roofline"]
        row(f"roofline/{r['arch']}/{r['shape']}",
            rl["bound_s"] * 1e6,
            f"dom={rl['dominant']}_c={rl['compute_s']:.3f}s"
            f"_m={rl['memory_s']:.3f}s_x={rl['collective_s']:.3f}s"
            f"_useful={rl['useful_flops_ratio']:.2f}")


# ---------------------------------------------------------------------------
# Serving: continuous batching under a Poisson trace (DESIGN.md §9)
# ---------------------------------------------------------------------------
def bench_serve(quick=False):
    from benchmarks import serve
    doc = serve.run(quick)
    for r in doc["runs"]:
        sampling = r.get("sampling", "greedy")
        name = (f"serve/{r['arch']}/{r['mode']}"
                + (f"/{sampling}" if sampling != "greedy" else ""))
        row(name,
            r["p50_token_latency_s"] * 1e6,
            f"tok_per_s={r['tokens_per_s']:.1f}"
            f"_p99_ms={r['p99_token_latency_s'] * 1e3:.1f}"
            + (f"_speedup_vs_loop=x{r['prefill_speedup_vs_loop']:.2f}"
               if "prefill_speedup_vs_loop" in r else "")
            + (f"_vs_greedy=x{r['sampling_overhead_vs_greedy']:.2f}"
               if "sampling_overhead_vs_greedy" in r else ""))
    for arch, rl in doc.get("roofline", {}).items():
        row(f"serve/{arch}/roofline", rl["decode_bound_s"] * 1e6,
            f"dom={rl['dominant']}_measured_over_bound="
            f"{rl['measured_over_bound']:.0f}x")
    return doc


def _write_section_json(out_dir, section, rows, extra, quick):
    dev = jax.devices()[0]
    payload = {
        "section": section,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "quick": bool(quick),
        "timestamp": time.time(),
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    }
    if isinstance(extra, dict):
        payload.update(extra)
    path = os.path.join(out_dir, f"BENCH_{section}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path}", flush=True)


#: section registry, in run order; ``--only`` choices and help text derive
#: from it, so a new ``bench_<name>`` only needs one entry here.  Each
#: section writes ``BENCH_<name>.json``.
SECTIONS = {
    "layer_times": bench_layer_times,
    "perf_model": bench_perf_model,
    "sync_modes": bench_sync_modes,
    "kernels": bench_kernels,
    "train": bench_train,
    "scaling": bench_scaling,
    "staleness": bench_staleness,
    "overlap": bench_overlap,
    "elastic": bench_elastic,
    "roofline": bench_roofline,
    "serve": bench_serve,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=sorted(SECTIONS),
                    metavar="SECTION",
                    help=f"run one section (default: all, in registry "
                         f"order) — {', '.join(SECTIONS)}")
    ap.add_argument("--out",
                    default=os.path.normpath(
                        os.path.join(os.path.dirname(__file__), "..")),
                    help="directory for the BENCH_<section>.json artifacts")
    ap.add_argument("--trace-out", default=None,
                    help="attach the obs tracer (DESIGN.md §11) and write a "
                         "Perfetto trace.json of the harness run here: one "
                         "span per section plus whatever its cells emit")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        from repro.obs import trace as obs_trace
        tracer = Tracer("bench")
        obs_trace.set_tracer(tracer)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in SECTIONS.items():
        if args.only and name != args.only:
            continue
        start = len(ROWS)
        try:
            if tracer is not None:
                with tracer.span(f"section/{name}", quick=bool(args.quick)):
                    extra = fn(quick=args.quick)
            else:
                extra = fn(quick=args.quick)
        except Exception:  # run the other sections, then fail
            traceback.print_exc()
            failed.append(name)
            continue
        _write_section_json(args.out, name, ROWS[start:], extra, args.quick)
    if tracer is not None:
        from repro.obs import trace as obs_trace
        obs_trace.set_tracer(None)
        tracer.write(args.trace_out)
    if failed:
        sys.exit(f"benchmark section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
