import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).
(the two os.environ lines above MUST precede any jax import — jax locks the
device count on first init)

For every (architecture x input-shape) cell this lowers + compiles the real
train_step / serve_step under the production mesh with ShapeDtypeStruct
inputs (no allocation), prints memory/cost analysis, and records roofline
terms to a JSON results file.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro.core import roofline as RL
from repro.core.chaos import SyncConfig
from repro.core.types import SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cell_status, input_specs
from repro.models import layers as ML
from repro.models.api import get_ops
from repro.train import sharding as SH
from repro.train.step import (init_train_state, make_optimizer,
                              make_train_step, state_specs)


def _layers_pair(cfg):
    """(L1, L2) reduced layer counts for the roofline tier — one and two
    periods of the arch's repeating layer pattern."""
    period = cfg.attn_every if cfg.family == "hybrid" else 1
    L1 = max(2, period)
    return L1, 2 * L1


def _with_layers(cfg, n):
    kw = {"n_layers": n}
    if cfg.family == "encdec":
        kw["n_enc_layers"] = max(1, round(cfg.n_enc_layers * n / cfg.n_layers))
    return dataclasses.replace(cfg, **kw)


def _batch_shardings(batch_abs, mesh):
    spec = jax.tree.map(lambda _: P("dp"), batch_abs)
    return SH.shardings_for(spec, batch_abs, mesh)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               sync_mode: str = "bsp", verbose: bool = True,
               compress: bool = False, extra_cfg: dict | None = None,
               unroll: bool = False, layers_override: int | None = None,
               rules: dict | None = None):
    """Lower+compile one cell.  Returns (compiled, info dict).

    unroll=False: production program (scan over layers) — compile-success
    proof + memory analysis.  unroll=True (+layers_override): straight-line
    HLO for roofline accounting (cost analysis counts loop bodies once).
    """
    cfg = C.get(arch)
    if layers_override:
        cfg = _with_layers(cfg, layers_override)
    if unroll:
        cfg = dataclasses.replace(cfg, scan_layers=False)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    status = cell_status(cfg, shape)
    if status != "ok":
        return None, {"arch": arch, "shape": shape_name, "status": status}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    sync = SyncConfig(mode=sync_mode, compress=compress)
    ops = get_ops(cfg)
    ML.UNROLL_ATTN = unroll
    t0 = time.time()
    try:
        with SH.use_mesh(mesh, rules):
            if shape.kind == "train":
                optimizer = make_optimizer(cfg)
                state_abs = init_train_state(cfg, jax.random.key(0), sync,
                                             optimizer, abstract=True)
                specs = state_specs(cfg, sync, optimizer)
                state_sh = SH.shardings_for(specs, state_abs, mesh,
                                            rules=rules)
                batch_abs = input_specs(cfg, shape)
                bsh = _batch_shardings(batch_abs, mesh)
                step = make_train_step(cfg, sync, optimizer)
                lowered = jax.jit(
                    step, in_shardings=(state_sh, bsh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,),
                ).lower(state_abs, batch_abs)
            elif shape.kind == "prefill":
                pspecs = ops.param_specs()
                params_abs = ops.abstract_params()
                psh = SH.shardings_for(pspecs, params_abs, mesh, rules=rules)
                batch_abs = input_specs(cfg, shape)
                bsh = _batch_shardings(batch_abs, mesh)

                def prefill(params, batch):
                    # hidden states only; project just the LAST position
                    # (prefill never needs the full (B,T,V) logits)
                    if cfg.family == "encdec":
                        h, _ = ops.forward(params, batch["tokens"],
                                           batch["frames"],
                                           return_hidden=True)
                    elif cfg.family == "vlm":
                        h, _ = ops.forward(
                            params, batch["tokens"],
                            patch_embeds=batch["patch_embeds"],
                            return_hidden=True)
                    else:
                        h, _ = ops.forward(params, batch["tokens"],
                                           return_hidden=True)
                    out = params.get("out_embed", params["embed"])
                    logits = jnp.einsum("bd,vd->bv", h[:, -1], out)
                    return jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)

                lowered = jax.jit(prefill, in_shardings=(psh, bsh)
                                  ).lower(params_abs, batch_abs)
            else:  # decode
                pspecs = ops.param_specs()
                params_abs = ops.abstract_params()
                psh = SH.shardings_for(pspecs, params_abs, mesh, rules=rules)
                cache_abs = ops.abstract_cache(shape.global_batch,
                                               shape.seq_len)
                csh = SH.shardings_for(
                    ops.cache_specs(shape.global_batch, shape.seq_len),
                    cache_abs, mesh, rules=rules)
                tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1),
                                               jnp.int32)
                tsh = SH.shardings_for(P("dp"), tok_abs, mesh)

                def serve(params, cache, tokens):
                    logits, new_cache = ops.decode(params, cache, tokens,
                                                   shape.seq_len - 1)
                    nxt = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)
                    return nxt.astype(jnp.int32), new_cache

                lowered = jax.jit(
                    serve, in_shardings=(psh, csh, tsh),
                    out_shardings=(tsh, csh),
                    donate_argnums=(1,),
                ).lower(params_abs, cache_abs, tok_abs)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    finally:
        ML.UNROLL_ATTN = False

    mf = RL.model_flops(cfg, shape)
    rl = RL.analyze(compiled, n_devices=n_dev, device_kind=RL.V5E,
                    model_flops_total=mf)
    mem = compiled.memory_analysis()
    # XLA-CPU promotes bf16 buffers to f32 for compute (wrapped_convert
    # computations with identical shapes): those f32 copies do not exist on
    # the bf16-native TPU target.  Estimate the inflation so the report can
    # carry a TPU-corrected peak alongside the raw CPU-backend number.
    import re as _re
    cpu_promo = 0
    for mm in _re.finditer(
            r"\(param_[\d.]+: bf16\[([\d,]+)\]\) -> f32\[\1\]",
            compiled.as_text()):
        n = 1
        for dd in mm.group(1).split(","):
            if dd:
                n *= int(dd)
        cpu_promo += 4 * n
    info = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev), "sync": sync_mode,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                / 2**30, 3),
            "cpu_bf16_promotion_gib": round(cpu_promo / 2**30, 3),
            "tpu_peak_estimate_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes
                 - cpu_promo) / 2**30, 3),
        } if mem else None,
        "roofline": rl.to_dict(),
    }
    if verbose:
        print(f"[{arch} x {shape_name} mesh={info['mesh']}] "
              f"compile={t_compile:.1f}s "
              f"peak/dev={info['memory_analysis']['peak_per_device_gib']}GiB "
              f"dominant={rl.dominant} "
              f"terms(c/m/x)={rl.compute_s:.4f}/{rl.memory_s:.4f}/"
              f"{rl.collective_s:.4f}s")
        print("  memory_analysis:", info["memory_analysis"])
        print("  cost_analysis: flops/dev=%.3e bytes/dev=%.3e" %
              (rl.flops, rl.bytes_accessed))
    return compiled, info


def roofline_cell(arch: str, shape_name: str, *, sync_mode: str = "bsp",
                  compress: bool = False, extra_cfg: dict | None = None,
                  verbose: bool = True, rules: dict | None = None):
    """Roofline tier: lower UNROLLED reduced-depth programs at two layer
    counts (L1, 2*L1) and extrapolate per-layer costs to the full depth.
    Exact for homogeneous stacks (all assigned archs repeat one pattern)."""
    cfg = C.get(arch)
    shape = SHAPES[shape_name]
    status = cell_status(cfg, shape)
    if status != "ok":
        return {"arch": arch, "shape": shape_name, "status": status,
                "tier": "roofline"}
    L1, L2 = _layers_pair(cfg)
    L = cfg.n_layers
    kw = dict(sync_mode=sync_mode, compress=compress, extra_cfg=extra_cfg,
              verbose=False, unroll=True, rules=rules)
    _, i1 = lower_cell(arch, shape_name, layers_override=L1, **kw)
    _, i2 = lower_cell(arch, shape_name, layers_override=L2, **kw)
    r1, r2 = i1["roofline"], i2["roofline"]

    def ext(key):
        v1, v2 = r1[key], r2[key]
        return v2 + (L - L2) * (v2 - v1) / (L2 - L1)

    flops = ext("flops_per_dev")
    bytes_acc = ext("bytes_per_dev")
    coll_eff = ext("collective_effective_bytes")
    coll_tot = ext("collective_bytes_per_dev")
    mf = RL.model_flops(cfg, shape)
    n_dev = i2["n_devices"]
    pk = RL.peaks(RL.V5E)     # the dry run models a v5e pod
    compute_s = flops / pk.flops
    memory_s = bytes_acc / pk.hbm_bw
    coll_s = coll_eff / pk.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    info = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "tier": "roofline", "mesh": i2["mesh"], "n_devices": n_dev,
        "sync": sync_mode, "layers_pair": [L1, L2],
        "roofline": {
            "flops_per_dev": flops, "bytes_per_dev": bytes_acc,
            "collective_bytes_per_dev": coll_tot,
            "collective_effective_bytes": coll_eff,
            "collective_counts_L2": r2["collective_counts"],
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s, "dominant": dominant,
            "bound_s": max(terms.values()),
            "roofline_fraction": max(terms.values()) / sum(terms.values()),
            "model_flops_total": mf,
            "model_flops_per_dev": mf / n_dev,
            "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
        },
    }
    if verbose:
        r = info["roofline"]
        print(f"[ROOFLINE {arch} x {shape_name}] dominant={dominant} "
              f"c/m/x = {compute_s:.4f}/{memory_s:.4f}/{coll_s:.4f} s "
              f"useful={r['useful_flops_ratio']:.2f}")
    return info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="also run the roofline tier (single-pod)")
    ap.add_argument("--sync", default="bsp")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in C.ASSIGNED:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    results = []
    out_path = args.out

    def record(info):
        results.append(info)
        if out_path:
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1, default=str)

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch, shape in cells:
        for mp in meshes:
            try:
                _, info = lower_cell(arch, shape, multi_pod=mp,
                                     sync_mode=args.sync)
                info["tier"] = "production"
            except Exception as e:
                info = {"arch": arch, "shape": shape, "tier": "production",
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": f"FAIL: {type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:]}
                print(f"[{arch} x {shape}] FAILED: {e}")
            record(info)
        if args.roofline:
            try:
                record(roofline_cell(arch, shape, sync_mode=args.sync))
            except Exception as e:
                print(f"[ROOFLINE {arch} x {shape}] FAILED: {e}")
                record({"arch": arch, "shape": shape, "tier": "roofline",
                        "status": f"FAIL: {type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:]})
    ok = sum(1 for r in results if r.get("status") == "ok")
    skip = sum(1 for r in results if str(r.get("status", "")).startswith("skip"))
    print(f"\n== dry-run: {ok} ok, {skip} skipped, "
          f"{len(results) - ok - skip} failed, {len(results)} total ==")


if __name__ == "__main__":
    main()
