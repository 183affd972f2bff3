"""Continuous-batching serving benchmark (DESIGN.md §9).

For each served family (dense GQA, MLA, state) this module replays the
same seeded Poisson request trace through the ServeEngine twice —

  * ``batched`` prefill: whole right-padded prompts in ONE dispatch
    through the q_offset-aware flash attention;
  * ``loop`` prefill: the pre-§9 token-at-a-time reference loop —

and reports tokens/sec, p50/p99 per-token latency (pure-decode step wall
time: every active request receives exactly one token per step), and the
batched-over-loop prefill speedup.  A roofline sanity row cross-checks the
measured decode step against the compiled dispatch's analytic bound
(core/roofline.py): on any backend measured >= bound must hold — the bound
uses TPU v5e roofs, so the CPU ratio is large but the direction is pinned.

Each engine is warmed by replaying the trace once untimed, so every
(A, T) prefill bucket and the decode program are compiled before timing.

Prints one JSON document {"runs": [...], "roofline": {...}} to stdout;
progress lines go to stderr.  ``benchmarks/run.py --only serve`` calls
``run`` in its own process.

    PYTHONPATH=src python -m benchmarks.serve [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ARCHS = ("qwen3-14b", "minicpm3-4b", "rwkv6-1.6b")


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _replay(eng, trace):
    """Run a trace to completion; returns (finished, step_stats) where each
    step stat is (wall_s, admitted_this_step, tokens_this_step)."""
    for r in trace:
        eng.submit(r)
    finished, stats = [], []
    while eng.pending or eng.active:
        pre0 = eng.counters["prefill_dispatch"]
        tok0 = (eng.counters["prefill_tokens"]
                + eng.counters["decode_tokens"])
        t0 = time.perf_counter()
        finished.extend(eng.step())
        dt = time.perf_counter() - t0
        stats.append((dt, eng.counters["prefill_dispatch"] - pre0,
                      eng.counters["prefill_tokens"]
                      + eng.counters["decode_tokens"] - tok0))
    return finished, stats


def _bench_mode(arch, mode, trace, slots, max_seq, seed,
                temperature=0.0, top_p=1.0):
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(arch, slots=slots, max_seq=max_seq, seed=seed,
                      prefill_mode=mode, temperature=temperature,
                      top_p=top_p)
    sampling = ("greedy" if temperature <= 0
                else f"t{temperature:g}_p{top_p:g}")
    _log(f"[serve-bench] {arch}/{mode}/{sampling}: warmup replay")
    _replay(eng, [r.__class__(**vars(r)) for r in trace])
    eng.clock, eng.step_idx = 0.0, 0
    _log(f"[serve-bench] {arch}/{mode}: measured replay")
    t0 = time.perf_counter()
    finished, stats = _replay(eng, [r.__class__(**vars(r)) for r in trace])
    wall = time.perf_counter() - t0
    toks = sum(s[2] for s in stats)
    decode_steps = [s[0] for s in stats if s[1] == 0 and s[2] > 0]
    lat = (np.percentile(decode_steps, [50, 99]) if decode_steps
           else np.array([float("nan")] * 2))
    return {
        "arch": arch, "mode": mode, "sampling": sampling, "slots": slots,
        "requests": len(trace), "tokens": int(toks), "wall_s": wall,
        "tokens_per_s": toks / wall,
        "p50_token_latency_s": float(lat[0]),
        "p99_token_latency_s": float(lat[1]),
        "gen_checksum": int(sum(int(f.tokens.sum()) for f in finished)
                            % (1 << 31)),
    }, eng


def _roofline_row(eng, arch):
    """Analytic bound for ONE decode dispatch of the warmed engine."""
    import jax
    import jax.numpy as jnp
    from repro.core import roofline

    toks = jnp.asarray(eng.last_tok)
    cur = jnp.asarray(eng.kv.cursors)
    rids = jnp.asarray(eng.slot_rid)
    poss = jnp.zeros_like(rids)
    compiled = eng._decode.lower(eng.params, eng.kv.tree, toks, cur,
                                 rids, poss).compile()
    n_active_params = eng.cfg.active_param_count()
    # on the CPU the bound models the v5e this engine is built for
    kind = (roofline.V5E if jax.default_backend() == "cpu"
            else jax.devices()[0].device_kind)
    rl = roofline.analyze(compiled, n_devices=1, device_kind=kind,
                          model_flops_total=2.0 * n_active_params
                          * eng.kv.slots)
    return {"arch": arch, "decode_bound_s": rl.bound_s,
            "dominant": rl.dominant,
            "flops_per_dispatch": rl.flops,
            "bytes_per_dispatch": rl.bytes_accessed,
            "device_kind": kind,
            "note": "bound uses the roofs of device_kind (a v5e when run "
                    "on the CPU); sanity contract is measured_p50 >= "
                    "bound on every backend"}


def run(quick: bool = False, seed: int = 0) -> dict:
    from repro.serve.engine import poisson_trace
    import repro.configs as C

    slots, requests, gen = (2, 4, 4) if quick else (4, 10, 8)
    prompt_lens = (4, 12) if quick else (6, 24)
    max_seq = 64
    runs, roofline_info = [], {}
    for arch in ARCHS:
        cfg = C.smoke(arch)
        trace = poisson_trace(seed, requests, rate=1.0,
                              vocab=cfg.vocab_size,
                              prompt_lens=prompt_lens, max_new=gen)
        per_mode = {}
        for mode in ("batched", "loop"):
            r, eng = _bench_mode(arch, mode, trace, slots, max_seq, seed)
            per_mode[mode] = r
            runs.append(r)
            _log(f"[serve-bench] {arch}/{mode}: "
                 f"{r['tokens_per_s']:.1f} tok/s "
                 f"p50={r['p50_token_latency_s'] * 1e3:.1f}ms "
                 f"p99={r['p99_token_latency_s'] * 1e3:.1f}ms")
            if mode == "batched":
                rl = _roofline_row(eng, arch)
                rl["measured_p50_s"] = r["p50_token_latency_s"]
                rl["measured_over_bound"] = (
                    r["p50_token_latency_s"] / rl["decode_bound_s"]
                    if rl["decode_bound_s"] else float("nan"))
                roofline_info[arch] = rl
        b, l = per_mode["batched"], per_mode["loop"]
        if b["gen_checksum"] != l["gen_checksum"]:
            _log(f"[serve-bench] WARNING {arch}: batched/loop token "
                 f"checksums differ ({b['gen_checksum']} vs "
                 f"{l['gen_checksum']})")
        speedup = b["tokens_per_s"] / l["tokens_per_s"]
        b["prefill_speedup_vs_loop"] = speedup
        _log(f"[serve-bench] {arch}: batched prefill speedup x{speedup:.2f}")
        # sampling-mode column: the same trace through seeded top-p
        # sampling fused into the decode dispatch (cost of sampling =
        # this row vs the greedy batched row)
        rs, _ = _bench_mode(arch, "batched", trace, slots, max_seq,
                            seed, temperature=0.8, top_p=0.9)
        rs["sampling_overhead_vs_greedy"] = (
            b["tokens_per_s"] / rs["tokens_per_s"])
        runs.append(rs)
        _log(f"[serve-bench] {arch}: sampled decode "
             f"{rs['tokens_per_s']:.1f} tok/s "
             f"(x{rs['sampling_overhead_vs_greedy']:.2f} vs greedy)")
    return {"runs": runs, "roofline": roofline_info}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps(run(args.quick, args.seed), indent=1))


if __name__ == "__main__":
    main()
