"""Paper reproduction: the CHAOS speedup/scalability study.

Prints, side by side:
  - Fig 7/8-style speedup curves predicted by the paper's performance
    model (Section 5.2, Listing 2) and Table 8 (480..3840 threads),
  - the MEASURED worker-scaling curves from ``BENCH_scaling.json``
    (``benchmarks/run.py --only scaling``): the worker-mesh superstep
    path run at 1/2/4/8 workers for the three Table-2 nets x three sync
    modes, with the model's prediction for the same worker count,
  - a live CHAOS run through the production driver on up to 4 workers
    (``repro.launch.train.train(workers=N)``); on the CPU, forced host
    devices give it 4:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/chaos_speedup.py
"""
import json
import os
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, SRC)

from repro.core import perf_model as pm


def model_curves():
    print("== speedup vs Phi 1T (performance model, Listing 2) ==")
    print(f"{'threads':>8} {'small':>8} {'medium':>8} {'large':>8}")
    for p in (15, 30, 60, 120, 180, 240, 244):
        row = [f"{pm.predict_speedup(a, p):8.1f}"
               for a in ("small", "medium", "large")]
        print(f"{p:8d} " + " ".join(row))
    print("paper Result 3: up to 103x vs Phi 1T\n")

    print("== Table 8: predicted minutes beyond hardware threads ==")
    t8 = pm.table8()
    for arch in ("small", "medium", "large"):
        cells = "  ".join(f"{p}T={t8[arch][p]:6.1f}min"
                          for p in (480, 960, 1920, 3840))
        paper = "  ".join(f"{pm.PAPER_TABLE8[arch][p]}" for p in
                          (480, 960, 1920, 3840))
        print(f"{arch:7s} pred: {cells}")
        print(f"{'':7s} paper: {paper}")


def measured_curves(path=None):
    """Measured steps/sec + speedup per worker count (BENCH_scaling.json)
    printed next to the performance model's prediction for the same worker
    count — the paper's measured-vs-modeled methodology (Figs 11-13)."""
    path = path or os.path.join(ROOT, "BENCH_scaling.json")
    print("\n== measured worker scaling (BENCH_scaling.json) ==")
    if not os.path.exists(path):
        print(f"  {path} not found — generate it with:\n"
              f"    PYTHONPATH=src python -m benchmarks.run --only scaling")
        return
    with open(path) as f:
        data = json.load(f)
    runs = [r for r in data.get("runs", []) if not r.get("use_kernel")]
    if not runs:
        print("  no xla-path runs recorded")
        return
    print("  (forced host devices share one CPU: measured speedup shows "
          "the\n   harness + overhead trend; 'model' is the paper's "
          "prediction at N threads)")
    for net in ("chaos-small", "chaos-medium", "chaos-large"):
        net_runs = [r for r in runs if r["net"] == net]
        if not net_runs:
            continue
        print(f"\n  {net}")
        print(f"  {'mode':>9s} " + " ".join(
            f"{'N=' + str(n):>16s}"
            for n in sorted({r['workers'] for r in net_runs})))
        for mode in ("bsp", "chaos", "localsgd"):
            cells = []
            for r in sorted((r for r in net_runs if r["mode"] == mode),
                            key=lambda r: r["workers"]):
                cells.append(f"{r['steps_per_s']:6.2f}st/s "
                             f"{r['speedup_vs_1']:4.2f}x")
            if cells:
                print(f"  {mode:>9s} " + " ".join(f"{c:>16s}"
                                                  for c in cells))
        model = " ".join(
            f"{pm.predict_speedup(net.split('-')[1], n):15.2f}x"
            for n in sorted({r['workers'] for r in net_runs}))
        print(f"  {'model':>9s} {model}")


def staleness_curves(path=None):
    """Measured staleness-τ curves (BENCH_staleness.json): final error and
    steps/sec vs τ per net and worker count — the paper's Result 1-2 claim
    (accuracy not significantly degraded by asynchronous stale updates)
    next to the Listing-2 speedup model's prediction for the same worker
    count.  τ=0 IS bsp (the strategy registry resolves it), so its column
    is the synchronous baseline."""
    path = path or os.path.join(ROOT, "BENCH_staleness.json")
    print("\n== measured staleness-tau curves (BENCH_staleness.json) ==")
    if not os.path.exists(path):
        print(f"  {path} not found — generate it with:\n"
              f"    PYTHONPATH=src python -m benchmarks.run "
              f"--only staleness")
        return
    with open(path) as f:
        data = json.load(f)
    runs = data.get("runs", [])
    if not runs:
        print("  no runs recorded")
        return
    print("  (error columns are hardware-independent; steps/s on forced "
          "host\n   devices shares one CPU — see the artifact's note)")
    lw_runs = [r for r in runs if r.get("layerwise")]
    if lw_runs:
        print("  layerwise (per-bucket exchange, DESIGN.md §6) rows:")
        for r in lw_runs:
            s = r.get("speedup_vs_batched", float("nan"))
            print(f"    {r['net']:>12s} tau={r['tau']} N={r['workers']}: "
                  f"err={r['final_error']:.3f} "
                  f"{r['steps_per_s']:.1f} steps/s ({s:.2f}x batched)")
    for net in ("chaos-small", "chaos-medium", "chaos-large"):
        net_runs = [r for r in runs
                    if r["net"] == net and not r.get("layerwise")]
        if not net_runs:
            continue
        taus = sorted({r["tau"] for r in net_runs})
        print(f"\n  {net} (error | steps/s per tau)")
        print(f"  {'workers':>9s} " + " ".join(
            f"{'tau=' + str(t):>16s}" for t in taus))
        for n in sorted({r["workers"] for r in net_runs}):
            cells = []
            for t in taus:
                r = next((r for r in net_runs
                          if r["tau"] == t and r["workers"] == n), None)
                cells.append(f"{r['final_error']:.3f}|"
                             f"{r['steps_per_s']:6.2f}st/s" if r else "-")
            print(f"  {'N=' + str(n):>9s} " + " ".join(
                f"{c:>16s}" for c in cells))
        deltas = [abs(r.get("error_delta_vs_tau0", 0.0)) for r in net_runs
                  if r["tau"] > 0]
        if deltas:
            print(f"  max |error - tau0 error| = {max(deltas):.4f} "
                  f"(paper claim: not significantly degraded)")


def measured_workers():
    """Live demo: CHAOS workers through the production driver's worker-mesh
    route (shard_map superstep), in this process — a chip belongs to one
    process, so no child is started."""
    import jax

    from repro.launch.train import train

    n = min(4, len(jax.devices()))
    print(f"\n== live: {n} CHAOS worker(s) via repro.launch.train ==")
    _, losses = train("chaos-small", 12, "chaos", superstep=4, workers=n)
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} over {len(losses)} "
          f"steps")


if __name__ == "__main__":
    model_curves()
    measured_curves()
    staleness_curves()
    measured_workers()
