"""Observability subsystem (DESIGN.md §11): tracer export format, stamp
pairing, metrics bus semantics, the PR-6 metrics-out schema fold, and the
two overhead pins — obs off is bit-exact, obs on costs <= 2%."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import JsonlSink, MetricsBus, Tracer, get_tracer, set_tracer
from repro.obs import trace as obs_trace

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# tracer: chrome export format
# ---------------------------------------------------------------------------
def test_tracer_chrome_export(tmp_path):
    tr = Tracer("train")
    with tr.span("superstep", step_start=0, k=2):
        with tr.span("checkpoint", step=1):
            pass
    tr.instant("fault", kind="kill")
    tr.counter("watchdog/superstep_s", 0.25)
    tr.complete("request/7", 100.0, 250.0, process="serve", thread="slot0",
                rid=7)
    path = tmp_path / "trace.json"
    tr.write(str(path))

    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    # every non-metadata event's track carries metadata
    procs = {e["pid"] for e in evs if e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]) for e in evs
               if e.get("name") == "thread_name"}
    for e in evs:
        if e["ph"] == "M":
            continue
        assert e["pid"] in procs
        assert (e["pid"], e.get("tid", 0)) in threads
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    sup, ckpt = by_name["superstep"], by_name["checkpoint"]
    assert sup["ph"] == ckpt["ph"] == "X"
    # nesting: the inner span lies within the outer on the same track
    assert (sup["pid"], sup["tid"]) == (ckpt["pid"], ckpt["tid"])
    assert sup["ts"] <= ckpt["ts"]
    assert ckpt["ts"] + ckpt["dur"] <= sup["ts"] + sup["dur"] + 1e-3
    assert by_name["fault"]["ph"] == "i"
    assert by_name["watchdog/superstep_s"]["ph"] == "C"
    assert by_name["request/7"]["dur"] == pytest.approx(150.0)
    # the sibling JSONL has one event per line
    lines = (tmp_path / "trace.jsonl").read_text().splitlines()
    assert len(lines) == len(evs)
    assert all(json.loads(ln) for ln in lines)


def test_tracer_stamp_pairing():
    """bucket_issue/bucket_gate inside a jitted function pair into
    exchange/exchange_wait spans, and an injected delay is actually slept
    by the gate (the PR-7 deadline contract)."""
    tr = Tracer("train")

    @jax.jit
    def f(x):
        g = x * 2.0
        tok = tr.bucket_issue(g, "conv0", delay_ms=30.0,
                              args={"bytes": 128, "tau": 0})
        g = tr.bucket_gate(g, tok, g, "conv0")
        return g

    x = jnp.ones((4,))
    t0 = time.monotonic()
    y1 = jax.block_until_ready(f(x))
    y2 = jax.block_until_ready(f(x))
    dt = time.monotonic() - t0
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_allclose(np.asarray(y1), 2.0)  # value-preserving
    assert dt >= 0.05                                # 2 x 30ms slept

    spans = tr.finalize()
    ex = [e for e in spans if e["name"] == "exchange/conv0"]
    wait = [e for e in spans if e["name"] == "exchange_wait/conv0"]
    assert len(ex) == len(wait) == 2
    for e in ex + wait:
        assert e["args"]["bucket"] == "conv0"
        assert e["args"]["bytes"] == 128
    for w in wait:
        assert w["args"]["slept_ms"] == pytest.approx(30.0, rel=0.5)
        assert w["dur"] >= 25e3                      # us


def test_tracer_global_install():
    assert get_tracer() is None
    with obs_trace.span("noop") as t:
        assert t is None                             # no-op without tracer
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        assert prev is None and get_tracer() is tr
        with obs_trace.span("superstep"):
            pass
        assert any(e["name"] == "superstep" for e in tr.to_chrome()
                   ["traceEvents"])
    finally:
        set_tracer(prev)
    assert get_tracer() is None


# ---------------------------------------------------------------------------
# metrics bus
# ---------------------------------------------------------------------------
def test_metrics_bus_summary(tmp_path):
    sink = JsonlSink(str(tmp_path / "metrics.jsonl"))
    bus = MetricsBus(sink=sink)
    bus.counter("serve/decode_dispatch")
    bus.counter("serve/decode_dispatch", 3)
    bus.gauge("train/steps_per_s", 12.5)
    for v in [0.1, 0.2, 0.3]:
        bus.observe("serve/ttft_s", v)
    bus.series("train/loss", 0, 2.5)
    bus.series("train/loss", 2, 2.3)
    bus.series("train/loss", 2, 2.2)                 # same step overwrites
    bus.event("resize", **{"from": 4, "to": 3})
    bus.flush(step=2)
    bus.close()

    s = bus.summary()
    assert s["counters"]["serve/decode_dispatch"] == 4
    assert s["gauges"]["train/steps_per_s"] == 12.5
    h = s["histograms"]["serve/ttft_s"]
    assert h["count"] == 3
    assert h["mean"] == pytest.approx(0.2)
    assert h["min"] == 0.1 and h["max"] == 0.3
    assert s["series"]["train/loss"]["steps"] == [0, 2]
    assert s["series"]["train/loss"]["values"] == [2.5, 2.2]
    assert s["events"]["resize"][0]["to"] == 3
    assert bus.series_sorted("train/loss") == [2.5, 2.2]
    lines = [json.loads(ln) for ln in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert lines                                     # flush wrote something


def test_metrics_out_schema(tmp_path):
    """write_metrics_out preserves the PR-6 --metrics-out contract that
    CI's preemption smoke asserts on: losses/resizes/faults/workers_final."""
    bus = MetricsBus()
    for t, v in enumerate([2.5, 2.4, 2.3, 2.2]):
        bus.series("train/loss", t, v)
    bus.event("resize", **{"from": 4, "to": 3, "path": "dense"})
    bus.event("fault", kind="kill", at=2)
    path = str(tmp_path / "metrics.json")
    bus.write_metrics_out(path, arch="chaos-small", sync="bsp", steps=4,
                          workers_final=3)
    doc = json.loads(open(path).read())
    assert doc["arch"] == "chaos-small"
    assert doc["sync"] == "bsp"
    assert doc["steps"] == 4
    assert doc["losses"] == [2.5, 2.4, 2.3, 2.2]
    assert (doc["resizes"][0]["from"], doc["resizes"][0]["to"]) == (4, 3)
    assert doc["faults"][0]["kind"] == "kill"
    assert doc["workers_final"] == 3


# ---------------------------------------------------------------------------
# overhead pins: obs off is bit-exact; obs on (bus attached) <= 2%
# ---------------------------------------------------------------------------
def _timed_train(steps, superstep, bus=None):
    from repro.launch.train import train
    t0 = time.perf_counter()
    _, losses = train("chaos-small", steps, "bsp", batch=8,
                      log_every=10_000, superstep=superstep,
                      metrics_bus=bus)
    return time.perf_counter() - t0, [float(x) for x in losses]


def test_obs_overhead_and_bit_exactness():
    steps, K = 48, 8
    _timed_train(8, 8)                               # warm compile caches
    assert get_tracer() is None                      # tracing disabled
    # min-of-attempts absorbs scheduler noise; the losses pin is hard on
    # every attempt, the <=2% steps/sec pin must hold for the best pair
    base_losses = obs_losses = None
    best_base = best_obs = float("inf")
    last_bus = None
    for _ in range(3):
        dt_b, l_b = _timed_train(steps, K)
        bus = MetricsBus()
        dt_o, l_o = _timed_train(steps, K, bus=bus)
        if base_losses is None:
            base_losses, obs_losses = l_b, l_o
        assert l_b == base_losses and l_o == obs_losses
        best_base = min(best_base, dt_b)
        best_obs = min(best_obs, dt_o)
        last_bus = bus
        if best_obs <= best_base * 1.02:
            break
    # bit-exactness: the bus only OBSERVES host-side values — losses from
    # the obs run are bit-identical to the no-obs run
    assert obs_losses == base_losses
    s = last_bus.summary()
    assert s["series"]["train/loss"]["values"] == base_losses
    assert s["gauges"]["train/steps_per_s"] > 0
    assert best_obs <= best_base * 1.02, (
        f"obs-on train {best_obs:.3f}s vs {best_base:.3f}s "
        f"(+{(best_obs / best_base - 1) * 100:.1f}%, budget 2%)")


# ---------------------------------------------------------------------------
# 4-worker traced driver run: structure + exchange_us cross-check
# ---------------------------------------------------------------------------
def test_traced_interleave_driver(tmp_path):
    """The acceptance path: --trace-out on the 4-worker interleave driver
    with injected collective latency yields per-bucket exchange spans for
    every bucket x step x worker, each covering its injected latency, and
    their summed gate-wait agrees with the sleeps the same run computed
    within 25%."""
    trace_path = str(tmp_path / "trace.json")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "chaos-small",
         "--steps", "8", "--superstep", "2", "--workers", "4",
         "--sync", "bsp", "--layerwise", "--interleave",
         "--collective-delay", "400", "--trace-out", trace_path],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-4000:]

    root = os.path.join(os.path.dirname(__file__), "..")
    check = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "trace_check.py"),
         trace_path, "--steps", "8", "--superstep", "2", "--workers", "4",
         "--check-waits", "--tolerance", "0.25"],
        capture_output=True, text=True, timeout=120)
    assert check.returncode == 0, (check.stdout + check.stderr)[-4000:]
    assert "OK" in check.stdout


# ---------------------------------------------------------------------------
# watchdog gauges
# ---------------------------------------------------------------------------
def test_watchdog_exports_observations():
    from repro.launch.train import StragglerWatchdog
    bus, tr = MetricsBus(), Tracer("train")
    wd = StragglerWatchdog(warmup=0, bus=bus, tracer=tr)
    for step in range(10):
        assert not wd.observe(step, 0.1)
    assert wd.observe(10, 0.9)                       # straggler
    s = bus.summary()
    h = s["histograms"]["watchdog/superstep_s"]
    assert h["count"] == 11                          # every observation
    assert s["gauges"]["watchdog/superstep_s"] == pytest.approx(0.9)
    assert s["events"]["straggler"][0]["step"] == 10
    evs = tr.to_chrome()["traceEvents"]
    assert any(e["name"] == "watchdog/superstep_s" and e["ph"] == "C"
               for e in evs)
    assert any(e["name"] == "straggler" and e["ph"] == "i" for e in evs)
