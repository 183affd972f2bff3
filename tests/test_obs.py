"""Observability subsystem (DESIGN.md §11): tracer export format, spans
on the profiler's host plane and clock, metrics bus semantics, the PR-6
metrics-out schema fold, and the two overhead pins — obs off is
bit-exact, obs on costs <= 2%."""
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
    req = tr.open("request/7", rid=7)
    time.sleep(0.002)
    tr.complete(req, process="serve", thread="slot0", generated=3)
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
    assert by_name["request/7"]["dur"] >= 2e3                # us
    assert by_name["request/7"]["args"] == {"rid": 7, "generated": 3}
    # the sibling JSONL has one event per line
    lines = (tmp_path / "trace.jsonl").read_text().splitlines()
    assert len(lines) == len(evs)
    assert all(json.loads(ln) for ln in lines)


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


def _xplane_spans(trace_dir, names):
    """{name: [(start, end) ns since the Unix epoch]} of the host events
    with those names in the profile written under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    data = ProfileData.from_file(paths[0])
    env = data.find_plane_with_name("Task Environment")
    t0 = dict(env.stats)["profile_start_time"]
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.setdefault(ev.name, []).append(
                            (t0 + ev.start_ns, t0 + ev.end_ns))
    return out


def test_spans_on_the_profiler_host_plane(tmp_path):
    """Under ``jax.profiler``: an obs span and the feed's spans from a
    short PrefetchFeed run sit on the host plane, without a tracer too,
    and a tracer's trace.json start agrees with the profile's within 1 ms
    (one clock)."""
    from repro.data.pipeline import ImagePipeline
    from repro.launch.train import PrefetchFeed, superstep_schedule

    imgs = np.zeros((16, 29, 29, 1), np.float32)
    labels = np.zeros((16,), np.int32)
    pipe = ImagePipeline(imgs, labels, batch=4, seed=0, sample_mode="queue")
    tr = Tracer("train")
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for _ in PrefetchFeed(pipe, superstep_schedule(0, 6, 2)):
            pass
        with obs_trace.span("checkpoint", step=3):   # no tracer installed
            time.sleep(0.002)
        with tr.span("superstep", step_start=0, k=2):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    got = _xplane_spans(str(tmp_path / "prof"),
                        {"feed/wait", "feed/build", "feed/put",
                         "checkpoint", "superstep"})
    assert len(got["feed/build"]) == len(got["feed/put"]) == 3
    assert len(got["feed/wait"]) >= 3
    assert len(got["checkpoint"]) == 1
    (start, end), = got["superstep"]
    ev, = [e for e in tr.to_chrome()["traceEvents"]
           if e["name"] == "superstep"]
    assert abs(ev["ts"] * 1e3 - start) < 1e6                # ns
    assert abs((ev["ts"] + ev["dur"]) * 1e3 - end) < 1e6


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
# 4-worker traced driver run: trace.json format + superstep structure
# ---------------------------------------------------------------------------
def test_traced_interleave_driver(tmp_path):
    """--trace-out on the 4-worker interleave driver with injected
    collective latency writes a trace.json that Perfetto loads, with one
    superstep span per dispatch, each holding its dispatch and
    loss_readback spans."""
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
         trace_path, "--steps", "8", "--superstep", "2"],
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
