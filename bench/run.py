#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload large-chaos-xla-1chip --seed 7 \
        --seconds 10 --trace 0

A cell is a configuration (``bench/configs/<name>.json``: a model's sizes
and the name of its family) under a traffic mix
(``bench/traffic/<name>.json``, the training job).  Everything that
depends on the kind of model lives in the family's module
(``bench/families/<family>.py``, whose docstring lists its functions): the
program's model configuration, the inputs made from the seed, the pipeline
the feed draws from, and the counts of operations and bytes.  The run
drives the program's worker route as ``launch/train.py::_train`` builds
it, from the same objects: ``make_optimizer``, ``init_worker_state`` and
``make_worker_superstep`` over ``make_host_mesh``, fed by ``PrefetchFeed``
with ``put_worker_sharded`` over the family's pipeline, one host sync per
superstep on the loss vector.

Set-up (``setup_s``, from the start of the process): imports, the chips,
the inputs made from the seed, the weights made on the chips from the
seed, the first superstep (which compiles or loads the compiled step from
the persistent cache), and warm-up supersteps until two in a row agree.
Then the window: supersteps until ``--seconds`` have passed.  With
``--trace 1`` the window is a profiler-traced one of at least
``TRACE_SECONDS`` and ``TRACE_SUPERSTEPS``, and the line carries the
per-layer metrics (``bench/metrics/<name>.py``, each handed the reduced
trace, the compiled step's scopes and the family module) instead of the
end-to-end ones.

``correct`` compares the first superstep of the timed path with the plain
reference (``bench/reference/``), after the window, by ``bench/check.py``
against the cell's limits (``bench/checks/<cell>.json``).

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")

#: the traced window: at least this many seconds and supersteps
TRACE_SECONDS = 1.0
TRACE_SUPERSTEPS = 4
#: warm-up ends when a superstep's time is within this share of the one
#: before it; at least WARMUP_MIN and at most WARMUP_MAX supersteps
WARMUP_AGREE = 0.25
WARMUP_MIN, WARMUP_MAX = 2, 12
#: steps the feed schedule covers: more than any window can use
SCHEDULE_STEPS = 4_000_000


class Failure(Exception):
    """A run that must end without a result."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything ``BENCHMARK.json`` and the cell's files say about it."""
    import traffic as traffic_mod

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    applies = lambda m: name in m.get("workloads", [name])
    return {
        "name": name,
        "chips": cell["chips"],
        "cfg": cfg,
        "family": load_family(cfg),
        "traffic": traffic_mod.load(cell["traffic"]),
        "limits": load_json(os.path.join(BENCH, "checks", f"{name}.json")),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def _load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return _load_module("metrics", metric).read


def load_reference(name: str):
    return _load_module("reference", name)


def load_family(cfg: dict):
    """The module of the family the configuration file names."""
    name = cfg.get("family")
    if not name:
        raise Failure(f"configuration {cfg.get('name')!r} names no family")
    if not os.path.exists(os.path.join(BENCH, "families", f"{name}.py")):
        raise Failure(f"configuration {cfg.get('name')!r}: no module for "
                      f"family {name!r} in bench/families")
    return _load_module("families", name)


def prepare_env() -> None:
    """Environment the program reads, set before JAX is imported."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise Failure(f"no program under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    # block configs from the committed cache (heuristic defaults), never
    # from the home directory
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(BENCH,
                                                      "autotune.json")


def chips_or_fail(n: int):
    """The first ``n`` TPU chips; anything else is a failure."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failure(f"needs a TPU; JAX found {devs[0].platform} "
                      f"({devs[0].device_kind})")
    if len(devs) < n:
        raise Failure(f"the cell asks for {n} chips; JAX found {len(devs)}")
    return devs


class Job:
    """The program's timed path for one cell and seed, built as
    ``launch/train.py::_train`` builds its worker route.

    ``fault`` plants a fault in the timed path (for the harness's own
    tests and the control readings, never in a benchmark run):
    ``"unchanged"`` returns the state it was given, ``"half_batch"``
    feeds the first half of every batch twice, ``"altered_loss"`` reports
    the first step's loss 1% high, ``"no_exchange"`` leaves the exchange
    between workers out of the state (its stale terms stay zero)."""

    def __init__(self, cell: dict, seed: int, data,
                 fault: str | None = None):
        import jax

        from repro.core.chaos import SyncConfig
        from repro.core.types import WorkerConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import (PrefetchFeed, put_worker_sharded,
                                        superstep_schedule)
        from repro.train.step import (init_worker_state, make_optimizer,
                                      make_worker_superstep)
        from repro.train.sync import get_strategy

        cfg, tr, family = cell["cfg"], cell["traffic"], cell["family"]
        try:
            arch = family.arch(cfg, tr)
        except ValueError as e:
            raise Failure(str(e)) from None
        self.k, self.batch = tr["superstep"], tr["batch"]
        optimizer = make_optimizer(
            arch, total_steps=family.total_steps(cfg, tr))
        worker = WorkerConfig(workers=tr["workers"],
                              logical_shards=tr["logical_shards"])
        worker.validate_batch(self.batch)
        self.mesh = make_host_mesh(tr["workers"])
        sync = SyncConfig(mode=tr["sync"], axis_name=worker.axis,
                          staleness=tr["staleness"])
        self.super_fn = make_worker_superstep(arch, sync, worker, self.mesh,
                                              optimizer)
        self.stacked = get_strategy(sync).stacked_state
        self.state = init_worker_state(arch, jax.random.key(seed), sync,
                                       worker, optimizer)
        pipe = family.pipeline(cfg, tr, seed, data)
        if fault == "half_batch":
            pipe = _HalfBatch(pipe)
        if fault in ("unchanged", "altered_loss", "no_exchange"):
            self.super_fn = _planted(self.super_fn, fault)
        elif fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        put = lambda p, s, k: put_worker_sharded(p, s, k, self.mesh, worker)
        self.feed = PrefetchFeed(
            pipe, superstep_schedule(0, SCHEDULE_STEPS, self.k), put=put)
        self._it = iter(self.feed)

    def superstep(self, annotate=None):
        """One superstep through the feed; returns (feed wait s, host
        losses).  ``annotate(name)`` gives a context per host phase."""
        import contextlib

        span = annotate or (lambda name: contextlib.nullcontext())
        t0 = time.perf_counter()
        with span("feed_wait"):
            _, _, batch = next(self._it)
        self.last_batch = batch
        t1 = time.perf_counter()
        with span("dispatch"):
            self.state, metrics = self.super_fn(self.state, batch)
        with span("loss_readback"):
            losses = np.asarray(metrics["loss"])
        return t1 - t0, losses

    def host_state(self) -> dict:
        """Weights and stale exchange terms on the host, each with a
        leading worker axis (a strategy whose workers stay identical keeps
        one copy)."""
        import jax

        st = jax.device_get(self.state)
        params = st["params"]
        if not self.stacked:
            params = jax.tree.map(lambda x: x[None], params)
        return {"params": params, "stale": st["sync"].get("hist", {})}

    def close(self) -> None:
        self.feed.stop()
        self.state = None


class _HalfBatch:
    """A pipeline whose every batch repeats its first half: half of the
    batch left out, the mean taken over the rest."""

    def __init__(self, pipe):
        self._pipe = pipe

    def superstep_at(self, start: int, k: int):
        out = self._pipe.superstep_at(start, k)
        half = out["labels"].shape[1] // 2
        return {key: np.concatenate([v[:, :half], v[:, :half]], axis=1)
                for key, v in out.items()}


def _planted(super_fn, fault: str):
    import jax
    import jax.numpy as jnp

    def fn(state, batch):
        if fault == "unchanged":
            kept = jax.tree.map(jnp.copy, state)
            _, metrics = super_fn(state, batch)
            return kept, metrics
        new_state, metrics = super_fn(state, batch)
        if fault == "no_exchange":
            sync = new_state["sync"]
            hist = jax.tree.map(jnp.zeros_like, sync["hist"])
            return {**new_state, "sync": {**sync, "hist": hist}}, metrics
        loss = metrics["loss"]
        return new_state, {**metrics, "loss": loss.at[0].mul(1.01)}
    return fn


def first_superstep(job: Job) -> dict:
    """Drive the first superstep; return what the comparison reads."""
    before = job.host_state()
    _, losses = job.superstep()
    after = job.host_state()
    return {"losses": losses, "params0": before["params"],
            "params": after["params"], "stale": after["stale"]}


def reference_readings(cell: dict, seed: int, data,
                       precision: str = "float32",
                       exchange: bool = True) -> dict:
    """The reference's first superstep on the family's inputs ``data``."""
    ref = load_reference(cell["cfg"]["reference"])
    return ref.follow(cell["cfg"], cell["traffic"], seed, *data,
                      cell["traffic"]["superstep"], precision=precision,
                      exchange=exchange)


def compiled_hlo(job: Job) -> str:
    """The HLO text of the step the job's supersteps run, which names every
    operation the device trace shows."""
    return job.super_fn.lower(job.state, job.last_batch).compile().as_text()


def count_compiles():
    """A list that grows by one for each backend compilation."""
    import jax

    seen: list[float] = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def run(cell: dict, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, fault: str | None = None) -> dict:
    """One run; returns the result line's object (and, under ``_log``,
    what goes to standard error)."""
    import jax

    import devtrace
    import peaks as peaks_mod
    import scopes as scopes_mod

    parts = {"import": time.perf_counter() - T_START}
    t = time.perf_counter()
    compiles = count_compiles()
    devs = (chips_or_fail(cell["chips"]) if require_tpu
            else jax.devices()[:cell["chips"]])
    parts["device_init"] = time.perf_counter() - t

    family = cell["family"]
    t = time.perf_counter()
    data = family.inputs(cell["cfg"], seed)
    parts["inputs"] = time.perf_counter() - t

    t = time.perf_counter()
    job = Job(cell, seed, data, fault=fault)
    jax.block_until_ready(job.state)
    parts["init"] = time.perf_counter() - t

    t = time.perf_counter()
    prog = first_superstep(job)
    parts["first_superstep"] = time.perf_counter() - t

    t = time.perf_counter()
    prev, warm = None, 0
    while warm < WARMUP_MAX:
        t0 = time.perf_counter()
        job.superstep()
        dt = time.perf_counter() - t0
        warm += 1
        if (warm >= WARMUP_MIN and prev is not None
                and abs(dt - prev) <= WARMUP_AGREE * prev):
            break
        prev = dt
    parts["warmup"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_START
    compiles_setup = len(compiles)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    annotate = jax.profiler.TraceAnnotation if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    feed_waits, step_s, failed = [], [], 0
    t_begin = t_prev = time.perf_counter()
    while True:
        wait, losses = job.superstep(annotate)
        now = time.perf_counter()
        feed_waits.append(wait)
        step_s.append((now - t_prev) / job.k)
        failed += int(np.sum(~np.isfinite(losses)))
        t_prev = now
        span = now - t_begin
        if trace:
            if span >= min(seconds, TRACE_SECONDS) and \
                    len(step_s) >= TRACE_SUPERSTEPS:
                break
        elif span >= seconds:
            break
    jax.block_until_ready(job.state)
    window_s = time.perf_counter() - t_begin
    if trace:
        jax.profiler.stop_trace()
    compiles_window = len(compiles) - compiles_setup
    steps = len(step_s) * job.k
    images_done = steps * job.batch

    mesh_devs = list(job.mesh.devices.flat)
    stats = [d.memory_stats() or {} for d in mesh_devs]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    hlo = compiled_hlo(job) if trace else ""
    job.close()
    del job

    reduced = None
    if trace:
        xplanes = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                   for f in fs if f.endswith(".xplane.pb")]
        if xplanes:
            weights = family.conv_weight_shapes(cell["cfg"])
            reduced = devtrace.Reduced(
                devtrace.load(xplanes[0]),
                classes=devtrace.hlo_classes(hlo, weights))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    ref = reference_readings(cell, seed, data)
    import check
    correct, checks = check.judge(check.readings(prog, ref), cell["limits"])
    reference_s = time.perf_counter() - t

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    images_per_s = images_done / window_s
    log = {"setup_parts_s": parts, "window_s": window_s,
           "supersteps": len(step_s), "images_per_s": images_per_s,
           "compiles_setup": compiles_setup,
           "compiles_in_window": compiles_window,
           "warmup_supersteps": warm, "reference_s": reference_s}
    metrics = {}
    out = {"correct": bool(correct) and failed == 0, "attempted": steps,
           "failed": failed, "metrics": metrics, "device": device}
    if not trace:
        e2e = {
            "images_per_s": images_per_s,
            "step_ms_p95": statistics.quantiles(
                [s * 1e3 for s in step_s], n=20)[-1]
            if len(step_s) >= 2 else step_s[0] * 1e3,
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        pk = (peaks_mod.peaks(dev.device_kind) if require_tpu
              else {"flops": 1.0, "hbm_bytes_per_s": 1.0})
        ctx = _Ctx(reduced=reduced, steps=steps, images=images_done,
                   window_s=window_s, feed_wait_s=feed_waits,
                   cfg=cell["cfg"], traffic=cell["traffic"],
                   chips=len(mesh_devs), peaks=pk, family=family,
                   scopes=scopes_mod.of_hlo(hlo))
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None and reduced.chips:
            device["busy_s"] = reduced.busy_ns() * 1e-9
            device["window_s"] = reduced.window_ns * 1e-9
            out["breakdown"] = {"device_ops": reduced.top_ops(),
                                "idle_gaps": reduced.idle_gaps()}
            log["device_scopes"] = scopes_mod.breakdown(ctx)
        out["traced_images_per_s"] = images_per_s
    out["checks"] = checks
    out["_log"] = log
    return out


class _Ctx:
    """What a per-layer metric reader may read: the reduced trace
    (``reduced``) and the compiled step's ``scopes`` (or None), the
    window's ``steps``, ``images``, ``window_s`` and ``feed_wait_s``, the
    cell's ``cfg``, ``traffic`` and ``family`` module, the ``chips`` and
    their ``peaks``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), for every program
    however quick to compile, so that a cell's second run compiles
    nothing."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as place

    place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        prepare_env()
        cell = load_cell(args.workload)
        use_compile_cache()
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except Failure as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    log = out.pop("_log")
    print("[bench] " + json.dumps(log), file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r} "
              f"at {c['at']}", file=sys.stderr, flush=True)
    print(f"[bench] correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
