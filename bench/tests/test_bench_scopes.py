"""The readers of device time by the program's named scopes
(``bench/scopes.py``): hand-computed values on a synthetic record, nothing
from a trace the compiled step does not name or from a program without
scopes, and the scopes of the step a run compiles, on the CPU."""
import pytest

import devtrace
import run
import scopes

READERS = ("fprop_device_ms", "bprop_device_ms", "update_device_ms",
           "exchange_device_ms")


def _rec():
    # two chips over a 100 ns window
    return {
        "host": [["dispatch", 0, 100]],
        "devices": {
            "0": [["fusion.1", "", 0, 20],        # conv2 fwd
                  ["conv2d_bwd_tanh.2", "", 20, 50],   # conv2 bwd
                  ["fusion.3", "", 40, 60],       # loss bwd, overlaps .2
                  ["fusion.4", "", 60, 70],       # update
                  ["all-gather.5", "", 70, 80],   # exchange/conv2
                  ["copy.6", "", 80, 82]],        # no scope
            "1": [["fusion.1", "", 0, 10],
                  ["fusion.7", "", 10, 30],       # pool1 fwd
                  ["fusion.4", "", 30, 36]],
        }}


HLO_TEXT = """\
ENTRY %main.2 (Arg_0.1: f32[2]) -> f32[2] {
  ROOT %fusion.1 = f32[2]{0} fusion(%Arg_0.1), kind=kLoop, calls=%f, metadata={op_name="jit(superstep)/conv2/tanh"}
}
"""

SCOPES = {"fusion.1": ("conv2", "fwd"), "conv2d_bwd_tanh.2": ("conv2", "bwd"),
          "fusion.3": ("loss", "bwd"), "fusion.4": ("update", "fwd"),
          "all-gather.5": ("exchange/conv2", "fwd"), "copy.6": None,
          "fusion.7": ("pool1", "fwd")}


def _ctx(rec=None, **kw):
    fields = dict(reduced=devtrace.Reduced(rec or _rec()), steps=2,
                  images=0, window_s=0.0, feed_wait_s=[], cfg={},
                  traffic={}, chips=2, peaks={}, family=None,
                  scopes=SCOPES)
    fields.update(kw)
    return run._Ctx(**fields)


@pytest.mark.parametrize("metric,ns", [
    # chip 0: fusion.1 [0,20]; chip 1: fusion.1 [0,10] + fusion.7 [10,30]
    ("fprop_device_ms", (20 + 30) / 2),
    # chip 0: [20,50] U [40,60] = 40; chip 1: none
    ("bprop_device_ms", (40 + 0) / 2),
    # chip 0: fusion.4 + all-gather.5 = 20; chip 1: fusion.4 = 6
    ("update_device_ms", (20 + 6) / 2),
    # chip 0: all-gather.5 [70,80]; chip 1: none
    ("exchange_device_ms", (10 + 0) / 2),
])
def test_reader_on_a_synthetic_record(metric, ns):
    got = run.load_reader(metric)(_ctx())
    assert got == pytest.approx(ns * 1e-6 / 2)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_from_nothing(metric):
    empty = run._Ctx(reduced=None, steps=0, images=0, window_s=0.0,
                     feed_wait_s=[], cfg={}, traffic={}, chips=1,
                     peaks={}, family=None, scopes=None)
    assert run.load_reader(metric)(empty) is None


def test_an_unnamed_trace_reads_nothing():
    """Operations the compiled step does not name (another program's
    trace) make every reader give None rather than a wrong sum."""
    ctx = _ctx(scopes={"copy.6": None})
    for metric in READERS:
        assert run.load_reader(metric)(ctx) is None


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    import repro.obs.trace as obs_trace
    monkeypatch.delattr(obs_trace, "hlo_scopes")
    ctx = _ctx(scopes=scopes.of_hlo(HLO_TEXT))
    assert ctx.scopes is None
    for metric in READERS:
        assert run.load_reader(metric)(ctx) is None


def test_a_step_that_exchanges_nothing_reads_nothing():
    """One worker: no operation falls under an ``exchange/`` scope."""
    ctx = _ctx(scopes={**SCOPES, "all-gather.5": None})
    assert run.load_reader("exchange_device_ms")(ctx) is None
    assert run.load_reader("update_device_ms")(ctx) == pytest.approx(
        (10 + 6) / 2 * 1e-6 / 2)


def test_breakdown():
    got = dict(scopes.breakdown(_ctx()))
    assert list(got)[-1] == "unscoped"
    assert got["conv2/bwd"] == pytest.approx(30 / 2 * 1e-9)
    assert got["update/fwd"] == pytest.approx((10 + 6) / 2 * 1e-9)
    assert got["unscoped"] == pytest.approx(2 / 2 * 1e-9)


def test_scopes_rebuilt_from_the_cells_step():
    """The scopes of the step a run's supersteps drive, from the HLO text
    the run compiles (here on the CPU at a small size): its operations
    carry every layer both ways, the loss and the update."""
    run.prepare_env()
    cell = run.load_cell("medium-chaos-xla-1chip")
    cell["cfg"] = dict(cell["cfg"], train_images=64)
    cell["traffic"] = dict(cell["traffic"], batch=16, logical_shards=2,
                           superstep=2)
    job = run.Job(cell, 0, cell["family"].inputs(cell["cfg"], 0))
    try:
        job.superstep()
        got = scopes.of_hlo(run.compiled_hlo(job))
    finally:
        job.close()
    ctx = _ctx(scopes=got)
    seen = set(got.values())
    for layer in ("conv0", "pool1", "conv2", "pool3", "fc4", "fc5"):
        assert {(layer, "fwd"), (layer, "bwd")} <= seen, layer
    assert ("loss", "fwd") in seen and ("update", "fwd") in seen
    # a record of the rebuilt step's own operations reads as named
    by_scope = {}
    for name, s in got.items():
        by_scope.setdefault(s, name)
    rec = {"host": [["dispatch", 0, 100]],
           "devices": {"0": [[by_scope[("conv2", "fwd")], "", 0, 10],
                             [by_scope[("conv2", "bwd")], "", 10, 40],
                             [by_scope[("update", "fwd")], "", 40, 45]]}}
    ctx.reduced, ctx.steps = devtrace.Reduced(rec), 1
    assert run.load_reader("fprop_device_ms")(ctx) == pytest.approx(10e-6)
    assert run.load_reader("bprop_device_ms")(ctx) == pytest.approx(30e-6)
    assert run.load_reader("update_device_ms")(ctx) == pytest.approx(5e-6)
