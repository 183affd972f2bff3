"""The trace reduction on small recorded traces."""
import json
import os

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _rec():
    # two chips over a 100 ns window; host phases cover it
    return {
        "host": [["feed_wait", 0, 10], ["dispatch", 10, 20],
                 ["loss_readback", 20, 100]],
        "devices": {
            "0": [["convolution.1", "convolution", 10, 40],
                  ["fusion.2", "non-fusion elementwise", 30, 50],
                  ["all-gather.3", "collective", 45, 70],
                  ["fusion.4", "loop fusion", 80, 90]],
            "1": [["convolution.1", "convolution", 20, 60],
                  ["all-reduce.5", "collective", 60, 80]],
        }}


def test_union_merges_overlaps_and_drops_empty():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (4, 4)]) == [(0, 3),
                                                                (5, 7)]


def test_subtract():
    assert devtrace.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2),
                                                               (3, 5)]


@pytest.mark.parametrize("name,cat,cls", [
    ("convolution.12", "", "conv"),
    ("fusion.3", "convolution fusion", "conv"),
    ("conv2d_bwd_fused", "", "conv"),
    ("all-gather-start.1", "", "collective"),
    ("fusion.9", "collective", "collective"),
    ("fusion.7", "loop fusion", "other"),
    ("reduce-window.2", "", "other"),
])
def test_classify(name, cat, cls):
    assert devtrace.classify(name, cat) == cls


def test_idle_is_one_minus_the_union_over_the_window():
    r = devtrace.Reduced(_rec())
    assert r.window_ns == 100
    # chip 0 busy [10,70] + [80,90] = 70; chip 1 busy [20,80] = 60
    assert r.busy_ns() == pytest.approx(65)


def test_class_times_and_exposed_collective():
    r = devtrace.Reduced(_rec())
    assert r.class_ns("conv") == pytest.approx((30 + 40) / 2)
    assert r.class_ns("collective") == pytest.approx((25 + 20) / 2)
    # chip 0: busy 70 less conv/collective cover [10,40]+[45,70] = 15;
    # chip 1: all of its busy time is conv or collective
    assert r.class_ns("other") == pytest.approx((15 + 0) / 2)
    # chip 0: all-gather [45,70] minus fusion.2 [30,50] -> 20;
    # chip 1: all-reduce [60,80] runs alone -> 20
    assert r.exposed_collective_ns() == pytest.approx(20)


def test_idle_gaps_are_named_by_the_host_phase():
    r = devtrace.Reduced(_rec())
    gaps = dict((p, s * 1e9) for p, s in r.idle_gaps())
    # chip 0 idle: [0,10] feed_wait, [70,80] and [90,100] loss_readback
    assert gaps == pytest.approx({"feed_wait": 10, "loss_readback": 20})


def test_top_ops_are_averaged_over_chips():
    top = devtrace.Reduced(_rec()).top_ops(2)
    assert top[0][0] == "convolution.1"
    assert top[0][1] == pytest.approx(35e-9)


def test_loops_are_not_leaves():
    rec = {"host": [["dispatch", 0, 100]],
           "devices": {"0": [["while.1", "", 0, 100],
                             ["convolution.2", "", 10, 50],
                             ["fusion.3", "", 50, 60],
                             ["while.4", "", 60, 90],
                             ["fusion.5", "", 70, 80]]}}
    r = devtrace.Reduced(rec)
    assert r.class_ns("conv") == pytest.approx(40)
    # fusion.3, fusion.5 and the loops' own time around them
    assert r.class_ns("other") == pytest.approx(60)
    assert [n for n, _ in r.top_ops()] == ["convolution.2", "fusion.3",
                                           "fusion.5"]


def test_window_clips_operations():
    r = devtrace.Reduced(_rec(), lo=15, hi=35)
    assert r.window_ns == 20
    assert r.class_ns("conv") == pytest.approx((20 + 15) / 2)


HLO = """\
%fused_computation.1 (param_0: f32[32,26,26,20]) -> f32[5,5,20,60] {
  %param_0 = f32[32,26,26,20]{0,3,2,1} parameter(0)
  ROOT %conv_general_dilated.4 = f32[5,5,20,60]{3,2,1,0} convolution(%param_0, %param_0), window={size=22x22}, dim_labels=f01b_i01o->01bf
}

%fused_computation.2 (param_0: f32[32,150]) -> f32[32,10] {
  %param_0 = f32[32,150]{1,0} parameter(0)
  ROOT %convolution.7 = f32[32,10]{0,1} convolution(%param_0, %param_0), dim_labels=bf_io->bf
}

%fused_computation.3 (param_0: f32[2,150]) -> f32[8,150] {
  %param_0 = f32[2,150]{1,0} parameter(0)
  ROOT %all-gather.1 = f32[8,150]{1,0} all-gather(%param_0), dimensions={0}
}

ENTRY %main.9 (Arg_0.1: f32[32,26,26,20]) -> f32[5,5,20,60] {
  %Arg_0.1 = f32[32,26,26,20]{0,3,2,1} parameter(0)
  %fusion.131 = (f32[20]{0:T(128)S(1)}, bf16[5,5,20,60]{0:T(8,128)(2,1)}) fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(superstep)/while"}
  %convolution_add_fusion.3 = f32[32,10]{0,1} fusion(%Arg_0.1), kind=kOutput, calls=%fused_computation.2
  %fusion.5 = f32[8,150]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  %all-reduce-start.2 = f32[10]{0} all-reduce-start(%Arg_0.1), to_apply=%add
  %jvp__.58 = f32[32,26,32,20]{3,2,1,0} custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,29,35,1]{3,2,1,0}, f32[4,4,1,20]{3,2,1,0}}
  %jvp__.60 = f32[32,11,11,60]{3,2,1,0} custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,22,22,60]{3,2,1,0}}
  ROOT %select_and_scatter.13 = f32[32,22,22,60]{3,0,2,1} select-and-scatter(%Arg_0.1), window={size=1x2x2x1 stride=1x2x2x1}
}
"""


def test_hlo_classes():
    got = devtrace.hlo_classes(HLO, [(4, 4, 1, 20), (5, 5, 20, 60)])
    assert got["fusion.131"] == "conv"           # holds a windowed conv
    assert got["convolution_add_fusion.3"] == "other"   # an FC product
    assert got["fusion.5"] == "collective"
    assert got["all-reduce-start.2"] == "collective"
    assert got["jvp__.58"] == "conv"             # a Pallas conv kernel
    assert got["jvp__.60"] == "other"            # a Pallas pool kernel
    assert got["select_and_scatter.13"] == "other"


def test_short_name():
    assert devtrace.short_name("%fusion.12 = f32[2] fusion(%a)") == \
        "fusion.12"


def test_recorded_chip_traces():
    """Slices of traces recorded on a v5e (two or three host phases of a
    steady window, with the classes the compiled step's HLO gave their
    operations): the reduction's invariants hold on the names and nesting
    the chip really writes."""
    files = sorted(f for f in os.listdir(DATA) if f.endswith(".json"))
    assert len(files) >= 2
    for f in files:
        with open(os.path.join(DATA, f)) as fh:
            rec = json.load(fh)
        r = devtrace.Reduced(rec, classes=rec["classes"])
        assert 0 < r.busy_ns() <= r.window_ns
        parts = sum(r.class_ns(c) for c in ("conv", "collective", "other"))
        assert parts >= r.busy_ns() * (1 - 1e-9)
        # convolutions are most of a large-net step on every path
        assert r.class_ns("conv") > 0.5 * r.busy_ns()
        assert r.exposed_collective_ns() <= r.class_ns("collective")
        if "4chip" in rec["cell"]:
            assert len(r.chips) == 4 and r.class_ns("collective") > 0
        else:
            assert r.class_ns("collective") == 0
