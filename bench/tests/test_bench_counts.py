"""The Table-2 family's operation and byte counts against hand table2."""
import run


def _cfg(name):
    return run.load_json(f"{run.BENCH}/configs/{name}.json")


table2 = run.load_family(_cfg("chaos-large"))


def test_forward_macs_match_hand_counts():
    # conv0 26*26*4*4*1*20 + conv2 22*22*5*5*20*60 + conv4 6*6*6*6*60*100
    # + fc6 900*150 + fc7 150*10
    assert table2.forward_macs(_cfg("chaos-large")) == (
        216_320 + 14_520_000 + 7_776_000 + 135_000 + 1_500) == 22_648_820
    # conv0 216,320 + conv2 9*9*5*5*20*40 + fc4 360*150 + fc5 150*10
    assert table2.forward_macs(_cfg("chaos-medium")) == (
        216_320 + 1_620_000 + 54_000 + 1_500) == 1_891_820


def test_param_counts_match_table_2():
    for name in ("chaos-large", "chaos-medium"):
        cfg = _cfg(name)
        assert table2.param_count(cfg) == cfg["params"]


def test_train_flops_count_forward_and_both_gradients():
    # 2 * (forward + weight gradients + input gradients but the first's)
    assert table2.train_flops_per_image(_cfg("chaos-large")) == \
        2 * (2 * 22_648_820 + 22_648_820 - 216_320) == 135_460_280
    assert table2.train_flops_per_image(_cfg("chaos-medium")) == \
        2 * (2 * 1_891_820 + 1_891_820 - 216_320) == 10_918_280


def test_conv_work_by_hand_for_one_image():
    cfg = {"input_hw": 6, "classes": 2, "layers": [["conv", 3, 3],
                                                   ["conv", 2, 2]]}
    # conv0: 6x6x1 -> 4x4x3, 3x3 kernel: 16*9*3 = 432 MACs; conv1:
    # 4x4x3 -> 3x3x2, 2x2 kernel: 9*4*3*2 = 216 MACs; conv0 needs no input
    # gradient
    flops, nbytes = table2.conv_work(cfg, 1)
    assert flops == 2 * (2 * 432) + 2 * (3 * 216)
    x0, w0, y0 = 36 * 4, 27 * 4, 48 * 4
    x1, w1, y1 = 48 * 4, 24 * 4, 18 * 4
    assert nbytes == 2 * (x0 + w0 + y0) + 3 * (x1 + w1 + y1)


def test_conv_weight_shapes():
    assert table2.conv_weight_shapes(_cfg("chaos-large")) == [
        (4, 4, 1, 20), (5, 5, 20, 60), (6, 6, 60, 100)]
    assert table2.conv_weight_shapes(_cfg("chaos-medium")) == [
        (4, 4, 1, 20), (5, 5, 20, 40)]
