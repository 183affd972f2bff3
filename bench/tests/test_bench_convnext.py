"""The ConvNeXt configuration in the benchmark: the family module's counts
by hand and at published sizes, a program without the model stopping with
a ``Failure``, the readers of the depthwise and pointwise sublayers on a
synthetic record and on the scopes of the step a run compiles, and
``correct`` on the CPU at a small size: a sound run passes, each planted
fault and the bfloat16 control fail the cell's own limits."""
import pytest

import check
import devtrace
import run
import scopes

CELL = "convnext-t-chaos-xla-1chip"
SEED = 2**31 + 93


def _small():
    run.prepare_env()
    cell = run.load_cell(CELL)
    cell["cfg"] = dict(cell["cfg"], train_images=64, input_hw=32,
                       classes=10, dims=[8, 16, 24, 32], depths=[1, 1, 1, 1])
    cell["traffic"] = dict(cell["traffic"], batch=16, logical_shards=4,
                           superstep=2)
    return cell


@pytest.fixture(scope="module")
def cell():
    return _small()


@pytest.fixture(scope="module")
def family():
    return run.load_cell(CELL)["family"]


TINY = {"input_hw": 8, "classes": 3, "dims": [2, 4], "depths": [1, 1]}


def test_layers_and_counts_by_hand(family):
    kinds = [(l["kind"], l["h_out"], l["c_in"], l["c_out"])
             for l in family.layer_shapes(TINY)]
    assert kinds == [("stem", 2, 3, 2), ("dwconv", 2, 2, 2),
                     ("pointwise", 2, 2, 8), ("pointwise", 2, 8, 2),
                     ("down", 1, 2, 4), ("dwconv", 1, 4, 4),
                     ("pointwise", 1, 4, 16), ("pointwise", 1, 16, 4),
                     ("head", 1, 4, 3)]
    # stem 4*(16*3*2); dw 4*49*2; pw 4*2*8 twice; down 1*(4*2*4);
    # dw 49*4; pw 4*16 twice; head 4*3
    macs = [384, 392, 64, 64, 32, 196, 64, 64, 12]
    assert [family.layer_macs(l) for l in family.layer_shapes(TINY)] == macs
    assert family.forward_macs(TINY) == sum(macs)
    assert family.train_flops_per_image(TINY) == 2 * (3 * sum(macs) - 384)
    # weights and biases, the LayerNorms of the stem, both blocks, the
    # downsample and the head, and the two blocks' layer scales
    assert family.param_count(TINY) == (
        96 + 2 + 4 + (98 + 2) + 4 + (16 + 8) + (16 + 2) + 2
        + (32 + 4) + 4 + (196 + 4) + 8 + (64 + 16) + (64 + 4) + 4
        + (12 + 3) + 8)


def test_work_by_hand_for_one_image(family):
    """The depthwise convolution of the first block: x, w -> y; x, dy ->
    dw; dy, w -> dx, float32."""
    flops, nbytes = family.dwconv_work(dict(TINY, depths=[1, 0],
                                            dims=[2, 4]), 1)
    x = y = 2 * 2 * 2 * 4
    w = 49 * 2 * 4
    assert flops == 3 * 2 * 392
    assert nbytes == 3 * (x + y + w)
    mf, _ = family.mlp_work(TINY, 1)
    assert mf == 3 * 2 * (64 + 64 + 64 + 64)
    cf, cb = family.conv_work(TINY, 2)
    stem = 2 * 2 * 384 * 2
    assert cf == stem + 3 * 2 * 2 * (392 + 64 + 64 + 32 + 196 + 64 + 64)
    assert cb > 0


def test_published_sizes(family):
    cfg = run.load_cell(CELL)["cfg"]
    assert family.param_count(cfg) == cfg["params"] == 28_589_128
    assert family.forward_macs(cfg) == cfg["forward_macs_per_image"]
    assert family.train_flops_per_image(cfg) == 26_704_286_208
    assert family.conv_weight_shapes(cfg) == [
        (4, 4, 3, 96), (7, 7, 1, 96), (2, 2, 96, 192), (7, 7, 1, 192),
        (2, 2, 192, 384), (7, 7, 1, 384), (2, 2, 384, 768), (7, 7, 1, 768)]
    arch = family.arch(cfg, run.load_cell(CELL)["traffic"])
    assert arch.param_count() == 28_589_128


def test_inputs_are_a_function_of_the_seed(family, cell):
    a = family.inputs(cell["cfg"], SEED)
    b = family.inputs(cell["cfg"], SEED)
    c = family.inputs(cell["cfg"], 6)
    assert a[0].dtype.name == "uint8" and a[0].shape == (64, 32, 32, 3)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()


def test_a_program_without_the_model_is_a_failure(cell, monkeypatch):
    import repro.configs as C

    def missing(name):
        raise ModuleNotFoundError(f"No module named 'repro.configs.{name}'")
    monkeypatch.setattr(C, "get", missing)
    data = cell["family"].inputs(cell["cfg"], SEED)
    with pytest.raises(run.Failure, match="no 'convnext-tiny'"):
        run.Job(cell, SEED, data)


# two chips over a 100 ns window
REC = {"host": [["dispatch", 0, 100]],
       "devices": {"0": [["fusion.1", "", 0, 20],       # s0b0/dwconv fwd
                         ["fusion.2", "", 20, 30],      # s1b0/dwconv bwd
                         ["fusion.3", "", 25, 45],      # s0b0/mlp fwd
                         ["fusion.4", "", 45, 50]],     # s0b0/norm fwd
                   "1": [["fusion.1", "", 0, 10],
                         ["fusion.3", "", 10, 40]]}}
SCOPES = {"fusion.1": ("s0b0/dwconv", "fwd"),
          "fusion.2": ("s1b0/dwconv", "bwd"),
          "fusion.3": ("s0b0/mlp", "fwd"), "fusion.4": ("s0b0/norm", "fwd")}
PEAKS = {"flops": 1e9, "hbm_bytes_per_s": 1e9}


def _ctx(family, **kw):
    fields = dict(reduced=devtrace.Reduced(REC), steps=2, images=0,
                  window_s=0.0, feed_wait_s=[], cfg=TINY,
                  traffic={"batch": 4}, chips=2, peaks=PEAKS,
                  family=family, scopes=SCOPES)
    fields.update(kw)
    return run._Ctx(**fields)


def test_sublayer_readers_on_a_synthetic_record(family):
    ctx = _ctx(family)
    # chip 0: [0,20] U [20,30] = 30; chip 1: [0,10] = 10
    dw_ms = (30 + 10) / 2 * 1e-6 / 2
    assert run.load_reader("dwconv_device_ms")(ctx) == pytest.approx(dw_ms)
    flops, nbytes = family.dwconv_work(TINY, 2)
    least = max(flops / 1e9, nbytes / 1e9)
    assert run.load_reader("dwconv_roofline")(ctx) == pytest.approx(
        least / (dw_ms * 1e-3) * 100)
    # chip 0: [25,45] = 20; chip 1: [10,40] = 30
    mlp_ms = (20 + 30) / 2 * 1e-6 / 2
    flops, nbytes = family.mlp_work(TINY, 2)
    least = max(flops / 1e9, nbytes / 1e9)
    assert run.load_reader("mlp_roofline")(ctx) == pytest.approx(
        least / (mlp_ms * 1e-3) * 100)


def test_sublayer_readers_read_nothing_without_sublayers(family):
    """A Table-2 step names no block sublayers; a family without the
    counts gives no roofline."""
    ctx = _ctx(family, scopes={"fusion.1": ("conv2", "fwd"),
                               "fusion.2": ("conv2", "bwd"),
                               "fusion.3": ("fc4", "fwd"),
                               "fusion.4": ("loss", "fwd")})
    for metric in ("dwconv_device_ms", "dwconv_roofline", "mlp_roofline"):
        assert run.load_reader(metric)(ctx) is None
    assert run.load_reader("mlp_roofline")(_ctx(object())) is None


def test_sublayer_scopes_of_the_cells_step(cell):
    """The step a run's supersteps drive, compiled on the CPU at a small
    size, names every block's depthwise convolution and pointwise pair
    both ways."""
    job = run.Job(cell, 0, cell["family"].inputs(cell["cfg"], 0))
    try:
        job.superstep()
        got = scopes.of_hlo(run.compiled_hlo(job))
    finally:
        job.close()
    seen = set(got.values())
    for i in range(4):
        for sub in ("dwconv", "norm", "mlp"):
            for direction in ("fwd", "bwd"):
                assert (f"s{i}b0/{sub}", direction) in seen
    for layer in ("stem", "down1", "down2", "down3", "head", "loss"):
        assert (layer, "fwd") in seen, layer
    assert ("update", "fwd") in seen


def test_sound_run_is_correct(cell):
    out = run.run(cell, SEED, 0.2, False, require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert out["_log"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "altered_loss"])
def test_planted_fault_is_not_correct(cell, fault):
    out = run.run(cell, SEED, 0.2, False, require_tpu=False, fault=fault)
    assert not out["correct"], (fault, out["checks"])


def test_control_is_not_correct(cell):
    data = cell["family"].inputs(cell["cfg"], SEED)
    ref = run.reference_readings(cell, SEED, data)
    control = run.reference_readings(cell, SEED, data, precision="bfloat16")
    ok, checks = check.judge(check.readings(control, ref), cell["limits"])
    assert not ok, checks


def test_one_pass_reference_rounds_every_product(cell):
    """``one_pass`` (bfloat16-rounded operands, float32 sums, both
    directions) moves the first superstep off the float32 reference by
    rounding, less than the all-bfloat16 control does, and its first
    loss by the forward products alone."""
    data = cell["family"].inputs(cell["cfg"], SEED)
    ref = run.reference_readings(cell, SEED, data)
    one = check.readings(run.reference_readings(
        cell, SEED, data, precision="one_pass"), ref)
    bf16 = check.readings(run.reference_readings(
        cell, SEED, data, precision="bfloat16"), ref)
    # rounding to 8 mantissa bits: a relative error of about 2**-9 per
    # operand, averaged down over the sums
    assert 0 < one["loss_gap"][0] < 5e-3
    assert 0 < one["change_gap"][0] < bf16["change_gap"][0]


def test_gradient_faults_are_planted_and_taken_out(cell):
    """``bench/convnext_readings.py`` plants each gradient fault in the
    program and leaves the program as it was afterwards: a zeroed
    depthwise gradient shows in ``change_gap``, a negated one only in the
    direction of the change (``diff_gap``), and the next sound reading is
    the reference's again."""
    import convnext_readings as cr

    data = cell["family"].inputs(cell["cfg"], SEED)
    ref = run.reference_readings(cell, SEED, data)
    blocks = [f"s{i}b{j}" for i, depth in enumerate(cell["cfg"]["depths"])
              for j in range(depth)]
    leaf = blocks[min(cr.GRAD_FAULT_BLOCK, len(blocks) - 1)] + "/dw_w"
    zero = cr.got_for(cell, SEED, data, "grad:zero_dw")
    neg = cr.got_for(cell, SEED, data, "grad:neg_dw")
    sound = cr.got_for(cell, SEED, data, "program")
    gap, at = check.readings(zero, ref)["change_gap"]
    assert gap > 0.5 and at.endswith(leaf), (gap, at)
    # negated: the size of every change as the reference's; its direction
    # is reversed in that leaf (||-d - d|| = 2 ||d||)
    assert check.readings(neg, ref)["change_gap"][0] < 0.05
    gap, at = cr.diff_gap(neg, ref)
    assert gap > 1.5 and at == leaf, (gap, at)
    assert cr.diff_gap(sound, ref)[0] < 1e-3
    with pytest.raises(ValueError, match="unknown gradient fault"):
        cr.got_for(cell, SEED, data, "grad:nothing")
