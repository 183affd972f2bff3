"""``correct`` on the CPU at a small size: a sound run passes, and each
fault the training cells can have, planted under the harness, and the
control (the reference in a lower precision, in the program's place)
fail the cells' own limits."""
import numpy as np
import pytest

import check
import run

SEED = 2**31 + 77


def _small(cell_name, workers=1):
    run.prepare_env()
    cell = run.load_cell(cell_name)
    cell["cfg"] = dict(cell["cfg"], train_images=256)
    cell["traffic"] = dict(cell["traffic"], batch=32, logical_shards=4,
                           superstep=2, workers=workers)
    return cell


@pytest.fixture(scope="module")
def cell():
    return _small("medium-chaos-xla-1chip")


@pytest.fixture(scope="module")
def data(cell):
    return cell["family"].inputs(cell["cfg"], SEED)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(cell, trace):
    out = run.run(cell, SEED, 0.2, trace, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-2:] == ["checks", "_log"]
    names = set(out["metrics"])
    if trace:
        # no device planes on the CPU: only the host's metrics read
        assert names == {"feed_wait_ms", "mfu"}
    else:
        assert names == {"images_per_s", "step_ms_p95", "setup_s"}
    assert out["_log"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "altered_loss"])
def test_planted_fault_is_not_correct(cell, fault):
    out = run.run(cell, SEED, 0.2, False, require_tpu=False, fault=fault)
    assert not out["correct"], (fault, out["checks"])


def test_unchanged_state_reads_one(cell):
    out = run.run(cell, SEED, 0.2, False, require_tpu=False,
                  fault="unchanged")
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_control_is_not_correct(cell, data):
    ref = run.reference_readings(cell, SEED, data)
    control = run.reference_readings(cell, SEED, data, precision="bfloat16")
    ok, checks = check.judge(check.readings(control, ref), cell["limits"])
    assert not ok, checks


def test_exchange_left_out_reads_one(data):
    """Four workers with the exchange between them left out: the stale
    term that only the exchange fills reads 1."""
    cell = _small("large-chaos-xla-1chip", workers=4)
    ref = run.reference_readings(cell, SEED, data)
    alone = run.reference_readings(cell, SEED, data, exchange=False)
    values = check.readings(alone, ref)
    assert values["stale_gap"][0] == pytest.approx(1.0)
    assert values["change_gap"][0] > cell["limits"]["change_gap"]


def test_reference_follows_the_program_queue_and_init(cell, data):
    """The reference's own data order and weights are the program's."""
    import jax

    import repro.configs as C
    from repro.data.pipeline import ImagePipeline
    from repro.models import cnn
    from repro.models import layers as L

    images, labels = data
    ref = run.load_reference("table2_cnn")
    pipe = ImagePipeline(images, labels, batch=32, seed=SEED,
                         sample_mode="queue")
    for step in (0, 7, 8, 9):
        rows = ref.queue_rows(len(images), 32, SEED, step)
        np.testing.assert_array_equal(pipe.batch_at(step)["images"],
                                      images[rows])
    arch = C.get("chaos-medium")
    want = cnn.build_params(arch, L.InitFactory(jax.random.key(SEED),
                                                jax.numpy.float32))
    got = ref.init_params(cell["cfg"], SEED)
    jax.tree.map(np.testing.assert_array_equal, got, want)
