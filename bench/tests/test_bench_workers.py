"""The four-worker cell on four CPU devices at a small size: a sound run is
``correct`` with its stale exchange terms compared, and each fault the
cell can have, planted under the timed path, is not."""
import json
import os
import subprocess
import sys

import pytest

import run

SEED = 2**31 + 313
FAULTS = ("unchanged", "half_batch", "altered_loss", "no_exchange")

DRIVE = '''import json, sys
sys.path.insert(0, sys.argv[1])
import run
run.prepare_env()
cell = run.load_cell("large-chaos-xla-4chip")
cell["cfg"] = dict(cell["cfg"], train_images=256)
cell["traffic"] = dict(cell["traffic"], batch=32, superstep=2)
res = {}
for fault in [None] + sys.argv[3].split(","):
    out = run.run(cell, int(sys.argv[2]), 0.2, False, require_tpu=False,
                  fault=fault)
    res[fault or "sound"] = {"correct": out["correct"],
                             "checks": out["checks"],
                             "count": out["device"]["count"],
                             "compiles_in_window":
                                 out["_log"]["compiles_in_window"]}
print(json.dumps(res))
'''


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, run.BENCH, str(SEED), ",".join(FAULTS)],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_four_worker_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["count"] == 4 and out["compiles_in_window"] == 0
    assert set(out["checks"]) == {"loss_gap", "change_gap", "stale_gap"}


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def test_exchange_left_out_of_the_state_reads_one(runs):
    assert runs["no_exchange"]["checks"]["stale_gap"]["value"] == \
        pytest.approx(1.0)
