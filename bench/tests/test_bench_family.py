"""A configuration of another model family joins the benchmark as new files
and one new entry per list of ``BENCHMARK.json``: in a copy of the
benchmark, a family module that reuses the Table-2 code under another
name, with its configuration, traffic and limits, runs at a small size on
the CPU through that module, and no file the copy had before is changed
but ``BENCHMARK.json``, which only gains entries."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

SEED = 2**31 + 91

#: the new family: the Table-2 code under another name, recording which of
#: its functions the harness called
FAMILY = '''"""The Table-2 family under another name (the harness's own test)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "twin_base", os.path.join(os.path.dirname(__file__), "table2_cnn.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

CALLS = set()


def _through(name):
    def call(*args, **kw):
        CALLS.add(name)
        return getattr(_base, name)(*args, **kw)
    return call


for _name in ("arch", "inputs", "pipeline", "total_steps",
              "train_flops_per_image", "conv_work", "conv_weight_shapes"):
    globals()[_name] = _through(_name)
'''

DRIVE = '''import json, sys
sys.path.insert(0, sys.argv[1])
import run
run.prepare_env()
cell = run.load_cell("twin-medium-xla-1chip")
cell["cfg"] = dict(cell["cfg"], train_images=256)
cell["traffic"] = dict(cell["traffic"], batch=32, logical_shards=4,
                       superstep=2)
out = run.run(cell, int(sys.argv[2]), 0.2, True, require_tpu=False)
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "metrics": sorted(out["metrics"]),
                  "calls": sorted(cell["family"].CALLS)}))
'''


def _files(top):
    return sorted(os.path.relpath(os.path.join(d, f), top)
                  for d, dirs, fs in os.walk(top) if "__pycache__" not in d
                  for f in fs)


def _add(copy):
    """The new configuration's files and entries, as a later PR adds
    them."""
    bench = copy / "bench"
    (bench / "families" / "twin_cnn.py").write_text(FAMILY)
    cfg = run.load_json(os.path.join(run.BENCH, "configs",
                                     "chaos-medium.json"))
    cfg.update(name="twin-medium", family="twin_cnn")
    (bench / "configs" / "twin-medium.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(run.BENCH, "traffic", "chaos-xla-1chip.json"),
                bench / "traffic" / "twin-xla-1chip.json")
    shutil.copy(os.path.join(run.BENCH, "checks",
                             "medium-chaos-xla-1chip.json"),
                bench / "checks" / "twin-medium-xla-1chip.json")
    spec = run.load_json(copy / "BENCHMARK.json")
    spec["configs"].append({
        "name": "twin-medium", "source": "the Table-2 medium net",
        "file": "bench/configs/twin-medium.json",
        "reduced": ["train_images"], "why": "a second family"})
    spec["workloads"].append({
        "name": "twin-medium-xla-1chip", "config": "twin-medium",
        "traffic": "twin-xla-1chip", "chips": 1, "why": "a second family"})
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    copy = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), copy)
    shutil.copytree(run.BENCH, copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(run.ROOT, "src"), copy / "src")
    _add(copy)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(copy / "bench"), str(SEED)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return copy, json.loads(p.stdout.strip().splitlines()[-1])


def test_a_new_family_runs_through_its_module(added):
    _, out = added
    assert out["correct"], out["checks"]
    assert {"arch", "inputs", "pipeline", "total_steps",
            "train_flops_per_image"} <= set(out["calls"])
    assert "mfu" in out["metrics"]


def test_a_new_family_is_new_files_and_entries_only(added):
    copy, _ = added
    before = _files(run.BENCH)
    after = _files(copy / "bench")
    assert set(before) <= set(after)
    assert sorted(set(after) - set(before)) == [
        "checks/twin-medium-xla-1chip.json", "configs/twin-medium.json",
        "families/twin_cnn.py", "traffic/twin-xla-1chip.json"]
    _, changed, errors = filecmp.cmpfiles(run.BENCH, copy / "bench", before,
                                          shallow=False)
    assert changed == [] and errors == []
    old = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    new = run.load_json(copy / "BENCHMARK.json")
    for key, value in old.items():
        if key in ("configs", "workloads"):
            assert new[key][:-1] == value
        else:
            assert new[key] == value
