"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

import run


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "large-chaos-xla-1chip", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()
