"""``bench/run.py`` refuses to run, with no result line, where it cannot
measure: no TPU, or no program beside the benchmark."""
import os
import shutil
import subprocess
import sys

import _paths

ARGS = ["--workload", "mine.T10I4D100K", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(_paths.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(_paths.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no program" in p.stderr
