"""The command refuses to run without a TPU, or without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "train.h128.i30", "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root, env=env,
                          capture_output=True, text=True, timeout=120)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            return "correct" in json.loads(line)
        except ValueError:
            continue
    return False


def test_exits_nonzero_without_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not _has_result(p.stdout)


def test_exits_nonzero_with_only_benchmark_files(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
