"""``bench/run.py`` refuses to run without the chip or without the program."""
import os
import shutil
import subprocess
import sys

from bench import spec

ARGS = ["--workload", "tinyimagenet-poisson", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directory has no program to measure."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
