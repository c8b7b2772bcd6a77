"""The command exits non-zero and prints no result where JAX finds no
TPU, and in a directory holding only BENCHMARK.json and the benchmark."""
import os
import shutil
import subprocess
import sys

from harness import cells

ARGS = ["--workload", "qwen2-72b.chat", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run(cells.CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_bare_benchmark_directory(tmp_path):
    shutil.copytree(cells.BENCH, tmp_path / "bench")
    shutil.copy(cells.CHECKOUT / "BENCHMARK.json", tmp_path)
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
