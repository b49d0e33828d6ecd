"""Off the chip a run fails and prints no result."""
import os
import subprocess
import sys

from bench.tests.tiny import ROOT


def run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_run_exits_nonzero_with_no_result():
    p = run(["--workload", "qwen3-8b.chat2k-c32", "--seed", "5",
             "--seconds", "5", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no result" in p.stderr


def test_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run(["--workload", "qwen3-4b.decode2k-c32", "--seed", "5",
             "--seconds", "5", "--trace", "1"], tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
