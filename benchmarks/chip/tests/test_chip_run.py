"""The benchmark's command refuses to run where it cannot measure: with no
TPU, and in a directory that holds only the benchmark's own files.  It
then exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
COMMAND = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "vga-fleet.cams16-codec", "--seed", str(2 ** 31 + 3),
           "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(COMMAND, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_no_tpu_means_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
