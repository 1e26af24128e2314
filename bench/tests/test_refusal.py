"""With no TPU the benchmark exits non-zero and prints no result; in a
directory that holds only the benchmark it does the same."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(root, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "r18x5.stage2",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
