"""The command exits non-zero and prints no result where there is no
chip, and where the checkout holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "alex-mix-readheavy-batch", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_no_chip_no_result():
    proc = _run(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / BENCH.relative_to(ROOT),
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    _no_result(_run(tmp_path))
