"""A run that finds no TPU exits non-zero and prints no result line; so does
one in a directory that holds only ``BENCHMARK.json`` and ``benchmark/``.

    python3 -m pytest benchmark/tests/test_no_tpu.py -q
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "raft-train-things", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.strip().splitlines()[-1:]:
        try:
            assert "metrics" not in json.loads(line)
        except ValueError:
            pass


def test_cpu_only_machine_is_refused(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    _no_result(_run(ROOT, env))


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    _no_result(_run(tmp_path, env))
