"""``rehearse.py`` on the CPU still ends with a result line; the metrics
that read the program's marks in the untraced window are there, the ones
that join them with a device trace are not (the CPU's capture has no
device plane).

    python3 -m pytest benchmark/tests/test_rehearse_timeline.py -q
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SETUP = {"boot_s", "backend_init_s", "prepare_s"}
UNTRACED = {"train": SETUP | {"loop_host_ms", "put_ms", "gc_pause_ms"},
            "serve": SETUP | {"batch_assemble_ms", "batch_fetch_ms",
                              "batch_respond_ms", "serve_gc_pause_ms",
                              "serve_host_stall_ms"}}
JOINED = {"step_gap_host_ms", "step_gap_launch_ms", "serve_gap_host_ms",
          "serve_gap_launch_ms"}


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_rehearsal_reports_the_untraced_metrics(kind, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/tests/rehearse.py", "--kind", kind,
         "--trace", "1", "--seed", "2147483659"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["device"]["platform"] == "cpu"
    names = {k.removeprefix("cpu_rehearsal.") for k in result["metrics"]}
    assert UNTRACED[kind] <= names, UNTRACED[kind] - names
    assert not JOINED & names
    other = "serve" if kind == "train" else "train"
    assert not (UNTRACED[other] - SETUP) & names
    for name in UNTRACED[kind]:
        value = result["metrics"][f"cpu_rehearsal.{name}"]["value"]
        assert value == value and value >= 0.0      # finite
