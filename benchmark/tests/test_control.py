"""The control, at a size a test run can hold: the reference computed in
fp8 (the precision below the configuration's bf16) is told apart from the
program by the toy limits, and the program is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control.py -q

The readings at the cells' own sizes, on the chip, are made by
``control.py`` and recorded beside each limit in ``reference/limits/``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TOY = ROOT / "benchmark/tests/toy/traffic"


@pytest.mark.parametrize("workload,kind,seconds", [
    ("raft-train-things", "train", "0"), ("raft-serve-mixed", "serve", "4")])
def test_control_fails_a_limit_and_the_program_none(workload, kind, seconds):
    proc = subprocess.run(
        [sys.executable, "benchmark/tests/control.py", "--workload", workload,
         "--toy", "--platform", "cpu", "--seeds", "11", "--seconds", seconds,
         "--out", str(Path("bench_out/test_control") / kind)],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(next(ln for ln in proc.stdout.splitlines()
                          if ln.startswith('{"seed"')))
    limits = json.loads((TOY / f"toy-{kind}.json").read_text())[
        "rehearsal_limits"]
    assert all(row["sound"][k] <= limits[k] for k in limits), row["sound"]
    assert any(not (row["control"][k] <= limits[k]) for k in limits), \
        row["control"]
