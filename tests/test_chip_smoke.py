"""chip_smoke.py off the chip: it must fail, fast, and print no result.

What it checks on the chip cannot run here; what can is the other half
of its contract — no accelerator, no ``ok`` line — and the guard that
keeps a second process from asking for a chip its parent holds.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).parent.parent


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    # the failure is the trainer refusing the platform, not a fallback
    # onto the CPU that then fails some later check
    assert "--device 'tpu': no such jax platform available" in proc.stderr
    assert not list(tmp_path.glob("*/train/*/events.jsonl"))


def test_chip_smoke_never_imports_jax():
    """One process per chip: the parent runs its phases as children, so
    it must stay off jax itself."""
    code = ("import sys; sys.argv = ['chip_smoke.py']; "
            f"sys.path.insert(0, {str(REPO)!r}); import chip_smoke; "
            "assert 'jax' not in sys.modules, 'chip_smoke imported jax'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_fleet_of_processes_is_refused_off_the_cpu_platform(monkeypatch):
    from raft_meets_dicl_tpu.cmd.serve import _check_fleet_platform

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(ValueError, match="belongs to one process"):
        _check_fleet_platform(2, None)
    with pytest.raises(ValueError, match="belongs to one process"):
        _check_fleet_platform(2, "tpu")
    _check_fleet_platform(1, "tpu")
    _check_fleet_platform(2, "cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _check_fleet_platform(4, None)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="belongs to one process"):
        _check_fleet_platform(2, None)
