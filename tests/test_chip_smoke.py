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


# -- the four-chip leg's reading, on records written by hand -------------------


def _mesh_events(devices=4, collectives=True):
    """The events a twelve-step mesh run of ``main.py train`` leaves, as far
    as ``chip_smoke.mesh_phase`` reads them."""
    device = {"platform": "tpu", "backend": "tpu",
              "device_kind": "TPU v5 lite", "device_count": 4}
    held = {"kind": "aot", "event": "save", "program": "train_step",
            "mosaic_calls": 2, "mesh": {"data": 4}}
    if collectives:
        held["collectives"] = {"counts": {"all-reduce": 6, "all-to-all": 9},
                               "bytes": {"all-reduce": 12760376,
                                         "all-to-all": 543024000},
                               "total_bytes": 555784376}
    events = [
        {"kind": "boot", "compile_cache": "/tmp/cache"},
        {"kind": "run_start", "devices_used": 4, **device},
        {"kind": "sharding", "mesh": {"data": 4}},
        {"kind": "aot", "event": "miss", "program": "train_step"},
        {"kind": "compile", "label": "train_step", "seconds": 170.0,
         "mesh": {"data": 4}},
        held,
    ]
    for i in range(12):
        events.append({"kind": "step", "step": i, "step_time": 0.58,
                       "batch": 24, "put": [0.0, 0.1], "devices": devices})
        if i % 10 == 9:
            events.append({"kind": "device_sync", "step": i, "loss": 3.5})
    events += [{"kind": "epoch_end", "loss": 3.4},
               {"kind": "memory", "device_peak_gib": 10.4}]
    return events


def _smoke_on(tmp_path, monkeypatch, events):
    import json

    sys.path.insert(0, str(REPO))
    import chip_smoke

    run = tmp_path / "mesh" / "run0"
    run.mkdir(parents=True)
    (run / "events.jsonl").write_text(
        "\n".join(json.dumps(e) for e in events) + "\n")
    asked = []
    monkeypatch.setattr(chip_smoke, "run_phase",
                        lambda name, argv, log, deadline: asked.append(argv))
    return chip_smoke, asked


def test_mesh_leg_reads_the_default_mesh_and_its_collectives(
        tmp_path, monkeypatch, capsys):
    chip_smoke, asked = _smoke_on(tmp_path, monkeypatch, _mesh_events())
    device, record = chip_smoke.mesh_phase(tmp_path, deadline=0.0)
    # the normal path: no --device-ids, the strategy at six pairs a chip
    (argv,) = asked
    assert "--device-ids" not in argv and "--mesh" not in argv
    assert "cfg/strategy/dev/synth-things-dp4.yaml" in argv
    assert (REPO / "cfg/strategy/dev/synth-things-dp4.yaml").is_file()
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    assert record["mesh"] == {"data": 4} and record["devices_used"] == 4
    assert record["collectives"]["counts"]["all-reduce"] == 6
    assert record["steps"] == 12 and record["device_peak_gib"] == 10.4
    assert "[mesh] {" in capsys.readouterr().out


@pytest.mark.parametrize("events, why", [
    (_mesh_events(devices=1), "did not feed four chips"),
    (_mesh_events(collectives=False), "says nothing of its collectives"),
], ids=["one-device-put", "no-collectives-record"])
def test_mesh_leg_fails_a_run_that_was_no_mesh_run(tmp_path, monkeypatch,
                                                   events, why):
    chip_smoke, _ = _smoke_on(tmp_path, monkeypatch, events)
    with pytest.raises(chip_smoke.Failed, match=why):
        chip_smoke.mesh_phase(tmp_path, deadline=0.0)


def test_the_dp4_strategy_is_the_things_stage_at_six_pairs_a_chip():
    import yaml

    one = yaml.safe_load((REPO / "cfg/strategy/dev/synth-things.yaml")
                         .read_text())["stages"][0]
    four = yaml.safe_load((REPO / "cfg/strategy/dev/synth-things-dp4.yaml")
                          .read_text())["stages"][0]
    assert four["data"]["batch-size"] == 4 * one["data"]["batch-size"] == 24
    assert four["data"]["source"]["size"] % 24 == 0
    for key in ("model", "loss", "optimizer", "lr-scheduler", "gradient"):
        assert four[key] == one[key], key
    assert four["data"]["source"]["shape"] == one["data"]["source"]["shape"]
