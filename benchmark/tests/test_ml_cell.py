"""The cell ``ml-train-things`` as data, and its readers on a program
that says less than the change's.

    python3 -m pytest benchmark/tests/test_ml_cell.py -q

The rehearsal drives the cell's driver, reference, check and readers at
toy shapes on the CPU, twice from one program store: the second process
loads the train step and must still report what its trace noted.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import check, spec  # noqa: E402

CELL = "ml-train-things"
NEW = {"matching_levels_batched"}
SHARED = {"sw_ms", "sw_roofline", "matching_mb_per_step"}


def test_the_cell_lists_its_metrics_and_every_reader_loads():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["reference"] == "ml"
    assert cell.traffic_name == "train-things"
    assert [m["name"] for m in cell.end_to_end] == ["train_pairs_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW | SHARED <= names and "up8_combine_roofline" not in names
    assert not {n for n in names if n.startswith("serve_")}
    for name in names:
        assert callable(spec.load_reader(name))
    # beside the new note, what the other learned-cost cell reports
    other = {m["name"] for m in spec.load_cell("ctf3-train-things").per_layer}
    assert names - other == NEW and other <= names
    assert set(check.limits_for(CELL)) == {
        "loss_gap", "flow_gap", "grad_norm_gap", "param_change_gap"}


def test_the_configuration_is_the_published_one_with_nothing_reduced():
    import yaml

    cell = spec.load_cell(CELL)
    model = cell.config["model"]
    assert model["id"] == "raft+dicl/ml" and cell.config["reduced"] == []
    p, a = model["model"]["parameters"], model["model"]["arguments"]
    assert (p["corr-levels"], p["corr-radius"], p["corr-channels"]) == (
        4, 4, 32)
    assert (p["context-channels"], p["recurrent-channels"]) == (128, 128)
    assert p["share-dicl"] is False and p["dap-type"] == "separate"
    assert a == {"iterations": 12, "dap": True}
    assert cell.config["train"] == dict(cell.config["train"],
                                        crop=[384, 640], batch_per_chip=6)
    # what the yaml states it states here too, but the precision policy
    upstream = yaml.safe_load((ROOT / "cfg/model/raft+dicl-ml.yaml")
                              .read_text())
    assumed = {e["key"] for e in cell.config["assumed"]}
    assert assumed == {"model.parameters.mixed-precision", "train.crop",
                       "train.batch_per_chip"}
    for key, value in upstream["model"]["parameters"].items():
        if key != "mixed-precision":
            assert p[key] == value, key
    assert upstream["model"]["arguments"] == a
    assert upstream["loss"] == model["loss"]
    assert upstream["input"] == model["input"]
    # the written-out defaults are the program's own
    from raft_meets_dicl_tpu import models

    cfg = models.load(upstream).model.get_config()["parameters"]
    for key, value in p.items():
        if key != "mixed-precision":
            assert cfg[key] == value, key


def _run(events):
    return {"kind": "train", "events": events,
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": {"executions": 2, "op_s": {}, "op_count": {},
                      "busy_s": 1.0, "window_s": 1.0}}


def _aot(**notes):
    return {"kind": "aot", "event": "hit", "program": "train_step", **notes}


@pytest.mark.parametrize("events, want", [
    ([_aot(sw_fused_calls=48, matching_volume_bytes=5803868160,
           matching_levels_batched=4)], 4.0),
    ([{"kind": "compile", "label": "train_step", "seconds": 1.0,
       "matching_levels_batched": 1}], 1.0),
    # the parent's program: the notes of one visit, and not the new one
    ([_aot(sw_fused_calls=4, matching_volume_bytes=483655680)], None),
    ([_aot(matching_levels_batched=4) | {"program": "eval_step"}], None),
    ([], None),
])
def test_the_new_reader_on_programs_that_say_more_and_less(events, want):
    from benchmark.layers import matching_levels_batched

    assert matching_levels_batched.read(_run(events)) == want
    assert matching_levels_batched.read(
        _run(events) | {"kind": "serve"}) is None


def test_no_reader_of_the_programs_notes_raises_on_the_parents_program(
        capsys):
    # the parent's train step: the notes of one visit of the scan's body,
    # and not the new one. The four readers that read a program's notes
    # return a number or nothing, with a trace and without
    events = [_aot(sw_fused_calls=4, matching_volume_bytes=483655680),
              {"kind": "step", "step": 0,
               "counters": {"matching_volume_bytes": 483655680}}]
    for run in (_run(events), _run(events) | {"trace": None}):
        values = {name: spec.load_reader(name)(run)
                  for name in sorted(NEW | SHARED)}
        assert values == {"matching_levels_batched": None,
                          "matching_mb_per_step": 483.65568,
                          "sw_ms": None, "sw_roofline": None}
    assert "sw_fused_calls=4 sw_fallback_calls=0" in capsys.readouterr().out


def test_rehearsal_is_correct_and_a_loaded_program_keeps_its_notes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    # 2 iterations of b2 128x128 in bf16: on each of four levels frame
    # one's 32 channels and the 25 windows of them (the toy's radius is
    # 2), at 16x16
    volume = 2 * (2 * 4 * 2 * 16 * 16 * 32 * 26)
    for boot in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "benchmark/tests/rehearse_ml.py", "--trace",
             "1", "--seed", "2147483659"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        result = json.loads(lines[-1])
        assert result["correct"] and result["device"]["platform"] == "cpu"
        metrics = {k.removeprefix("cpu_rehearsal."): v["value"]
                   for k, v in result["metrics"].items()}
        assert metrics["matching_mb_per_step"] == volume / 1e6, boot
        # off the TPU the levels' nets run one after the other, through
        # the plain sampler: no sampler path is reported
        assert metrics["matching_levels_batched"] == 1.0, boot
        assert not {"sw_ms", "sw_roofline"} & set(metrics)
        events = [json.loads(ln) for ln in (
            ROOT / "bench_out/rehearsal/toy-ml/seed2147483659_trace1"
            / "events.jsonl").read_text().splitlines()]
        step = [e for e in events if e["kind"] == "aot"
                and e.get("program") == "train_step"
                and e["event"] in ("hit", "save")]
        assert [e["event"] for e in step] == [
            "save" if boot == "cold" else "hit"]
        assert step[0]["matching_levels_batched"] == 1
        assert step[0]["matching_volume_bytes"] == volume
        compiles = [e for e in events if e["kind"] == "compile"
                    and e.get("label") == "train_step"]
        assert len(compiles) == (1 if boot == "cold" else 0)
