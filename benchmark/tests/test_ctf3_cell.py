"""The cell ``ctf3-train-things`` as data, and its readers where the
program took another path than the kernel's.

    python3 -m pytest benchmark/tests/test_ctf3_cell.py -q

The rehearsal drives the cell's driver, reference, check and readers at
toy shapes on the CPU, twice from one program store: the second process
loads the train step and must still report the counts its trace noted.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import check, spec  # noqa: E402

NEW = {"sw_ms", "sw_roofline", "matching_mb_per_step"}


def test_the_cell_lists_its_metrics_and_every_reader_loads():
    cell = spec.load_cell("ctf3-train-things")
    assert cell.chips == 1 and cell.config["reference"] == "ctf3"
    assert [m["name"] for m in cell.end_to_end] == ["train_pairs_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names and "up8_combine_roofline" not in names
    assert not {n for n in names if n.startswith("serve_")}
    for name in names:
        assert callable(spec.load_reader(name))
    # the other train cell reports every metric this one does, but the new
    other = {m["name"] for m in spec.load_cell("raft-train-things").per_layer}
    assert names - other == NEW
    assert set(check.limits_for("ctf3-train-things")) == {
        "loss_gap", "flow_gap", "grad_norm_gap", "param_change_gap"}
    assert cell.config["model"]["model"]["arguments"]["iterations"] == [4, 3, 3]
    assert cell.config["train"] == dict(cell.config["train"],
                                        crop=[384, 704], batch_per_chip=6)


def test_rehearsal_is_correct_and_a_loaded_program_keeps_its_counts(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    # 2/1/1 iterations of b2 64x128 in bf16: f1 + 81 windows of 32 channels
    volume = 2 * 32 * 2 * 82 * (2 * 2 * 4 + 4 * 8 + 8 * 16)
    for boot in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "benchmark/tests/rehearse_ctf3.py", "--trace",
             "1", "--seed", "2147483659"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        result = json.loads(lines[-1])
        assert result["correct"] and result["device"]["platform"] == "cpu"
        metrics = {k.removeprefix("cpu_rehearsal."): v["value"]
                   for k, v in result["metrics"].items()}
        assert metrics["matching_mb_per_step"] == volume / 1e6, boot
        # the CPU's capture has no device plane; where it has one, a
        # fallback keeps the kernel's metrics away (test_sw_readers.py)
        assert not {"sw_ms", "sw_roofline"} & set(metrics)
        events = [json.loads(ln) for ln in (
            ROOT / "bench_out/rehearsal/toy-ctf3/seed2147483659_trace1"
            / "events.jsonl").read_text().splitlines()]
        step = [e for e in events if e["kind"] == "aot"
                and e.get("program") == "train_step"
                and e["event"] in ("hit", "save")]
        assert [e["event"] for e in step] == [
            "save" if boot == "cold" else "hit"]
        assert step[0]["sw_fallback_calls"] == 4
        assert step[0]["matching_volume_bytes"] == volume
        compiles = [e for e in events if e["kind"] == "compile"
                    and e.get("label") == "train_step"]
        assert len(compiles) == (1 if boot == "cold" else 0)
