"""The cell ``raft-train-things-dp4`` as data, and its four readers.

    python3 -m pytest benchmark/tests/test_dp4_cell.py -q

The readers on hand-laid intervals (what is exposed, what is hidden, what a
one-chip trace gives), on a recorded fragment of a traced run of the cell on
four v5e chips (two steps of the first two device planes, my chip run, PR
39; ``tests/dump_chips.py`` kept it), and the CPU rehearsal of the cell at
toy shapes on four virtual devices, through ``run.py``'s own path.
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

from benchmark.harness import check, spec, xtrace  # noqa: E402
from benchmark.layers import _chips  # noqa: E402

CELL = "raft-train-things-dp4"
NEW = {"collective_ms", "collective_exposed_ms", "collective_mb_per_step",
       "chip_step_spread_ms"}
RECORDED = Path(__file__).parent / "data" / "dp4_chips_capture_small.json.gz"


def test_the_cell_lists_its_metrics_and_every_reader_loads():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.config["reference"] == "raft"
    assert cell.traffic_name == "train-things"
    assert cell.config_name == "raft-baseline-dp4"
    assert [m["name"] for m in cell.end_to_end] == ["train_pairs_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names
    assert not {n for n in names if n.startswith(("serve_", "sw_", "wcp_"))}
    for name in names:
        assert callable(spec.load_reader(name))
    # beside its four, what the one-chip control cell reports but the
    # combine kernel's roofline share (its reader has not been seen to find
    # the kernel under the mesh: PERF.md section 7)
    other = {m["name"] for m in spec.load_cell("raft-train-things").per_layer}
    assert names - other == NEW
    assert other - names == {"up8_combine_roofline"}
    assert set(check.limits_for(CELL)) == {
        "loss_gap", "flow_gap", "grad_norm_gap", "param_change_gap"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_pairs_per_s"
            assert m["layer"] == ("device" if m["name"].startswith("chip_")
                                  else "partitioned step")
    # the benchmark's one four-chip cell: a quarter of six, rounded down
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL] and len(bench["workloads"]) // 4 >= len(four)


def test_the_configuration_is_raft_baselines_plus_the_layout():
    cell = spec.load_cell(CELL)
    base = spec.load_cell("raft-train-things").config
    cfg = cell.config
    for key in ("model", "reference", "precision", "control_precision",
                "weights", "knobs"):
        assert cfg[key] == base[key], key
    assert "serve" not in cfg
    assert {k: cfg["train"][k] for k in ("crop", "batch_per_chip")} == {
        "crop": [400, 720], "batch_per_chip": 6}
    assert cfg["reduced"] == [] and cfg["knobs"] == {}
    layout = cfg["layout"]
    assert layout["chips"] == cell.chips == 4
    assert layout["mesh"] == {"data": 4} and layout["processes"] == 1
    assert layout["global_batch"] == 6 * 4
    # what differs in the environment is the loader's size alone
    env = json.loads(json.dumps(cfg["env"]))
    assert env["loader"].pop("num_workers") == 16
    want = json.loads(json.dumps(base["env"]))
    want["loader"].pop("num_workers")
    assert env == want
    assert [a["key"] for a in cfg["assumed"]] == [
        "layout.global_batch", "stage.optimizer.lr", "env.loader.num_workers"]
    assert all(a["why"] for a in cfg["assumed"])
    # the stage it runs is the file the one-chip control runs
    assert cell.traffic == spec.load_cell("raft-train-things").traffic
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "raft-baseline-dp4")
    assert entry["reduced"] == [] and entry["file"].endswith(
        "configs/raft-baseline-dp4.json")


def test_the_driver_builds_the_global_batch_and_asks_for_all_four_chips():
    from benchmark.harness import train

    cell = spec.load_cell(CELL)
    strat, batch = train._stage_config(cell)
    assert batch == 24
    data = strat["stages"][0]["data"]
    assert data["batch-size"] == 24 and data["source"]["shape"] == [400, 720]
    # warm-up, the window at the predicted 70 pairs/s and the traced tail
    # fit the epoch
    steps = cell.traffic["warmup_steps"] + 50 * 75 // 24 \
        + cell.traffic["trace_steps"] + 10
    assert steps < cell.traffic["epoch_steps"]


# -- the readers on hand-laid intervals ---------------------------------------

STEP = "jit_step(123)"
AR = "%all-reduce.1 = f32[64]{0} all-reduce()"
A2A = "%all-to-all.2 = bf16[6,4,100,720,3]{3,2,4,0,1} all-to-all()"
CP_START = ("%collective-permute-start.3 = (bf16[6,50,90,256]{3,2,1,0}, "
            "bf16[6,50,90,256]{3,2,1,0}) collective-permute-start()")
CP_DONE = ("%collective-permute-done.3 = bf16[6,50,90,256]{3,2,1,0} "
           "collective-permute-done()")
CONV = "%fusion.9 = bf16[8,200,360,64]{3,2,1,0} fusion(), kind=kOutput"
LOOP = "%while.4 = (s32[], f32[2]) while()"
MS = 1e6   # ns


def _plane(name, execs, ops):
    return {"name": name, "lines": [
        {"name": "XLA Modules",
         "events": [[STEP, s * MS, d * MS, {}] for s, d in execs]},
        {"name": "XLA Ops",
         "events": [[t, s * MS, d * MS, {}] for t, s, d in ops]}]}


def _run(capture, events=(), kind="train"):
    run = {"kind": kind, "events": list(events),
           "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
           "trace": xtrace.reduce(capture, "jit_step")}
    found = next(p for p in capture["planes"]
                 if xtrace._DEVICE.match(p["name"]))
    lines = {ln["name"]: ln["events"] for ln in found["lines"]}
    run["first_plane_ops"] = _chips.split(
        [(n, s, d) for n, s, d, _ in lines["XLA Modules"]],
        [(n, s, d) for n, s, d, _ in lines["XLA Ops"]],
        {m.split("(")[0] for m in run["trace"]["module"]})
    return run


def _read(run):
    return {name: spec.load_reader(name)(run) for name in sorted(NEW)}


def _two_chips():
    # chip 0, two steps of 10 ms: an all-reduce of 1 ms alone, an
    # all-to-all of 2 ms of which 0.5 under a fusion, a permute's start
    # wholly under a fusion and its done of 0.25 ms alone; the while that
    # holds them all is no operation. Chip 1 is busy 1 ms a step less.
    def step(t):
        return [(LOOP, t, 10.0), (CONV, t, 3.0), (AR, t + 3.0, 1.0),
                (A2A, t + 4.0, 2.0), (CONV, t + 5.5, 2.5),
                (CP_START, t + 6.0, 0.5), (CP_DONE, t + 8.0, 0.25),
                (CONV, t + 8.25, 1.75)]
    chip0 = _plane("/device:TPU:0", [(0, 10), (12, 10)], step(0) + step(12))
    short = [(CONV, t, 9.0) for t in (0, 12)]
    chip1 = _plane("/device:TPU:1", [(0, 10), (12, 10)], short)
    return {"planes": [chip0, chip1]}


def test_collective_time_and_what_of_it_is_exposed():
    said = {"kind": "aot", "event": "save", "program": "train_step",
            "mesh": {"data": 4}, "collectives": {
                "counts": {"all-reduce": 6, "all-to-all": 9},
                "bytes": {"all-reduce": 12_760_376, "all-to-all": 543_024_000},
                "total_bytes": 555_784_376}}
    values = _read(_run(_two_chips(), [said]))
    # 1 + 2 + 0.5 + 0.25 a step in collective operations
    assert values["collective_ms"] == pytest.approx(3.75)
    # less the 0.5 of the all-to-all and the 0.5 of the start under fusions
    assert values["collective_exposed_ms"] == pytest.approx(2.75)
    assert values["collective_mb_per_step"] == pytest.approx(555.784376)
    # chip 0 is busy 10 ms a step, chip 1 nine
    assert values["chip_step_spread_ms"] == pytest.approx(1.0)


def test_where_the_device_runs_one_operation_at_a_time_all_of_it_is_exposed():
    ops = [(CONV, 0, 4.0), (AR, 4.0, 1.0), (CONV, 5.0, 5.0)]
    capture = {"planes": [_plane("/device:TPU:0", [(0, 10)], ops),
                          _plane("/device:TPU:1", [(0, 10)], ops)]}
    values = _read(_run(capture))
    assert values["collective_ms"] == values["collective_exposed_ms"] == \
        pytest.approx(1.0)
    assert values["chip_step_spread_ms"] == pytest.approx(0.0)
    # the program said nothing: nothing, not zero
    assert values["collective_mb_per_step"] is None


def test_a_one_chip_trace_and_an_untraced_run_give_nothing():
    one = {"planes": [_plane("/device:TPU:0", [(0, 10)], [(CONV, 0, 9.0)])]}
    run = _run(one)
    assert _read(run)["collective_ms"] is None
    assert _read(run)["chip_step_spread_ms"] is None
    run["first_plane_ops"] = _chips._first_plane_ops(run)
    assert _read(run)["collective_exposed_ms"] is None
    untraced = {"kind": "train", "events": [], "trace": None,
                "trace_dir": None}
    assert set(_read(untraced).values()) == {None}
    serve = dict(_run(_two_chips()), kind="serve")
    serve["first_plane_ops"] = None
    assert set(_read(serve).values()) == {None}


def test_the_programs_record_is_read_from_the_event_that_holds_the_step():
    read = spec.load_reader("collective_mb_per_step")
    run = {"kind": "train", "events": [
        {"kind": "aot", "event": "miss", "program": "train_step",
         "mesh": {"data": 4}},
        {"kind": "aot", "event": "hit", "program": "eval_step",
         "collectives": {"total_bytes": 5}},
        {"kind": "aot", "event": "hit", "program": "train_step",
         "mesh": {"data": 4}, "collectives": {"counts": {}, "bytes": {},
                                              "total_bytes": 749_320_376}}]}
    assert read(run) == pytest.approx(749.320376)
    # the parent's program: the mesh step runs, and says nothing
    assert read({"kind": "train", "events": run["events"][:1]}) is None


def test_readers_on_the_recorded_fragment_of_two_chips():
    capture = xtrace.load_saved(RECORDED)
    assert [p["name"] for p in capture["planes"]] == ["/device:TPU:0",
                                                      "/device:TPU:1"]
    run = _run(capture)
    t = run["trace"]
    assert t["chips"] == 2 and t["executions"] == 2
    values = _read(run)
    # a brute-force reading of the first plane on a microsecond grid
    import numpy as np

    execs, ops = run["first_plane_ops"]
    t0 = execs[0][0]
    grid_c = np.zeros(int((execs[-1][1] - t0) / 1e3) + 2, bool)
    grid_o = np.zeros_like(grid_c)
    for text, s, d in ops:
        g = grid_c if xtrace.op_class(text) == "collective" else grid_o
        g[int((s - t0) / 1e3): int((s + d - t0) / 1e3) + 1] = True
    assert values["collective_exposed_ms"] == pytest.approx(
        (grid_c & ~grid_o).sum() / 1e3 / 2, rel=0.05, abs=0.05)
    assert 0 < values["collective_exposed_ms"] <= values["collective_ms"]
    assert values["collective_ms"] == pytest.approx(
        1e3 * t["class_s_per_exec"]["collective"])
    assert values["chip_step_spread_ms"] == pytest.approx(
        1e3 * abs(t["busy_s_per_chip"][0] - t["busy_s_per_chip"][1]) / 2)
    # every kind the compiled text holds shows up as operations
    kinds = {xtrace.parse_op(text)[1].removesuffix("-start")
             .removesuffix("-done") for text, _, _ in ops
             if xtrace.op_class(text) == "collective"}
    assert {"all-reduce", "all-to-all", "collective-permute"} <= kinds


# -- the rehearsal ------------------------------------------------------------


def test_cpu_rehearsal_of_the_cell_on_four_virtual_devices(tmp_path):
    env = dict(os.environ, RMD_AOT_DIR=str(tmp_path / "aot"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/tests/rehearse_dp4.py"),
         "--trace", "1", "--seconds", "2"],
        env=env, capture_output=True, text=True, timeout=1500, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                                "memory_peak_bytes": 0}
    metrics = result["metrics"]
    assert all(k.startswith("cpu_rehearsal.") for k in metrics)
    # the program's record comes through; a CPU has no device plane, so the
    # three trace readers stay away
    assert metrics["cpu_rehearsal.collective_mb_per_step"]["value"] > 0
    for name in NEW - {"collective_mb_per_step"}:
        assert f"cpu_rehearsal.{name}" not in metrics
    assert "[check] window_compiles: 0" in proc.stdout
