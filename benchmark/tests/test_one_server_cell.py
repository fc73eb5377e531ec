"""The cell ``raft-dicl-serve-mixed`` as data, its driver's draw of a model a
request, and its readers (six of the serve path, eleven of a model's own
phases) on runs that name a model and on runs that do not.

    python3 -m pytest benchmark/tests/test_one_server_cell.py -q

On hand-made records and events, on the small recorded capture of
``test_dicl_cell.py`` (two served batches on one v5e chip: one device gap)
with the dispatch thread's marks laid around its two executions, and
through the CPU rehearsal (``rehearse_one_server.py``: toy models, two
sessions behind one scheduler, the real driver, check and readers).
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

from benchmark.harness import check, schedule, serve_models, spec, xtrace  # noqa: E402
from benchmark.layers import _models, _timeline  # noqa: E402
from benchmark.reference import one_server  # noqa: E402

CELL = "raft-dicl-serve-mixed"
NEW = {"serve_raft_p95_ms", "serve_dicl_p95_ms", "serve_raft_fill_pct",
       "serve_dicl_fill_pct", "serve_model_switch_pct", "serve_switch_gap_ms"}
# a model's own phases: the one-model reader on that model's executions
PHASES = {"serve_raft_device_batch_ms", "serve_raft_encoder_ms",
          "serve_raft_corr_build_ms", "serve_raft_lookup_ms",
          "serve_raft_update_ms", "serve_dicl_device_batch_ms",
          "serve_dicl_encoder_ms", "serve_dicl_lookup_ms",
          "serve_dicl_mnet_ms", "serve_dicl_warp_ms", "serve_dicl_context_ms"}
RAFT, DICL = "raft/baseline", "dicl/baseline"
CAPTURE = Path(__file__).parent / "data" / "dicl_capture_small.json.gz"


def test_the_cell_lists_its_metrics_and_every_reader_loads():
    cell = spec.load_cell(CELL)
    mixed = spec.load_cell("raft-serve-mixed")
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_models"
    assert [m["name"] for m in cell.end_to_end] == ["serve_p95_ms", "setup_s"]
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    # every serve metric without a list of cells reads this cell too
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in mixed.per_layer} | NEW | PHASES
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW | PHASES:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"
            assert m["layer"] == ("serve path" if m["name"] in NEW
                                  else "model step")
    assert [m["name"] for m in bench["per_layer"][-17:-11]] == [
        "serve_raft_p95_ms", "serve_dicl_p95_ms", "serve_raft_fill_pct",
        "serve_dicl_fill_pct", "serve_model_switch_pct",
        "serve_switch_gap_ms"]
    assert {m["name"] for m in bench["per_layer"][-11:]} == PHASES
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "raft-dicl-one-server"
    # a limit a model, each with the rule that set it
    limits = check.limits_for(CELL)
    assert set(limits) == {"serve_flow_gap"}
    assert set(limits["serve_flow_gap"]) == {RAFT, DICL}
    # the driver is the eight functions run.py calls
    driver = spec.load_driver(cell.traffic["kind"])
    for name in ("run", "readings", "write_records", "print_rates",
                 "memory_peak", "attempted_failed", "trace_module", "check"):
        assert callable(getattr(driver, name))


def test_the_configuration_is_the_two_accepted_ones_with_nothing_reduced():
    cfg = spec.load_cell(CELL).config
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "raft-dicl-one-server")
    assert entry["reduced"] == [] == cfg["reduced"]
    alone = {RAFT: spec.load_cell("raft-serve-mixed").config,
             DICL: spec.load_cell("dicl-serve-mixed").config}
    assert [e["model"]["id"] for e in cfg["models"]] == [RAFT, DICL]
    for e in cfg["models"]:
        mine = alone[e["model"]["id"]]
        for key in ("model", "reference", "serve", "precision",
                    "control_precision"):
            assert e[key] == mine[key], key
        assert one_server.entry_of(cfg, e["model"]["id"]) is e
        assert one_server.module_of(e).__name__.endswith(mine["reference"])
    assert cfg["models"][1]["widths"] == alone[DICL]["widths"]
    assert cfg["reference"] == "one_server"
    assert cfg["layout"]["chips"] == cfg["layout"]["processes"] == 1
    assert cfg["layout"]["dispatch_threads"] == 1
    assert {a["key"] for a in cfg["assumed"]} == {
        "traffic.models", "traffic.arrivals", "models[].serve"}
    assert all(a["why"] for a in cfg["assumed"])
    with pytest.raises(KeyError):
        one_server.entry_of(cfg, "raft/fs")
    # one server has one max-wait and one queue bound (a lane's)
    assert len({(e["serve"]["max-wait-ms"], e["serve"]["queue-limit"],
                 e["serve"]["wire-format"]) for e in cfg["models"]}) == 1
    # cfg/serve/two-models.yaml is the same deployment on the normal path
    import yaml

    served = yaml.safe_load(
        (ROOT / "cfg/serve/two-models.yaml").read_text())["serve"]
    assert [(m["buckets"], m["batch-size"]) for m in served["models"]] == [
        (e["serve"]["buckets"], e["serve"]["batch-size"])
        for e in cfg["models"]]


def test_the_traffic_is_serve_mixed_to_the_letter_with_a_model_a_request():
    mixed = spec.load_cell("raft-serve-mixed").traffic
    ours = spec.load_cell(CELL).traffic
    same = ("group", "shapes", "clients", "payload_pool", "discard_s",
            "trace_s", "timeout_s", "trace_module")
    assert {k: ours[k] for k in same} == {k: mixed[k] for k in same}
    assert ours["models"] == [{"id": RAFT, "weight": 3},
                              {"id": DICL, "weight": 1}]
    # eight requests are followed by the references: two a model and size
    assert ours["check_per_shape"] * len(ours["shapes"]) * len(
        ours["models"]) == 8
    rate = ours["rate_per_s"]
    assert rate == int(rate) and "sweep" in ours["rate_from"]
    plan = schedule.build(ours, 7, 50 + ours["trace_s"])
    assert len(plan) % ours["group"] == 0
    assert plan[-1][0] >= ours["discard_s"] + 50
    asked = serve_models.model_plan(ours, 7, len(plan))
    assert asked == serve_models.model_plan(ours, 7, len(plan))
    assert asked != serve_models.model_plan(ours, 8, len(plan))
    # 3:1 exactly in every group of 8, for every seed
    for seed in (7, 4100000077):
        got = serve_models.model_plan(ours, seed, len(plan))
        for k in range(0, len(got), 8):
            assert got[k:k + 8].count(RAFT) == 6
            assert got[k:k + 8].count(DICL) == 2
    # drawn apart from the size: every model meets every size, and over a
    # run a size's share of a model's requests is near the mix's half
    pairs = list(zip(asked, (s for _, s in plan)))
    for model in (RAFT, DICL):
        sizes = [s for m, s in pairs if m == model]
        assert 0.4 < sizes.count(0) / len(sizes) < 0.6
    # a 50 s window counts over 900 requests and over 200 of the less
    # popular model (the window cuts a group: a quarter to within a group)
    counted = [m for m, (due, _) in zip(asked, plan) if 2.0 <= due < 52.0]
    assert len(counted) == int(50 * rate) > 900
    assert abs(counted.count(DICL) - len(counted) / 4) <= 2 < 200


# -- the readers on hand-made runs --------------------------------------------


def _run(named=True):
    """A window of four batches (raft, raft, dicl, raft) and eight counted
    requests; with ``named`` false the same run as a program and a driver
    from before the field leave it."""
    def rec(i, model, ms, counted=True, ok=True):
        r = {"i": i, "due": float(i), "done": i + ms / 1e3,
             "counted": counted, "ok": ok, "model": model}
        if not named:
            del r["model"]
        return r

    records = [rec(i, RAFT, 100.0 + 10 * i) for i in range(6)]
    records += [rec(6, DICL, 700.0), rec(7, DICL, 900.0),
                rec(8, DICL, 5000.0, counted=False),
                rec(9, RAFT, 5000.0, ok=False)]
    events = []
    for k, (model, size, fill) in enumerate(
            [(RAFT, 8, 0), (RAFT, 6, 2), (DICL, 2, 6), (RAFT, 4, 4)]):
        t = 10.0 + k
        marks = {m: 100.0 + k + 0.01 * j
                 for j, m in enumerate(("wait", "dispatch", "assembled",
                                        "called", "ready", "fetched",
                                        "completed"))}
        batch = {"kind": "serve", "event": "batch", "t": t, "model": model,
                 "size": size, "fill": fill}
        traced = {"kind": "trace", "event": "batch", "t": t, "model": model,
                  "marks": marks}
        if not named:
            del batch["model"], traced["model"]
        events += [batch, traced]
    # a batch outside the window is not counted
    events.append({"kind": "serve", "event": "batch", "t": 99.0,
                   "model": DICL, "size": 8, "fill": 0})
    return {"kind": "serve", "records": records, "events": events,
            "readings": {"window_wall": (9.0, 20.0)}, "trace": None,
            "trace_dir": None,
            "cell": SimpleNamespace(traffic={"trace_module": "jit_step"})}


def test_the_readers_read_a_run_that_names_its_models():
    run = _run()
    read = {name: spec.load_reader(name)(run) for name in NEW}
    assert read["serve_raft_p95_ms"] == pytest.approx(147.5)
    assert read["serve_dicl_p95_ms"] == pytest.approx(890.0)
    assert read["serve_raft_fill_pct"] == pytest.approx(100 * 18 / 24)
    assert read["serve_dicl_fill_pct"] == pytest.approx(25.0)
    # raft raft dicl raft: two of three consecutive pairs switch
    assert read["serve_model_switch_pct"] == pytest.approx(100 * 2 / 3)
    assert read["serve_switch_gap_ms"] is None        # no capture


def test_the_readers_give_nothing_where_no_record_names_a_model():
    run = _run(named=False)
    for name in NEW | PHASES:
        assert spec.load_reader(name)(run) is None, name
    # nor for a train run, nor where one model answered every batch
    assert all(spec.load_reader(name)(dict(_run(), kind="train")) is None
               for name in NEW | PHASES)
    # a model's phases need a capture, whoever names the batches
    assert all(spec.load_reader(name)(_run()) is None for name in PHASES)
    one = _run()
    for e in one["events"]:
        if "model" in e:
            e["model"] = RAFT
    assert _models.switch_pct(one) is None


# -- the gap before a switch, on the recorded capture --------------------------

START_NS = 1_790_000_000_000_000_000
PERF0 = 7000.0


def _traced(monkeypatch, tmp_path, models):
    """A traced serve run whose capture is the recorded one (two executions
    of ``jit_step``) and whose two batches name ``models``."""
    capture = xtrace.load_saved(CAPTURE)
    execs = _timeline.device_intervals(capture, "jit_step")[0]
    assert len(execs) == 2
    events = [{"kind": "clock", "t": 0.0, "perf_counter": PERF0,
               "time_ns": START_NS}]
    for (s, e), model in zip(execs, models):
        ns = {"wait": s - 6e6, "dispatch": s - 5e6, "assembled": s - 4e6,
              "called": s - 1e6, "ready": e + 1e5, "fetched": e + 1e6,
              "completed": e + 2e6}
        ev = {"kind": "trace", "event": "batch", "t": 0.0,
              "marks": {k: PERF0 + v / 1e9 for k, v in ns.items()}}
        if model is not None:
            ev["model"] = model
        events.append(ev)
    monkeypatch.setattr(xtrace, "find_xplane", lambda d: tmp_path / "x.pb")
    monkeypatch.setattr(xtrace, "load", lambda p: capture)
    monkeypatch.setattr(_timeline, "profile_start_ns", lambda p: START_NS)
    run = {"kind": "serve", "events": events, "records": [],
           "readings": {"window_wall": (0.0, 1.0)}, "trace_dir": tmp_path,
           "trace": xtrace.reduce(capture, "jit_step"),
           "cell": SimpleNamespace(traffic={"trace_module": "jit_step"})}
    return run, execs


def test_the_switch_gap_is_the_device_gap_before_another_models_batch(
        monkeypatch, tmp_path, capsys):
    run, execs = _traced(monkeypatch, tmp_path, (RAFT, DICL))
    gap = spec.load_reader("serve_switch_gap_ms")(run)
    j = _timeline.of(run)
    assert j["ok"] and j["matched"] == 2
    # one gap, and it is the one before the switch: host + launch parts
    assert gap == pytest.approx(
        1e3 * (j["host_s"][0] + j["launch_s"][0]), abs=1e-9)
    assert 0.0 < gap <= (execs[1][0] - execs[0][1]) / 1e6
    assert "[switch] gaps=1 switch_n=1" in capsys.readouterr().out
    # the same batches under one model: a gap, and no switch to read
    same, _ = _traced(monkeypatch, tmp_path, (RAFT, RAFT))
    assert spec.load_reader("serve_switch_gap_ms")(same) is None
    assert _models.gaps(same) == [(pytest.approx(gap / 1e3), RAFT, RAFT)]
    # and a program that names no model gives nothing, not 0.0
    old, _ = _traced(monkeypatch, tmp_path, (None, None))
    assert spec.load_reader("serve_switch_gap_ms")(old) is None
    assert spec.load_reader("serve_gap_host_ms")(old) is not None


# -- a model's own phases, on the recorded capture ---------------------------

OWNERS = [e for e in json.loads((CAPTURE.parent / "dicl_capture_events.json")
                                .read_text()) if e.get("event") == "owners"]
DICL_PHASES = {"serve_dicl_encoder_ms": "serve_encoder_ms",
               "serve_dicl_lookup_ms": "serve_lookup_ms",
               "serve_dicl_mnet_ms": "serve_mnet_ms",
               "serve_dicl_warp_ms": "serve_warp_ms",
               "serve_dicl_context_ms": "serve_context_ms"}


def _read(run, names):
    return {name: spec.load_reader(name)(run) for name in names}


def test_a_models_phases_are_the_one_model_readers_on_its_own_executions(
        monkeypatch, tmp_path, capsys):
    # the capture is dicl-serve-mixed's, its two batches both DICL's: as
    # the driver of one model leaves the run, the one-model readers read it
    # the capture is dicl-serve-mixed's, its two batches both DICL's: a
    # model's readers read what the one-model readers read of the run ...
    run, execs = _traced(monkeypatch, tmp_path, (DICL, DICL))
    run["events"] += OWNERS
    want = _read(run, DICL_PHASES.values())
    assert all(v > 0.0 for v in want.values()), want
    got = _read(run, PHASES)
    for mine, theirs in DICL_PHASES.items():
        assert got[mine] == pytest.approx(want[theirs], rel=1e-9), mine
    assert got["serve_dicl_device_batch_ms"] == pytest.approx(
        spec.load_reader("serve_device_batch_ms")(run))
    out = capsys.readouterr().out
    assert "[owners] model=dicl/baseline: 2 of 2 traced batches" in out
    # ... and nothing under the other model's name
    assert all(v is None for k, v in got.items() if "_raft_" in k), got

    # the first batch another model's: DICL's phases are the second
    # execution's alone, where the one-model readers spread the module's
    # time over both executions
    mixed, _ = _traced(monkeypatch, tmp_path, (RAFT, DICL))
    mixed["events"] += OWNERS
    got = _read(mixed, PHASES)
    assert "1 of 2 traced batches" in capsys.readouterr().out
    busy = mixed["trace"]["exec_busy_s"]
    assert got["serve_dicl_device_batch_ms"] == pytest.approx(1e3 * busy[1])
    view = _models.alone(mixed, "dicl")
    assert view["trace"]["executions"] == 1
    inside = sum(d for _, s, d in _models.joined(mixed)["ops"]
                 if execs[1][0] <= s < execs[1][1])
    assert sum(view["trace"]["op_s"].values()) == pytest.approx(inside / 1e9)
    parts = sum(got[f"serve_dicl_{k}_ms"]
                for k in ("encoder", "lookup", "warp", "context"))
    batch = got["serve_dicl_device_batch_ms"]
    assert 0.8 * batch < parts < batch
    assert got["serve_dicl_mnet_ms"] < got["serve_dicl_lookup_ms"]
    # RAFT's batch has no record of its own here: nothing, not DICL's
    assert all(v is None for k, v in got.items() if "_raft_" in k), got

    # a program that names no model on its batches
    old, _ = _traced(monkeypatch, tmp_path, (None, None))
    old["events"] += OWNERS
    assert _read(old, PHASES) == dict.fromkeys(PHASES)


# -- through the driver, on the CPU --------------------------------------------


def test_the_rehearsal_runs_the_cell_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RMD_AOT_DIR=str(tmp_path / "aot"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/tests/rehearse_one_server.py"),
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=1500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 16
    got = {k.split(".", 1)[1] for k in result["metrics"]}
    # a CPU capture has no device plane: the join stays away
    assert NEW - {"serve_switch_gap_ms"} <= got
    assert not ({"serve_switch_gap_ms"} | PHASES) & got
    out = proc.stdout
    assert "[check] serve_flow_gap[raft/baseline]" in out
    assert "[check] serve_flow_gap[dicl/baseline]" in out
    assert '[models] counted {"raft/baseline": 12, "dicl/baseline": 4}' in out
