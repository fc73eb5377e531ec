"""graftprof: the trace-event parser, op-class bucketing, the
measured-vs-predicted calibration table, the machine-scoped
prof-budget.json drift gate, the /profilez retention fix, and the
telemetry/report/metrics round-trip — plus one real segmented CPU
capture of a toy registered program end to end."""

import json
from pathlib import Path

import pytest

from raft_meets_dicl_tpu import telemetry
from raft_meets_dicl_tpu.analysis import profile as prof
from raft_meets_dicl_tpu.telemetry import metrics as metrics_mod
from raft_meets_dicl_tpu.telemetry import sidecar

pytestmark = pytest.mark.obs

REPO = Path(__file__).parent.parent
CANNED = Path(__file__).parent / "data" / "graftprof"
MACHINE = "cpu:test"


# -- machine + peaks ----------------------------------------------------------


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error(monkeypatch):
    import jax

    class Device:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [Device("TPU v5 lite")])
    spec = prof.machine_spec()
    assert spec["machine_id"] == "tpu:tpu-v5-lite"
    # one v5e chip: 197 TFLOP/s bf16, 819 GB/s (Google Cloud, "TPU v5e")
    assert spec["peak_flops"] == 197e12
    assert spec["peak_bytes_per_s"] == 819e9

    monkeypatch.setattr(jax, "devices", lambda: [Device("TPU v9")])
    with pytest.raises(ValueError, match="TPU v9"):
        prof.machine_spec()


# -- op-class bucketing -------------------------------------------------------


def test_op_class_bucketing():
    # both HLO (hyphens) and StableHLO (underscores) spellings, fused
    # names, leading % and instance suffixes
    assert prof.op_class("dot.42") == "dot"
    assert prof.op_class("%dot_general.3") == "dot"
    assert prof.op_class("convolution.2") == "conv"
    assert prof.op_class("convolution_fusion") == "conv"
    assert prof.op_class("gather.4") == "gather"
    assert prof.op_class("dynamic-update-slice.8") == "gather"
    assert prof.op_class("dynamic_slice.1") == "gather"
    assert prof.op_class("reduce.7") == "reduce"
    assert prof.op_class("reduce_window.1") == "reduce"
    # collectives win over their substrings (all-REDUCE, reduce-SCATTER)
    assert prof.op_class("all-reduce.3") == "collective"
    assert prof.op_class("reduce-scatter.1") == "collective"
    assert prof.op_class("all_gather.9") == "collective"
    assert prof.op_class("collective-permute.1") == "collective"
    assert prof.op_class("infeed.6") == "infeed"
    assert prof.op_class("outfeed.1") == "infeed"
    assert prof.op_class("add_rsqrt_fusion.5") == "elementwise"
    assert prof.op_class("copy.1") == "elementwise"
    assert prof.op_class("convert_convert_fusion") == "elementwise"


# -- trace parsing (canned fixture) ------------------------------------------


def test_collect_trace_canned_fixture():
    collected = prof.collect_trace(CANNED)
    assert collected["source"] == "trace-json"
    assert len(collected["ops"]) == 9  # host events without hlo_op skip
    by_module = {}
    for module, _, s in collected["ops"]:
        by_module[module] = by_module.get(module, 0.0) + s
    assert by_module["jit_step"] == pytest.approx(4040e-6)
    assert by_module["jit_eval_step"] == pytest.approx(300e-6)
    classes = prof.class_seconds(
        [o for o in collected["ops"] if o[0] == "jit_step"])
    assert classes["dot"] == pytest.approx(1000e-6)
    assert classes["conv"] == pytest.approx(2000e-6)
    assert classes["collective"] == pytest.approx(500e-6)
    assert classes["gather"] == pytest.approx(290e-6)  # gather + dus
    assert classes["elementwise"] == pytest.approx(125e-6)
    assert classes["infeed"] == pytest.approx(75e-6)
    assert classes["reduce"] == pytest.approx(50e-6)


def test_attribute_trace_canned_fixture(monkeypatch):
    monkeypatch.setattr(prof, "_program_records", lambda: [])
    summary = prof.attribute_trace(CANNED)
    assert summary["source"] == "trace-json"
    assert summary["op_events"] == 9
    assert summary["device_seconds"] == pytest.approx(4340e-6)
    assert [m["module"] for m in summary["modules"]] == \
        ["jit_step", "jit_eval_step"]  # sorted by device time
    step = summary["modules"][0]
    assert step["classes"]["conv"] == pytest.approx(2000e-6)
    assert step["top_ops"][0]["op"] == "convolution.2"
    text = prof.render_attribution(summary)
    assert "jit_step" in text and "conv" in text


def test_trace_errors_are_clean(tmp_path):
    # empty dir: no capture at all
    with pytest.raises(prof.TraceError, match="no profiler capture"):
        prof.collect_trace(tmp_path)
    # malformed JSON
    bad = tmp_path / "host.trace.json"
    bad.write_text("{not json")
    with pytest.raises(prof.TraceError, match="unreadable trace file"):
        prof.collect_trace(tmp_path)
    # valid JSON without traceEvents
    bad.write_text(json.dumps({"foo": 1}))
    with pytest.raises(prof.TraceError, match="no traceEvents"):
        prof.collect_trace(tmp_path)
    # a trace with only host events: nothing to attribute
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "ts": 0, "dur": 5, "name": "PyCall", "args": {}}]}))
    with pytest.raises(prof.TraceError, match="no device op events"):
        prof.collect_trace(tmp_path)


# -- calibration budget -------------------------------------------------------


def _report(key="('train_step', 'm', ())", ratio=1.5, fp="abc",
            classes=None):
    classes = classes if classes is not None else {
        "dot": {"seconds": 0.006, "predicted_seconds": 0.004,
                "ratio": 1.5},
        "elementwise": {"seconds": 0.0001,
                        "predicted_seconds": 0.0001, "ratio": 1.0},
    }
    predicted = sum(c.get("predicted_seconds", 0.0)
                    for c in classes.values())
    measured = sum(c.get("seconds", 0.0) for c in classes.values())
    return {"key": key, "label": "t", "kind": "train_step",
            "fingerprint": fp, "repeats": 2, "source": "trace-json",
            "device_seconds": measured, "predicted_seconds": predicted,
            "ratio": ratio, "classes": classes,
            "flops": 10**9, "bytes": 10**8}


def _budget(ratio=1.5, fp="abc", classes=None):
    entry = {"ratio": ratio, "fingerprint": fp, "device_seconds": 0.006,
             "classes": classes or {"dot": {"ratio": 1.5}}}
    return prof.ProfBudget({
        "version": 1,
        "machines": {MACHINE: {"entries": {_report()["key"]: entry}}},
    }, path="prof-budget.json")


def test_budget_ratio_band_and_drift():
    b = _budget(ratio=1.5)
    assert b.check(_report(ratio=1.5), MACHINE) == []
    # multiplicative band [r/(1+tol), r*(1+tol)], tol=1.5 -> [0.6, 3.75]
    assert b.check(_report(ratio=3.7), MACHINE) == []
    drift = b.check(_report(ratio=4.0), MACHINE)
    assert [f.rule for f in drift] == ["prof-calibration"]
    assert "graftprof.py --update" in drift[0].message
    slow = _budget(ratio=1.5).check(_report(ratio=0.5), MACHINE)
    assert [f.rule for f in slow] == ["prof-calibration"]


def test_budget_unpinned_and_machine_scoping():
    b = _budget()
    unpinned = b.check(_report(key="('other', 'm', ())"), MACHINE)
    assert [f.rule for f in unpinned] == ["prof-unpinned"]
    # same program on a different machine: unpinned there, never gated
    # against this machine's ratio
    other = b.check(_report(ratio=99.0), "tpu:v4")
    assert [f.rule for f in other] == ["prof-unpinned"]


def test_budget_class_ratio_gates_only_visible_classes():
    classes = {
        "dot": {"seconds": 0.04, "predicted_seconds": 0.004,
                "ratio": 10.0},  # pinned 1.5, tol 3.0 -> band hi 6.0
        "elementwise": {"seconds": 0.01,
                        "predicted_seconds": 0.00001, "ratio": 1000.0},
    }
    b = _budget(ratio=1.5)
    rep = _report(ratio=1.5, classes=classes)
    findings = b.check(rep, MACHINE)
    msgs = [f.message for f in findings]
    # dot (>=5% of predicted step, pinned) gates; elementwise's wild
    # ratio is below the share floor and has no pin — silent
    assert len(findings) == 1 and "dot ratio 10.00" in msgs[0]


def test_budget_fingerprint_mismatch_is_note_not_finding():
    b = _budget(fp="abc")
    rep = _report(fp="DIFFERENT")
    assert b.check(rep, MACHINE) == []
    assert rep["stale_fingerprint"] is True
    text = prof.render_reports(prof.ProfReport(
        reports=[rep], machine={"machine_id": MACHINE}))
    assert "[stale fingerprint]" in text


def test_budget_stale_entries_and_version_gate(tmp_path):
    b = _budget()
    b.check(_report(), MACHINE)
    assert b.unused_entries(MACHINE) == []
    b2 = _budget()
    assert b2.unused_entries(MACHINE) == [_report()["key"]]
    with pytest.raises(ValueError, match="unsupported prof-budget"):
        prof.ProfBudget({"version": 99, "machines": {}})


def test_budget_pin_roundtrip_preserves_other_machines(tmp_path):
    b = _budget()
    rep = _report(ratio=2.0, fp="new")
    data = b.pinned_data([rep], "tpu:v4")
    path = tmp_path / "prof-budget.json"
    path.write_text(json.dumps(data))
    b2 = prof.ProfBudget.load(path)
    # the old machine's pin survived, the new machine got pinned
    assert b2.check(_report(), MACHINE) == []
    assert b2.check(_report(ratio=2.0, fp="new"), "tpu:v4") == []
    entry = b2.entries_for("tpu:v4")[rep["key"]]
    assert entry["ratio"] == 2.0 and entry["fingerprint"] == "new"


# -- real segmented capture (toy program) ------------------------------------


@pytest.fixture(scope="module")
def toy_audit(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_meets_dicl_tpu import compile as programs

    def toy_prof_step(x, w):
        y = jnp.tanh(x @ w)
        return jnp.sum(y * y)

    key = programs.ProgramKey(
        kind="toy_prof_step", model="toy",
        flags=programs.flag_items(shape=(192, 192)))
    p = programs.register_step("toy_prof_step", jax.jit(toy_prof_step),
                               key=key)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(192, 192), jnp.float32)
    w = jnp.asarray(rng.rand(192, 192), jnp.float32)
    out_dir = tmp_path_factory.mktemp("graftprof-capture")
    rep = prof.audit_profiles(entries=[(p, (x, w), {})],
                              out_dir=out_dir, repeats=2)
    return rep, out_dir


def test_toy_capture_produces_calibration_row(toy_audit):
    rep, _ = toy_audit
    assert rep.ok and len(rep.reports) == 1
    r = rep.reports[0]
    assert r["kind"] == "toy_prof_step"
    assert r["device_seconds"] > 0
    assert r["predicted_seconds"] > 0
    assert r["ratio"] > 0
    assert r["achieved_flops"] > 0
    # the matmul dominates and must be attributed to the dot class
    assert r["classes"]["dot"]["seconds"] > 0
    assert r["fingerprint"] and len(r["fingerprint"]) == 64
    assert rep.machine["machine_id"].startswith("cpu:")


def test_toy_capture_segment_manifest_and_pin_roundtrip(toy_audit,
                                                        tmp_path):
    rep, out_dir = toy_audit
    manifest = json.loads((out_dir / prof.MANIFEST_NAME).read_text())
    assert manifest["segments"][0]["key"] == rep.reports[0]["key"]
    # re-attribute the kept capture from disk: identical measurement
    reports = prof.attribute_segments(out_dir)
    assert reports[0]["device_seconds"] == \
        rep.reports[0]["device_seconds"]
    # pin this machine, re-check the same run: green, no stale entries
    mid = rep.machine["machine_id"]
    b = prof.ProfBudget(
        prof.ProfBudget.empty().pinned_data(rep.reports, mid))
    b.path = "x"
    assert b.check(rep.reports[0], mid) == []
    assert b.unused_entries(mid) == []


# -- telemetry / report / metrics round-trip ---------------------------------


def _prof_report(drift=False):
    from raft_meets_dicl_tpu.analysis.lint import Finding

    rep = prof.ProfReport(reports=[_report()],
                          machine={"machine_id": MACHINE,
                                   "n_devices": 1,
                                   "peak_flops": 1e11,
                                   "peak_bytes_per_s": 2e10})
    if drift:
        rep.findings.append(Finding(
            rule="prof-calibration", path="analysis/profile", line=1,
            message=f"{_report()['key']}: measured/predicted ratio "
                    f"4.00 vs pinned 1.50"))
    return rep


def test_profile_events_flow_into_telemetry_report():
    rep = _prof_report(drift=True)
    tele = telemetry.Telemetry()          # in-memory sink
    prof.emit_events(rep, tele)
    from raft_meets_dicl_tpu.telemetry import report as trep

    stats = trep.prof_stats(tele.events)
    assert len(stats["programs"]) == 1
    assert len(stats["drifted"]) == 1
    text = trep.render(tele.events)
    assert "== profiling" in text
    assert _report()["key"][:72] in text
    assert "[drift]" in text
    flags = trep.find_anomalies(tele.events)
    assert any("calibration drift" in f for f in flags)


def test_profile_events_clean_run_has_no_anomaly():
    tele = telemetry.Telemetry()
    prof.emit_events(_prof_report(drift=False), tele)
    from raft_meets_dicl_tpu.telemetry import report as trep

    assert not any("calibration drift" in f
                   for f in trep.find_anomalies(tele.events))


def test_publish_metrics_roundtrip():
    reg = metrics_mod.MetricsRegistry()
    prof.publish_metrics(_prof_report(), reg)
    parsed = metrics_mod.parse_text(reg.render())
    sec = parsed["rmd_prof_device_seconds"]
    assert sec[tuple(sorted([("program", "train_step")]))] == \
        pytest.approx(0.0061)
    ratio = parsed["rmd_prof_calibration_ratio"]
    assert ratio[tuple(sorted([("program", "train_step")]))] == 1.5
    cls = parsed["rmd_prof_class_seconds"]
    assert cls[tuple(sorted([("klass", "dot")]))] == \
        pytest.approx(0.006)


def _canned_record():
    """An ``owners`` record for the canned capture's ``jit_step``: every
    operation but the last reduce has an owner."""
    return {"module": "jit_step", "owners": {
        "encoders": {"encoders": {"fwd": ["convolution.2:f32[8,8]"]}},
        "lookup": {"lookup": {"fwd": ["dot.1:f32[8,8]",
                                      "gather.4:f32[8,8]"],
                              "bwd": ["dynamic-update-slice.8:f32[8,8]"]}},
        "update": {"update": {"fwd": ["add_rsqrt_fusion.5:f32[8]"]}},
        "optimizer": {"optimizer": {"fwd": ["all-reduce.3:f32[8,8]"]}},
        "input": {"input": {"fwd": ["infeed.6:f32[8]"]}},
    }, "inferred_keys": []}


def test_attribute_trace_names_program_and_phases_from_the_record(
        monkeypatch):
    ops = {o for m, o, _ in prof.collect_trace(CANNED)["ops"]
           if m == "jit_step"}
    rec = _canned_record()
    from raft_meets_dicl_tpu.compile import owners
    assert {k.split(":")[0] for k in owners.flat(rec)} < ops
    other = {"module": "jit_step", "owners": {"up8": {"up8": {"fwd": [
        "convolution.2:f32[8,8]"]}}}, "inferred_keys": []}
    monkeypatch.setattr(prof, "_program_records", lambda: [
        ("('eval_step', 'm', ())", other), ("('train_step', 'm', ())", rec)])
    summary = prof.attribute_trace(CANNED)
    step, evals = summary["modules"]
    # the record that covers the module's operations names its program;
    # one that covers half of them, or another module's, names nothing
    assert step["program"] == "('train_step', 'm', ())"
    assert step["covered"] == pytest.approx(1 - 50 / 4040, abs=1e-3)
    assert step["phases"]["encoders"] == pytest.approx(2000e-6)
    assert step["phases"]["lookup"] == pytest.approx(1290e-6)
    assert step["phases"]["unowned"] == pytest.approx(50e-6)
    assert sum(step["phases"].values()) == pytest.approx(step["seconds"])
    assert list(step["phases"])[:2] == ["input", "encoders"]
    assert (evals["program"], evals["phases"]) == (None, {})
    assert "candidates" not in step
    text = prof.render_attribution(summary)
    assert "train_step" in text and "phases: input" in text


def test_attribute_trace_reads_the_live_registrys_records():
    # what Program._emit keeps is what attribute_trace finds
    from raft_meets_dicl_tpu import compile as programs

    programs.reset()
    key = programs.ProgramKey("train_step", "canned")
    prog = programs.register_step("train_step", lambda x: x, key=key)
    prog.owners[()] = _canned_record()
    try:
        assert prof._program_records() == [(key.canonical(),
                                            _canned_record())]
        step = prof.attribute_trace(CANNED)["modules"][0]
        assert step["program"] == key.canonical()
        assert step["phases"]["update"] == pytest.approx(125e-6)
    finally:
        programs.reset()


def test_publish_attribution_metrics_roundtrip(monkeypatch):
    # pin the registry's records empty: earlier test files may have left
    # a live program whose record would relabel the jit_step row
    monkeypatch.setattr(prof, "_program_records", lambda: [])
    reg = metrics_mod.MetricsRegistry()
    summary = prof.attribute_trace(CANNED)
    prof.publish_attribution_metrics(summary, reg)
    parsed = metrics_mod.parse_text(reg.render())
    sec = parsed["rmd_prof_device_seconds"]
    assert sec[tuple(sorted([("program", "jit_step")]))] == \
        pytest.approx(4040e-6)
    cls = parsed["rmd_prof_class_seconds"]
    assert cls[tuple(sorted([("klass", "conv")]))] == \
        pytest.approx(2000e-6)


def test_profile_event_kind_is_registered():
    from raft_meets_dicl_tpu.telemetry.core import SCHEMA

    assert "profile" in SCHEMA
    assert SCHEMA["profile"] == {"program", "seconds"}


# -- /profilez retention + inline attribution --------------------------------


def test_evict_captures_bounded_retention(tmp_path):
    import os
    import time as time_mod

    dirs = []
    for i in range(5):
        d = tmp_path / f"rmd-profilez-{i}"
        d.mkdir()
        ts = time_mod.time() - (5 - i) * 60
        os.utime(d, (ts, ts))
        dirs.append(d)
    evicted = sidecar.evict_captures(keep=2, tmp_root=str(tmp_path))
    assert sorted(evicted) == sorted(str(d) for d in dirs[:3])
    assert sorted(p.name for p in tmp_path.iterdir()) == \
        ["rmd-profilez-3", "rmd-profilez-4"]
    # keep is floored at 1: a zero knob never deletes the capture the
    # caller is about to return
    sidecar.evict_captures(keep=0, tmp_root=str(tmp_path))
    assert [p.name for p in tmp_path.iterdir()] == ["rmd-profilez-4"]


def test_capture_profile_attribution_and_eviction(monkeypatch, tmp_path):
    import threading

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    canned = {"source": "trace-json", "device_seconds": 0.004,
              "op_events": 9, "modules": [
                  {"module": "jit_step", "program": None,
                   "seconds": 0.004, "classes": {"conv": 0.002},
                   "phases": {}, "covered": 0.0, "top_ops": []}]}
    monkeypatch.setattr(prof, "attribute_trace", lambda d: canned)
    reg = metrics_mod.MetricsRegistry()
    payload = sidecar.capture_profile(threading.Lock(), 0.1,
                                      registry=reg)
    assert payload["dir"].startswith(str(tmp_path))
    assert payload["attribution"] is canned
    parsed = metrics_mod.parse_text(reg.render())
    assert parsed["rmd_prof_device_seconds"][
        tuple(sorted([("program", "jit_step")]))] == \
        pytest.approx(0.004)
    # the capture dir itself survives the eviction pass
    assert Path(payload["dir"]).is_dir()


def test_capture_profile_attribution_failure_is_advisory(monkeypatch,
                                                         tmp_path):
    import threading

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))

    def boom(d):
        raise prof.TraceError("nothing executed")

    monkeypatch.setattr(prof, "attribute_trace", boom)
    payload = sidecar.capture_profile(threading.Lock(), 0.1)
    assert "attribution" not in payload
    assert "nothing executed" in payload["attribution_error"]
    assert Path(payload["dir"]).is_dir()


def test_capture_profile_attribution_knob_off(monkeypatch, tmp_path):
    import threading

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    monkeypatch.setenv("RMD_PROFILE_ATTRIBUTION", "0")
    called = []
    monkeypatch.setattr(prof, "attribute_trace",
                        lambda d: called.append(d))
    payload = sidecar.capture_profile(threading.Lock(), 0.1)
    assert "attribution" not in payload and not called


# -- CLI contract -------------------------------------------------------------


def _cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graftprof_cli", REPO / "scripts" / "graftprof.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_graftprof_cli_json_schema():
    mod = _cli()
    payload = mod.json_report(_prof_report())
    assert payload["schema"] == 1
    assert payload["ok"] is True and payload["exit_code"] == 0
    json.dumps(payload)
    bad = mod.json_report(_prof_report(drift=True))
    assert bad["ok"] is False and bad["exit_code"] == 1


def test_graftprof_cli_trace_dir_mode(capsys, tmp_path):
    mod = _cli()
    assert mod.main(["--trace-dir", str(CANNED)]) == 0
    out = capsys.readouterr().out
    assert "jit_step" in out and "device op time" in out
    assert mod.main(["--trace-dir", str(CANNED),
                     "--format", "json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["schema"] == 1 and payload["op_events"] == 9
    # an unusable dir is a usage error (exit 2), not a traceback
    assert mod.main(["--trace-dir", str(tmp_path)]) == 2
    assert "no profiler capture" in capsys.readouterr().err
