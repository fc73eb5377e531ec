"""Telemetry subsystem: schema + sink semantics, report rendering and
anomaly flags, compile attribution, the training-loop integration (CPU
smoke train emitting a schema-valid events.jsonl), and the satellite
fixes riding with it (raft/fs legacy checkpoint remap, per-chip volume
budget)."""

import json

import numpy as np
import pytest

from raft_meets_dicl_tpu import telemetry
from raft_meets_dicl_tpu.telemetry import report


def _base(kind, **fields):
    return {"v": telemetry.SCHEMA_VERSION, "t": 0.0, "kind": kind, **fields}


# -- schema / sink --------------------------------------------------------


def test_validate_event_accepts_all_kinds():
    ok = [
        _base("run_start", dir="/tmp/run"),
        _base("run_end"),
        _base("stage_start", stage=0, step=0),
        _base("stage_end", stage=0, step=10),
        _base("epoch_start", stage=0, epoch=0, step=0),
        _base("epoch_end", stage=0, epoch=0, step=10),
        _base("step", step=1, phases={"dispatch": 0.1}, step_time=0.2,
              throughput_ema=5.0),
        _base("device_sync", step=1, seconds=0.01),
        _base("compile", label="train_step", seconds=3.5),
        _base("cache", event="hit"),
        _base("memory", host_rss_gib=1.5, live_arrays=10),
        _base("nonfinite", step=7),
        _base("checkpoint", path="x.ckpt", step=5, seconds=0.4),
    ]
    for ev in ok:
        telemetry.validate_event(ev)


def test_validate_event_rejects_malformed():
    with pytest.raises(ValueError):
        telemetry.validate_event(_base("step", step=1))  # missing fields
    with pytest.raises(ValueError):
        telemetry.validate_event(_base("no-such-kind"))
    with pytest.raises(ValueError):
        telemetry.validate_event({"t": 0.0, "kind": "run_end"})  # no version
    with pytest.raises(ValueError):
        telemetry.validate_event(
            _base("step", step=1, phases={"a": "fast"}, step_time=0.1,
                  throughput_ema=1.0))  # non-numeric phase
    with pytest.raises(ValueError):
        telemetry.validate_event(_base("cache", event="maybe"))


def test_sink_writes_schema_valid_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    sink = telemetry.Telemetry(path)

    sink.emit("stage_start", stage=0, step=0)
    with sink.span("dispatch"):
        pass
    sink.add_phase("data_wait", 0.025)
    ev = sink.step_event(0, stage=0, epoch=0)
    assert ev["phases"]["data_wait"] == pytest.approx(0.025)
    sink.emit("epoch_end", stage=0, epoch=0, step=1)
    sink.close()

    events, errors = report.load_events(path)
    assert not errors
    assert [e["kind"] for e in events] == ["stage_start", "step", "epoch_end"]
    # phases drained into the step event
    assert set(events[1]["phases"]) == {"dispatch", "data_wait"}


def test_step_event_throughput_ema():
    sink = telemetry.Telemetry()  # memory-only
    for i in range(3):
        sink.add_phase("dispatch", 0.01)
        sink.step_event(i)
    assert len(sink.events) == 3
    assert all(e["throughput_ema"] > 0 for e in sink.events)
    # phases reset between steps
    assert sink.events[-1]["phases"] == {"dispatch": 0.01}


def test_step_counters_drain_and_render():
    """add_count accumulates per-step scalars (wire_bytes: the
    host→device transfer volume) into the next step event; the report
    aggregates and renders them."""
    sink = telemetry.Telemetry()  # memory-only
    sink.add_count("wire_bytes", 2 ** 20)
    sink.add_count("wire_bytes", 2 ** 20)  # two puts, one step (prefetch)
    ev = sink.step_event(0)
    assert ev["counters"] == {"wire_bytes": 2 ** 21}
    telemetry.validate_event(ev)
    # counters reset between steps; a counter-less step omits the field
    ev2 = sink.step_event(1)
    assert "counters" not in ev2

    stats = report.counter_stats(sink.events)
    assert stats["wire_bytes"]["total"] == 2 ** 21
    assert stats["wire_bytes"]["mean"] == 2 ** 20  # over BOTH steps
    text = report.render(sink.events)
    assert "wire_bytes" in text and "MiB/step" in text

    with pytest.raises(ValueError):
        telemetry.validate_event(
            _base("step", step=1, phases={}, step_time=0.1,
                  throughput_ema=1.0, counters={"wire_bytes": "big"}))


def test_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("RMD_TELEMETRY", "0")
    assert not telemetry.enabled()

    sink = telemetry.create(tmp_path / "events.jsonl")
    assert isinstance(sink, telemetry.NullTelemetry)
    with sink.span("dispatch"):
        pass
    sink.add_phase("x", 1.0)
    sink.step_event(0)
    sink.emit("nonfinite", step=0)
    sink.close()
    assert not (tmp_path / "events.jsonl").exists()

    monkeypatch.delenv("RMD_TELEMETRY")
    assert telemetry.enabled()


def test_memory_snapshot_fields():
    snap = telemetry.memory_snapshot()
    assert snap["host_rss_gib"] > 0
    assert isinstance(snap["live_arrays"], int)


def test_memory_snapshot_reports_the_fullest_device_with_its_temporaries(
        monkeypatch):
    """Every local device is read, and a program's reserved temporaries
    count (the v5e's ``*_reserved``, which ``*_in_use`` leaves out)."""
    import jax

    gib = 2 ** 30

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    monkeypatch.setattr(jax, "local_devices", lambda: [
        Device({"bytes_in_use": gib, "peak_bytes_in_use": 2 * gib,
                "bytes_reserved": gib, "peak_bytes_reserved": 4 * gib}),
        Device({"bytes_in_use": 3 * gib, "peak_bytes_in_use": 3 * gib}),
        Device(None),
    ])
    snap = telemetry.memory_snapshot()
    assert snap["device_peak_gib"] == 6.0
    assert snap["device_bytes_gib"] == 3.0


# -- report ---------------------------------------------------------------


def _synth_events():
    evs = [_base("run_start", dir="/tmp/r"),
           _base("stage_start", stage=0, step=0)]
    for i in range(10):
        wall = 0.1 if i != 7 else 0.5  # spike at step 7
        evs.append(_base(
            "step", step=i, stage=0,
            phases={"dispatch": wall * 0.8, "data_wait": wall * 0.1},
            step_time=wall, throughput_ema=1.0 / wall))
    evs.append(_base("compile", label="train_step", seconds=2.0))  # recompile
    evs.append(_base("device_sync", step=9, seconds=0.001, steps=10,
                     wall=1.0))
    evs.append(_base("memory", host_rss_gib=2.0, live_arrays=42,
                     device_peak_gib=7.5))
    evs.append(_base("nonfinite", step=9, stage=0))
    evs.append(_base("stage_end", stage=0, step=10))
    return evs


def test_phase_stats_and_device_time():
    evs = _synth_events()
    stats = report.phase_stats(evs)
    assert stats["dispatch"]["share"] == pytest.approx(0.8, abs=0.01)
    assert stats["step"]["max"] == pytest.approx(0.5)
    assert stats["other"]["share"] == pytest.approx(0.1, abs=0.01)

    dev = report.device_step_time(evs)
    assert dev["steps_covered"] == 10
    assert dev["mean_step"] == pytest.approx(0.1)


def test_report_flags_anomalies_and_renders():
    evs = _synth_events()
    flags = report.find_anomalies(evs)
    assert any("spike" in f and "step 7" in f for f in flags)
    assert any("recompile" in f for f in flags)
    assert any("non-finite" in f for f in flags)

    text = report.render(evs)
    assert "step phase breakdown" in text
    assert "dispatch" in text
    assert "train_step" in text
    assert "device peak 7.50 GiB" in text
    assert "anomalies (" in text


def test_report_clean_run_no_flags():
    evs = [_base("stage_start", stage=0, step=0),
           _base("compile", label="train_step", seconds=1.0)]
    evs += [_base("step", step=i, stage=0, phases={"dispatch": 0.1},
                  step_time=0.1, throughput_ema=10.0) for i in range(8)]
    assert report.find_anomalies(evs) == []
    assert "anomalies: none" in report.render(evs)


def test_load_events_reports_bad_lines(tmp_path):
    path = tmp_path / "events.jsonl"
    good = json.dumps(_base("run_end"))
    path.write_text(good + "\nnot json\n"
                    + json.dumps({"v": 99, "t": 0, "kind": "run_end"}) + "\n")
    events, errors = report.load_events(path)
    assert len(events) == 1
    assert len(errors) == 2
    assert "schema errors: 2" in report.render(events, errors)


# -- compile attribution --------------------------------------------------


def test_instrument_jit_attributes_compiles():
    import jax
    import jax.numpy as jnp

    sink = telemetry.activate(telemetry.Telemetry())
    try:
        fn = telemetry.instrument_jit(
            "probe_fn", jax.jit(lambda x: x * 3 + 1))
        x = jnp.arange(7.0)  # unique shape to force a fresh compile
        np.testing.assert_allclose(np.asarray(fn(x)), np.arange(7.0) * 3 + 1)
        compiles = [e for e in sink.events if e["kind"] == "compile"]
        assert any(e["label"] == "probe_fn" for e in compiles)

        n = len(sink.events)
        fn(x)  # cached: no new compile events
        assert len([e for e in sink.events[n:]
                    if e["kind"] == "compile"]) == 0
    finally:
        telemetry.deactivate()


# -- training-loop integration (CPU smoke train) --------------------------


def test_smoke_train_emits_schema_valid_events(tmp_path, monkeypatch):
    """A tiny CPU train run must produce a validating events.jsonl with
    step phases, compile attribution, boundaries, a checkpoint event, and
    a device-sync sample — and the report must render from it."""
    from test_strategy import _make_context, _make_stage

    monkeypatch.setenv("RMD_FINITE_CHECK_EVERY", "1")

    # cold program registry: the compiled-program registry dedupes the
    # train step by its stable (model, stage-config) key, so a previous
    # test's identical context would hand this run an already-compiled
    # program — and the compile-attribution assertion below needs to see
    # the compile happen
    from raft_meets_dicl_tpu import compile as programs

    programs.reset()

    sink = telemetry.activate(telemetry.create(tmp_path / "events.jsonl"))
    try:
        ctx, mgr = _make_context(tmp_path, [_make_stage(epochs=1)])
        ctx.run()
        assert ctx.step == 2
        mgr.create(ctx.log, ctx, ctx.current_stage, epoch=0, step=ctx.step,
                   metrics={"loss": 1.0})
        # checkpoint writes (and their telemetry event) run on the
        # background writer; join before closing the sink
        mgr.checkpoints[-1].wait()
    finally:
        telemetry.deactivate()

    events, errors = report.load_events(tmp_path / "events.jsonl")
    assert not errors, errors[:3]

    kinds = [e["kind"] for e in events]
    assert kinds.count("stage_start") == 1
    assert kinds.count("stage_end") == 1
    assert kinds.count("epoch_start") == 1
    assert kinds.count("epoch_end") == 1
    assert kinds.count("step") == 2
    assert "memory" in kinds
    assert "device_sync" in kinds
    assert "checkpoint" in kinds

    steps = [e for e in events if e["kind"] == "step"]
    for ev in steps:
        assert {"dispatch", "host"} <= set(ev["phases"])
        assert ev["stage"] == 0
    # the prefetch pipeline phases land on at least one step
    all_phases = set().union(*(e["phases"] for e in steps))
    assert {"data_wait", "device_put"} <= all_phases

    compiles = [e for e in events if e["kind"] == "compile"]
    assert any(e["label"] == "train_step" for e in compiles)

    # async checkpoint save: the event splits the loop stall (snapshot)
    # from the background serialize+write milliseconds
    chk = [e for e in events if e["kind"] == "checkpoint"][-1]
    assert chk["blocking_ms"] >= 0.0
    assert chk["background_ms"] > 0.0
    assert chk["seconds"] == pytest.approx(
        (chk["blocking_ms"] + chk["background_ms"]) / 1e3, abs=1e-3)

    text = report.render(events)
    assert "step phase breakdown" in text
    assert "train_step" in text


def test_training_disabled_telemetry_runs_clean(tmp_path, monkeypatch):
    """RMD_TELEMETRY=0 keeps the loop on null-sink no-ops end to end."""
    from test_strategy import _make_context, _make_stage

    monkeypatch.setenv("RMD_TELEMETRY", "0")
    sink = telemetry.activate(telemetry.create(tmp_path / "events.jsonl"))
    try:
        ctx, _ = _make_context(tmp_path, [_make_stage(epochs=1)])
        ctx.run()
        assert ctx.step == 2
    finally:
        telemetry.deactivate()
    assert not (tmp_path / "events.jsonl").exists()


# -- satellite: raft/fs legacy checkpoint remap ---------------------------


TINY_FS_MODEL = {
    "name": "tiny-fs", "id": "tiny-fs",
    "model": {
        "type": "raft/fs",
        "parameters": {"corr-levels": 2, "corr-radius": 2,
                       "corr-channels": 32, "context-channels": 16,
                       "recurrent-channels": 16},
        "arguments": {"iterations": 2},
    },
    "loss": {"type": "raft/sequence"},
    "input": None,
}


def test_legacy_fs_checkpoint_remaps_up8(tmp_path):
    """Pre-round-5 raft/fs checkpoints stored Up8Network under the scan
    body (_FsStep_0); loading one against the hoisted layout must restore
    the weights into top-level Up8Network_0."""
    import jax
    from flax import serialization

    import raft_meets_dicl_tpu.models as models
    from raft_meets_dicl_tpu import strategy

    spec = models.load(TINY_FS_MODEL)
    rng = jax.random.PRNGKey(0)
    img = np.zeros((1, 32, 48, 3), np.float32)
    variables = spec.model.init(rng, img, img, iterations=1)

    sd = serialization.to_state_dict(
        jax.tree.map(np.asarray, variables))
    assert "Up8Network_0" in sd["params"], "hoisted layout changed?"

    # fabricate the legacy layout: Up8Network params inside the scan body
    body = "ScanCheckpoint_FsStep_0"
    legacy = {"params": dict(sd["params"])}
    legacy["params"][body] = dict(legacy["params"][body])
    legacy["params"][body]["Up8Network_0"] = \
        legacy["params"].pop("Up8Network_0")
    legacy |= {k: v for k, v in sd.items() if k != "params"}

    chkpt = strategy.Checkpoint(
        model="tiny-fs",
        iteration=strategy.checkpoint.Iteration(0, 0, 0),
        metrics=None,
        state=strategy.checkpoint.State(
            model=legacy, optimizer={}, scaler={},
            lr_sched_inst=[], lr_sched_epoch=[],
        ),
        metadata={},
    )
    path = tmp_path / "legacy.ckpt"
    chkpt.save(path)

    # fresh init with a different seed: restore must overwrite it
    variables2 = spec.model.init(jax.random.PRNGKey(1), img, img,
                                 iterations=1)
    restored, _, _ = strategy.Checkpoint.load(path).apply(
        variables=variables2)

    want = jax.tree.leaves(variables)
    got = jax.tree.leaves(restored)
    assert len(want) == len(got)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


# -- satellite: per-chip volume budget under SPMD -------------------------


def test_volume_level_split_is_per_chip():
    from raft_meets_dicl_tpu.models.impls.raft_fs import volume_level_split
    from raft_meets_dicl_tpu.parallel.mesh import set_data_axis_size

    # one level of 0.5 GiB (global): 2x charge exceeds a 0.6 GiB budget
    # unsharded, but fits once the batch is split over 8 chips
    shape, levels, itemsize = (8, 64, 64), 1, 4
    assert volume_level_split(shape, levels, itemsize, budget_gib=0.6) == 1
    set_data_axis_size(8)
    try:
        assert volume_level_split(shape, levels, itemsize,
                                  budget_gib=0.6) == 0
    finally:
        set_data_axis_size(1)
