"""Telemetry subsystem: schema + sink semantics, report rendering and
anomaly flags, compile attribution, the training-loop integration (CPU
smoke train emitting a schema-valid events.jsonl), and the satellite
fixes riding with it (raft/fs legacy checkpoint remap, per-chip volume
budget)."""

import dataclasses
import json
import os
import time

import numpy as np
import pytest

from raft_meets_dicl_tpu import telemetry
from raft_meets_dicl_tpu.telemetry import core, report, steptrace, witness


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
    ev = sink.step_event(0, phases={"dispatch": 0.001, "data_wait": 0.025},
                         stage=0, epoch=0)
    assert ev["phases"]["data_wait"] == pytest.approx(0.025)
    sink.emit("epoch_end", stage=0, epoch=0, step=1)
    sink.close()

    events, errors = report.load_events(path)
    assert not errors
    assert [e["kind"] for e in events] == ["stage_start", "step", "epoch_end"]
    # the caller's phases ride in the step event
    assert set(events[1]["phases"]) == {"dispatch", "data_wait"}


def test_step_event_throughput_ema():
    sink = telemetry.Telemetry()  # memory-only
    for i in range(3):
        sink.step_event(i, phases={"dispatch": 0.01})
    assert len(sink.events) == 3
    assert all(e["throughput_ema"] > 0 for e in sink.events)
    # each step's phases are its own
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
    sink.clock()
    sink.add_count("x", 1)
    sink.step_event(0, phases={"dispatch": 0.1})
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


def test_report_renders_the_timeline_when_a_file_has_one():
    """``clock``/``span`` events and ``marks`` are optional: a file from
    before schema 1.7 renders as it did, a new one gains a section."""
    old = [_base("step", step=i, phases={"dispatch": 0.01}, step_time=0.1,
                 throughput_ema=10.0) for i in range(3)]
    assert report.timeline_stats(old) is None
    assert "== timeline ==" not in report.render(old)

    new = old + [
        _base("clock", perf_counter=100.0, time_ns=1_000_000_000_000),
        _base("clock", perf_counter=200.0, time_ns=1_100_000_050_000),
        _base("span", name="boot", t0=90.0, t1=100.0),
        _base("span", name="gc", t0=150.0, t1=150.25, generation=2),
        _base("span", name="gc", t0=160.0, t1=160.05, generation=1),
        _base("step", step=3, phases={"dispatch": 0.01}, step_time=0.1,
              throughput_ema=10.0, marks={"start": 1.0, "done": 1.1}),
        _base("trace", event="batch", marks={
            "wait": 1.0, "dispatch": 1.2, "assembled": 1.21, "called": 1.22,
            "ready": 1.45, "fetched": 1.46, "completed": 1.48}),
    ]
    stats = report.timeline_stats(new)
    assert stats["clocks"] == 2 and stats["drift_us"] == pytest.approx(50.0)
    assert stats["spans"]["gc"] == {"count": 2, "total": pytest.approx(0.3),
                                    "max": pytest.approx(0.25)}
    assert stats["marked_steps"] == 1
    assert stats["batch_legs_s"]["called->ready"] == pytest.approx(0.23)
    text = report.render(new)
    assert "== timeline ==" in text and "span gc" in text
    assert "called->ready 230.00 ms" in text


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
        # the phases are the step trace's, computed from the marks the
        # event carries (test_step_marks_phases_and_put pins the sums)
        assert set(ev["phases"]) == set(steptrace.PHASES)
        assert set(ev["marks"]) == set(steptrace.MARKS)
        assert ev["stage"] == 0
    # one clock: at activate() and at the stage's start; set-up has spans
    clocks = [e for e in events if e["kind"] == "clock"]
    assert len(clocks) >= 2
    names = [e["name"] for e in events if e["kind"] == "span"]
    for name in ("data", "state", "step_build", "prepare"):
        assert names.count(name) == 1, names

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


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One run of the tiny loop (an epoch of two steps) with the sink on:
    ``(sink, ctx)``."""
    from test_strategy import _make_context, _make_stage

    sink = telemetry.activate(telemetry.Telemetry())
    try:
        ctx, _ = _make_context(tmp_path_factory.mktemp("tiny_run"),
                               [_make_stage(epochs=1)])
        ctx.run()
    finally:
        telemetry.deactivate()
    return sink, ctx


def test_step_marks_phases_and_put(tiny_run):
    """The step event carries the loop thread's marks, absolute and in
    order; its phases are differences of those marks and telescope to the
    step's total; ``put`` is the interval of this step's own batch, ahead
    of the pull's return on the worker's thread."""
    sink, ctx = tiny_run
    steps = [e for e in sink.events if e["kind"] == "step"]
    assert [e["step"] for e in steps] == [0, 1]
    for ev in steps:
        telemetry.validate_event(ev)
        marks = [ev["marks"][m] for m in steptrace.MARKS]
        assert marks == sorted(marks)
        total = ev["marks"]["done"] - ev["marks"]["start"]
        p0, p1 = ev["put"]
        assert p0 <= p1
        assert {"data_wait", "device_put", "dispatch"} <= set(ev["phases"])
        assert ev["phases"]["device_put"] == pytest.approx(p1 - p0, abs=5e-6)
        # the worker's put lies outside the step: the one phase that is
        # not part of the sum
        on_loop = sum(ev["phases"].values()) - ev["phases"]["device_put"]
        assert on_loop == pytest.approx(total, abs=1e-5)
    # each step got its own batch's fetch: the mean seconds a worker spent
    # on one of its samples; ``cpu`` is the process's CPU clock at ``start``
    for ev in steps:
        assert 0.0 < ev["fetch"] < 60.0
    assert steps[0]["cpu"] < steps[1]["cpu"]
    wall = steps[1]["marks"]["start"] - steps[0]["marks"]["start"]
    assert steps[1]["cpu"] - steps[0]["cpu"] <= wall * len(
        os.sched_getaffinity(0)) + 0.05
    # the window events come from the same records
    assert any(e["kind"] == "steptrace" for e in sink.events) or \
        ctx.steptraces.snapshot()["count"] == 2
    # and no second set of timers: nothing else feeds step phases
    assert not hasattr(sink, "span") and not hasattr(sink, "add_phase")


def test_a_steps_pull_and_put_lie_one_after_the_other_before_its_data_mark(
        tiny_run):
    """The feed is one worker that does one thing after the other, read
    from the loop's own events: a step's batch was pulled, then put, and
    both ended before the loop's ``data`` mark took the batch off the
    queue; the next step's pull began after this step's put had ended.
    ``pull_ms`` + ``put_ms`` is therefore the worker's period."""
    sink, _ = tiny_run
    steps = [e for e in sink.events if e["kind"] == "step"]
    assert len(steps) == 2
    for ev in steps:
        (t0, t1), (p0, p1) = ev["pull"], ev["put"]
        assert t0 <= t1 <= p0 <= p1 <= ev["marks"]["data"]
    # how far the worker runs ahead of the loop is the scheduler's to say
    # (under six test workers the loop thread may finish step one before
    # batch two is staged): only the order the construction guarantees
    first, second = steps
    assert first["put"][1] <= second["pull"][0]


def test_put_rides_with_its_own_batch():
    """Under prefetch depth 2 the worker runs ahead of the consumer; the
    put interval that comes out with batch i is the one taken around
    batch i's put, not the newest one."""
    import time

    from raft_meets_dicl_tpu.strategy.training import _device_prefetch

    seen = {}

    def put(batch):
        seen[int(batch[0][0])] = time.perf_counter()
        time.sleep(0.002)
        return batch

    items = [(np.full((1,), i), None, None, None, [i]) for i in range(5)]
    stream = _device_prefetch(iter(items), put, depth=2)
    first = next(stream)
    time.sleep(0.05)          # the worker fills the queue meanwhile
    for host, _dev, meta, (t0, t1), _pull in [first, *stream]:
        i = meta[0]
        assert t0 <= seen[i] <= t1
        assert all(not (t0 <= seen[j] <= t1) for j in seen if j != i)


class _SlowSource:
    """Four one-sample batches; every access takes ``seconds``."""

    def __init__(self, seconds, fail_first=()):
        self.seconds, self.failing = seconds, set(fail_first)

    def __len__(self):
        return 4

    def __getitem__(self, index):
        from raft_meets_dicl_tpu.data.collection import (Metadata, SampleArgs,
                                                         SampleId)

        time.sleep(self.seconds)
        if index in self.failing:
            self.failing.discard(index)
            raise IOError(f"sample {index}")
        img = np.full((1, 4, 4, 3), index, np.float32)
        meta = Metadata(True, "slow", SampleId(f"s{index}", SampleArgs(),
                                               SampleArgs()), ((0, 4), (0, 4)))
        return img, img, np.zeros((1, 4, 4, 2), np.float32), \
            np.ones((1, 4, 4), bool), [meta]


@pytest.mark.parametrize("workers", [0, 2], ids=["inline", "pool"])
def test_loader_stamps_each_samples_fetch_seconds(workers):
    """``Loader._fetch`` times ``source[index]`` and writes the seconds on
    the sample's metadata, so they reach the step with the batch; a retry
    is stamped with the access that succeeded."""
    from raft_meets_dicl_tpu.models.input import Loader

    loader = Loader(_SlowSource(0.02, fail_first={1}), batch_size=2,
                    num_workers=workers, retries=1)
    batches = list(loader)
    assert len(batches) == 2
    for *_arrays, meta in batches:
        assert len(meta) == 2
        for m in meta:
            assert 0.02 <= m.fetch_s < 0.02 + 0.5
    # the reading is no part of a sample's identity
    m = batches[0][4][0]
    assert dataclasses.replace(m, fetch_s=9.0) == m
    assert "fetch_s" not in repr(m)


def test_steptrace_record_carries_fetch_and_cpu():
    tr = steptrace.StepTrace(step=7)
    before = time.process_time()
    tr.mark("start", 1.0).mark("data", 1.5).mark("done", 2.0)
    assert before <= tr.cpu <= time.process_time()
    # no loader reading (a direct caller): the record leaves ``fetch`` out
    rec = tr.record()
    assert "fetch" not in rec and rec["cpu"] == round(tr.cpu, 6)
    tr.fetch = 0.0123456789
    assert tr.record()["fetch"] == 0.012346
    # marks and phases are what they were
    assert rec["phases"] == {"data_wait": 0.5, "host_prep": 0.5}


def test_boot_span_says_how_many_cpus_the_process_may_use(monkeypatch):
    monkeypatch.setattr(core, "_boot_done", False)
    try:
        sink = telemetry.activate(telemetry.Telemetry())
        boot = [e for e in sink.events
                if e["kind"] == "span" and e["name"] == "boot"]
        assert len(boot) == 1
        assert boot[0]["cpus"] == len(os.sched_getaffinity(0)) >= 1
        telemetry.validate_event(boot[0])
    finally:
        telemetry.deactivate()


def _input_run(steps, cpus):
    """What the benchmark's readers take: the events and the window."""
    events = [_base("span", name="boot", t0=0.0, t1=1.0, cpus=cpus)]
    for i, (start, cpu, fetch) in enumerate(steps):
        events.append(_base(
            "step", step=i, phases={}, step_time=0.5, throughput_ema=2.0,
            marks={"start": start, "done": start + 0.4}, fetch=fetch,
            cpu=cpu))
    return {"kind": "train", "events": events,
            "readings": {"window_wall": (-1.0, 1.0)}}


def test_input_readers_read_the_step_events_and_none_without_them():
    """``fetch_ms`` and ``host_cpu_pct`` (benchmark/layers) on the events
    this program writes; on a program that writes neither reading, the
    parent of PR 40, both find nothing and say None."""
    from benchmark.layers import fetch_ms, host_cpu_pct

    # four steps half a second apart on a host of 8: 2, 3 and 2.5
    # CPU-seconds between consecutive ``start`` marks
    run = _input_run([(10.0, 100.0, 0.040), (10.5, 102.0, 0.050),
                      (11.0, 105.0, 0.070), (11.5, 107.5, 0.060)], cpus=8)
    assert fetch_ms.read(run) == pytest.approx(55.0)
    assert host_cpu_pct.read(run) == pytest.approx(100 * 5.0 / 8)

    def without(*keys):
        return dict(run, events=[{k: v for k, v in e.items()
                                  if k not in keys} for e in run["events"]])

    old = without("fetch", "cpu", "cpus")
    for ev in old["events"]:
        telemetry.validate_event(ev)
    assert fetch_ms.read(old) is None and host_cpu_pct.read(old) is None
    assert host_cpu_pct.read(without("cpus")) is None
    assert host_cpu_pct.read(without("cpu")) is None
    assert fetch_ms.read(without("cpu", "cpus")) == pytest.approx(55.0)


def test_early_spans_are_delivered_on_activate(monkeypatch):
    """A span taken before the process's first sink waits in a small
    bounded list and comes out of ``activate()``, ``boot`` first; after
    that a span without an enabled sink is dropped."""
    monkeypatch.setattr(core, "_hold_early", True)
    monkeypatch.setattr(core, "_boot_done", False)
    core._early.clear()
    try:
        telemetry.emit_span("backend_init", 10.0, 12.5)
        with telemetry.interval("model_load", thread="main"):
            pass
        assert [f["name"] for f in core._early] == [
            "boot", "backend_init", "model_load"]
        sink = telemetry.activate(telemetry.Telemetry())
        spans = [e for e in sink.events if e["kind"] == "span"]
        assert [e["name"] for e in spans] == ["boot", "backend_init",
                                              "model_load"]
        # boot: process start to the first thing the program marked
        assert spans[0]["t1"] == 10.0
        assert (spans[1]["t0"], spans[1]["t1"]) == (10.0, 12.5)
        assert spans[2]["thread"] == "main"
        assert sink.events[0]["kind"] == "clock"
        for ev in sink.events:
            telemetry.validate_event(ev)
        assert not core._early
    finally:
        telemetry.deactivate()
    # the first activate() is past: nothing is held any more
    telemetry.emit_span("model_load", 1.0, 2.0)
    assert not core._early
    # and the list is bounded while it holds
    monkeypatch.setattr(core, "_hold_early", True)
    for i in range(200):
        telemetry.emit_span("data", float(i), float(i) + 1)
    assert len(core._early) == core._early.maxlen
    core._early.clear()


def test_clock_event_pairs_the_two_clocks():
    import time

    sink = telemetry.Telemetry()
    before = time.perf_counter(), time.time_ns()
    ev = sink.clock()
    after = time.perf_counter(), time.time_ns()
    telemetry.validate_event(ev)
    assert before[0] <= ev["perf_counter"] <= after[0]
    assert before[1] <= ev["time_ns"] <= after[1]


def test_gc_span_on_a_forced_collection(monkeypatch):
    import gc

    monkeypatch.setattr(witness, "GC_MIN_S", 0.0)
    sink = telemetry.activate(telemetry.Telemetry())
    try:
        gc.collect()
    finally:
        telemetry.deactivate()
    spans = [e for e in sink.events
             if e["kind"] == "span" and e["name"] == "gc"]
    assert spans and spans[-1]["generation"] == 2
    assert spans[-1]["t0"] <= spans[-1]["t1"]
    assert isinstance(spans[-1]["collected"], int)
    telemetry.validate_event(spans[-1])
    # the hook is gone with the sink
    assert witness._on_gc not in gc.callbacks


def test_ticker_lateness_arithmetic_on_an_injected_clock():
    # due at 10.02; 30 ms late is within tolerance, 51 ms is a stall
    assert witness.late_span(10.0, 10.05) is None
    assert witness.late_span(10.0, 10.069) is None
    assert witness.late_span(10.0, 10.0711) == (10.02, 10.0711)
    assert witness.late_span(0.0, 2.0, tick=0.5, late=1.0) == (0.5, 2.0)

    # the loop itself, on a clock that jumps 1.5 s across one sleep
    times = iter([100.0, 100.02, 100.02, 101.54, 101.54])

    class Stop:
        calls = 0

        def wait(self, timeout):
            Stop.calls += 1
            return Stop.calls > 2

    sink = telemetry.activate(telemetry.Telemetry())
    try:
        witness._tick(Stop(), clock=lambda: next(times))
    finally:
        telemetry.deactivate()
    stalls = [e for e in sink.events
              if e["kind"] == "span" and e["name"] == "stall"]
    assert [(e["t0"], e["t1"]) for e in stalls] == [(100.04, 101.54)]
    assert stalls[0]["thread"] == "witness-ticker"
    # what the process did meanwhile: CPU seconds, major faults, preemptions
    assert stalls[0]["cpu_s"] >= 0.0
    assert stalls[0]["majflt"] >= 0 and stalls[0]["nivcsw"] >= 0


def test_kill_switch_starts_no_thread_and_no_gc_hook(monkeypatch):
    import gc
    import threading

    monkeypatch.setenv("RMD_TELEMETRY", "0")
    telemetry.activate(telemetry.create())
    try:
        assert witness.running() == (False, False)
        assert witness._on_gc not in gc.callbacks
        assert not [t for t in threading.enumerate()
                    if t.name == "witness-ticker"]
        telemetry.emit_span("model_load", 1.0, 2.0)   # dropped, not held
        assert not core._early
    finally:
        telemetry.deactivate()
    monkeypatch.delenv("RMD_TELEMETRY")
    telemetry.activate(telemetry.create())
    try:
        assert witness.running() == (True, True)
    finally:
        telemetry.deactivate()
    assert witness.running() == (False, False)


def test_schema_7_knows_clock_and_span():
    from raft_meets_dicl_tpu.analysis import telemetrykinds

    assert telemetry.SCHEMA_MINOR == 7
    assert telemetry.SCHEMA["clock"] == {"perf_counter", "time_ns"}
    assert telemetry.SCHEMA["span"] == {"name", "t0", "t1"}
    assert {"clock", "span"} <= set(telemetrykinds._schema())
    telemetry.validate_event(_base("clock", perf_counter=1.0, time_ns=2))
    telemetry.validate_event(_base("span", name="gc", t0=1.0, t1=2.0))
    with pytest.raises(ValueError):
        telemetry.validate_event(_base("span", name="gc", t0=1.0))


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
