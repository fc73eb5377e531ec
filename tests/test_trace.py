"""Live observability plane tests: tracing, metrics, SLO, endpoints.

The trace decomposition is pinned as *exact* (phases telescope to the
end-to-end total — nothing hides between phases), the metrics registry
round-trips through its own Prometheus text parser, the SLO burn-rate
math matches the SRE definitions, and the HTTP plane is exercised over
a real socket: /healthz readiness flips with the warm pool, /metrics
parses with nonzero request counters. Scheduler propagation runs on the
host-only fake session; one real tiny-model test covers span propagation
through an actual pad-tiled partial batch.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import serve, telemetry
from raft_meets_dicl_tpu.analysis import telemetrykinds
from raft_meets_dicl_tpu.analysis.lint import Module
from raft_meets_dicl_tpu.models.input import ShapeBuckets
from raft_meets_dicl_tpu.models.wire import WireFormat
from raft_meets_dicl_tpu.serve import Scheduler, ServeSession, observe
from raft_meets_dicl_tpu.telemetry import (
    core, metrics as metrics_mod, report as treport, slo as slo_mod,
    trace as trace_mod,
)
from raft_meets_dicl_tpu.testing import faults

pytestmark = pytest.mark.obs

TINY_OBS_MODEL = {
    "name": "obs tiny", "id": "obs-tiny",
    "model": {"type": "raft/baseline",
              "parameters": {"corr-levels": 2, "corr-radius": 2,
                             "corr-channels": 32, "context-channels": 16,
                             "recurrent-channels": 16},
              "arguments": {"iterations": 2}},
    "loss": {"type": "raft/sequence"},
    "input": {"padding": {"type": "modulo", "mode": "zeros",
                          "size": [8, 8]}},
}


@pytest.fixture(autouse=True)
def _obs_hygiene(monkeypatch):
    """Fresh in-memory sink + fresh default metrics registry per test."""
    monkeypatch.delenv("RMD_FAULT", raising=False)
    monkeypatch.delenv("RMD_FAULT_STATE", raising=False)
    faults.reset()
    metrics_mod.reset()
    sink = telemetry.activate(telemetry.Telemetry())
    yield sink
    telemetry.deactivate()
    metrics_mod.reset()
    faults.reset()


def _pair(shape, seed=0):
    rng = np.random.default_rng(seed)
    h, w = shape
    return (rng.random((h, w, 3), dtype=np.float32),
            rng.random((h, w, 3), dtype=np.float32))


class FakeSession:
    def __init__(self, buckets, batch_size=4, delay_s=0.0):
        self.buckets = buckets
        self.batch_size = batch_size
        self.delay_s = delay_s

    def encode_image(self, img):
        return np.asarray(img, np.float32) * 2.0 - 1.0

    def compiles(self):
        return 0

    def run(self, img1, img2):
        if self.delay_s:
            time.sleep(self.delay_s)
        return (img1 + img2)[..., :2]

    def fetch(self, flow):
        return np.asarray(flow)


def _fake_scheduler(batch_size=2, max_wait_ms=2.0, queue_limit=64):
    buckets = ShapeBuckets([(16, 24), (32, 48)])
    session = FakeSession(buckets, batch_size=batch_size)
    return Scheduler(session, batch_size=batch_size,
                     max_wait_ms=max_wait_ms, queue_limit=queue_limit)


def _trace_events(sink, event):
    return [e for e in sink.events
            if e["kind"] == "trace" and e["event"] == event]


def _get(url):
    """(status, parsed JSON or text) for a GET, 4xx/5xx included."""
    try:
        with urllib.request.urlopen(url, timeout=10.0) as resp:
            body = resp.read().decode()
            code = resp.status
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        code = e.code
    try:
        return code, json.loads(body)
    except json.JSONDecodeError:
        return code, body


# -- trace decomposition ------------------------------------------------------


def test_phases_telescope_exactly():
    rt = trace_mod.RequestTrace(klass="fast", bucket=(16, 24))
    for i, mark in enumerate(trace_mod.MARKS):
        rt.mark(mark, t=10.0 + i * 0.25)
    phases = rt.phases()
    assert set(phases) == set(trace_mod.PHASES)
    # exact telescoping: the phases are differences of one clock at
    # consecutive marks, so they sum to total with no residual
    assert sum(phases.values()) == rt.total() == pytest.approx(1.25)
    rec = rt.record()
    assert rec["klass"] == "fast" and rec["bucket"] == "16x24"
    assert sum(rec["phases"].values()) == pytest.approx(rec["total"],
                                                        abs=1e-5)


def test_phases_skip_unhit_marks():
    rt = trace_mod.RequestTrace()
    rt.mark("submit", t=1.0)
    rt.mark("dispatch", t=3.0)   # enqueue never hit
    rt.mark("released", t=4.0)
    phases = rt.phases()
    # gaps bridge the missing marks, attribution still covers everything
    assert phases == {"admission": 2.0, "batch_form": 1.0}
    assert sum(phases.values()) == rt.total() == 3.0


def test_unknown_mark_rejected():
    with pytest.raises(ValueError, match="unknown trace mark"):
        trace_mod.RequestTrace().mark("teleport")


def test_batch_trace_links_members():
    bt = trace_mod.BatchTrace((32, 48), "quality", program="prog@abc")
    members = [trace_mod.RequestTrace(klass="quality") for _ in range(3)]
    for rt in members:
        bt.link(rt)
    bt.fill = 4
    bt.mark("dispatch", 5.0)
    bt.mark("completed", 5.5)
    rec = bt.record()
    assert rec["size"] == 3 and rec["fill"] == 4
    assert rec["bucket"] == "32x48" and rec["program"] == "prog@abc"
    assert rec["members"] == [rt.trace_id for rt in members]
    assert all(rt.batch_id == bt.batch_id for rt in members)
    assert rec["seconds"] == 0.5
    assert rec["marks"] == {"dispatch": 5.0, "completed": 5.5}
    with pytest.raises(ValueError, match="unknown batch mark"):
        bt.mark("teleport")


def test_trace_summary_snapshot_and_tail():
    ts = trace_mod.TraceSummary()
    # 9 fast requests at 10ms, one slow one queue-dominated at 100ms
    for _ in range(9):
        ts.add({"klass": "fast", "total": 0.010,
                "phases": {"queue": 0.002, "device": 0.008}})
    ts.add({"klass": "fast", "total": 0.100,
            "phases": {"queue": 0.090, "device": 0.010}})
    snap = ts.snapshot()
    assert snap["count"] == 10
    fast = snap["classes"]["fast"]
    assert fast["count"] == 10
    assert fast["p50_ms"] == pytest.approx(10.0)
    assert fast["p99_ms"] == pytest.approx(100.0)
    tail = snap["tail"]
    assert tail["count"] == 1
    assert tail["dominant"] == "queue" and tail["queue_dominated"]
    assert tail["phases_ms"]["queue"] == pytest.approx(90.0)


def test_trace_summary_bounded():
    ts = trace_mod.TraceSummary(capacity=8)
    for i in range(50):
        ts.add({"klass": "", "total": float(i), "phases": {}})
    assert len(ts) == 8
    assert ts.snapshot()["classes"][""]["count"] == 8


# -- metrics registry + exposition --------------------------------------------


def test_metric_name_convention_enforced():
    reg = metrics_mod.MetricsRegistry()
    with pytest.raises(ValueError, match="rmd_<subsystem>_<name>"):
        reg.gauge("queue_depth", "no rmd_ prefix")
    with pytest.raises(ValueError, match="rmd_<subsystem>_<name>"):
        reg.gauge("rmd_depth", "too few segments")
    with pytest.raises(ValueError, match="must end in _total"):
        reg.counter("rmd_serve_requests", "counter suffix")
    with pytest.raises(ValueError, match="bad label name"):
        reg.gauge("rmd_serve_depth", "bad label", ("Klass!",))


def test_counter_only_goes_up():
    reg = metrics_mod.MetricsRegistry()
    c = reg.counter("rmd_test_ops_total", "ops")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_labels_checked_and_rendered():
    reg = metrics_mod.MetricsRegistry()
    c = reg.counter("rmd_test_reqs_total", "reqs", ("klass", "bucket"))
    c.labels(klass="fast", bucket="16x24").inc(3)
    with pytest.raises(ValueError, match="takes labels"):
        c.labels(klass="fast")
    with pytest.raises(ValueError, match="needs .labels"):
        c.inc()
    parsed = metrics_mod.parse_text(reg.render())
    key = (("bucket", "16x24"), ("klass", "fast"))
    assert parsed["rmd_test_reqs_total"][key] == 3.0


def test_histogram_cumulative_buckets():
    reg = metrics_mod.MetricsRegistry()
    h = reg.histogram("rmd_test_lat_seconds", "latency",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    parsed = metrics_mod.parse_text(reg.render())
    buckets = parsed["rmd_test_lat_seconds_bucket"]
    assert buckets[(("le", "0.01"),)] == 1.0
    assert buckets[(("le", "0.1"),)] == 2.0
    assert buckets[(("le", "1"),)] == 3.0
    assert buckets[(("le", "+Inf"),)] == 4.0
    assert parsed["rmd_test_lat_seconds_count"][()] == 4.0
    assert parsed["rmd_test_lat_seconds_sum"][()] == pytest.approx(5.555)


def test_registry_reregistration_idempotent_or_loud():
    reg = metrics_mod.MetricsRegistry()
    g1 = reg.gauge("rmd_test_depth_now", "depth")
    assert reg.gauge("rmd_test_depth_now", "depth") is g1
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("rmd_test_depth_now_total", "ok")  # different name: fine
        reg.counter("rmd_test_depth_now", "clash")


def test_render_parses_as_prometheus_text():
    reg = metrics_mod.MetricsRegistry()
    reg.gauge("rmd_test_ready_flag", 'docs with "quotes" and\nnewline').set(1)
    reg.counter("rmd_test_n_total", "n").inc(7)
    text = reg.render()
    assert "# HELP rmd_test_ready_flag" in text
    assert "# TYPE rmd_test_n_total counter" in text
    parsed = metrics_mod.parse_text(text)
    assert parsed["rmd_test_ready_flag"][()] == 1.0
    assert parsed["rmd_test_n_total"][()] == 7.0


# -- SLO burn-rate windows ----------------------------------------------------


def test_class_slo_burn_math():
    s = slo_mod.ClassSLO("fast", target_ms=50.0, objective=0.9,
                         window_s=60.0)
    for _ in range(8):
        assert s.record(0.010, now=100.0)       # good: 10ms <= 50ms
    for _ in range(2):
        assert not s.record(0.200, now=100.0)   # bad
    snap = s.snapshot(now=100.0)
    assert snap["good"] == 8 and snap["bad"] == 2
    assert snap["attainment"] == pytest.approx(0.8)
    # burn = (1 - 0.8) / (1 - 0.9): missing the objective 2x over budget
    assert snap["burn_rate"] == pytest.approx(2.0)


def test_class_slo_window_prunes():
    s = slo_mod.ClassSLO("fast", target_ms=50.0, window_s=10.0)
    s.record(0.200, now=100.0)  # bad, but ages out below
    s.record(0.010, now=111.0)
    snap = s.snapshot(now=111.0)
    assert snap["good"] == 1 and snap["bad"] == 0
    assert snap["attainment"] == 1.0 and snap["burn_rate"] == 0.0


def test_class_slo_validates_config():
    with pytest.raises(ValueError, match="target_ms"):
        slo_mod.ClassSLO("x", target_ms=0.0)
    with pytest.raises(ValueError, match="objective"):
        slo_mod.ClassSLO("x", target_ms=1.0, objective=1.0)


def test_slo_tracker_default_fallback_and_untracked():
    tracker = slo_mod.SLOTracker(
        class_targets={"fast": 20.0, "balanced": 0.0, "": 80.0},
        objective=0.99, window_s=60.0)
    # balanced had no target of its own: inherits the "" default
    assert tracker.classes() == ["", "balanced", "fast"]
    assert tracker
    snap = tracker.snapshot(now=10.0)
    assert snap["balanced"]["target_ms"] == 80.0
    assert tracker.record("quality", 0.001) is None  # untracked: ignored
    empty = slo_mod.SLOTracker(class_targets={"fast": 0.0, "": 0.0})
    assert not empty


def test_slo_tracker_emits_valid_rate_limited_events(_obs_hygiene):
    tracker = slo_mod.SLOTracker(class_targets={"fast": 50.0},
                                 objective=0.99, window_s=60.0,
                                 emit_interval_s=30.0)
    tracker.record("fast", 0.010, now=100.0)
    assert len(tracker.maybe_emit(_obs_hygiene, now=100.0)) == 1
    assert tracker.maybe_emit(_obs_hygiene, now=110.0) == []   # interval
    assert len(tracker.maybe_emit(_obs_hygiene, now=131.0)) == 1
    events = [e for e in _obs_hygiene.events if e["kind"] == "slo"]
    assert len(events) == 2
    for ev in events:
        core.validate_event(ev)  # slo events honor their SCHEMA entry
        assert ev["klass"] == "fast" and ev["target_ms"] == 50.0


# -- scheduler propagation (host-only fake session) ---------------------------


def test_scheduler_emits_linked_trace_events(_obs_hygiene):
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    try:
        img1, img2 = _pair((14, 20))
        res = sched.submit(img1, img2).result(timeout=10.0)
    finally:
        sched.stop(drain=True)
    # legacy spans stay untouched alongside the new decomposition
    for span in ("admission", "queue", "dispatch", "device", "total"):
        assert span in res.spans

    reqs = _trace_events(_obs_hygiene, "request")
    batches = _trace_events(_obs_hygiene, "batch")
    assert len(reqs) == 1 and len(batches) == 1
    for ev in reqs + batches:
        core.validate_event(ev)
    req, batch = reqs[0], batches[0]
    # fan-in linkage: the batch span names its member request spans
    assert req["trace"] in batch["members"]
    assert req["batch"] == batch["batch"]
    assert req["bucket"] == batch["bucket"] == "16x24"
    # exact critical-path decomposition: phases sum to end-to-end total
    assert set(req["phases"]) == set(trace_mod.PHASES)
    assert sum(req["phases"].values()) == pytest.approx(req["total"],
                                                        abs=1e-5)
    assert req["total"] * 1e3 <= res.spans["total"] * 1e3 + 1.0

    # the live aggregate saw the same record
    snap = sched.trace_summary.snapshot()
    assert snap["count"] == 1 and snap["tail"]["count"] == 1


class MarkedSession(FakeSession):
    """A stand-in that hands back ``run_marks`` like the real session."""

    run_marks = None

    def run(self, img1, img2):
        flow = (img1 + img2)[..., :2]
        called = time.perf_counter()
        time.sleep(0.003)            # the device's execution
        self.run_marks = (called, time.perf_counter())
        return flow


@pytest.mark.parametrize("session_cls", [FakeSession, MarkedSession],
                         ids=["no-run-marks", "run-marks"])
def test_batch_marks_ordered_and_request_phases_unchanged(_obs_hygiene,
                                                          session_cls):
    """The dispatch thread's marks of a batch are ordered wait ≤ dispatch
    ≤ assembled ≤ called ≤ ready ≤ fetched ≤ completed; ``called`` and
    ``ready`` are the session's own (no new sync); request phases keep
    their names and sum, and the older spans are the same marks."""
    buckets = ShapeBuckets([(16, 24), (32, 48)])
    session = session_cls(buckets, batch_size=2)
    sched = Scheduler(session, batch_size=2, max_wait_ms=2.0,
                      queue_limit=64).start()
    try:
        tickets = [sched.submit(*_pair((14, 20), seed=i), client=f"c{i}")
                   for i in range(4)]
        results = [t.result(timeout=10.0) for t in tickets]
    finally:
        sched.stop(drain=True)

    batches = _trace_events(_obs_hygiene, "batch")
    reqs = {e["trace"]: e for e in _trace_events(_obs_hygiene, "request")}
    assert batches and len(reqs) == 4
    for b in batches:
        core.validate_event(b)
        marks = [b["marks"][m] for m in trace_mod.BATCH_MARKS]
        assert marks == sorted(marks), b["marks"]
        if session_cls is MarkedSession:
            assert b["marks"]["ready"] - b["marks"]["called"] >= 0.003
        else:
            assert b["marks"]["ready"] == b["marks"]["called"]
        assert b["seconds"] == pytest.approx(
            b["marks"]["completed"] - b["marks"]["dispatch"], abs=5e-6)
        for member in b["members"]:
            r = reqs[member]
            # a request's dispatch/launched/fetched are its batch's
            # dispatch/ready/fetched: one stamp each
            assert r["marks"]["dispatch"] == b["marks"]["dispatch"]
            assert r["marks"]["launched"] == b["marks"]["ready"]
            assert r["marks"]["fetched"] == b["marks"]["fetched"]
            assert r["marks"]["released"] <= b["marks"]["completed"] + 1e-6
    # a later batch's wait follows the earlier one's completion
    ordered = sorted(batches, key=lambda b: b["marks"]["dispatch"])
    for a, b in zip(ordered, ordered[1:]):
        assert a["marks"]["completed"] <= b["marks"]["wait"] + 1e-6

    for r in reqs.values():
        assert list(r["marks"]) == list(trace_mod.MARKS)
        assert set(r["phases"]) == set(trace_mod.PHASES)
        assert sum(r["phases"].values()) == pytest.approx(r["total"],
                                                          abs=1e-5)
        assert r["total"] == pytest.approx(
            r["marks"]["released"] - r["marks"]["submit"], abs=5e-6)
    # FlowResult.spans and the serve/request event's spans: same marks
    by_rid = {e["rid"]: e for e in _obs_hygiene.events
              if e["kind"] == "serve" and e["event"] == "request"}
    traces = {e["rid"]: e for e in reqs.values()}
    for res in results:
        m = traces[res.rid]["marks"]
        assert set(res.spans) == {"admission", "queue", "dispatch",
                                  "device", "total"}
        assert res.spans["queue"] == pytest.approx(
            m["dispatch"] - m["enqueue"], abs=5e-6)
        assert res.spans["dispatch"] == pytest.approx(
            m["launched"] - m["dispatch"], abs=5e-6)
        assert res.spans["total"] == pytest.approx(
            m["released"] - m["submit"], abs=5e-6)
        assert by_rid[res.rid]["seconds"] == pytest.approx(
            res.spans["total"], abs=5e-6)


def test_scheduler_metrics_counters(_obs_hygiene):
    reg = metrics_mod.registry()
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    try:
        for seed in range(3):
            sched.submit(*_pair((14, 20), seed=seed)).result(timeout=10.0)
    finally:
        sched.stop(drain=True)
    parsed = metrics_mod.parse_text(reg.render())
    key = (("bucket", "16x24"), ("klass", ""), ("model", ""))
    assert parsed["rmd_serve_requests_total"][key] == 3.0
    assert parsed["rmd_serve_request_latency_seconds_count"][
        (("klass", ""), ("model", ""))] == 3.0
    assert sum(parsed["rmd_serve_batches_total"].values()) >= 1.0


def test_scheduler_heartbeat_and_queue_depths():
    sched = _fake_scheduler(batch_size=4, max_wait_ms=1e4)  # not started
    img1, img2 = _pair((14, 20))
    sched.submit(img1, img2)
    sched.submit(*_pair((30, 40)))
    depths = sched.queue_depths()
    assert depths == {"16x24": 1, "32x48": 1}
    assert sched.heartbeat_age() < 10.0
    sched.start()
    sched.stop(drain=True)
    time.sleep(0.01)
    assert sched.heartbeat_age() >= 0.0


# -- HTTP plane ---------------------------------------------------------------


def test_endpoints_over_real_socket(_obs_hygiene):
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    server = serve.serve_observer(sched.session, sched, port=0,
                                  sink=_obs_hygiene)
    try:
        # readiness gates /healthz: FakeSession has no ready attr -> 503
        code, health = _get(server.url + "/healthz")
        assert code == 503
        assert health["ready"] is False and health["live"] is True

        sched.session.ready = True  # what warm_pool() flips on the real one
        code, health = _get(server.url + "/healthz")
        assert code == 200 and health["ready"] is True

        for seed in range(4):
            sched.submit(*_pair((14, 20), seed=seed)).result(timeout=10.0)

        code, text = _get(server.url + "/metrics")
        assert code == 200
        parsed = metrics_mod.parse_text(text)
        key = (("bucket", "16x24"), ("klass", ""), ("model", ""))
        assert parsed["rmd_serve_requests_total"][key] == 4.0
        assert parsed["rmd_serve_ready"][()] == 1.0
        assert parsed["rmd_telemetry_dropped_total"][()] == 0.0

        code, status = _get(server.url + "/statusz")
        assert code == 200
        assert status["requests"] == 4 and status["pending"] == 0
        assert status["classes"][""]["count"] == 4
        assert status["tail"]["count"] >= 1

        code, err = _get(server.url + "/nope")
        assert code == 404 and "no route" in err["error"]
    finally:
        server.close()
        sched.stop(drain=True)


def test_observer_liveness_goes_stale():
    sched = _fake_scheduler()  # never started: heartbeat only from init
    obs = observe.Observer(FakeSession(ShapeBuckets([(16, 24)])), sched,
                           registry=metrics_mod.MetricsRegistry(),
                           stale_heartbeat_s=1e-9)
    payload, code = obs.health()
    assert code == 503 and payload["live"] is False


# -- real tiny model: spans through a pad-tiled partial batch -----------------


@pytest.fixture(scope="module")
def tiny_session():
    spec = models.load(TINY_OBS_MODEL)
    return ServeSession(spec, ShapeBuckets([(32, 48)]),
                        wire=WireFormat.from_config("u8"), batch_size=2)


def test_readiness_flips_with_warm_pool_and_traces_flow(tiny_session,
                                                        _obs_hygiene):
    session = tiny_session
    if not session.ready:  # module fixture: first test in pays the warm-up
        obs = observe.Observer(session, _fake_scheduler(),
                               registry=metrics_mod.MetricsRegistry())
        assert not obs.ready()
        session.warm_pool()
    assert session.ready

    sched = Scheduler(session, max_wait_ms=1.0).start()
    server = serve.serve_observer(session, sched, port=0, sink=_obs_hygiene)
    try:
        code, health = _get(server.url + "/healthz")
        assert code == 200 and health["ready"] is True

        # partial batch (1 of 2) off-bucket: pad + tile to the full
        # program, the trace still decomposes exactly
        res = sched.submit(*_pair((28, 40), seed=7)).result(timeout=60.0)
        assert res.flow.shape == (28, 40, 2)

        reqs = _trace_events(_obs_hygiene, "request")
        batches = _trace_events(_obs_hygiene, "batch")
        assert len(reqs) == 1 and len(batches) == 1
        assert reqs[0]["trace"] in batches[0]["members"]
        assert batches[0]["fill"] == 1  # one live request, one pad slot
        assert batches[0]["program"]   # compiled-program fingerprint
        assert sum(reqs[0]["phases"].values()) == pytest.approx(
            reqs[0]["total"], abs=1e-5)
    finally:
        server.close()
        sched.stop(drain=True)


# -- forward compatibility (report reader) ------------------------------------


def test_load_events_skips_newer_producer_records(tmp_path):
    path = tmp_path / "events.jsonl"
    lines = [
        {"v": 1, "t": 1.0, "kind": "run_end"},                    # fine
        {"v": 1, "t": 2.0, "kind": "hologram", "x": 1},           # newer kind
        {"v": 1.5, "t": 3.0, "kind": "run_end"},                  # newer minor
        {"v": 99, "t": 4.0, "kind": "run_end"},                   # alien major
        {"v": 1, "t": 5.0, "kind": "cache", "event": "nope"},     # corrupt
    ]
    path.write_text("".join(json.dumps(e) + "\n" for e in lines))
    skipped = []
    events, errors = treport.load_events(path, skipped=skipped)
    assert [e["kind"] for e in events] == ["run_end"]
    # unknown kind + newer minor are warn-and-skip, not errors
    assert [n for n, _ in skipped] == [2, 3]
    # an alien major version and a corrupt record stay hard errors
    assert [n for n, _ in errors] == [4, 5]


def test_trace_and_slo_report_sections(_obs_hygiene):
    sink = _obs_hygiene
    for total, queue in ((0.010, 0.001), (0.012, 0.002), (0.200, 0.190)):
        sink.emit("trace", event="request", trace="req-x", batch="b-x",
                  klass="fast", bucket="16x24", total=total,
                  phases={"queue": queue, "device": total - queue})
    sink.emit("trace", event="batch", batch="b-x", bucket="16x24",
              klass="fast", size=3, fill=3, members=["req-x"],
              seconds=0.01, program="p@1")
    sink.emit("slo", klass="fast", target_ms=50.0, objective=0.99,
              window_s=60.0, good=2, bad=1, attainment=0.6667,
              burn_rate=33.33)

    tstats = treport.trace_stats(sink.events)
    assert tstats["requests"] == 3 and tstats["batches"] == 1
    assert tstats["classes"]["fast"]["count"] == 3
    assert tstats["tail"]["dominant"] == "queue"
    assert tstats["tail"]["queue_dominated"]

    sstats = treport.slo_stats(sink.events)
    assert sstats["classes"]["fast"]["worst_burn_rate"] == 33.33

    text = treport.render(sink.events)
    assert "== tracing ==" in text and "== slo ==" in text
    anomalies = treport.find_anomalies(sink.events)
    assert any("burn" in a for a in anomalies)
    assert any("queue-dominated" in a for a in anomalies)


# -- non-blocking bounded sink ------------------------------------------------


def test_nonblocking_sink_drops_and_counts(tmp_path, monkeypatch):
    monkeypatch.setenv("RMD_TELEMETRY_BUFFER", "4")
    path = tmp_path / "events.jsonl"
    sink = telemetry.Telemetry(path, nonblocking=True)
    # jam the disk: the writer thread blocks on the io lock, the bounded
    # queue fills, further emits are shed and counted -- never blocking
    with sink._io_lock:
        for i in range(100):
            sink.emit("cache", event="hit", n=i)
        time.sleep(0.05)  # emit() returned instantly every time
        dropped = sink.dropped()
        assert dropped >= 100 - 2 * 4  # at most 2 batches escaped the queue
    sink.close()
    written = sum(1 for _ in open(path))
    assert written + sink.dropped() == 100
    assert sink.dropped() >= dropped


def test_blocking_and_null_sinks_never_drop():
    assert telemetry.Telemetry().dropped() == 0
    assert telemetry.NullTelemetry().dropped() == 0


def test_rotation_caps_file_size(tmp_path, monkeypatch):
    monkeypatch.setenv("RMD_TELEMETRY_MAX_MB", "0.0002")  # ~200 bytes
    path = tmp_path / "events.jsonl"
    sink = telemetry.Telemetry(path)
    for i in range(40):
        # an unbuffered kind: every emit is its own write batch, so the
        # size check runs (buffered kinds only rotate at flush points)
        sink.emit("run_end", n=i)
    sink.close()
    rotated = tmp_path / "events.jsonl.1"
    assert rotated.exists()
    max_bytes = int(0.0002 * 2 ** 20)
    assert path.stat().st_size <= max_bytes + 200
    # both generations still parse line-by-line
    for f in (path, rotated):
        for line in f.read_text().splitlines():
            assert json.loads(line)["kind"] == "run_end"


def test_rotation_off_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("RMD_TELEMETRY_MAX_MB", raising=False)
    path = tmp_path / "events.jsonl"
    sink = telemetry.Telemetry(path)
    for i in range(40):
        sink.emit("cache", event="hit", n=i)
    sink.close()
    assert not (tmp_path / "events.jsonl.1").exists()
    assert sum(1 for _ in open(path)) == 40


# -- graftlint: telemetry-unregistered-kind -----------------------------------


def mk(source, rel="raft_meets_dicl_tpu/serve/fixture.py"):
    import textwrap
    return Module(rel, rel, textwrap.dedent(source))


def test_lint_flags_unregistered_emit_kind():
    findings = telemetrykinds.check(mk("""
        tele.emit("run_end")
        tele.emit("telport", step=3)
        tele.emit(kind="hologram")
        tele.emit(kind)          # computed: runtime's problem
        queue.emit("not telemetry")
    """))
    msgs = [f.message for f in findings]
    assert len(findings) == 3
    assert any("'telport'" in m for m in msgs)
    assert any("'hologram'" in m for m in msgs)
    assert any("'not telemetry'" in m for m in msgs)


def test_lint_enforces_metric_name_convention():
    findings = telemetrykinds.check(mk("""
        reg.counter("rmd_serve_requests_total", "ok")
        reg.gauge("rmd_serve_queue_depth", "ok")
        reg.histogram("serve_latency_seconds", "no prefix")
        reg.counter("rmd_serve_shed", "no _total suffix")
        reg.gauge(name_var, "computed: skipped")
        histogram("rmd_bad_but_bare", "numpy import, not the registry")
    """))
    assert len(findings) == 2
    assert "breaks the" in findings[0].message
    assert "must end in _total" in findings[1].message


def test_lint_rule_registered_in_default_set():
    from raft_meets_dicl_tpu.analysis import lint as lint_mod
    names = {r.name for r in lint_mod.default_rules()}
    assert telemetrykinds.RULE in names
