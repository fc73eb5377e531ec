"""Serving-path tests: scheduler core, backpressure, request faults.

The scheduler/batcher mechanics (bucket coalescing determinism, partial
padding, bounded-queue sheds, sticky per-client ordering, request-level
fault degradation) run against a host-only fake session — no jax, so the
invariants are pinned fast and in isolation. The device half (partial
batches bit-exactly riding the full batch's compiled program, the warm
pool's zero-compile AOT contract) runs a real tiny model.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import serve, telemetry
from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu.models.input import ShapeBuckets
from raft_meets_dicl_tpu.models.wire import WireFormat
from raft_meets_dicl_tpu.serve import (
    BucketBatcher, ServeError, ServeRejected, ServeSession, Scheduler,
)
from raft_meets_dicl_tpu.telemetry import report as treport
from raft_meets_dicl_tpu.testing import faults

pytestmark = pytest.mark.serve

REPO = Path(__file__).parent.parent

TINY_SERVE_MODEL = {
    "name": "serve tiny", "id": "serve-tiny",
    "model": {"type": "raft/baseline",
              "parameters": {"corr-levels": 2, "corr-radius": 2,
                             "corr-channels": 32, "context-channels": 16},
              "arguments": {"iterations": 2}},
    "loss": {"type": "raft/sequence"},
    "input": {"padding": {"type": "modulo", "mode": "zeros",
                          "size": [8, 8]}},
}


@pytest.fixture(autouse=True)
def _serve_hygiene(monkeypatch):
    """Every test starts unarmed with a fresh memory telemetry sink."""
    monkeypatch.delenv("RMD_FAULT", raising=False)
    monkeypatch.delenv("RMD_FAULT_STATE", raising=False)
    faults.reset()
    sink = telemetry.activate(telemetry.Telemetry())
    yield sink
    telemetry.deactivate()
    faults.reset()


def _serve_events(sink, event):
    return [e for e in sink.events
            if e["kind"] == "serve" and e["event"] == event]


def _pair(shape, seed=0):
    rng = np.random.default_rng(seed)
    h, w = shape
    return (rng.random((h, w, 3), dtype=np.float32),
            rng.random((h, w, 3), dtype=np.float32))


class FakeSession:
    """Host-only stand-in for ServeSession: the 'flow' is a deterministic
    numpy function of the encoded inputs, so scheduler mechanics are
    testable without any device work."""

    def __init__(self, buckets, batch_size=4, delay_s=0.0, gain=1.0):
        self.buckets = buckets
        self.batch_size = batch_size
        self.delay_s = delay_s
        self.gain = gain            # tells one stand-in model from another
        self.batch_shapes = []

    def encode_image(self, img):
        return np.asarray(img, np.float32) * 2.0 - 1.0

    def compiles(self):
        return 0

    def run(self, img1, img2):
        self.batch_shapes.append(img1.shape)
        if self.delay_s:
            time.sleep(self.delay_s)
        return (img1 + img2)[..., :2] * self.gain

    def fetch(self, flow):
        return np.asarray(flow)


def _fake_scheduler(batch_size=2, max_wait_ms=5.0, queue_limit=64,
                    delay_s=0.0):
    buckets = ShapeBuckets([(16, 24), (32, 48)])
    session = FakeSession(buckets, batch_size=batch_size, delay_s=delay_s)
    return Scheduler(session, batch_size=batch_size,
                     max_wait_ms=max_wait_ms, queue_limit=queue_limit)


def _offer(batcher, rid, bucket, client="c"):
    h, w = bucket
    img = np.zeros((h, w, 3), np.float32)
    req = serve.FlowRequest(rid=rid, client=client, seq=rid, bucket=bucket,
                            shape=(h, w), img1=img, img2=img, ticket=None,
                            t_submit=time.perf_counter())
    assert batcher.offer(req)
    return req


# -- batcher core -------------------------------------------------------------


def test_bucket_assignment_smallest_fit():
    buckets = ShapeBuckets([(32, 48), (16, 24), (32, 32)])
    b = BucketBatcher(buckets, batch_size=2, queue_limit=8)
    assert b.assign(10, 20) == (16, 24)    # smallest area that fits
    assert b.assign(16, 24) == (16, 24)    # exact fit
    assert b.assign(20, 30) == (32, 32)    # skips too-small buckets
    assert b.assign(30, 40) == (32, 48)
    assert b.assign(33, 20) is None        # oversized
    assert b.assign(20, 60) is None


def test_take_full_batches_first_then_fifo():
    buckets = ShapeBuckets([(16, 24), (32, 48)])
    b = BucketBatcher(buckets, batch_size=2, queue_limit=8)
    # older partial in the small bucket, then a full batch in the big one
    r0 = _offer(b, 0, (16, 24))
    r1 = _offer(b, 1, (32, 48))
    r2 = _offer(b, 2, (32, 48))
    now = time.perf_counter()
    bucket, batch = b.take(now, max_wait_s=60.0)
    assert bucket == (32, 48)              # full beats older partial
    assert [r.rid for r in batch] == [1, 2]  # strict FIFO within bucket
    # the partial hasn't expired: take reports its wake-up deadline
    bucket, deadline = b.take(now, max_wait_s=60.0)
    assert bucket is None
    assert deadline == pytest.approx(r0.t_enqueue + 60.0)
    # expired (or drained) partials dispatch
    bucket, batch = b.take(r0.t_enqueue + 61.0, max_wait_s=60.0)
    assert bucket == (16, 24) and [r.rid for r in batch] == [0]


def test_take_is_deterministic_for_a_submission_sequence():
    def coalesce():
        buckets = ShapeBuckets([(16, 24), (32, 48)])
        b = BucketBatcher(buckets, batch_size=2, queue_limit=16)
        order = [(16, 24), (32, 48), (16, 24), (32, 48), (16, 24)]
        for rid, bucket in enumerate(order):
            _offer(b, rid, bucket)
        batches = []
        while True:
            bucket, batch = b.take(time.perf_counter() + 1e6,
                                   max_wait_s=0.0, drain=True)
            if bucket is None:
                break
            batches.append((bucket, [r.rid for r in batch]))
        return batches

    assert coalesce() == coalesce()
    assert coalesce() == [((16, 24), [0, 2]), ((32, 48), [1, 3]),
                          ((16, 24), [4])]


def test_assemble_fills_partial_by_tiling_last():
    buckets = ShapeBuckets([(16, 24)])
    b = BucketBatcher(buckets, batch_size=3, queue_limit=8)
    r = _offer(b, 0, (16, 24))
    r.img1 = np.random.default_rng(0).random((16, 24, 3)).astype(np.float32)
    r.img2 = r.img1 + 1.0
    img1, img2, fill = b.assemble([r])
    assert fill == 2
    assert img1.shape == (3, 16, 24, 3)
    np.testing.assert_array_equal(img1[1], img1[0])
    np.testing.assert_array_equal(img1[2], img1[0])
    np.testing.assert_array_equal(img2[1], img2[0])


# -- scheduler: admission, backpressure, ordering, faults ---------------------


def test_scheduler_round_trip_and_spans(_serve_hygiene):
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    try:
        img1, img2 = _pair((14, 20))
        t = sched.submit(img1, img2)
        res = t.result(timeout=10.0)
    finally:
        sched.stop(drain=True)
    assert res.bucket == (16, 24)
    assert res.shape == (14, 20)
    assert res.flow.shape == (14, 20, 2)
    # the fake 'flow' is encode(img1)+encode(img2), cropped to the raw
    # extent — padding never leaks into the response
    want = (img1 * 2 - 1) + (img2 * 2 - 1)
    np.testing.assert_allclose(res.flow, want[..., :2], rtol=1e-6)
    for span in ("admission", "queue", "dispatch", "device", "total"):
        assert span in res.spans
    ev = _serve_events(_serve_hygiene, "request")
    assert len(ev) == 1 and ev[0]["rid"] == 0
    assert ev[0]["bucket"] == "16x24"
    bev = _serve_events(_serve_hygiene, "batch")
    assert len(bev) == 1 and bev[0]["size"] == 1 and bev[0]["fill"] == 1


def test_backpressure_sheds_at_queue_bound(_serve_hygiene):
    # not started: nothing drains the queues, so the bound is reachable
    sched = _fake_scheduler(batch_size=4, queue_limit=2, max_wait_ms=1e4)
    img1, img2 = _pair((14, 20))
    sched.submit(img1, img2)
    sched.submit(img1, img2)
    with pytest.raises(ServeRejected) as exc:
        sched.submit(img1, img2)
    assert exc.value.reason == "queue_full"
    ev = _serve_events(_serve_hygiene, "reject")
    assert len(ev) == 1
    assert ev[0]["reason"] == "queue_full" and ev[0]["bucket"] == "16x24"
    # the shed request never consumed a sequence slot: draining the two
    # admitted ones still releases both
    sched.start()
    sched.stop(drain=True)
    assert len(_serve_events(_serve_hygiene, "request")) == 2


def test_sticky_per_client_release_order():
    sched = _fake_scheduler(batch_size=1, max_wait_ms=1e4)  # never started
    img1, img2 = _pair((14, 20))
    tickets = [sched.submit(img1, img2, client="a") for _ in range(3)]
    batches = []
    for _ in range(3):
        bucket, batch = sched.batcher.take(time.perf_counter(), 0.0,
                                           drain=True)
        batches.append((bucket, batch))
    # complete out of order: 2 first — it must be held until 0 and 1 land
    sched._dispatch(*batches[2])
    assert not tickets[2].done()
    sched._dispatch(*batches[0])
    assert tickets[0].done() and not tickets[2].done()
    sched._dispatch(*batches[1])
    assert tickets[1].done() and tickets[2].done()
    rids = [t.result(timeout=1.0).rid for t in tickets]
    assert rids == [0, 1, 2]


def test_malformed_and_oversized_are_typed_at_admission(_serve_hygiene,
                                                        monkeypatch):
    sched = _fake_scheduler()
    img1, img2 = _pair((14, 20))
    with pytest.raises(ServeError) as exc:
        sched.submit(np.zeros((14, 20), np.float32), img2)
    assert exc.value.kind == "malformed"
    with pytest.raises(ServeError) as exc:
        sched.submit(img1, _pair((16, 20))[1])
    assert exc.value.kind == "malformed"
    with pytest.raises(ServeError) as exc:
        sched.submit(*_pair((64, 64)))  # fits no bucket
    assert exc.value.kind == "oversized"
    # fault-injected variants (the request-level faults harness)
    monkeypatch.setenv("RMD_FAULT",
                       "serve_malformed@index=3,serve_oversized@index=4")
    with pytest.raises(ServeError) as exc:
        sched.submit(img1, img2)
    assert exc.value.kind == "malformed"
    with pytest.raises(ServeError) as exc:
        sched.submit(img1, img2)
    assert exc.value.kind == "oversized"
    kinds = [e["error"] for e in _serve_events(_serve_hygiene, "error")]
    assert kinds == ["malformed", "malformed", "oversized", "malformed",
                     "oversized"]
    assert sched.pending() == 0  # nothing ever queued


def test_decode_fault_degrades_without_poisoning(_serve_hygiene,
                                                 monkeypatch):
    # rid 1 fails during batch preparation; rid 0 (same batch) must still
    # serve, and the dispatch loop must keep taking work afterwards
    monkeypatch.setenv("RMD_FAULT", "serve_decode_error@index=1")
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    try:
        img1, img2 = _pair((14, 20))
        t0 = sched.submit(img1, img2)
        t1 = sched.submit(img1, img2)
        res0 = t0.result(timeout=10.0)
        with pytest.raises(ServeError) as exc:
            t1.result(timeout=10.0)
        assert exc.value.kind == "decode"
        assert res0.flow.shape == (14, 20, 2)
        # loop alive: a later request still round-trips
        t2 = sched.submit(img1, img2)
        assert t2.result(timeout=10.0).rid == 2
    finally:
        sched.stop(drain=True)
    bev = _serve_events(_serve_hygiene, "batch")
    # the poisoned request was removed before assembly: first batch
    # dispatched size 1 (refilled by tiling), second size 1
    assert [e["size"] for e in bev] == [1, 1]
    errs = _serve_events(_serve_hygiene, "error")
    assert len(errs) == 1 and errs[0]["error"] == "decode"


def test_stop_without_drain_fails_queued_typed():
    sched = _fake_scheduler(batch_size=4, max_wait_ms=1e4).start()
    img1, img2 = _pair((14, 20))
    t = sched.submit(img1, img2)
    sched.stop(drain=False)
    with pytest.raises(ServeError) as exc:
        t.result(timeout=5.0)
    assert exc.value.kind == "internal"
    with pytest.raises(ServeRejected) as exc:
        sched.submit(img1, img2)
    assert exc.value.reason == "shutdown"


def test_loadgen_open_loop_summary():
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0).start()
    try:
        report = serve.loadgen.run_open_loop(
            sched, [(14, 20), (16, 24), (30, 40)], requests=9,
            rate_hz=500.0)
    finally:
        sched.stop(drain=True)
    assert report["requests"] == 9 and report["completed"] == 9
    assert report["rejected"] == {} and report["errors"] == {}
    assert report["p50_ms"] <= report["p99_ms"]
    assert report["pairs_per_sec"] > 0
    for span in ("admission", "queue", "dispatch", "device", "total"):
        assert span in report["spans_ms"]


def test_serve_report_section_renders(_serve_hygiene):
    monkeypatch_events = _serve_hygiene
    sched = _fake_scheduler(batch_size=2, max_wait_ms=2.0, queue_limit=1)
    img1, img2 = _pair((14, 20))
    t = sched.submit(img1, img2)
    with pytest.raises(ServeRejected):
        sched.submit(img1, img2)  # queue bound 1: typed shed
    sched.start()
    sched.stop(drain=True)
    t.result(timeout=5.0)
    stats = treport.serve_stats(monkeypatch_events.events)
    assert stats["requests"] == 1
    assert stats["rejects"] == {"queue_full": 1}
    assert stats["buckets"]["16x24"]["requests"] == 1
    text = treport.render(monkeypatch_events.events)
    assert "== serving ==" in text
    assert "queue_full" in text
    assert "bucket 16x24" in text


# -- one server, several models (host-only stand-ins) -------------------------

# model "a": two buckets at batch 2; model "b": one bucket of its own at
# batch 3, and a flow three times a's for the same pair
A_BUCKETS, B_BUCKETS = [(16, 24), (32, 48)], [(16, 32)]


def _two_model_scheduler(max_wait_ms=5.0, queue_limit=64, delay_s=0.0):
    sessions = {
        "a": FakeSession(ShapeBuckets(A_BUCKETS), batch_size=2,
                         delay_s=delay_s),
        "b": FakeSession(ShapeBuckets(B_BUCKETS), batch_size=3,
                         delay_s=delay_s, gain=3.0),
    }
    return Scheduler(sessions, max_wait_ms=max_wait_ms,
                     queue_limit=queue_limit), sessions


def _fake_flow(img1, img2, gain=1.0):
    return ((img1 * 2 - 1) + (img2 * 2 - 1))[..., :2] * gain


def test_a_batch_never_mixes_models_and_a_reply_is_its_own_models(
        _serve_hygiene):
    sched, sessions = _two_model_scheduler(max_wait_ms=2.0)
    asked = [("a", (14, 20)), ("b", (14, 30)), ("a", (30, 40)),
             ("b", (16, 32)), ("a", (16, 24)), ("b", (10, 10)),
             ("b", (12, 28)), ("a", (14, 20))]
    pairs = [_pair(shape, seed=i) for i, (_, shape) in enumerate(asked)]
    sched.start()
    try:
        tickets = [sched.submit(a, b, client=f"c{i % 3}", model=model)
                   for i, ((model, _), (a, b)) in enumerate(zip(asked, pairs))]
        results = [t.result(timeout=10.0) for t in tickets]
    finally:
        sched.stop(drain=True)
    for (model, shape), (a, b), res in zip(asked, pairs, results):
        assert res.model == model and res.shape == shape
        assert res.bucket in (A_BUCKETS if model == "a" else B_BUCKETS)
        # b's session triples the flow: a request run on the other model's
        # session would read a third, or three times, of what it should
        np.testing.assert_allclose(
            res.flow, _fake_flow(a, b, sessions[model].gain), rtol=1e-6)
    # each session saw only its own buckets, at its own batch size
    assert {s[:3] for s in sessions["a"].batch_shapes} <= {
        (2, 16, 24), (2, 32, 48)}
    assert {s[:3] for s in sessions["b"].batch_shapes} == {(3, 16, 32)}
    # every batch is one model's, and its members are that model's requests
    requests = {e["trace"]: e for e in _serve_hygiene.events
                if e["kind"] == "trace" and e["event"] == "request"}
    batches = [e for e in _serve_hygiene.events
               if e["kind"] == "trace" and e["event"] == "batch"]
    assert sum(len(b["members"]) for b in batches) == len(asked)
    for b in batches:
        assert b["model"] in ("a", "b")
        assert {requests[m]["model"] for m in b["members"]} == {b["model"]}
    by_model = {}
    for e in _serve_events(_serve_hygiene, "batch"):
        by_model[e["model"]] = by_model.get(e["model"], 0) + e["size"]
    assert by_model == {"a": 4, "b": 4}
    # the report's rows are a model's bucket: two models may share a size
    rows = treport.serve_stats(_serve_hygiene.events)["buckets"]
    assert set(rows) <= {"a:16x24", "a:32x48", "b:16x32"}
    assert sum(r["requests"] for name, r in rows.items()
               if name.startswith("b:")) == 4


def test_one_models_full_lane_sheds_its_own_requests_only(_serve_hygiene):
    # not started: nothing drains, so a's 16x24 lane reaches its bound
    sched, sessions = _two_model_scheduler(max_wait_ms=1e4, queue_limit=2)
    img1, img2 = _pair((14, 20))
    kept = [sched.submit(img1, img2, model="a") for _ in range(2)]
    with pytest.raises(ServeRejected) as exc:
        sched.submit(img1, img2, model="a")
    assert exc.value.reason == "queue_full" and "a:16x24" in str(exc.value)
    # the other model's requests of the same size are admitted: the bound
    # is a lane's, and a lane is one model's
    others = [sched.submit(img1, img2, model="b") for _ in range(2)]
    # and a's other bucket has a lane of its own too
    kept.append(sched.submit(*_pair((30, 40)), model="a"))
    with pytest.raises(ServeRejected):
        sched.submit(img1, img2, model="a")
    rejects = _serve_events(_serve_hygiene, "reject")
    assert [(e["model"], e["bucket"], e["reason"]) for e in rejects] == [
        ("a", "16x24", "queue_full")] * 2
    assert sched.queue_depths() == {"a:16x24": 2, "a:32x48": 1,
                                    "b:16x32": 2}
    sched.start()
    sched.stop(drain=True)
    for t in others:
        res = t.result(timeout=5.0)
        assert res.model == "b"
        np.testing.assert_allclose(res.flow, _fake_flow(img1, img2, 3.0),
                                   rtol=1e-6)
    assert all(t.result(timeout=5.0).model == "a" for t in kept)
    from raft_meets_dicl_tpu.telemetry import metrics as metrics_mod

    shed = metrics_mod.parse_text(metrics_mod.registry().render())[
        "rmd_serve_shed_total"]
    assert shed[(("model", "a"), ("reason", "queue_full"))] >= 2.0
    assert (("model", "b"), ("reason", "queue_full")) not in shed


def test_release_order_per_client_holds_across_models():
    sched, _ = _two_model_scheduler(max_wait_ms=1e4)   # never started
    img1, img2 = _pair((14, 20))
    # one client asks a, b, a: three lanes' worth of batches
    tickets = [sched.submit(img1, img2, client="x", model=m)
               for m in ("a", "b", "a")]
    taken = {}
    while True:
        bucket, batch = sched.batcher.take(time.perf_counter(), 0.0,
                                           drain=True)
        if bucket is None:
            break
        taken[batch[0].model] = (bucket, batch)
    assert [r.rid for r in taken["a"][1]] == [0, 2]
    assert [r.rid for r in taken["b"][1]] == [1]
    # a's batch holds the client's first and third request: the third is
    # held until b's batch has released the second
    sched._dispatch(*taken["a"])
    assert tickets[0].done() and not tickets[2].done()
    sched._dispatch(*taken["b"])
    assert tickets[1].done() and tickets[2].done()
    assert [t.result(timeout=1.0).model for t in tickets] == ["a", "b", "a"]


def test_an_unknown_or_missing_model_is_refused_at_admission(_serve_hygiene):
    sched, _ = _two_model_scheduler()
    img1, img2 = _pair((14, 20))
    for model in (None, "c", ""):
        with pytest.raises(ServeError) as exc:
            sched.submit(img1, img2, model=model)
        assert exc.value.kind == "unknown_model"
    e1 = np.zeros((16, 24, 3), np.float32)
    with pytest.raises(ServeError) as exc:
        sched.submit_encoded(e1, e1, (14, 20))
    assert exc.value.kind == "unknown_model"
    # never a default: nothing was queued for either model
    assert sched.pending() == 0
    errors = _serve_events(_serve_hygiene, "error")
    assert [(e["error"], e["model"]) for e in errors] == [
        ("unknown_model", "")] * 4
    # a model's buckets are its own: b has no 32x48
    with pytest.raises(ServeError) as exc:
        sched.submit(*_pair((30, 40)), model="b")
    assert exc.value.kind == "oversized"
    # one session: no name means that session, another name is refused
    one = _fake_scheduler()
    assert one.submit(img1, img2).rid == 0
    assert one.submit(img1, img2, model="").rid == 1
    with pytest.raises(ServeError) as exc:
        one.submit(img1, img2, model="a")
    assert exc.value.kind == "unknown_model"


def test_the_lane_order_of_a_fixed_submission_sequence_is_pinned():
    """``take``'s rule over lanes of two models: full lanes first (a lane
    is full at its model's batch size), the earliest head among them;
    then the oldest head; within a lane FIFO."""
    def coalesce():
        b = BucketBatcher(ShapeBuckets(A_BUCKETS), batch_size=2,
                          queue_limit=16, model="a")
        b.add_model("b", ShapeBuckets(B_BUCKETS), batch_size=3,
                    queue_limit=16)
        order = [("b", (16, 32)), ("a", (16, 24)), ("b", (16, 32)),
                 ("a", (32, 48)), ("a", (16, 24)), ("b", (16, 32)),
                 ("b", (16, 32)), ("a", (32, 48)), ("a", (16, 24))]
        for rid, (model, bucket) in enumerate(order):
            h, w = bucket
            img = np.zeros((h, w, 3), np.float32)
            assert b.offer(serve.FlowRequest(
                rid=rid, client="c", seq=rid, bucket=bucket, shape=bucket,
                img1=img, img2=img, ticket=None,
                t_submit=time.perf_counter(), model=model))
        batches = []
        while True:
            bucket, batch = b.take(time.perf_counter() + 1e6,
                                   max_wait_s=0.0, drain=True)
            if bucket is None:
                break
            assert len({r.model for r in batch}) == 1
            batches.append((batch[0].model, bucket, [r.rid for r in batch]))
        return batches

    assert coalesce() == coalesce()
    # b's lane is full at three (head 0), a's at two (heads 1 and 3); the
    # partials follow by the age of their heads
    assert coalesce() == [("b", (16, 32), [0, 2, 5]),
                          ("a", (16, 24), [1, 4]),
                          ("a", (32, 48), [3, 7]),
                          ("b", (16, 32), [6]),
                          ("a", (16, 24), [8])]


def test_every_serve_record_names_the_model(_serve_hygiene):
    from raft_meets_dicl_tpu.telemetry import metrics as metrics_mod

    def switches():
        return metrics_mod.parse_text(metrics_mod.registry().render())[
            "rmd_serve_model_switches_total"].get((), 0.0)

    before = switches()
    sched, _ = _two_model_scheduler(max_wait_ms=1e4)    # never started
    img1, img2 = _pair((14, 20))
    tickets = [sched.submit(img1, img2, client="x", model=m)
               for m in ("a", "b", "b", "a")]
    assert sched.queue_depths() == {"a:16x24": 2, "a:32x48": 0,
                                    "b:16x32": 2}
    # a (full), then b and a's none left: dispatch a, b, then a again
    order = []
    for _ in range(2):
        bucket, batch = sched.batcher.take(time.perf_counter(), 0.0,
                                           drain=True)
        order.append(batch[0].model)
        sched._dispatch(bucket, batch)
    assert order == ["a", "b"]
    t = sched.submit(img1, img2, client="y", model="a")
    sched._dispatch(*sched.batcher.take(time.perf_counter(), 0.0,
                                        drain=True))
    assert t.done() and all(x.done() for x in tickets)
    # a -> b -> a: two batches whose model differs from the one before
    assert switches() - before == 2.0
    for event in ("batch", "request"):
        got = [e["model"] for e in _serve_events(_serve_hygiene, event)]
        assert got and set(got) == {"a", "b"}
    for event in ("batch", "request"):
        traced = [e for e in _serve_hygiene.events
                  if e["kind"] == "trace" and e["event"] == event]
        assert traced and all(e["model"] in ("a", "b") for e in traced)
    parsed = metrics_mod.parse_text(metrics_mod.registry().render())
    key = (("bucket", "16x32"), ("klass", ""), ("model", "b"))
    assert parsed["rmd_serve_requests_total"][key] >= 2.0
    assert parsed["rmd_serve_batches_total"][key] >= 1.0
    assert parsed["rmd_serve_request_latency_seconds_count"][
        (("klass", ""), ("model", "b"))] >= 2.0
    assert parsed["rmd_serve_fill_slots_total"][(("model", "b"),)] >= 1.0
    # one stand-in session: the same records, the field empty, lanes
    # named as ever
    one = _fake_scheduler(max_wait_ms=1e4)
    one.submit(img1, img2)
    assert one.queue_depths() == {"16x24": 1, "32x48": 0}
    one._dispatch(*one.batcher.take(time.perf_counter(), 0.0, drain=True))
    assert _serve_events(_serve_hygiene, "batch")[-1]["model"] == ""


def test_several_models_refuse_ladder_and_video_sessions_at_start():
    plain = FakeSession(ShapeBuckets(A_BUCKETS), batch_size=2)
    for attr, value in (("ladder", object()), ("video", True)):
        odd = FakeSession(ShapeBuckets(B_BUCKETS), batch_size=2)
        setattr(odd, attr, value)
        with pytest.raises(ValueError, match="several models"):
            Scheduler({"a": plain, "b": odd})
    with pytest.raises(ValueError, match="at least one"):
        Scheduler({})


def test_the_slo_windows_of_several_models_are_a_models_own():
    from raft_meets_dicl_tpu.telemetry import slo as slo_mod

    tracker = slo_mod.SLOTracker(class_targets={"": 50.0}, objective=0.9,
                                 window_s=10.0, by_model=True)
    assert tracker and tracker.snapshot() == {}
    tracker.record("", 0.010, now=100.0, model="a")
    tracker.record("", 0.200, now=100.0, model="b")
    snap = tracker.snapshot(now=100.0)
    assert sorted(snap) == ["a:", "b:"]
    assert (snap["a:"]["model"], snap["a:"]["good"], snap["a:"]["bad"]) == (
        "a", 1, 0)
    assert (snap["b:"]["model"], snap["b:"]["good"], snap["b:"]["bad"]) == (
        "b", 0, 1)
    # one model's server: keyed by class as ever, the model in the entry
    one = slo_mod.SLOTracker(class_targets={"": 50.0})
    one.record("", 0.010, now=100.0, model="a")
    assert list(one.snapshot(now=100.0)) == [""]
    assert one.snapshot(now=100.0)[""]["model"] == "a"


# -- device half: real tiny model --------------------------------------------


@pytest.fixture(scope="module")
def tiny_session():
    spec = models.load(TINY_SERVE_MODEL)
    return ServeSession(spec, ShapeBuckets([(32, 48)]),
                        wire=WireFormat.from_config("u8"), batch_size=2)


def test_partial_batch_rides_full_batch_program(tiny_session):
    session = tiny_session
    session.warm_pool()
    c0 = session.compiles()
    sched = Scheduler(session, max_wait_ms=1.0).start()
    try:
        img1, img2 = _pair((28, 40), seed=7)
        res = sched.submit(img1, img2).result(timeout=60.0)
    finally:
        sched.stop(drain=True)
    assert res.flow.shape == (28, 40, 2)
    # serving — including the partial batch — compiled nothing new
    assert session.compiles() == c0

    # bit-exact: the same pair tiled to the full batch size through the
    # program directly must produce the identical cropped flow
    e1, e2 = sched.batcher.encode_pair(img1, img2, (32, 48),
                                       session.encode_image)
    b1 = np.stack([e1, e1])
    b2 = np.stack([e2, e2])
    flow = session.fetch(session.run(b1, b2))
    np.testing.assert_array_equal(res.flow, flow[0, :28, :40, :])


def test_warm_pool_prebuild_then_zero_compile_replica(tmp_path,
                                                      _serve_hygiene):
    cfg = dict(TINY_SERVE_MODEL, id="serve-aot", name="serve aot")
    buckets = [(32, 48)]
    programs.enable_aot(str(tmp_path))
    try:
        programs.reset()
        s1 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          wire=WireFormat.from_config("u8"), batch_size=2)
        out1 = s1.warm_pool()
        assert [o["compiles"] for o in out1] == [1]
        assert [o["aot_saves"] for o in out1] == [1]

        # "new replica": drop every in-process program and model object;
        # only the exported artifacts remain
        programs.reset()
        s2 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          wire=WireFormat.from_config("u8"), batch_size=2)
        out2 = s2.warm_pool()
        assert [o["compiles"] for o in out2] == [0]
        assert [o["aot_hits"] for o in out2] == [1]

        # and it actually serves
        sched = Scheduler(s2, max_wait_ms=1.0).start()
        try:
            res = sched.submit(*_pair((30, 44))).result(timeout=60.0)
        finally:
            sched.stop(drain=True)
        assert res.flow.shape == (30, 44, 2)
        assert s2.compiles() == 0
    finally:
        programs.disable_aot()
    warm = _serve_events(_serve_hygiene, "warmup")
    assert len(warm) == 2
    assert warm[0]["aot_saves"] == 1 and warm[1]["aot_hits"] == 1


TINY_DICL_MODEL = {
    "name": "serve tiny dicl", "id": "serve-tiny-dicl",
    "model": {"type": "dicl/baseline",
              "parameters": {
                  "feature-channels": 8,
                  "displacement-range": {f"level-{i}": [3, 3]
                                         for i in range(2, 7)}},
              "arguments": {"raw": True, "dap": True, "ctx": True,
                            "context_scale": {
                                f"level-{i}": 2.0 ** (1 - i)
                                for i in range(2, 7)}}},
    "loss": {"type": "dicl/multiscale"},
    "input": {"clip": [0, 1], "range": [-1, 1],
              "padding": {"type": "modulo", "mode": "zeros",
                          "size": [128, 128]}},
}


@pytest.fixture(scope="module")
def tiny_dicl_session():
    """The tiny ``dicl/baseline`` at two buckets, warm: one build for the
    cases that serve it alone and beside ``tiny_session``."""
    spec = models.load(TINY_DICL_MODEL)
    session = ServeSession(spec, ShapeBuckets([(128, 128), (128, 256)]),
                           wire=WireFormat.from_config("u8"), batch_size=2)
    return session, session.warm_pool()


def test_a_model_without_iterations_serves_through_batcher_and_scheduler(
        _serve_hygiene, tiny_dicl_session):
    """``dicl/baseline``: no recurrence, no ``iterations`` argument, no
    ladder, no video. The session builds its eval program and its warm
    pool for it at two buckets, and what the scheduler releases is the
    model called directly on the padded, wire-decoded pair."""
    import jax

    session, outcomes = tiny_dicl_session
    spec = session.spec
    assert "iterations" not in spec.model.arguments
    assert [(o["bucket"], o["compiles"]) for o in outcomes] == [
        ("128x128", 1), ("128x256", 1)]
    assert not any("rung" in o for o in outcomes)
    # the final-only form, in the key: its own program, its own artifact
    assert "('final_only', 'True')" in dict(session.eval_fn.key.flags)["args"]
    c0 = session.compiles()

    shapes = [(100, 120), (120, 200)] * 3
    sched = Scheduler(session, batch_size=2, max_wait_ms=20.0).start()
    try:
        pairs = [_pair(shape, seed=i) for i, shape in enumerate(shapes)]
        tickets = [sched.submit(a, b, client=f"c{i % 2}")
                   for i, (a, b) in enumerate(pairs)]
        results = [t.result(timeout=120.0) for t in tickets]
    finally:
        sched.stop(drain=True)
    assert session.compiles() == c0
    batches = _serve_events(_serve_hygiene, "batch")
    assert sum(b["size"] for b in batches) == len(shapes)
    assert {b["bucket"] for b in batches} == {"128x128", "128x256"}
    assert all("iterations" not in b and b["compiles"] == 0 for b in batches)

    model = spec.model
    direct = jax.jit(lambda v, a, b: model.get_adapter().wrap_result(
        model.apply(v, a, b), a.shape[1:3]).final())
    for (img1, img2), shape, res in zip(pairs, shapes, results):
        bucket = (128, 128) if shape == (100, 120) else (128, 256)
        e1, e2 = sched.batcher.encode_pair(img1, img2, bucket,
                                           session.encode_image)
        n1, n2, _, _ = session.wire.decode(e1[None], e2[None])
        want = np.asarray(direct(session.variables, n1, n2))[0]
        assert res.flow.shape == (*shape, 2)
        # ten program outputs against one, a batch of 2 against one
        # sample: other fusions, the same arithmetic
        np.testing.assert_allclose(res.flow, want[:shape[0], :shape[1]],
                                   atol=1e-4)
        assert float(np.abs(res.flow).mean()) > 1e-3


def _grid_pair(shape, seed):
    """A pair on the 8-bit grid, so the u8 wire carries it exactly."""
    a, b = _pair(shape, seed=seed)
    return (np.rint(a * 255).astype(np.float32) / 255,
            np.rint(b * 255).astype(np.float32) / 255)


def test_two_models_behind_one_scheduler_answer_as_each_alone_would(
        _serve_hygiene, tiny_session, tiny_dicl_session):
    """The deployment's semantics: every request is answered as its own
    model alone would answer it. A two-model scheduler's flows equal, bit
    for bit, those of two one-model schedulers given the same requests
    (the same programs on the same batches), and agree with the plain
    references of both models on the sessions' own weights."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    from benchmark.harness import serve_check
    from benchmark.reference import common as refc
    from benchmark.reference import dicl as ref_dicl
    from benchmark.reference import raft as ref_raft

    raft, (dicl, _) = tiny_session, tiny_dicl_session
    raft.warm_pool()
    sessions = {raft.spec.id: raft, dicl.spec.id: dicl}
    assert sorted(sessions) == ["serve-tiny", "serve-tiny-dicl"]
    # at the buckets' own sizes (followed by the references) and under them
    asked = [("serve-tiny", (32, 48)), ("serve-tiny-dicl", (128, 128)),
             ("serve-tiny-dicl", (100, 120)), ("serve-tiny", (28, 40)),
             ("serve-tiny", (32, 48)), ("serve-tiny-dicl", (128, 256)),
             ("serve-tiny-dicl", (128, 128)), ("serve-tiny", (30, 44))]
    pairs = [_grid_pair(shape, seed=20 + i)
             for i, (_, shape) in enumerate(asked)]
    c0 = raft.compiles() + dicl.compiles()

    def served(sched, mine, named):
        # everything queued before the thread starts: a lane's batches are
        # then the same FIFO chunks whoever else the scheduler serves
        tickets = [sched.submit(*pairs[i], client=f"c{i % 3}",
                                model=asked[i][0] if named else None)
                   for i in mine]
        sched.start()
        sched.stop(drain=True)
        return {i: t.result(timeout=120.0) for i, t in zip(mine, tickets)}

    both = served(Scheduler(sessions, max_wait_ms=1e4),
                  range(len(asked)), named=True)
    alone = {}
    for name, session in sessions.items():
        mine = [i for i, (model, _) in enumerate(asked) if model == name]
        alone.update(served(Scheduler(session, max_wait_ms=1e4), mine,
                            named=False))
    assert raft.compiles() + dicl.compiles() == c0   # nothing new compiled
    for i, (model, shape) in enumerate(asked):
        assert both[i].model == alone[i].model == model
        assert both[i].flow.shape == (*shape, 2)
        assert np.array_equal(both[i].flow, alone[i].flow)
    batches = _serve_events(_serve_hygiene, "batch")
    assert {b["model"] for b in batches} == set(sessions)

    cfgs = {"serve-tiny": (ref_raft, TINY_SERVE_MODEL),
            "serve-tiny-dicl": (ref_dicl, TINY_DICL_MODEL)}
    with jax.default_matmul_precision("highest"):
        for i, (model, shape) in enumerate(asked):
            if shape not in sessions[model].buckets.sizes:
                continue
            module, cfg = cfgs[model]
            flat = refc.flatten(jax.tree.map(
                lambda x: x, dict(sessions[model].variables)))
            want = jax.jit(lambda f, a, b, m=module, c=cfg, s=shape:
                           serve_check.reference_flow(m, c, f, None, a, b, s))(
                flat, jnp.asarray(pairs[i][0]), jnp.asarray(pairs[i][1]))
            # test_a_model_without_iterations...'s tolerance on the flow,
            # test_reference_dicl's on the gap
            np.testing.assert_allclose(both[i].flow, np.asarray(want),
                                       atol=1e-4)
            gap, magnitude = serve_check.relative_epe(both[i].flow,
                                                      np.asarray(want))
            assert gap < 1e-4 and magnitude > 1e-3


@pytest.mark.slow
def test_cli_serve_smoke(tmp_path):
    import yaml

    (tmp_path / "model.yaml").write_text(yaml.safe_dump(TINY_SERVE_MODEL))
    (tmp_path / "serve.yaml").write_text(yaml.safe_dump({
        "serve": {
            "model": "./model.yaml",
            "buckets": "32x48",
            "wire-format": "u8",
            "batch-size": 2,
            "max-wait-ms": 5,
            "requests": 6,
            "rate": 50,
        }
    }))
    import os
    import re

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    env["RMD_AOT_DIR"] = str(tmp_path / "programs")
    env["RMD_COMPILE_CACHE"] = str(tmp_path / "xla-cache")

    pre = subprocess.run(
        [sys.executable, str(REPO / "main.py"), "serve", "-c", "serve.yaml",
         "--prebuild"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert pre.returncode == 0, pre.stderr[-2000:]
    built = json.loads(pre.stdout.strip().splitlines()[-1])
    assert built["prebuild"][0]["aot_saves"] >= 0

    proc = subprocess.run(
        [sys.executable, str(REPO / "main.py"), "serve", "-c", "serve.yaml"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["completed"] == 6
    assert report["p50_ms"] <= report["p99_ms"]
