"""Streaming-video engine tests: warm-start programs, fw/bw products,
sticky serve sessions, sequence runner.

The host half pins the session-cache policy (bounded LRU + TTL + shape
check with an injectable clock), the forwards-backwards consistency
math on analytic flows (constant translation, layered motion), and the
report/visual plumbing — no jax. The device half runs a real tiny
model: the zero-init warm program must be bit-exact with its plain rung
twin, the sequence runner must spend fewer iterations on warm frames,
and the serve path must stay zero-compile while sticking warm state to
clients.
"""

import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import evaluation, serve, telemetry, visual
from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu.models.input import ShapeBuckets
from raft_meets_dicl_tpu.serve import (
    LadderSpec, Scheduler, ServeError, ServeSession,
)
from raft_meets_dicl_tpu.telemetry import report as treport
from raft_meets_dicl_tpu.video import (
    SequenceRunner, SessionCache, fw_bw_flows, fw_bw_products,
    fw_bw_products_batch, warp_flow,
)

pytestmark = pytest.mark.video

TINY_VIDEO_MODEL = {
    "name": "video tiny", "id": "video-tiny",
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
def _video_hygiene():
    """Every test runs against a fresh in-memory telemetry sink."""
    sink = telemetry.activate(telemetry.Telemetry())
    yield sink
    telemetry.deactivate()


def _events(sink, kind, event=None):
    return [e for e in sink.events if e["kind"] == kind
            and (event is None or e.get("event") == event)]


class _Clock:
    """Injectable monotonic clock for TTL tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# -- session cache: bounded, TTL-evicted, shape-checked ----------------------


def test_session_cache_hit_miss_and_shape_check(_video_hygiene):
    cache = SessionCache(capacity=4, ttl_s=10.0, clock=_Clock())
    flow = np.ones((4, 6, 2), np.float32)

    assert cache.get("cam0") is None            # cold: nothing stored
    cache.put("cam0", flow)
    assert len(cache) == 1
    np.testing.assert_array_equal(cache.get("cam0"), flow)
    np.testing.assert_array_equal(cache.get("cam0", shape=(4, 6, 2)), flow)

    # resolution switch: the old carry is useless and must be dropped
    assert cache.get("cam0", shape=(8, 12, 2)) is None
    assert cache.get("cam0") is None

    ev = [(e["event"], e["client"]) for e in _events(_video_hygiene,
                                                     "session")]
    assert ev == [("miss", "cam0"), ("hit", "cam0"), ("hit", "cam0"),
                  ("miss", "cam0"), ("miss", "cam0")]


def test_session_cache_ttl_eviction(_video_hygiene):
    clock = _Clock()
    cache = SessionCache(capacity=4, ttl_s=5.0, clock=clock)
    cache.put("cam0", np.zeros((2, 3, 2), np.float32))

    clock.t = 4.0
    assert cache.get("cam0") is not None        # within TTL: refreshed
    clock.t = 8.5
    assert cache.get("cam0") is not None        # touch at 4.0 reset the TTL
    clock.t = 15.0
    assert cache.get("cam0") is None            # stalled past TTL: cold
    assert len(cache) == 0

    evicts = _events(_video_hygiene, "session", "evict")
    assert len(evicts) == 1 and evicts[0]["reason"] == "ttl"


def test_session_cache_capacity_lru(_video_hygiene):
    cache = SessionCache(capacity=2, ttl_s=100.0, clock=_Clock())
    row = np.zeros((2, 3, 2), np.float32)
    cache.put("a", row)
    cache.put("b", row)
    cache.get("a")                              # touch: 'b' is now LRU
    cache.put("c", row)                         # bound 2: evicts 'b'
    assert cache.get("a") is not None
    assert cache.get("b") is None
    assert cache.get("c") is not None

    evicts = _events(_video_hygiene, "session", "evict")
    assert [(e["client"], e["reason"]) for e in evicts] == [
        ("b", "capacity")]


def test_session_cache_drop_and_validation():
    cache = SessionCache(capacity=2, ttl_s=1.0, clock=_Clock())
    cache.put("a", np.zeros((2, 3, 2), np.float32))
    assert cache.drop("a") is True              # stream closed
    assert cache.drop("a") is False
    assert len(cache) == 0

    with pytest.raises(ValueError):
        SessionCache(capacity=0, ttl_s=1.0)
    with pytest.raises(ValueError):
        SessionCache(capacity=1, ttl_s=0.0)


# -- forwards-backwards products ---------------------------------------------


def test_warp_flow_zero_is_identity():
    rng = np.random.default_rng(0)
    flow_b = rng.normal(size=(6, 8, 2)).astype(np.float32)
    warped, inside = warp_flow(flow_b, np.zeros((6, 8, 2), np.float32))
    np.testing.assert_allclose(warped, flow_b, rtol=1e-6)
    assert inside.all()


def test_fw_bw_products_constant_translation():
    h, w, d = 16, 20, 3.0
    flow_fw = np.zeros((h, w, 2), np.float32)
    flow_fw[..., 0] = d
    flow_bw = -flow_fw

    occ, conf = fw_bw_products(flow_fw, flow_bw)
    assert occ.shape == (h, w) and occ.dtype == bool
    assert conf.shape == (h, w) and conf.dtype == np.float32

    # consistent interior: round trip returns home, confidence ~= 1
    assert not occ[:, : w - 3].any()
    np.testing.assert_allclose(conf[:, : w - 3], 1.0, atol=1e-5)
    # pixels whose forward flow leaves the image are occluded by
    # definition, with zero confidence
    assert occ[:, w - 2 :].all()
    np.testing.assert_array_equal(conf[:, w - 2 :], 0.0)


def test_fw_bw_products_layered_motion_occlusion():
    # a foreground square moves right by d over a static background: the
    # background band it covers is occluded in frame 2, everything else
    # is consistent
    h, w, d = 24, 32, 4
    r0, r1, c0, c1 = 8, 16, 8, 16
    flow_fw = np.zeros((h, w, 2), np.float32)
    flow_fw[r0:r1, c0:c1, 0] = d
    flow_bw = np.zeros((h, w, 2), np.float32)
    flow_bw[r0:r1, c0 + d : c1 + d, 0] = -d

    occ, conf = fw_bw_products(flow_fw, flow_bw)

    covered = np.zeros((h, w), bool)
    covered[r0:r1, c1 : c1 + d] = True
    assert occ[covered].all()                  # the covered band is flagged
    assert not occ[~covered].any()             # fg + far bg are consistent
    assert conf[covered].max() < conf[~covered].min()


def test_fw_bw_products_batch_and_shape_check():
    flow = np.zeros((2, 8, 10, 2), np.float32)
    occ, conf = fw_bw_products_batch(flow, flow)
    assert occ.shape == (2, 8, 10) and conf.shape == (2, 8, 10)

    with pytest.raises(ValueError):
        fw_bw_products(np.zeros((8, 10, 2)), np.zeros((8, 12, 2)))


def test_fw_bw_flows_splits_doubled_batch():
    def step(variables, a, b):
        return (np.asarray(a) - np.asarray(b))[..., :2], None

    rng = np.random.default_rng(1)
    img1 = rng.random((2, 6, 8, 3), dtype=np.float32)
    img2 = rng.random((2, 6, 8, 3), dtype=np.float32)
    fw, bw = fw_bw_flows(step, None, img1, img2)
    np.testing.assert_allclose(fw, (img1 - img2)[..., :2], rtol=1e-6)
    np.testing.assert_allclose(bw, (img2 - img1)[..., :2], rtol=1e-6)


# -- visual + inspect plumbing -----------------------------------------------


def test_occlusion_overlay_contract():
    img = np.full((6, 8, 3), 0.5)
    occ = np.zeros((6, 8), bool)
    occ[2, 3] = True
    rgba = visual.occlusion_overlay(img, occ)
    assert rgba.shape == (6, 8, 4)
    assert rgba.min() >= 0.0 and rgba.max() <= 1.0
    np.testing.assert_array_equal(rgba[..., 3], 1.0)
    # occluded pixel is tinted red, the rest keep the image
    assert rgba[2, 3, 0] > rgba[0, 0, 0]
    np.testing.assert_allclose(rgba[0, 0, :3], 0.5)
    # mask-only render works without an image
    assert visual.occlusion_overlay(None, occ).shape == (6, 8, 4)


def test_confidence_to_rgba_contract():
    conf = np.linspace(0.0, 1.0, 48, dtype=np.float32).reshape(6, 8)
    rgba = visual.confidence_to_rgba(conf)
    assert rgba.shape == (6, 8, 4)
    assert rgba.min() >= 0.0 and rgba.max() <= 1.0
    # NaNs (never produced, but defensive) must not poison the render
    conf[0, 0] = np.nan
    assert np.isfinite(visual.confidence_to_rgba(conf)).all()


class _Writer:
    def __init__(self):
        self.tags = {}

    def add_image(self, tag, img, step, dataformats=None):
        self.tags[tag] = np.asarray(img)


def test_write_images_accepts_fwbw_products():
    from raft_meets_dicl_tpu.data.collection import Metadata
    from raft_meets_dicl_tpu.inspect import summary

    rng = np.random.default_rng(2)
    img = rng.random((1, 8, 10, 3), dtype=np.float32) * 2.0 - 1.0
    flow = rng.normal(size=(1, 8, 10, 2)).astype(np.float32)
    valid = np.ones((1, 8, 10), bool)
    meta = [Metadata(True, "d", None, ((0, 8), (0, 10)))]

    # default call: exactly the four existing TB tags, mirrors unchanged
    writer = _Writer()
    summary.write_images(writer, "p/", 0, img, img, flow, flow, valid,
                         meta, step=0)
    assert sorted(writer.tags) == ["p/flow-est", "p/flow-gt", "p/img1",
                                   "p/img2"]

    writer = _Writer()
    occ = np.zeros((1, 8, 10), bool)
    conf = np.ones((1, 8, 10), np.float32)
    summary.write_images(writer, "p/", 0, img, img, flow, flow, valid,
                         meta, step=0, occlusion=occ, confidence=conf)
    assert "p/fwbw-occlusion" in writer.tags
    assert "p/fwbw-confidence" in writer.tags
    assert writer.tags["p/fwbw-occlusion"].shape == (8, 10, 4)
    assert writer.tags["p/fwbw-confidence"].shape == (8, 10, 4)


# -- telemetry report --------------------------------------------------------


def test_video_stats_and_report_section():
    events = [
        {"kind": "video", "event": "frame", "frame": 0, "warm": False,
         "iterations": 12, "rungs": 1, "seconds": 0.5, "epe": 1.5},
        {"kind": "video", "event": "frame", "frame": 1, "warm": True,
         "iterations": 4, "rungs": 1, "seconds": 0.2, "epe": 1.6},
        {"kind": "video", "event": "frame", "frame": 2, "warm": True,
         "iterations": 4, "rungs": 1, "seconds": 0.2, "epe": 1.4},
        {"kind": "video", "event": "sequence", "frames": 3,
         "warm_frames": 2, "mean_iterations": 6.67, "frames_per_sec": 3.3,
         "seconds": 0.9, "mean_epe": 1.5},
        {"kind": "session", "event": "miss", "client": "a"},
        {"kind": "session", "event": "hit", "client": "a"},
        {"kind": "session", "event": "evict", "client": "a",
         "reason": "ttl"},
        {"kind": "serve", "event": "batch", "bucket": "32x48", "size": 2,
         "fill": 0, "compiles": 0, "seconds": 0.1, "video": True,
         "warm_members": 1, "products": True},
    ]
    stats = treport.video_stats(events)
    assert stats["cold"]["frames"] == 1
    assert stats["cold"]["mean_iterations"] == 12.0
    assert stats["warm"]["frames"] == 2
    assert stats["warm"]["mean_iterations"] == 4.0
    assert stats["warm"]["mean_epe"] == pytest.approx(1.5)
    assert stats["sequences"][0]["warm_frames"] == 2
    assert stats["sessions"] == {"hits": 1, "misses": 1,
                                 "evictions": {"ttl": 1}}
    assert stats["batches"] == {"batches": 1, "requests": 2, "warm": 1,
                                "products": 1}

    text = treport.render(events)
    assert "== video ==" in text
    assert "cold frames: 1" in text and "warm frames: 2" in text
    assert "1 warm hits / 2 lookups (50%)" in text
    assert "evictions ttl=1" in text
    assert "1 video batches" in text

    assert treport.video_stats([]) is None
    assert "== video ==" not in treport.render([])


# -- scheduler admission: sequence requests need a video session --------------


class _PlainFakeSession:
    """Minimal non-video stand-in (mirrors test_serve.FakeSession)."""

    def __init__(self, buckets, batch_size=4):
        self.buckets = buckets
        self.batch_size = batch_size

    def encode_image(self, img):
        return np.asarray(img, np.float32)

    def compiles(self):
        return 0

    def run(self, img1, img2):
        return (img1 + img2)[..., :2]

    def fetch(self, flow):
        return np.asarray(flow)


def test_sequence_requests_need_video_session():
    session = _PlainFakeSession(ShapeBuckets([(16, 24)]))
    sched = Scheduler(session, batch_size=2)
    img = np.zeros((16, 24, 3), np.float32)
    with pytest.raises(ServeError) as exc:
        sched.submit(img, img, sequence=True)
    assert exc.value.kind == "no_video"


# -- loadgen: sticky streams --------------------------------------------------


class FakeVideoSession:
    """Host-only video session: deterministic flow + a 2x-coarse carry."""

    def __init__(self, buckets, batch_size=1):
        self.buckets = buckets
        self.batch_size = batch_size
        self.video = True

    def encode_image(self, img):
        return np.asarray(img, np.float32)

    def compiles(self):
        return 0

    def fetch(self, flow):
        return np.asarray(flow)

    def run(self, img1, img2):
        return (img1 + img2)[..., :2]

    def run_video(self, img1, img2, carry=None):
        b, h, w = img1.shape[:3]
        flow = (img1 + img2)[..., :2]
        state = {"flow": np.zeros((b, h // 2, w // 2, 2), np.float32),
                 "hidden": np.zeros((b, h // 2, w // 2, 4), np.float32),
                 "delta": np.zeros((b,), np.float32)}
        return flow, state, {"rungs": 1, "iterations": 4,
                             "warm": carry is not None}


def test_loadgen_sequence_streams_report_warm_split(_video_hygiene):
    session = FakeVideoSession(ShapeBuckets([(16, 24)]))
    sched = Scheduler(session, batch_size=1, max_wait_ms=2.0).start()
    try:
        report = serve.loadgen.run_open_loop(
            sched, [(16, 24)], requests=6, rate_hz=500.0, sequence=True,
            streams=2)
    finally:
        sched.stop(drain=True)
    assert report["completed"] == 6
    # 2 sticky streams: each pays exactly one cold first frame
    assert report["video"] == {"warm": 4, "cold": 2}
    batches = _events(_video_hygiene, "serve", "batch")
    assert all(b["video"] for b in batches)
    assert sum(b["warm_members"] for b in batches) == 4


# -- device half: real tiny model ---------------------------------------------


@pytest.fixture(scope="module")
def tiny_video():
    import jax
    import jax.numpy as jnp

    spec = models.load(TINY_VIDEO_MODEL)
    rng = np.random.default_rng(4)
    img1 = rng.random((1, 32, 48, 3), dtype=np.float32)
    img2 = rng.random((1, 32, 48, 3), dtype=np.float32)
    variables = spec.model.init(jax.random.PRNGKey(0), jnp.asarray(img1),
                                jnp.asarray(img2), iterations=1)
    return spec, variables, jnp.asarray(img1), jnp.asarray(img2)


def test_warm_program_zero_init_bit_parity(tiny_video):
    import jax.numpy as jnp

    spec, variables, img1, img2 = tiny_video
    plain = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    warm = evaluation.make_warm_fn(spec.model, 2, model_id=spec.id)

    # the warm flag keys its own program — one per (rung, warm) pair
    assert warm.key != plain.key
    assert "warm" in dict(warm.key.flags)
    assert "warm" not in dict(plain.key.flags)
    assert warm is evaluation.make_warm_fn(spec.model, 2, model_id=spec.id)

    flow_p, state_p = plain(variables, img1, img2)
    zeros = jnp.zeros_like(state_p["flow"])
    flow_w, state_w = warm(variables, img1, img2, zeros)

    # zero carry == cold start, bit for bit: warm-start can never be a
    # correctness hazard, only an optimization
    np.testing.assert_array_equal(np.asarray(flow_w), np.asarray(flow_p))
    np.testing.assert_array_equal(np.asarray(state_w["flow"]),
                                  np.asarray(state_p["flow"]))
    np.testing.assert_array_equal(np.asarray(state_w["hidden"]),
                                  np.asarray(state_p["hidden"]))


def _constant_motion_frames(n=4, shift=2, shape=(32, 48), seed=5):
    rng = np.random.default_rng(seed)
    base = rng.random((shape[0], shape[1], 3), dtype=np.float32)
    frames = [np.roll(base, i * shift, axis=1)[None] for i in range(n)]
    target = np.zeros((1, shape[0], shape[1], 2), np.float32)
    target[..., 0] = shift
    return frames, [target] * (n - 1)


def test_sequence_runner_warm_spends_fewer_iterations(tiny_video,
                                                      _video_hygiene):
    spec, variables, _, _ = tiny_video
    runner = SequenceRunner(
        spec.model, variables, model_id=spec.id,
        ladder=LadderSpec(rungs=(1, 2), threshold=float("inf")))
    frames, targets = _constant_motion_frames()

    cold = runner.run(frames, targets=targets, warm=False)
    assert [f.warm for f in cold.frames] == [False, False, False]
    assert [f.iterations for f in cold.frames] == [2, 2, 2]
    assert cold.mean_iterations() == 2.0
    assert cold.warm_frames() == 0

    res = runner.run(frames, targets=targets)
    assert [f.warm for f in res.frames] == [False, True, True]
    # warm frames stop at the bottom rung (threshold inf: no escalation)
    assert [f.iterations for f in res.frames] == [2, 1, 1]
    assert [f.rungs for f in res.frames] == [1, 1, 1]
    assert res.mean_iterations() < cold.mean_iterations()
    assert res.warm_frames() == 2
    assert res.mean_epe() is not None and res.mean_epe() >= 0.0
    assert res.frames_per_sec() > 0.0
    assert res.frames[0].flow.shape == (1, 32, 48, 2)

    # a second pass reuses every program: recompile-free by construction
    c0 = runner.compiles()
    runner.run(frames, warm=True, keep_flows=False)
    assert runner.compiles() == c0

    frame_ev = _events(_video_hygiene, "video", "frame")
    seq_ev = _events(_video_hygiene, "video", "sequence")
    assert len(frame_ev) == 9 and len(seq_ev) == 3
    assert frame_ev[3]["warm"] is False and frame_ev[4]["warm"] is True
    assert "epe" in frame_ev[3] and "epe" not in frame_ev[6]
    assert seq_ev[1]["warm_frames"] == 2

    with pytest.raises(ValueError):
        runner.run(frames[:1])


def test_sequence_runner_escalates_under_tight_threshold(tiny_video):
    spec, variables, _, _ = tiny_video
    runner = SequenceRunner(
        spec.model, variables, model_id=spec.id,
        ladder=LadderSpec(rungs=(1, 2), threshold=1e-12))
    frames, _ = _constant_motion_frames(n=3)
    res = runner.run(frames)
    # a random-init model never converges below 1e-12: every warm frame
    # escalates through the +1 continuation up to the full budget (3
    # frames = 2 pairs: one cold, one warm-escalated)
    assert [f.iterations for f in res.frames] == [2, 2]
    assert [f.rungs for f in res.frames] == [1, 2]
    assert [f.warm for f in res.frames] == [False, True]


def test_serve_video_sticky_sessions_zero_compile(monkeypatch,
                                                  _video_hygiene):
    monkeypatch.setenv("RMD_VIDEO_WARM_ITERATIONS", "2")
    spec = models.load(TINY_VIDEO_MODEL)
    session = ServeSession(spec, ShapeBuckets([(32, 48)]), batch_size=1,
                           video=True)
    outcomes = session.warm_pool()
    rungs = sorted(o["rung"] for o in outcomes if "rung" in o)
    assert rungs == ["base:2", "warm:2"]

    c0 = session.compiles()
    clock = _Clock()
    sched = Scheduler(session, batch_size=1, max_wait_ms=2.0).start()
    sched.sessions = SessionCache(capacity=4, ttl_s=30.0, clock=clock)
    try:
        rng = np.random.default_rng(6)
        base = rng.random((30, 44, 3), dtype=np.float32)
        frames = [np.roll(base, 2 * i, axis=1) for i in range(4)]

        results = []
        for i in range(3):
            t = sched.submit(frames[i], frames[i + 1], client="cam0",
                             sequence=True, products=(i == 2))
            results.append(t.result(timeout=120.0))

        # sticky: the first frame is cold, every later one warm-starts
        assert [r.warm for r in results] == [False, True, True]
        assert all(r.iterations == 2 for r in results)
        assert all(r.flow.shape == (30, 44, 2) for r in results)
        assert len(sched.sessions) == 1

        # fw/bw products ride the same programs and crop to the request
        assert results[2].occlusion is not None
        assert results[2].occlusion.shape == (30, 44)
        assert results[2].occlusion.dtype == bool
        assert results[2].confidence.shape == (30, 44)

        # an unrelated client never sees cam0's carry
        other = sched.submit(frames[0], frames[1], client="cam1",
                             sequence=True).result(timeout=120.0)
        assert other.warm is False
        assert len(sched.sessions) == 2

        # a stream that stalls past the TTL restarts cold
        clock.t = 31.0
        stale = sched.submit(frames[0], frames[1], client="cam0",
                             sequence=True).result(timeout=120.0)
        assert stale.warm is False
    finally:
        sched.stop(drain=True)

    # the whole exercise — warm starts, reversed products pair, TTL
    # restart — rode the prebuilt program pool
    assert session.compiles() == c0

    batches = _events(_video_hygiene, "serve", "batch")
    assert [b["warm_members"] for b in batches] == [0, 1, 1, 0, 0]
    assert all(b["video"] for b in batches)
    assert sum(1 for b in batches if b.get("products")) == 1


def test_video_warm_pool_prebuild_then_zero_compile_replica(tmp_path,
                                                            monkeypatch):
    monkeypatch.setenv("RMD_VIDEO_WARM_ITERATIONS", "2")
    cfg = dict(TINY_VIDEO_MODEL, id="video-aot", name="video aot")
    buckets = [(32, 48)]
    programs.enable_aot(str(tmp_path))
    try:
        programs.reset()
        s1 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          batch_size=1, video=True)
        out1 = s1.warm_pool()
        # eval + plain twin + warm variant all exported
        assert sum(o["aot_saves"] for o in out1) == 3

        programs.reset()
        s2 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          batch_size=1, video=True)
        out2 = s2.warm_pool()
        assert sum(o["compiles"] for o in out2) == 0
        assert sum(o["aot_hits"] for o in out2) == 3

        # and the replica actually serves warm frames without compiling
        sched = Scheduler(s2, batch_size=1, max_wait_ms=2.0).start()
        try:
            img = np.random.default_rng(7).random((30, 44, 3),
                                                  dtype=np.float32)
            r0 = sched.submit(img, img, client="c", sequence=True)
            r0.result(timeout=120.0)
            r1 = sched.submit(img, img, client="c", sequence=True)
            assert r1.result(timeout=120.0).warm is True
        finally:
            sched.stop(drain=True)
        assert s2.compiles() == 0
    finally:
        programs.disable_aot()
