"""Shape-bucketed evaluation: bucket assignment, bucket padding + mask
extension, shape-grouping loader, masked-metric contract (padded pixels
provably never contribute to EPE/Fl), per-bucket eval-fn caching and
precompile warmup, telemetry eval events, async checkpoint save, and the
intermediates batch-index fix.
"""

import numpy as np
import pytest

import raft_meets_dicl_tpu.metrics.functional as F
from raft_meets_dicl_tpu.data.collection import Metadata, SampleArgs, SampleId
from raft_meets_dicl_tpu.models import input as minput
from raft_meets_dicl_tpu.models.input import ShapeBuckets


def _meta(h, w, b=1, dsid="test"):
    return [
        Metadata(True, dsid, SampleId("s", SampleArgs(), SampleArgs()),
                 ((0, h), (0, w)))
        for _ in range(b)
    ]


def _sample(h, w, seed=0, b=1, dsid="test"):
    rng = np.random.RandomState(seed * 1000 + h * 10 + w)
    img1 = rng.rand(b, h, w, 3).astype(np.float32)
    img2 = rng.rand(b, h, w, 3).astype(np.float32)
    flow = rng.randn(b, h, w, 2).astype(np.float32) * 3
    valid = rng.rand(b, h, w) > 0.3
    return img1, img2, flow, valid, _meta(h, w, b, dsid)


# -- bucket policy -----------------------------------------------------------


def test_bucket_assignment_deterministic():
    # same assignment regardless of declaration order: smallest fitting
    # bucket by (area, h, w)
    a = ShapeBuckets([(64, 96), (48, 64), (64, 64)])
    b = ShapeBuckets([(64, 64), (64, 96), (48, 64)])

    for h, w in [(48, 64), (40, 60), (64, 64), (50, 70), (64, 96), (10, 90)]:
        assert a.assign(h, w) == b.assign(h, w)

    assert a.assign(48, 64) == (48, 64)
    assert a.assign(40, 60) == (48, 64)        # smallest that fits
    assert a.assign(56, 64) == (64, 64)        # (48,64) too short
    assert a.assign(64, 80) == (64, 96)
    assert a.assign(65, 96) is None            # larger than every bucket
    assert a.assign(10, 100) is None

    # spec parsing round-trips the same policy
    c = ShapeBuckets.parse("64x96,48x64,64x64")
    assert c.sizes == a.sizes
    assert ShapeBuckets.from_config(a.get_config()).sizes == a.sizes


def test_bucket_parse_errors_and_group_mode():
    with pytest.raises(ValueError, match="invalid bucket spec"):
        ShapeBuckets.parse("64x")
    g = ShapeBuckets.parse("group")
    assert g.sizes == []
    assert g.assign(10, 10) is None  # grouping only, no quantization


def test_bucket_pad_extends_valid_mask():
    buckets = ShapeBuckets([(32, 48)])
    img1, img2, flow, valid, meta = buckets.pad(*_sample(30, 40))

    assert img1.shape == (1, 32, 48, 3)
    assert flow.shape == (1, 32, 48, 2)
    assert valid.shape == (1, 32, 48)
    # padded rows/cols are invalid; content region keeps its mask
    assert not valid[:, 30:, :].any()
    assert not valid[:, :, 40:].any()
    # bottom/right padding leaves the content region (and extents) alone
    assert meta[0].original_extents == ((0, 30), (0, 40))
    # zeros mode pads images with 0.0
    assert img1[0, 31].sum() == 0.0

    # a sample already on a bucket passes through untouched
    s = _sample(32, 48)
    out = buckets.pad(*s)
    assert out[0] is s[0]


def test_bucket_raw_variant_constant():
    # wire pipelines pad raw values: normalized 0 maps to raw 0.5 for
    # clip (0,1) / range (-1,1)
    raw = ShapeBuckets([(32, 48)]).raw_variant((0.0, 1.0), (-1.0, 1.0))
    img1, *_ = raw.pad(*_sample(30, 40))
    assert img1[0, 31, 0, 0] == pytest.approx(0.5)


def test_bucket_modulo_compatibility_check():
    spec = minput.InputSpec.from_config({
        "padding": {"type": "modulo", "mode": "zeros", "size": [8, 8]},
    })
    with pytest.raises(ValueError, match="not a multiple"):
        spec.apply([], buckets=ShapeBuckets([(30, 48)]))
    # aligned buckets pass
    spec.apply([], buckets=ShapeBuckets([(32, 48)]))


# -- collate / loader --------------------------------------------------------


def test_collate_mixed_shape_error():
    s1 = _sample(30, 40, dsid="kitti")
    s2 = _sample(16, 24, dsid="kitti")
    with pytest.raises(ValueError) as exc:
        minput.collate([s1, s2])
    msg = str(exc.value)
    assert "kitti" in msg
    assert "30x40" in msg and "16x24" in msg
    assert "bucket" in msg


@pytest.mark.parametrize("workers", [0, 2])
def test_loader_group_by_shape(workers):
    shapes = [(32, 48), (16, 24), (32, 48), (16, 24), (32, 48), (24, 32)]
    source = [_sample(h, w, seed=i) for i, (h, w) in enumerate(shapes)]
    # tag samples so identity is observable after regrouping
    for i, s in enumerate(source):
        s[0][..., 0] = float(i)

    adapter = minput.JaxAdapter(source)
    loader = adapter.loader(batch_size=2, shuffle=False,
                            num_workers=workers, group_by_shape=True)

    batches = list(loader)
    ids = []
    for img1, img2, flow, valid, meta in batches:
        # every batch is single-shape and meta matches the batch size
        assert len(meta) == img1.shape[0]
        ids.append([float(v) for v in img1[:, 0, 0, 0]])

    # full same-shape batches first, stable epoch order within groups,
    # partial remainders flushed at the end, every sample exactly once
    assert ids[0] == [0.0, 2.0]
    assert ids[1] == [1.0, 3.0]
    assert sorted(x for chunk in ids for x in chunk) == [float(i) for i in range(6)]
    assert {tuple(chunk) for chunk in ids[2:]} == {(4.0,), (5.0,)}

    # drop_last drops the partial per-shape remainders
    loader = adapter.loader(batch_size=2, shuffle=False,
                            num_workers=workers, group_by_shape=True,
                            drop_last=True)
    assert [b[0].shape[0] for b in loader] == [2, 2]


def test_input_buckets_end_to_end_loader():
    shapes = [(30, 40), (14, 22), (28, 38), (15, 23), (31, 41)]
    source = [_sample(h, w, seed=i) for i, (h, w) in enumerate(shapes)]
    spec = minput.InputSpec()
    buckets = ShapeBuckets([(32, 48), (16, 24)])

    loader = spec.apply(source, buckets=buckets).jax().loader(
        batch_size=2, shuffle=False, num_workers=0, group_by_shape=True)

    got = {}
    for img1, _, _, valid, meta in loader:
        got.setdefault(img1.shape[1:3], 0)
        got[img1.shape[1:3]] += img1.shape[0]
        # padded pixels always masked out
        for b, m in enumerate(meta):
            (y0, y1), (x0, x1) = m.original_extents
            inv = np.ones(valid.shape[1:], bool)
            inv[y0:y1, x0:x1] = False
            assert not valid[b][inv].any()

    assert got == {(32, 48): 3, (16, 24): 2}


# -- masked-metric contract --------------------------------------------------


def _pad_batch(est, tgt, valid, bh, bw, garbage=0.0):
    b, h, w, _ = est.shape
    pe = np.full((b, bh, bw, 2), garbage, np.float32)
    pt = np.full((b, bh, bw, 2), garbage, np.float32)
    pv = np.zeros((b, bh, bw), bool)
    pe[:, :h, :w] = est
    pt[:, :h, :w] = tgt
    pv[:, :h, :w] = valid
    return pe, pt, pv


def test_masked_metrics_padded_bitexact():
    """Bucket-padded batch metrics must equal the unbucketed ones
    bit-for-bit: padded entries contribute exact zeros to the masked
    sums."""
    rng = np.random.RandomState(0)
    est = rng.randn(3, 30, 40, 2).astype(np.float32) * 3
    tgt = rng.randn(3, 30, 40, 2).astype(np.float32) * 3
    valid = rng.rand(3, 30, 40) > 0.3

    pe, pt, pv = _pad_batch(est, tgt, valid, 32, 48)

    ref = F.end_point_error(est, tgt, valid)
    got = F.end_point_error(pe, pt, pv)
    for k in ref:
        assert float(got[k]) == float(ref[k])

    assert float(F.fl_all(pe, pt, pv)) == float(F.fl_all(est, tgt, valid))


def test_padded_pixels_never_contribute():
    """Adversarial garbage in the padded region must not move EPE/Fl (or
    the masked AAE / flow-magnitude) at all."""
    rng = np.random.RandomState(1)
    est = rng.randn(2, 30, 40, 2).astype(np.float32) * 3
    tgt = rng.randn(2, 30, 40, 2).astype(np.float32) * 3
    valid = rng.rand(2, 30, 40) > 0.3

    clean = _pad_batch(est, tgt, valid, 32, 48, garbage=0.0)
    dirty = _pad_batch(est, tgt, valid, 32, 48, garbage=1e6)

    for k, v in F.end_point_error(*clean).items():
        assert float(F.end_point_error(*dirty)[k]) == float(v)
    assert float(F.fl_all(*dirty)) == float(F.fl_all(*clean))
    assert float(F.average_angular_error(dirty[0], dirty[1], dirty[2])) == \
        float(F.average_angular_error(clean[0], clean[1], clean[2]))
    assert float(F.flow_magnitude(dirty[0], valid=dirty[2])) == \
        float(F.flow_magnitude(clean[0], valid=clean[2]))


def test_masked_metric_classes():
    import raft_meets_dicl_tpu.metrics as metrics

    rng = np.random.RandomState(2)
    est = rng.randn(1, 20, 30, 2).astype(np.float32)
    tgt = rng.randn(1, 20, 30, 2).astype(np.float32)
    valid = np.ones((1, 20, 30), bool)
    pe, pt, pv = _pad_batch(est, tgt, valid, 24, 32, garbage=50.0)

    for cfg in ({"type": "aae", "masked": True},
                {"type": "flow-magnitude", "masked": True}):
        m = metrics.Metric.from_config(cfg)
        ref = m(metrics.MetricContext(), est, tgt, valid, 0.0)
        got = m(metrics.MetricContext(), pe, pt, pv, 0.0)
        # reduction order over the padded array may regroup partial sums;
        # the padded values themselves contribute exact zeros
        for k, v in ref.items():
            assert got[k] == pytest.approx(v, rel=1e-6)
        # masked flag survives the config round-trip
        assert metrics.Metric.from_config(m.get_config()).masked


# -- evaluation pipeline -----------------------------------------------------


_TRACES = [0]


def _local_model():
    """Padding-equivariant eval model: zero-bias local convs with ReLU.

    Zero is a fixed point of every layer, so the bucket 'zeros' padding
    (normalized-space 0.0) reproduces exactly what the convs' implicit
    SAME zero padding provides in the unbucketed forward — content-region
    outputs are identical between the bucketed and unbucketed pipelines,
    which isolates pipeline correctness from a real model's intrinsic
    border sensitivity.
    """
    import flax.linen as nn
    import jax.numpy as jnp

    from raft_meets_dicl_tpu.models.model import Model, ModelAdapter, Result

    class LocalFlow(nn.Module):
        @nn.compact
        def __call__(self, img1, img2, train=False, frozen_bn=False):
            _TRACES[0] += 1
            x = jnp.concatenate([img1, img2], axis=-1)
            x = nn.relu(nn.Conv(8, (3, 3), use_bias=False)(x))
            x = nn.relu(nn.Conv(8, (3, 3), use_bias=False)(x))
            return nn.Conv(2, (3, 3), use_bias=False)(x)

    class LocalResult(Result):
        def __init__(self, out):
            self.out = out

        def output(self, batch_index=None):
            if batch_index is None:
                return self.out
            return self.out[batch_index:batch_index + 1]

        def final(self):
            return self.out

        def intermediate_flow(self):
            return [self.out]

    class LocalAdapter(ModelAdapter):
        def wrap_result(self, result, original_shape):
            return LocalResult(result)

    class LocalModel(Model):
        def __init__(self):
            super().__init__(LocalFlow(), {})

        def get_adapter(self):
            return LocalAdapter(self)

    return LocalModel()


def _mixed_source(shapes, per_shape=2):
    out = []
    i = 0
    for h, w in shapes:
        for _ in range(per_shape):
            s = _sample(h, w, seed=i)
            s[4][0].sample_id.img1.kwargs["i"] = i
            out.append(s)
            i += 1
    return out


def _run_eval(model, variables, loader, **kwargs):
    from raft_meets_dicl_tpu import evaluation

    out = {}
    for s in evaluation.evaluate(model, variables, loader,
                                 show_progress=False, **kwargs):
        key = s.meta.sample_id.img1.kwargs["i"]
        out[key] = s
    return out


def test_evaluate_bucketed_epe_parity():
    """Acceptance: on a mixed-shape set (3 raw resolutions) the bucketed
    pipeline compiles at most n_buckets programs and per-sample EPE
    matches the unbucketed batch-1 path to <= 1e-3 relative."""
    import jax

    from raft_meets_dicl_tpu import evaluation

    model = _local_model()
    shapes = [(30, 44), (24, 34), (17, 25)]
    source = _mixed_source(shapes, per_shape=2)
    spec = minput.InputSpec(
        padding=minput.ModuloPadding("zeros", [8, 8]))
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 48, 3), np.float32),
                           np.zeros((1, 32, 48, 3), np.float32))

    ref_loader = spec.apply(source).jax().loader(
        batch_size=1, shuffle=False, num_workers=0)
    ref = _run_eval(model, variables, ref_loader)

    buckets = ShapeBuckets([(32, 48), (24, 40)])
    loader = spec.apply(source, buckets=buckets).jax().loader(
        batch_size=2, shuffle=False, num_workers=0, group_by_shape=True)

    _TRACES[0] = 0
    got = _run_eval(model, variables, loader, pad_to=2)

    # (30,44)->32x48, (24,34)->24x40, (17,25)->24x40: two dispatch shapes,
    # each traced once (pad_to reuses the full batch's program for the
    # remainder) — n_buckets programs for 3 raw shapes
    assert _TRACES[0] <= len(buckets.sizes)

    assert sorted(got) == sorted(ref)
    for k, r in ref.items():
        g = got[k]
        mask = np.asarray(r.valid, bool)
        (y0, y1), (x0, x1) = r.meta.original_extents
        # content region of the bucketed final matches the unbucketed one
        epe_r = np.linalg.norm(
            np.asarray(r.final) - np.asarray(r.target), axis=-1)
        h, w = epe_r.shape
        epe_g = np.linalg.norm(
            np.asarray(g.final)[:h, :w] - np.asarray(g.target)[:h, :w],
            axis=-1)
        a = float(epe_r[mask].mean())
        b = float(epe_g[np.asarray(g.valid, bool)[:h, :w]].mean())
        assert abs(a - b) <= 1e-3 * max(abs(a), 1e-9)
        # and the padded region of the bucketed sample is masked out
        gv = np.asarray(g.valid, bool)
        gv[:h, :w] = False
        assert not gv.any()


def test_evaluate_pad_to_and_warmup():
    """pad_to fills bucket remainders onto the full batch's program and
    warmup precompiles every bucket: the sweep itself traces nothing."""
    import jax

    from raft_meets_dicl_tpu import evaluation

    model = _local_model()
    source = _mixed_source([(30, 44), (17, 25)], per_shape=3)  # 3 per bucket
    spec = minput.InputSpec(padding=minput.ModuloPadding("zeros", [8, 8]))
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 48, 3), np.float32),
                           np.zeros((1, 32, 48, 3), np.float32))

    buckets = ShapeBuckets([(32, 48), (24, 32)])
    loader = spec.apply(source, buckets=buckets).jax().loader(
        batch_size=2, shuffle=False, num_workers=0, group_by_shape=True)

    fn = evaluation.make_eval_fn(model, None)
    stats = evaluation.EvalRunStats(name="warm")
    evaluation.warmup_eval_fn(fn, variables, buckets.sizes, 2, stats=stats)
    traces_after_warmup = _TRACES[0]
    assert stats.phases.get("warmup", 0.0) > 0.0

    got = _run_eval(model, variables, loader, eval_fn=fn, pad_to=2,
                    stats=stats)
    assert len(got) == 6
    # 3 samples / batch 2 per bucket => one full + one padded remainder
    # batch per bucket, all on the warmed programs: zero new traces
    assert _TRACES[0] == traces_after_warmup
    assert stats.batches == 4
    assert stats.samples == 6
    assert stats.pad_samples == 2
    assert stats.pad_waste_ratio() > 0.0


def test_eval_fn_dedupes_by_registry_key():
    import jax

    from raft_meets_dicl_tpu import evaluation

    model = _local_model()
    a = evaluation.make_eval_fn(model, {"x": 1})
    b = evaluation.make_eval_fn(model, {"x": 1})
    c = evaluation.make_eval_fn(model, {"x": 2})
    assert a is b          # same model + args: the registry's program
    assert a is not c      # different static args miss

    # array-valued args cannot be keyed exactly: built fresh each call
    d = evaluation.make_eval_fn(model, {"x": np.zeros(3)})
    e = evaluation.make_eval_fn(model, {"x": np.zeros(3)})
    assert d is not e


def test_eval_telemetry_event_and_report():
    from raft_meets_dicl_tpu import telemetry
    from raft_meets_dicl_tpu.telemetry import report
    from raft_meets_dicl_tpu.telemetry.core import validate_event

    sink = telemetry.Telemetry()
    old = telemetry.activate(sink)
    try:
        from raft_meets_dicl_tpu.evaluation import EvalRunStats

        stats = EvalRunStats(name="val")
        stats.add_batch((32, 48), 2, 0, 2 * 30 * 40, compiles=1)
        stats.add_batch((32, 48), 1, 1, 28 * 38, compiles=0)
        stats.emit()
    finally:
        telemetry.activate(old)

    evs = [e for e in sink.events if e["kind"] == "eval"]
    assert len(evs) == 1
    ev = validate_event(evs[0])
    assert ev["samples"] == 3
    assert ev["buckets"]["32x48"]["batches"] == 2
    assert ev["buckets"]["32x48"]["compiles"] == 1
    assert ev["pad_samples"] == 1
    waste = 1.0 - (2 * 30 * 40 + 28 * 38) / (2 * 32 * 48 + 2 * 32 * 48)
    assert ev["pad_waste_ratio"] == pytest.approx(waste, abs=1e-3)

    text = report.render(sink.events)
    assert "== evaluation ==" in text
    assert "val" in text
    assert "bucket 32x48" in text


# -- satellites --------------------------------------------------------------


def test_checkpoint_async_save(tmp_path):
    from raft_meets_dicl_tpu import strategy

    chkpt = strategy.Checkpoint(
        model="m",
        iteration=strategy.checkpoint.Iteration(0, 0, 5),
        metrics={"epe": 1.0},
        state=strategy.checkpoint.State(
            model={"params": {"w": np.arange(6, dtype=np.float32)}},
            optimizer={}, scaler={}, lr_sched_inst=[], lr_sched_epoch=[],
        ),
        metadata={},
    )

    sync_path = tmp_path / "sync.ckpt"
    assert chkpt.save(sync_path) is None

    bg_path = tmp_path / "bg.ckpt"
    fut = chkpt.save(bg_path, background=True)
    seconds = fut.result()
    assert seconds >= 0.0
    # identical bytes, atomically renamed (no tmp files left over)
    assert bg_path.read_bytes() == sync_path.read_bytes()
    assert not list(tmp_path.glob(".*tmp*"))

    restored = strategy.Checkpoint.load(bg_path)
    assert restored.iteration.step == 5
    np.testing.assert_array_equal(
        restored.state.model["params"]["w"], np.arange(6, dtype=np.float32))

    # entry.wait() joins an in-flight write before load/delete
    entry = restored.to_entry(bg_path)
    entry.pending = chkpt.save(bg_path, background=True)
    assert entry.load().model == "m"
    assert entry.pending is None


def test_intermediate_dump_indexes_sample(tmp_path):
    """A batched result dumps the requested sample's intermediates, not
    sample 0's."""
    import cv2

    from raft_meets_dicl_tpu.cmd.eval import save_intermediate_flow_visual

    rng = np.random.RandomState(3)
    batched = [rng.randn(3, 8, 12, 2).astype(np.float32),
               rng.randn(3, 16, 24, 2).astype(np.float32)]

    class Res:
        def __init__(self, out):
            self.out = out

        def intermediate_flow(self):
            return self.out

    save_intermediate_flow_visual(tmp_path / "b.png", Res(batched),
                                  batch_index=2)
    save_intermediate_flow_visual(
        tmp_path / "r.png", Res([x[2:3] for x in batched]), batch_index=0)

    for key in (".0", ".1"):
        got = cv2.imread(str(tmp_path / f"b{key}.png"))
        ref = cv2.imread(str(tmp_path / f"r{key}.png"))
        np.testing.assert_array_equal(got, ref)
