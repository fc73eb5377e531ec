"""Observability-layer tests: TB writer, SummaryInspector, validation-driven
checkpoints, hooks, and the grad-accum skip realignment."""

import numpy as np
import pytest

import raft_meets_dicl_tpu.inspect as inspect_
import raft_meets_dicl_tpu.models as models
import raft_meets_dicl_tpu.strategy as strategy
from raft_meets_dicl_tpu.data.collection import Collection
from raft_meets_dicl_tpu.data.dataset import Metadata, SampleArgs, SampleId
from raft_meets_dicl_tpu.utils.logging import Logger

from test_strategy import TINY_MODEL, FlowSource, _make_stage


def _read_events(tb_dir):
    """All (tag, step, value|'img') tuples from every event file in a dir."""
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader,
    )

    out = []
    for f in sorted(tb_dir.glob("events.out.tfevents.*")):
        for event in EventFileLoader(str(f)).Load():
            for value in event.summary.value:
                # the event writer migrates both scalars and images to the
                # generic tensor representation; the plugin name tells them
                # apart
                plugin = value.metadata.plugin_data.plugin_name
                if value.HasField("simple_value"):
                    out.append((value.tag, event.step, value.simple_value))
                elif plugin == "scalars" and value.HasField("tensor"):
                    out.append((value.tag, event.step,
                                float(value.tensor.float_val[0])))
                elif plugin == "images" or value.HasField("image"):
                    out.append((value.tag, event.step, "img"))
    return out


def test_summary_writer_scalars_and_images(tmp_path):
    w = inspect_.SummaryWriter(tmp_path / "tb")
    w.set_fmtargs({"n_stage": 0, "id_stage": "test.s0"})
    w.add_scalar("Train:S{n_stage}:{id_stage}/Loss", 0.5, 3)
    w.add_image("Train:S{n_stage}:{id_stage}/img1",
                np.random.rand(8, 12, 3).astype(np.float32), 3)
    w.add_image("rgba", np.random.rand(8, 12, 4), 4)
    w.close()

    events = _read_events(tmp_path / "tb")
    assert ("Train:S0:test.s0/Loss", 3, 0.5) in events
    assert ("Train:S0:test.s0/img1", 3, "img") in events
    assert ("rgba", 4, "img") in events


INSPECT_CFG = {
    "metrics": [{
        "prefix": "Train:S{n_stage}:{id_stage}/",
        "frequency": 1,
        "metrics": [
            {"type": "epe"},
            {"type": "loss"},
            {"type": "learning-rate"},
            {"type": "grad-norm"},
        ],
    }],
    "images": {"frequency": 1, "prefix": "Train:S{n_stage}:{id_stage}/"},
    "checkpoints": {
        "path": "checkpoints",
        "name": "{id_model}-s{n_stage}_e{n_epoch}_b{n_steps}"
                "-epe{m_EndPointError_mean:.4f}.ckpt",
        "compare": ["{m_EndPointError_mean}"],
        "keep": {"latest": 2, "best": 2},
    },
    "validation": [{
        "type": "strategy",
        "frequency": "epoch",
        "checkpoint": True,
        "tb-metrics-prefix": "Validation:S{n_stage}:{id_stage}:{id_val}/",
        "metrics": [
            {"reduce": "mean", "metric": {"type": "epe"}},
            {"reduce": "mean", "metric": {"type": "loss"}},
        ],
        "images": {"prefix": "Validation:S{n_stage}:{id_stage}:{id_val}/i{img_idx}/"},
    }],
    "tensorboard": {"path": "tb.{id_model}"},
}


def test_inspector_spec_roundtrip():
    spec = inspect_.load(INSPECT_CFG)
    cfg = spec.get_config()
    spec2 = inspect_.load(cfg)
    assert spec2.get_config() == cfg


def _make_inspected_context(tmp_path, stages, inspect_cfg):
    spec = models.load(TINY_MODEL)
    insp_spec = inspect_.load(inspect_cfg)
    inspector, mgr = insp_spec.build("tiny", tmp_path)

    log = Logger("test")
    ctx = strategy.TrainingContext(
        log, tmp_path, strategy.Strategy("continuous", stages), "tiny",
        spec.model, spec.model.get_adapter(), spec.loss, spec.input,
        inspector, mgr, loader_args={"num_workers": 0},
    )
    return ctx, mgr, inspector


def _stage_with_validation(epochs=1, accumulate=1):
    stage = _make_stage(epochs=epochs, accumulate=accumulate)
    stage.validation = [strategy.spec.ValidationSpec(
        name="fake", source=FlowSource(2), batch_size=1, images={0},
    )]
    return stage


def test_summary_inspector_end_to_end(tmp_path):
    """One epoch with the full inspector: train metrics + images to TB,
    epoch validation computes EPE and creates a checkpoint."""
    ctx, mgr, _ = _make_inspected_context(
        tmp_path, [_stage_with_validation()], INSPECT_CFG
    )
    ctx.run()
    assert ctx.step == 2

    # validation created checkpoints with the EPE metric in name + entry
    assert len(mgr.checkpoints) == 1
    entry = mgr.checkpoints[0]
    assert "EndPointError/mean" in entry.metrics
    entry.wait()  # the save's serialize+write runs on a background thread
    assert entry.path.exists()
    assert "-epe" in entry.path.name

    # checkpoint loads back
    chkpt = entry.load()
    assert chkpt.metrics["EndPointError/mean"] == entry.metrics["EndPointError/mean"]

    ctx.inspector.writer.close()
    events = _read_events(tmp_path / "tb.tiny")
    tags = {t for t, _, _ in events}

    assert "Train:S0:test.s0/Loss" in tags
    assert "Train:S0:test.s0/EndPointError/mean" in tags
    assert "Train:S0:test.s0/LearningRate" in tags
    assert "Train:S0:test.s0/GradientNorm/total" in tags
    assert "Train:S0:test.s0/img1" in tags
    assert "Train:S0:test.s0/flow-est" in tags
    assert "Validation:S0:test.s0:fake/EndPointError/mean" in tags
    assert "Validation:S0:test.s0:fake/i0/flow-est" in tags


# -- a step's scalars are launched with the step and read one step late ------

LATE_CFG = {
    "metrics": [{
        "prefix": "Train:S{n_stage}:{id_stage}/",
        "frequency": 1,
        "metrics": [
            {"type": "epe"},
            {"type": "fl-all"},
            {"type": "loss"},
            {"type": "learning-rate"},
            {"type": "flow-magnitude"},
        ],
    }],
    "checkpoints": INSPECT_CFG["checkpoints"],
    "tensorboard": {"path": "tb.{id_model}"},
}


class Recorder:
    """The inspector, plus a record of what each micro-batch computed
    (which the late scalars are compared with) and a stop on request.
    Everything not overridden forwards."""

    def __init__(self, inner, stop_after=None):
        self._inner = inner
        self._stop_after = stop_after
        self.batches = []       # (step, stage, final, target, valid, loss, lr)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def on_batch(self, log, ctx, stage, epoch, i, img1, img2, target, valid,
                 meta, result, loss):
        self.batches.append((ctx.step, stage, np.asarray(result.final()),
                             target, valid, float(loss), ctx.last_lr))
        return self._inner.on_batch(log, ctx, stage, epoch, i, img1, img2,
                                    target, valid, meta, result, loss)

    def on_step_end(self, log, ctx, stage, epoch, i):
        out = self._inner.on_step_end(log, ctx, stage, epoch, i)
        if ctx.step == self._stop_after:
            ctx.request_stop("test")
        return out


def _expected_scalars(batches):
    """{(tag, step): value} by a direct computation on what the steps'
    micro-batches put out: means over a step's micro-batches, the last
    learning rate."""
    from raft_meets_dicl_tpu.metrics import functional as F

    steps = {}
    for step, stage, final, target, valid, loss, lr in batches:
        epe = F.end_point_error(final, target, valid)
        vals = {f"EndPointError/{k}": float(v) for k, v in epe.items()}
        vals["Fl-all"] = float(F.fl_all(final, target, valid))
        vals["Loss"] = loss
        vals["FlowMagnitude"] = float(F.flow_magnitude(final))
        steps.setdefault(step, (stage, [], []))
        steps[step][1].append(vals)
        steps[step][2].append(lr)

    out = {}
    for step, (stage, vals, lrs) in steps.items():
        pfx = f"Train:S{stage.index}:{stage.id.replace('/', '.')}/"
        for k in vals[0]:
            out[pfx + k, step] = float(np.mean([v[k] for v in vals]))
        out[pfx + "LearningRate", step] = lrs[-1]
    return out


def _long_stage(id="test/s0", epochs=1, accumulate=1, validation=False):
    stage = _make_stage(epochs=epochs, accumulate=accumulate)
    stage.id = id
    stage.data = strategy.spec.DataSpec(FlowSource(8), epochs=epochs,
                                        batch_size=2)
    if validation:
        stage.validation = [strategy.spec.ValidationSpec(
            name="fake", source=FlowSource(2), batch_size=1, images=set())]
    return stage


ENDINGS = {
    # name: (stages, inspector config, step limit, stop after step, steps run)
    "epoch-validation": (
        lambda: [_long_stage(epochs=2, validation=True)],
        LATE_CFG | {"validation": INSPECT_CFG["validation"]}, None, None, 8),
    "request-stop": (lambda: [_long_stage()], LATE_CFG, None, 1, 2),
    "step-limit": (lambda: [_long_stage(epochs=2)], LATE_CFG, 5, None, 5),
    "stage-change": (
        lambda: [_long_stage("test/s0"), _long_stage("test/s1")],
        LATE_CFG, None, None, 8),
    "accumulate-2": (lambda: [_long_stage(accumulate=2)], LATE_CFG, None,
                     None, 2),
}


@pytest.mark.parametrize("ending", sorted(ENDINGS))
def test_late_scalars_every_step_once(tmp_path, ending):
    """However the loop ends, every step has each scalar tag exactly once,
    under its own index and stage, with the value a direct computation on
    that step's outputs gives."""
    stages, cfg, step_limit, stop_after, steps = ENDINGS[ending]
    ctx, _, inspector = _make_inspected_context(tmp_path, stages(), cfg)
    ctx.step_limit = step_limit
    ctx.inspector = rec = Recorder(inspector, stop_after)
    ctx.run()
    assert ctx.step == steps

    # read before the writer is closed: the flush where the loop stopped
    # has taken the last step's scalars down to the event file
    written = [(t, s, v) for t, s, v in _read_events(tmp_path / "tb.tiny")
               if t.startswith("Train:")]
    inspector.writer.close()
    expected = _expected_scalars(rec.batches)

    assert sorted((t, s) for t, s, _ in written) == sorted(expected)
    assert {s for _, s in expected} == set(range(steps))
    for tag, step, value in written:
        assert value == pytest.approx(expected[tag, step], rel=1e-5,
                                      abs=1e-7), (tag, step)


def test_scalars_are_read_after_the_next_launch(tmp_path, monkeypatch):
    """Between the launch of a step that is neither a finite-check nor an
    image step and the launch of the next one, the only fetch is the one
    for the step before: its own scalars are read once the next step is
    launched. ``scalars_late`` + ``scalars_flushed`` count every step."""
    from raft_meets_dicl_tpu import telemetry
    from raft_meets_dicl_tpu.metrics import functional as F
    from raft_meets_dicl_tpu.strategy import training

    monkeypatch.setenv("RMD_FINITE_CHECK_EVERY", "3")
    trail = []      # ("launch",) | ("fetch",) | ("write", step)

    real_fetch, real_make = F.fetch_scalars, training.make_train_step

    def fetch_scalars(scalars):
        trail.append(("fetch",))
        return real_fetch(scalars)

    def make_train_step(*args, **kwargs):
        step_fn = real_make(*args, **kwargs)

        def launch(*a, **kw):
            trail.append(("launch",))
            return step_fn(*a, **kw)

        return launch

    monkeypatch.setattr(F, "fetch_scalars", fetch_scalars)
    monkeypatch.setattr(training, "make_train_step", make_train_step)

    ctx, _, inspector = _make_inspected_context(
        tmp_path, [_long_stage(epochs=2)], LATE_CFG)
    add_scalar = inspector.writer.add_scalar
    monkeypatch.setattr(
        inspector.writer, "add_scalar",
        lambda key, value, step=None: (
            # the metric groups' scalars, not the telemetry mirror's
            trail.append(("write", step)) if key.startswith("Train:")
            else None, add_scalar(key, value, step)))

    sink = telemetry.activate(telemetry.Telemetry())
    counted = {}
    add_count = sink.add_count
    monkeypatch.setattr(
        sink, "add_count",
        lambda name, n: (counted.update({name: counted.get(name, 0) + n}),
                         add_count(name, n)))
    try:
        ctx.run()
    finally:
        telemetry.deactivate()
    assert ctx.step == 8

    launches = [k for k, ev in enumerate(trail) if ev == ("launch",)]
    assert len(launches) == 8
    for step, (a, b) in enumerate(zip(launches, launches[1:])):
        between = trail[a + 1:b]
        writes = {ev[1] for ev in between if ev[0] == "write"}
        if step in (0, 4):
            # the first step of an epoch: nothing is held from before it
            # (the epoch's end flushed the last step's)
            assert between == []
        elif step == 3:
            # the first epoch's last step: the loop stops stepping, and
            # the flush at the epoch's end reads it too
            assert between.count(("fetch",)) == 2
            assert writes == {2, 3}
        else:
            assert between.count(("fetch",)) == 1
            assert writes == {step - 1}

    # an epoch's last step (3 and 7) is written by the flush at its end
    assert counted["scalars_late"] == 6
    assert counted["scalars_flushed"] == 2
    late = [ev["counters"].get("scalars_late", 0) for ev in sink.events
            if ev["kind"] == "step"]
    assert late == [0, 1, 1, 1, 0, 1, 1, 1]


class SometimesInvalidSource(Collection):
    """FlowSource variant where selected sample indices are invalid."""

    type = "fake-flow-invalid"

    def __init__(self, n=6, invalid=(2,), h=32, w=48):
        self.inner = FlowSource(n, h, w)
        self.invalid = set(invalid)

    def __getitem__(self, index):
        img1, img2, flow, valid, meta = self.inner[index]
        if index in self.invalid:
            meta = [Metadata(False, m.dataset_id, m.sample_id,
                             m.original_extents) for m in meta]
        return img1, img2, flow, valid, meta

    def __len__(self):
        return len(self.inner)

    def get_config(self):
        return {"type": self.type}

    def description(self):
        return "fake flow with invalid samples"


def test_grad_accum_skip_stays_aligned(tmp_path):
    """An invalid batch mid-accumulation must cost one micro-batch, not
    desync the host step counter from optax.MultiSteps (VERDICT weak #4)."""
    from test_strategy import _make_context

    stage = _make_stage(epochs=1, accumulate=2)
    stage.data = strategy.spec.DataSpec(
        SometimesInvalidSource(n=5, invalid=(1,)), epochs=1, batch_size=1,
        shuffle=False,
    )

    ctx, _ = _make_context(tmp_path, [stage])
    ctx.run()

    # 5 batches, 1 skipped → 4 executed micro-batches → 2 optimizer steps;
    # the old (i+1)%accum boundary would have counted only 1
    assert ctx.step == 2

    # MultiSteps agrees: no partial accumulation left pending
    from raft_meets_dicl_tpu.strategy.training import TrainingContext  # noqa: F401
    mini_step = ctx.state.opt_state.mini_step
    assert int(np.asarray(mini_step)) == 0


def test_hooks_activation_and_gradient(tmp_path):
    """Activation-stats writes mean/var scalars via capture_intermediates;
    gradient anomaly hook sees grads (and stays silent on healthy ones)."""
    cfg = dict(INSPECT_CFG)
    cfg = {k: v for k, v in cfg.items() if k != "validation"}
    cfg["hooks"] = [
        {"type": "activation-stats", "modules": ["FeatureEncoderS3_0._Stem_0"],
         "prefix": "Train/ActivationStats/", "frequency": 1},
        {"type": "anomalydetect-gradient", "save-checkpoint": True,
         "checkpoint-fmt": "anomaly-b{n_step}.ckpt"},
    ]

    ctx, _, inspector = _make_inspected_context(
        tmp_path, [_make_stage(epochs=1)], cfg
    )
    assert inspector.wants_gradients  # grad-norm metric + gradient hook
    ctx.run()

    ctx.inspector.writer.close()
    events = _read_events(tmp_path / "tb.tiny")
    tags = {t for t, _, _ in events}

    act_tags = [t for t in tags
                if t.startswith("Train/ActivationStats/FeatureEncoderS3_0")]
    assert act_tags, f"no activation stats written; tags: {sorted(tags)[:20]}"
    assert any(t.endswith("/mean") for t in act_tags)
    assert any(t.endswith("/var") for t in act_tags)

    # healthy training: no anomaly checkpoints dumped
    assert not list(tmp_path.glob("anomaly-*.ckpt"))


def test_gradient_anomaly_dumps_checkpoint(tmp_path):
    """A non-finite gradient triggers the rolling debug checkpoint dump."""
    import jax.numpy as jnp

    from raft_meets_dicl_tpu.inspect.hooks.anomaly import GradientAnomalyDetector

    ctx, _, inspector = _make_inspected_context(
        tmp_path, [_make_stage(epochs=1)], INSPECT_CFG
    )
    # minimal live context for the dump
    ctx._ensure_variables(ctx.strategy.stages[0])
    ctx.current_stage = ctx.strategy.stages[0]
    ctx.current_stage.index = 0
    ctx.current_epoch = 0
    ctx.lr_sched_inst, ctx.lr_sched_epoch = [], []

    hook = GradientAnomalyDetector(checkpoint=True)
    writer = inspector.writer
    writer.set_fmtargs({"n_step": 0})
    hook.register(ctx, writer)

    log = Logger("test")
    hook.on_grads(log, ctx, {"w": jnp.array([1.0, float("nan")])})

    dumps = list(tmp_path.glob("anomaly_in_gradient-*.ckpt"))
    assert len(dumps) == 1
    # the dump is a loadable checkpoint
    chkpt = strategy.Checkpoint.load(dumps[0])
    assert chkpt.model == "tiny"


def test_tfdata_reads_back_writer_scalars(tmp_path):
    """utils.tfdata round-trips scalars written by our SummaryWriter."""
    from raft_meets_dicl_tpu.utils import tfdata

    w = inspect_.SummaryWriter(tmp_path / "tb")
    for step, value in enumerate((0.5, 0.25, 0.125)):
        w.add_scalar("Loss", value, step)
    w.add_scalar("Other", 1.0, 0)
    w.close()

    events = sorted((tmp_path / "tb").glob("events.out.tfevents.*"))
    df = tfdata.tfdata_scalars_to_pandas(events[0])
    loss = df[df.tag == "Loss"].sort_values("step")
    assert list(loss.step) == [0, 1, 2]
    assert list(loss.value) == [0.5, 0.25, 0.125]

    filtered = tfdata.tfdata_scalars_to_pandas(events[0], tags={"Other"})
    assert set(filtered.tag) == {"Other"}
