"""Metric registry tests: torch-golden parity for EPE/Fl-all, config
round-trips, collectors, tree statistics, and the evaluation generator."""

import numpy as np
import pytest
import torch

import raft_meets_dicl_tpu.metrics as metrics
from raft_meets_dicl_tpu.metrics import MetricContext, functional as F


def _random_flow(seed, b=2, h=13, w=17):
    rng = np.random.RandomState(seed)
    est = rng.randn(b, h, w, 2).astype(np.float32) * 3
    tgt = rng.randn(b, h, w, 2).astype(np.float32) * 3
    valid = rng.rand(b, h, w) > 0.3
    return est, tgt, valid


def _torch_epe(est, tgt, valid, distances=(1, 3, 5)):
    # reference semantics (src/metrics/epe.py:36-52), NCHW with dim=-3
    est_t = torch.from_numpy(est).permute(0, 3, 1, 2)
    tgt_t = torch.from_numpy(tgt).permute(0, 3, 1, 2)
    epe = torch.linalg.vector_norm(est_t - tgt_t, ord=2, dim=-3)
    epe = epe[torch.from_numpy(valid)]
    out = {"mean": epe.mean().item()}
    for d in distances:
        out[f"{d}px"] = (epe <= d).float().mean().item()
    return out


def test_epe_torch_parity():
    est, tgt, valid = _random_flow(0)
    golden = _torch_epe(est, tgt, valid)

    vals = F.end_point_error(est, tgt, valid)
    assert float(vals["mean"]) == pytest.approx(golden["mean"], rel=1e-5)
    for d in (1, 3, 5):
        assert float(vals[f"{d}px"]) == pytest.approx(golden[f"{d}px"], rel=1e-5)

    m = metrics.Metric.from_config({"type": "epe"})
    res = m(MetricContext(), est, tgt, valid, loss=0.0)
    assert res["EndPointError/mean"] == pytest.approx(golden["mean"], rel=1e-5)
    assert res["EndPointError/3px"] == pytest.approx(golden["3px"], rel=1e-5)


def test_fl_all_torch_parity():
    est, tgt, valid = _random_flow(1)

    est_t = torch.from_numpy(est).permute(0, 3, 1, 2)
    tgt_t = torch.from_numpy(tgt).permute(0, 3, 1, 2)
    epe = torch.linalg.vector_norm(est_t - tgt_t, ord=2, dim=-3)
    mag = torch.linalg.vector_norm(tgt_t, ord=2, dim=-3)
    v = torch.from_numpy(valid)
    golden = torch.logical_and(epe[v] > 3, epe[v] > 0.05 * mag[v]).float().mean().item()

    assert float(F.fl_all(est, tgt, valid)) == pytest.approx(golden, rel=1e-5)

    m = metrics.Metric.from_config({"type": "fl-all"})
    res = m(MetricContext(), est, tgt, valid, loss=0.0)
    assert res["Fl-all"] == pytest.approx(golden, rel=1e-5)


def test_aae_and_magnitude():
    est, tgt, valid = _random_flow(2)

    # published AAE definition (Barron et al.): angle between unit-extended
    # spatio-temporal vectors (u, v, 1)
    ext_e = np.concatenate([est, np.ones_like(est[..., :1])], axis=-1)
    ext_t = np.concatenate([tgt, np.ones_like(tgt[..., :1])], axis=-1)
    cos = (ext_e * ext_t).sum(-1) / (
        np.linalg.norm(ext_e, axis=-1) * np.linalg.norm(ext_t, axis=-1))
    golden = np.degrees(np.arccos(np.clip(cos, -1, 1)).mean())

    assert float(F.average_angular_error(est, tgt)) == pytest.approx(golden, rel=1e-4)

    golden_mag = np.linalg.norm(est, axis=-1).mean()
    assert float(F.flow_magnitude(est)) == pytest.approx(golden_mag, rel=1e-5)


def test_epe_empty_valid_is_finite():
    est, tgt, valid = _random_flow(3)
    vals = F.end_point_error(est, tgt, np.zeros_like(valid))
    assert np.isfinite(float(vals["mean"]))


def test_config_roundtrip_all_types():
    cfgs = [
        {"type": "epe", "key": "EndPointError/", "distances": [1, 3, 5]},
        {"type": "fl-all", "key": "Fl-all"},
        {"type": "aae", "key": "AverageAngularError"},
        {"type": "flow-magnitude", "key": "FlowMagnitude", "ord": 2},
        {"type": "loss", "key": "Loss"},
        {"type": "learning-rate", "key": "LearningRate"},
        {"type": "grad-norm", "key": "GradientNorm/", "parameters": "total", "ord": 2.0},
        {"type": "grad-mean", "key": "GradientMean/", "parameters": "total"},
        {"type": "grad-minmax", "key": "GradientMinMax/", "parameters": "total"},
        {"type": "param-norm", "key": "ParameterNorm/", "parameters": "total", "ord": 2.0},
        {"type": "param-mean", "key": "ParameterMean/", "parameters": "total"},
        {"type": "param-minmax", "key": "ParameterMinMax/", "parameters": "total"},
    ]
    for cfg in cfgs:
        m = metrics.Metric.from_config(cfg)
        cfg2 = m.get_config()
        m2 = metrics.Metric.from_config(cfg2)
        assert m2.get_config() == cfg2


def test_tree_stats_against_torch():
    rng = np.random.RandomState(4)
    tree = {
        "enc": {"kernel": rng.randn(3, 3, 8).astype(np.float32)},
        "head": {"bias": rng.randn(8).astype(np.float32)},
    }

    norms = F.tree_norm(tree)
    t_enc = torch.from_numpy(tree["enc"]["kernel"]).norm(p=2).item()
    t_head = torch.from_numpy(tree["head"]["bias"]).norm(p=2).item()
    assert norms["enc.kernel"] == pytest.approx(t_enc, rel=1e-5)
    t_total = torch.tensor([t_enc, t_head]).norm(p=2).item()
    assert norms["total"] == pytest.approx(t_total, rel=1e-5)

    mean = F.tree_mean(tree)
    n1, m1 = mean["enc.kernel"]
    assert n1 == tree["enc"]["kernel"].size
    assert m1 == pytest.approx(tree["enc"]["kernel"].mean(), rel=1e-4)
    n_tot, m_tot = mean["total"]
    exp = (tree["enc"]["kernel"].sum() + tree["head"]["bias"].sum()) / n_tot
    assert m_tot == pytest.approx(exp, rel=1e-4)

    mm = F.tree_minmax(tree)
    assert mm["total"][0] == pytest.approx(
        min(tree["enc"]["kernel"].min(), tree["head"]["bias"].min()), rel=1e-5)


def test_grad_param_metrics_selection():
    rng = np.random.RandomState(5)
    grads = {"enc": {"k": rng.randn(4, 4).astype(np.float32)},
             "head": {"b": rng.randn(4).astype(np.float32)}}
    ctx = MetricContext(lr=1e-4, params=grads, grads=grads)

    m = metrics.Metric.from_config({"type": "grad-norm", "parameters": "all"})
    out = m(ctx, None, None, None, 0.0)
    assert "GradientNorm/enc.k" in out and "GradientNorm/total" in out

    m = metrics.Metric.from_config(
        {"type": "grad-norm", "parameters": {"encoder": ["enc."]}})
    out = m(ctx, None, None, None, 0.0)
    assert set(out) == {"GradientNorm/encoder"}

    m = metrics.Metric.from_config({"type": "param-minmax", "parameters": "total"})
    out = m(ctx, None, None, None, 0.0)
    assert "ParameterMinMax/total/min" in out

    m = metrics.Metric.from_config({"type": "learning-rate"})
    assert m(ctx, None, None, None, 0.0)["LearningRate"] == pytest.approx(1e-4)


METRIC_CFGS = [
    {"type": "epe"},
    {"type": "fl-all"},
    {"type": "aae"},
    {"type": "aae", "masked": True},
    {"type": "flow-magnitude"},
    {"type": "flow-magnitude", "masked": True},
    {"type": "loss"},
    {"type": "learning-rate"},
    {"type": "grad-norm", "parameters": "all"},
    {"type": "grad-mean"},
    {"type": "grad-minmax"},
    {"type": "param-norm"},
    {"type": "param-mean", "parameters": "all"},
    {"type": "param-minmax"},
]


@pytest.mark.parametrize(
    "cfg", METRIC_CFGS,
    ids=lambda c: c["type"] + ("-masked" if c.get("masked") else ""))
def test_deferred_value_equals_immediate(cfg):
    """``compute`` launches and leaves its scalars where they are (the
    traceable metrics', unfetched, on the device); fetched later, alone or
    through the inspector's group, they are the values ``__call__`` reads
    at once."""
    import jax
    import jax.numpy as jnp

    from raft_meets_dicl_tpu.inspect.summary import MetricsGroup

    est, tgt, valid = _random_flow(8)
    rng = np.random.RandomState(9)
    tree = {"enc": {"k": rng.randn(4, 4).astype(np.float32)},
            "head": {"b": rng.randn(4).astype(np.float32)}}
    ctx = MetricContext(lr=2.5e-4, params=tree, grads=tree)
    est, loss = jnp.asarray(est), jnp.float32(1.75)

    m = metrics.Metric.from_config(cfg)
    immediate = m(ctx, est, tgt, valid, loss)
    assert immediate and all(type(v) is float for v in immediate.values())

    launched = m.compute(ctx, est, tgt, valid, loss)
    if m.traceable:
        assert all(isinstance(v, jax.Array) for v in launched.values())
    assert F.fetch_scalars(launched) == immediate

    group = MetricsGroup(1, "T/", [m])
    group.compute(ctx, est, tgt, valid, loss)
    taken = group.take()
    assert group.take() == [{}]         # handed over once
    late = group.reduce(F.fetch_scalars(taken))
    want = m.reduce({k: [v] for k, v in immediate.items()})
    assert list(late) == [f"T/{k}" for k in want]
    for k, v in want.items():
        assert late[f"T/{k}"] == pytest.approx(v, rel=1e-6, abs=1e-7)


def test_validation_metric_reads_at_once():
    """Validation accumulates floats, the same numbers as before: the mean
    over batches of what the functional form gives."""
    from raft_meets_dicl_tpu.inspect.summary import ValidationMetric

    vm = ValidationMetric(metrics.Metric.from_config({"type": "epe"}),
                          "mean", True)
    want = []
    for seed in (10, 11):
        est, tgt, valid = _random_flow(seed)
        vm.add(MetricContext(), est, tgt, valid, 0.5)
        want.append(float(F.end_point_error(est, tgt, valid)["mean"]))
    assert all(type(v) is float for vs in vm.values.values() for v in vs)
    res = dict(vm.result())
    assert res["EndPointError/mean"] == float(np.mean(want))
    assert set(res) == {"EndPointError/mean", "EndPointError/1px",
                        "EndPointError/3px", "EndPointError/5px"}


def test_metrics_group_and_collectors():
    est, tgt, valid = _random_flow(6)
    ms = metrics.Metrics.from_config(
        [{"type": "epe"}, {"type": "fl-all"}, {"type": "loss"}])
    res = ms(MetricContext(), est, tgt, valid, loss=1.25)
    assert res["Loss"] == 1.25
    assert "EndPointError/mean" in res and "Fl-all" in res

    cs = metrics.Collectors.from_config([{"type": "mean"}])
    cs.collect({"a": 1.0, "b": float("nan")})
    cs.collect({"a": 3.0, "b": 2.0})
    out = cs.results()["mean"]
    assert out["a"] == pytest.approx(2.0)
    assert out["b"] == pytest.approx(2.0)  # NaN skipped


def test_evaluator_end_to_end():
    """Random-init raft/baseline → EPE computed end-to-end per sample."""
    import jax

    import raft_meets_dicl_tpu.evaluation as evaluation
    import raft_meets_dicl_tpu.models as models

    spec = models.load({
        "name": "RAFT", "id": "raft-eval-test",
        "model": {"type": "raft/baseline",
                  "parameters": {"iterations": 2}},
        "loss": {"type": "raft/sequence"},
        "input": {},
    })
    model = spec.model

    rng = np.random.RandomState(7)
    img1 = rng.rand(2, 64, 96, 3).astype(np.float32)
    img2 = rng.rand(2, 64, 96, 3).astype(np.float32)
    flow = rng.randn(2, 64, 96, 2).astype(np.float32)
    valid = np.ones((2, 64, 96), bool)

    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1])

    loader = spec.input.apply([(img1, img2, flow, valid, [
        _meta(i) for i in range(2)])]).jax().loader(batch_size=1)

    ms = metrics.Metrics.from_config([{"type": "epe"}, {"type": "fl-all"}])
    collectors = metrics.Collectors.from_config([{"type": "mean"}])

    n = 0
    for sample in evaluation.evaluate(model, variables, loader,
                                      show_progress=False):
        assert sample.final.shape == (64, 96, 2)
        assert np.all(np.isfinite(sample.final))
        res = ms(MetricContext(), sample.final, sample.target, sample.valid,
                 loss=0.0)
        assert np.isfinite(res["EndPointError/mean"])
        collectors.collect(res)
        n += 1

    assert n == 2
    summary = collectors.results()["mean"]
    assert np.isfinite(summary["EndPointError/mean"])


def _meta(i):
    from raft_meets_dicl_tpu.data.collection import Metadata, SampleArgs, SampleId

    return Metadata(
        valid=True,
        dataset_id="test",
        sample_id=SampleId(format="test/{id}",
                           img1=SampleArgs([], {"id": i}),
                           img2=SampleArgs([], {"id": i + 1})),
        original_extents=((0, 64), (0, 96)),
    )
