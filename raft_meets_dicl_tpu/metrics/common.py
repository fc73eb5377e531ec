"""Metric registry base + collection/collector machinery.

Mirrors the reference's config-constructible metric protocol
(src/metrics/common.py:5-41) and the eval-side Collector pipeline
(src/cmd/eval.py:22-109), reshaped for the pure-function world: instead of
a live torch module + optimizer, ``compute`` receives a ``MetricContext``
carrying the current params/grads pytrees and learning rate.
"""

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, List, Optional

import numpy as np

from . import functional as F


@dataclass
class MetricContext:
    """What train-time metrics may look at besides estimate/target.

    ``params``/``grads`` are pytrees (host or device); ``lr`` is the current
    learning rate. Eval-time metrics receive an empty context.
    """

    lr: Optional[float] = None
    params: Any = None
    grads: Any = None


class Metric:
    type = None

    # whether ``compute`` is a pure function of (estimate, target, valid,
    # loss): the inspector's MetricsGroup then traces it into the one
    # program it launches a step
    traceable = False

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(
                f"invalid metric type '{cfg['type']}', expected '{cls.type}'"
            )

    @classmethod
    def from_config(cls, cfg):
        from . import flowmetrics, trainmetrics

        types = [
            flowmetrics.EndPointError,
            flowmetrics.FlAll,
            flowmetrics.AverageAngularError,
            flowmetrics.FlowMagnitude,
            trainmetrics.Loss,
            trainmetrics.LearningRate,
            trainmetrics.GradientNorm,
            trainmetrics.GradientMean,
            trainmetrics.GradientMinMax,
            trainmetrics.ParameterNorm,
            trainmetrics.ParameterMean,
            trainmetrics.ParameterMinMax,
        ]
        types = {t.type: t for t in types}

        return types[cfg["type"]].from_config(cfg)

    def get_config(self):
        raise NotImplementedError

    def compute(self, ctx, estimate, target, valid, loss):
        """Launch the computation and return {key: scalar} without
        waiting for it: a scalar is an on-device array that may still be
        in flight, or a float where the host has the value already.
        ``estimate``/``target`` are NHWC flow arrays (batched or single),
        ``valid`` the matching mask."""
        raise NotImplementedError

    def __call__(self, ctx, estimate, target, valid, loss):
        """{key: float}: ``compute``, then one fetch of all its scalars
        (validation and evaluation, which read every value at once)."""
        return F.fetch_scalars(OrderedDict(
            self.compute(ctx, estimate, target, valid, loss)))

    def reduce(self, values):
        """Reduce accumulated per-step value lists {key: [floats]}."""
        return {k: float(np.mean(vs)) for k, vs in values.items()}


class Metrics:
    """Ordered list of metrics evaluated together (src/cmd/eval.py:93-109)."""

    @classmethod
    def from_config(cls, cfg):
        return cls([Metric.from_config(c) for c in cfg])

    def __init__(self, metrics: List[Metric]):
        self.metrics = list(metrics)

    def get_config(self):
        return [m.get_config() for m in self.metrics]

    def __call__(self, ctx, estimate, target, valid, loss):
        result = OrderedDict()
        for metric in self.metrics:
            result.update(metric(ctx, estimate, target, valid, loss))
        return result


class Collector:
    type = None

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(
                f"invalid collector type '{cfg['type']}', expected '{cls.type}'"
            )

    @classmethod
    def from_config(cls, cfg):
        types = {MeanCollector.type: MeanCollector}
        return types[cfg["type"]].from_config(cfg)

    def collect(self, metrics):
        raise NotImplementedError

    def result(self):
        raise NotImplementedError

    def __call__(self, metrics):
        self.collect(metrics)


class MeanCollector(Collector):
    """Running per-key mean over collected metric dicts, NaN-skipping
    (src/cmd/eval.py:46-74)."""

    type = "mean"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls()

    def __init__(self):
        self.results = OrderedDict()

    def collect(self, metrics):
        for k, v in metrics.items():
            if np.isnan(v):
                continue
            self.results.setdefault(k, []).append(v)

    def result(self):
        return OrderedDict((k, float(np.mean(vs))) for k, vs in self.results.items())


class Collectors:
    @classmethod
    def from_config(cls, cfg):
        return cls([Collector.from_config(c) for c in cfg])

    def __init__(self, collectors: List[Collector]):
        self.collectors = list(collectors)

    def collect(self, metrics):
        for collector in self.collectors:
            collector.collect(metrics)

    def results(self):
        return {c.type: c.result() for c in self.collectors}
