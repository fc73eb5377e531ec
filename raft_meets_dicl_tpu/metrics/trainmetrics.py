"""Training-state metrics: loss, learning rate, gradient/parameter stats.

The reference versions hook a live torch module/optimizer
(src/metrics/loss.py, lr.py, grad.py, param.py); here the equivalent state
arrives as pytrees + a float lr in the ``MetricContext``. Parameter
selection semantics ('total' | 'all' | [names] | {group: [prefixes]})
match the reference exactly.
"""

from typing import List, Union

import numpy as np

from . import functional as F
from .common import Metric


class Loss(Metric):
    type = "loss"
    traceable = True

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "Loss"))

    def __init__(self, key: str = "Loss"):
        self.key = key

    def get_config(self):
        return {"type": self.type, "key": self.key}

    def compute(self, ctx, estimate, target, valid, loss):
        return {self.key: loss}


class LearningRate(Metric):
    type = "learning-rate"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "LearningRate"))

    def __init__(self, key: str = "LearningRate"):
        self.key = key

    def get_config(self):
        return {"type": self.type, "key": self.key}

    def compute(self, ctx, estimate, target, valid, loss):
        return {self.key: float(ctx.lr) if ctx.lr is not None else float("nan")}

    def reduce(self, values):
        return {k: vs[-1] for k, vs in values.items()}


def _normalize_params(params):
    if not isinstance(params, (list, dict)) and params != "all":
        return [params]
    return params


class _TreeMetric(Metric):
    """Shared parameter-selection logic over a named-stat dict."""

    def __init__(self, key, params):
        self.key = key
        self.params = _normalize_params(params)

    def get_config(self):
        return {"type": self.type, "key": self.key, "parameters": self.params}

    def _tree(self, ctx):
        raise NotImplementedError

    def _select(self, stats, collect):
        """stats: {name: stat}; collect(list-of-stats) aggregates a group."""
        if self.params == "all":
            return dict(stats)
        if isinstance(self.params, dict):
            out = {}
            for group, prefixes in self.params.items():
                # a group of exactly ['total'] passes the synthetic whole-
                # tree aggregate through (the reference configs' convention,
                # cfg/inspect/detailed-ctf3.yaml)
                if list(prefixes) == ["total"]:
                    out[group] = stats["total"]
                    continue
                # each leaf counts once even if several prefixes match, and
                # the synthetic 'total' aggregate never joins a group
                sel = [v for k, v in stats.items()
                       if k != "total" and any(k.startswith(p) for p in prefixes)]
                if not sel:
                    raise ValueError(
                        f"metric '{self.type}': parameter group '{group}' "
                        f"(prefixes {prefixes}) matches no parameter; "
                        f"available: {sorted(stats)[:10]}..."
                    )
                out[group] = collect(sel)
            return out
        return {name: stats[name] for name in self.params}


class GradientNorm(_TreeMetric):
    type = "grad-norm"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "GradientNorm/"), float(cfg.get("ord", 2)),
                   cfg.get("parameters", "total"))

    def __init__(self, key: str = "GradientNorm/", ord: float = 2,
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, params)
        self.ord = ord

    def get_config(self):
        return super().get_config() | {"ord": self.ord}

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.grads is None:
            return {}
        norms = F.tree_norm(ctx.grads, self.ord)
        sel = self._select(
            norms,
            lambda ns: float(np.linalg.norm(np.asarray(ns), ord=self.ord)),
        )
        return {f"{self.key}{k}": v for k, v in sel.items()}

    def reduce(self, values):
        return {k: vs[-1] for k, vs in values.items()}


class GradientMean(_TreeMetric):
    type = "grad-mean"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "GradientMean/"), cfg.get("parameters", "total"))

    def __init__(self, key: str = "GradientMean/",
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, params)

    @staticmethod
    def _collect(stats):
        total = sum(n for n, _ in stats) or 1
        return (total, sum((n / total) * m for n, m in stats))

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.grads is None:
            return {}
        mean = F.tree_mean(ctx.grads)
        sel = self._select(mean, self._collect)
        return {f"{self.key}{k}": m for k, (_, m) in sel.items()}

    def reduce(self, values):
        return {k: vs[-1] for k, vs in values.items()}


class GradientMinMax(_TreeMetric):
    type = "grad-minmax"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "GradientMinMax/"), cfg.get("parameters", "total"))

    def __init__(self, key: str = "GradientMinMax/",
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, params)

    @staticmethod
    def _collect(stats):
        return (min(lo for lo, _ in stats), max(hi for _, hi in stats))

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.grads is None:
            return {}
        mm = self._select(F.tree_minmax(ctx.grads), self._collect)
        out = {f"{self.key}{k}/min": lo for k, (lo, _) in mm.items()}
        out |= {f"{self.key}{k}/max": hi for k, (_, hi) in mm.items()}
        return out

    def reduce(self, values):
        out = {}
        for k, vs in values.items():
            out[k] = min(vs) if k.endswith("/min") else max(vs)
        return out


class ParameterNorm(GradientNorm):
    type = "param-norm"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "ParameterNorm/"), float(cfg.get("ord", 2)),
                   cfg.get("parameters", "total"))

    def __init__(self, key: str = "ParameterNorm/", ord: float = 2,
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, ord, params)

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.params is None:
            return {}
        norms = F.tree_norm(ctx.params, self.ord)
        sel = self._select(
            norms,
            lambda ns: float(np.linalg.norm(np.asarray(ns), ord=self.ord)),
        )
        return {f"{self.key}{k}": v for k, v in sel.items()}


class ParameterMean(GradientMean):
    type = "param-mean"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "ParameterMean/"), cfg.get("parameters", "total"))

    def __init__(self, key: str = "ParameterMean/",
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, params)

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.params is None:
            return {}
        mean = F.tree_mean(ctx.params)
        sel = self._select(mean, self._collect)
        return {f"{self.key}{k}": m for k, (_, m) in sel.items()}


class ParameterMinMax(GradientMinMax):
    type = "param-minmax"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("key", "ParameterMinMax/"), cfg.get("parameters", "total"))

    def __init__(self, key: str = "ParameterMinMax/",
                 params: Union[str, List[str]] = "total"):
        super().__init__(key, params)

    def compute(self, ctx, estimate, target, valid, loss):
        if ctx.params is None:
            return {}
        mm = self._select(F.tree_minmax(ctx.params), self._collect)
        out = {f"{self.key}{k}/min": lo for k, (lo, _) in mm.items()}
        out |= {f"{self.key}{k}/max": hi for k, (_, hi) in mm.items()}
        return out
