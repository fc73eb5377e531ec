"""wip/warp/2: recurrent warping units, coarse-to-fine
(kept-registered experiment).

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/outdated/wip_recwarp.py: per-level recurrent flow units —
sample the second frame's features over a displaced window around the
current coordinates ("warp with context"), run a MatchingNet + DAP, and
regress a soft-argmin delta — applied coarse-to-fine over a GA-Net p26
pyramid with coordinate upsampling between levels.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ....ops.upsample import interpolate_bilinear
from ...common.blocks.dicl import DisplacementAwareProjection, MatchingNet
from ...common.corr.common import sample_window, stack_pair
from ...common.encoders.dicl import FeatureEncoderGa
from ...common.grid import coordinate_grid
from ...config import register_model
from ...model import Model, ModelAdapter, Result
from .wip_warp import WipAdapter  # noqa: F401  (shape parity for tooling)

_LEVELS = 5  # 1/4 .. 1/64


class _RecurrentFlowUnit(nn.Module):
    """Window-sampled cost volume → DAP → soft-argmin coordinate update
    (reference wip_recwarp.py:106-178)."""

    feature_channels: int
    disp_range: tuple

    @nn.compact
    def __call__(self, feat1, feat2, coords, dap=True, train=False,
                 frozen_bn=False):
        from ..dicl import soft_argmin_flow

        assert self.disp_range[0] == self.disp_range[1], (
            "square displacement windows only"
        )
        radius = self.disp_range[0]

        window = sample_window(feat2, coords, radius)
        feat = stack_pair(feat1, window)

        cost = MatchingNet()(feat, train, frozen_bn)  # (B, H, W, du, dv)
        if dap:
            cost = DisplacementAwareProjection(self.disp_range)(cost)

        delta = soft_argmin_flow(cost)
        return coords + delta


class WipRecWarpModule(nn.Module):
    """Coarse-to-fine recurrent warping (reference WipModule,
    wip_recwarp.py:181-236)."""

    feature_channels: int = 32
    disp: tuple = ((3, 3),) * _LEVELS

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 iterations=(1,) * _LEVELS, dap=True, final_only=False):
        fnet = FeatureEncoderGa(output_dim=self.feature_channels, depth=6,
                                out_levels=(1, 2, 3, 4, 5))
        f1, f2 = fnet((img1, img2), train, frozen_bn)  # finest-first

        rfus = [
            _RecurrentFlowUnit(self.feature_channels, tuple(self.disp[i]))
            for i in range(_LEVELS)
        ]

        b = img1.shape[0]
        coords = coordinate_grid(b, *f1[-1].shape[1:3])

        out = []
        for i in range(_LEVELS - 1, -1, -1):  # coarse → fine
            h2, w2 = f1[i].shape[1:3]

            if coords.shape[1:3] != (h2, w2):
                h1, w1 = coords.shape[1:3]
                coords = interpolate_bilinear(coords, (h2, w2))
                coords = coords * jnp.asarray([w2 / w1, h2 / h1],
                                              dtype=coords.dtype)

            coords0 = coordinate_grid(b, h2, w2)

            for _ in range(iterations[i]):
                coords = rfus[i](f1[i], f2[i], coords, dap=dap, train=train,
                                 frozen_bn=frozen_bn)
                out.append(coords - coords0)

        return out


@register_model
class WipRecWarp(Model):
    """``wip/warp/2`` (reference wip_recwarp.py:237-283)."""

    type = "wip/warp/2"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            feature_channels=p.get("feature-channels", 32),
            disp=p.get("disp-range", [(3, 3)] * _LEVELS),
            arguments=cfg.get("arguments", {}),
        )

    def __init__(self, feature_channels=32, disp=((3, 3),) * _LEVELS,
                 arguments={}):
        self.feature_channels = feature_channels
        self.disp = tuple(tuple(d) for d in disp)

        super().__init__(
            WipRecWarpModule(feature_channels=feature_channels,
                             disp=self.disp),
            arguments=arguments,
        )

    def get_config(self):
        default_args = {"iterations": [1] * _LEVELS, "dap": True}
        return {
            "type": self.type,
            "parameters": {
                "feature-channels": self.feature_channels,
                "disp-range": [list(d) for d in self.disp],
            },
            "arguments": default_args | self.arguments,
        }

    def get_adapter(self) -> ModelAdapter:
        return WipRecWarpAdapter(self)


class WipRecWarpAdapter(ModelAdapter):
    def wrap_result(self, result, original_shape) -> Result:
        return WipRecWarpResult(result, original_shape)


class WipRecWarpResult(Result):
    """Per-iteration flow list; stored finest-first like the reference
    (wip_recwarp.py:286-314)."""

    def __init__(self, output, shape):
        super().__init__()
        self.result = list(reversed(output))
        self.shape = shape

    def output(self, batch_index=None):
        if batch_index is None:
            return self.result
        return [x[batch_index : batch_index + 1] for x in self.result]

    def final(self):
        flow = jax.lax.stop_gradient(self.result[0])

        _, fh, fw, _ = flow.shape
        th, tw = self.shape

        flow = interpolate_bilinear(flow, (th, tw))
        return flow * jnp.asarray([tw / fw, th / fh], dtype=flow.dtype)

    def intermediate_flow(self):
        return self.result
