"""DICL correlation module: MatchingNet cost over displaced feature pairs.

Behavioral equivalent of reference src/models/common/corr/dicl.py:8-61 in
NHWC: sample the second frame's features at the (2r+1)² displaced positions
around the current flow, stack with frame-1 features, run the MatchingNet
per displacement (displacements ride the batch axis through the convs), and
apply the displacement-aware projection.
"""

from typing import Any

import flax.linen as nn
import jax

from ..blocks.dicl import DisplacementAwareProjection, MatchingNet
from .common import (
    SoftArgMaxFlowRegression,
    SoftArgMaxFlowRegressionWithDap,
    record_matching_bytes,
    sample_window_fast,
)

__all__ = ["CorrelationModule", "SoftArgMaxFlowRegression",
           "SoftArgMaxFlowRegressionWithDap"]


class CorrelationModule(nn.Module):
    feature_dim: int
    radius: int
    dap_init: str = "identity"
    norm_type: str = "batch"
    mnet_scale: float = 1
    dtype: Any = None

    @property
    def output_dim(self):
        return (2 * self.radius + 1) ** 2

    @nn.compact
    def __call__(self, f1, f2, coords, dap=True, train=False, frozen_bn=False):
        b, h, w, _ = f1.shape

        # scopes: a device trace tells sampler, cost net and projection
        # apart by the name stack of their operations
        with jax.named_scope("matching/sampler"):
            window = sample_window_fast(f2, coords, self.radius)
        # unstacked pair: MatchingNet's first conv computes the f1 half
        # once and selects it for each of the (2r+1)² displacements by a
        # contraction over the batch, so that neither the stacked
        # (B, du, dv, H, W, 2C) volume nor a repeated f1 half is written
        # out (``ConvBlock``; channel order f1-first matches
        # ``stack_pair``, so parameters and checkpoints are unchanged)
        if self.dtype is not None:
            f1 = f1.astype(self.dtype)
            window = window.astype(self.dtype)
        if not self.is_initializing():
            record_matching_bytes(f1, window)

        with jax.named_scope("matching/mnet"):
            cost = MatchingNet(norm_type=self.norm_type,
                               scale=self.mnet_scale, dtype=self.dtype)(
                (f1, window), train, frozen_bn
            )  # (B, H, W, du, dv) float32

        if dap:
            with jax.named_scope("matching/dap"):
                cost = DisplacementAwareProjection(
                    (self.radius, self.radius), init=self.dap_init
                )(cost)

        return cost.reshape(b, h, w, self.output_dim)
