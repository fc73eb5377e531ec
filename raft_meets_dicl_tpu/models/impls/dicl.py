"""DICL baseline: displacement-invariant cost learning, coarse-to-fine.

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/dicl.py ("Displacement-Invariant Matching Cost Learning
for Accurate Optical Flow Estimation", Wang et al.; upstream
jytime/DICL-Flow):

- the full displacement-shifted matching volume is built from *static*
  integer shifts — a pad + (2r+1)² slice stack (the reference fills a zero
  tensor per displacement in a python loop, dicl.py:212-241). The copies
  are not cheap: the stack is the model's largest array, and how it is
  masked and handed to the MatchingNet decides what the TPU compiler moves
  (``displaced_pair_volume``),
- cost volumes are (B, H, W, du, dv) channels-last, so the DAP is one MXU
  1x1 conv and soft-argmin/entropy are trailing-axis reductions,
- the coarse-to-fine ladder (levels 6..2, GA-Net p26 features) warps the
  second frame's features by the upsampled coarse flow and refines with
  dilated context networks exactly like the reference (dicl.py:150-297).
"""

from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry
from ...ops.upsample import interpolate_bilinear, upsample_flow_2x
from ..common import warp
from ..common.blocks.dicl import (
    ConvBlock,
    DisplacementAwareProjection,
    MatchingNet,
)
from ..common.corr.common import record_matching_bytes
from ..common.encoders import dicl as dicl_encoders
from ..config import register_loss, register_model
from ..model import Loss, Model, ModelAdapter, Result

_DEFAULT_CONTEXT_SCALE = {
    "level-6": 1.0,
    "level-5": 1.0,
    "level-4": 1.0,
    "level-3": 1.0,
    "level-2": 1.0,
}


def flow_entropy(cost, eps=1e-9):
    """Normalized entropy of the displacement distribution
    (reference FlowEntropy, dicl.py:31-50). cost: (B, H, W, du, dv) →
    (B, H, W, 1)."""
    b, h, w, du, dv = cost.shape

    p = nn.softmax(cost.reshape(b, h, w, du * dv), axis=-1)
    plogp = -p * jnp.log(jnp.clip(p, eps, 1.0 - eps))
    entropy = plogp.sum(axis=-1) / np.log(du * dv)
    return entropy[..., None]


def soft_argmin_flow(cost):
    """Soft-argmin flow regression (reference FlowRegression, dicl.py:53-85).

    cost: (B, H, W, du, dv) — du indexes x-displacement, dv indexes y.
    Returns (B, H, W, 2) flow (u, v).
    """
    b, h, w, du, dv = cost.shape
    ru, rv = (du - 1) // 2, (dv - 1) // 2

    prob = nn.softmax(cost.reshape(b, h, w, du * dv), axis=-1)
    prob = prob.reshape(b, h, w, du, dv)

    disp_u = jnp.arange(-ru, ru + 1, dtype=cost.dtype)
    disp_v = jnp.arange(-rv, rv + 1, dtype=cost.dtype)

    u = jnp.einsum("bhwuv,u->bhw", prob, disp_u)
    v = jnp.einsum("bhwuv,v->bhw", prob, disp_v)
    return jnp.stack((u, v), axis=-1)


def displaced_pair_volume(feat1, feat2, disp_range):
    """Stack feature pairs for every integer displacement in the range.

    Returns (B, du, dv, H, W, 2C): at displacement d, the second half of
    the channels holds ``feat2[p + d]`` (zeros outside), and hypotheses
    whose displaced features sum to zero (out of bounds / holes) are
    zeroed entirely, in both halves — reference compute_cost semantics
    (dicl.py:212-241), realized as static pad + slice instead of
    per-displacement copies.

    Whether hypothesis (i, j) counts at (y, x) is a function of the padded
    map at (y + j, x + i) alone, so the sum over channels and the
    comparison are taken there, once, on one channel; the du·dv slices of
    that mask select from the slices of the map and from ``feat1``. (Taken
    on the stack, as until PR 47, it was a reduction, a comparison and two
    multiplications over arrays of the stack's size: 1.64 GB a half in
    float32 at level 2 of a served 512x1024 batch, 56 ms of a 207 ms batch
    on the v5e, more than the MatchingNet they fed.)

    The form is chosen by what the TPU compiler makes of it behind
    ``MatchingNet`` (tests/test_matching_compile.py holds it to this):

    - The mask selects, it does not multiply. A selection hands its
      operand through unchanged, so the compiler may round the stack to
      the bfloat16 its convolution reads *before* it moves it: both halves
      are written, relaid to the item-minor layout the convolutions run in
      and read as bfloat16, once each. A product's operand stays float32
      at twice the bytes. The values are those of the product (a masked
      element is ``+0`` where ``x * 0`` carried x's sign).
    - The selection comes after the stack, with the mask's own stack (one
      channel, 1/C of a half). Selected slice by slice before it, the
      halves reach the first convolution straight from their relayout
      copies, and the compiler then no longer fuses that convolution into
      the second layer's as its producer: the first activation
      (``392x128x256x96`` float32, 4.93 GB) is written and read back.
    - The halves are concatenated, as ever: the compiler takes the two as
      operands of that fusion and never writes the 2C-channel volume.
      ``MatchingNet``'s pair form (frame one's half convolved once and
      repeated by a one-hot contraction) cannot stand in: ``feat1`` under
      the mask differs between hypotheses inside a 3x3 support, so the
      shared half is not shared.
    """
    b, h, w, c = feat1.shape
    ru, rv = disp_range
    du, dv = 2 * ru + 1, 2 * rv + 1

    f2p = jnp.pad(feat2, ((0, 0), (rv, rv), (ru, ru), (0, 0)))
    # occluded / out-of-bounds positions of the padded map
    v = jax.lax.stop_gradient(f2p).sum(axis=-1, keepdims=True) != 0

    def hypotheses(x):
        # x-displacement di = i - ru, y-displacement dj = j - rv
        return jnp.stack(
            [x[:, j : j + h, i : i + w] for i in range(du) for j in range(dv)],
            axis=1,
        ).reshape(b, du, dv, h, w, x.shape[-1])

    shifted, valid = hypotheses(f2p), hypotheses(v)
    f1 = jnp.broadcast_to(feat1[:, None, None], shifted.shape)
    return jnp.concatenate(
        (jnp.where(valid, f1, 0), jnp.where(valid, shifted, 0)), axis=-1)


class CtfContextNet(nn.Module):
    """Dilated context network; level 2/3 depth by default, levels 4/5/6
    use progressively fewer layers (reference dicl.py:88-147)."""

    level: int = 3

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        plans = {
            # (channels, dilation) per layer; final 3x3 conv to 2 channels
            3: ((64, 1), (128, 2), (128, 4), (96, 8), (64, 16), (32, 1)),
            4: ((64, 1), (128, 2), (128, 4), (64, 8), (32, 1)),
            5: ((64, 1), (128, 2), (64, 4), (32, 1)),
            6: ((64, 1), (64, 2), (32, 1)),
        }
        plan = plans[min(max(self.level, 3), 6)]

        for ch, dil in plan:
            x = ConvBlock(ch, dilation=dil)(x, train, frozen_bn)
        return nn.Conv(2, (3, 3))(x)  # with bias, like the reference


class FlowLevel(nn.Module):
    """One coarse-to-fine level: cost volume → DAP → soft-argmin (+ coarse
    flow) → context refinement (reference FlowLevel, dicl.py:150-241)."""

    feature_channels: int
    level: int
    maxdisp: tuple
    dap_init: str = "identity"

    @nn.compact
    def __call__(self, img1, feat1, feat2, flow_coarse, raw=False, dap=True,
                 ctx=True, scale=1.0, train=False, frozen_bn=False):
        b, h, w, _ = feat1.shape

        # scopes: a device trace tells warp, shift stack, cost net,
        # projection and context network apart by the name stack of their
        # operations (``compile/owners.SCOPES``)
        flow_up = None
        if flow_coarse is not None:
            with jax.named_scope("warp"):
                flow_up = jax.lax.stop_gradient(upsample_flow_2x(flow_coarse))
                feat2, _mask = warp.warp_backwards(feat2, flow_up)
            if not self.is_initializing():
                telemetry.note_trace("warp_calls", 1)

        # matching cost
        with jax.named_scope("matching"):
            mvol = displaced_pair_volume(feat1, feat2, self.maxdisp)
        if not self.is_initializing():
            record_matching_bytes(mvol)
        with jax.named_scope("matching/mnet"):
            cost = MatchingNet()(mvol, train, frozen_bn)  # (B, H, W, du, dv)
        if dap:
            with jax.named_scope("matching/dap"):
                cost = DisplacementAwareProjection(
                    self.maxdisp, init=self.dap_init)(cost)

        # raw flow via soft-argmin, plus the coarse estimate; the entropy
        # of the same softmax for the context network
        with jax.named_scope("matching"):
            flow = soft_argmin_flow(cost)
            flow = flow + flow_up if flow_up is not None else flow
            entr = jax.lax.stop_gradient(flow_entropy(cost)) if ctx else None
        flow_raw = flow if raw else None

        if ctx:
            with jax.named_scope("context"):
                img1 = interpolate_bilinear(img1, (h, w))
                ctxf = jnp.concatenate(
                    (jax.lax.stop_gradient(flow), entr, feat1, img1), axis=-1
                )
                flow = flow + CtfContextNet(self.level)(
                    ctxf, train, frozen_bn) * scale

        return flow, flow_raw


class DiclModule(nn.Module):
    """Coarse-to-fine DICL stack over GA-Net features.

    ``levels`` picks the refinement ladder: (6..2) with p26 features is the
    baseline (reference DiclModule, dicl.py:244-297), (6..3) with a
    p36-shaped encoder is the 64to8 variant (reference dicl_64to8.py:102-151
    — its hand-written FeatureNet is the same hourglass minus the final
    1/4-level head).
    """

    disp_ranges: Dict[str, Any]
    dap_init: str = "identity"
    feature_channels: int = 32
    levels: tuple = (6, 5, 4, 3, 2)

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False, raw=False,
                 dap=True, ctx=True, context_scale=None, final_only=False):
        context_scale = context_scale or {
            f"level-{lvl}": 1.0 for lvl in self.levels
        }
        finest = min(self.levels)

        # encoder heads at exactly the levels the ladder consumes
        # (encoder level i is H/2^(i+1): flow level L sits at encoder level L-1)
        feature = dicl_encoders.FeatureEncoderGa(
            output_dim=self.feature_channels, depth=6,
            out_levels=tuple(lvl - 1 for lvl in sorted(self.levels)),
        )
        with jax.named_scope("encoders"):
            f1, f2 = feature((img1, img2), train, frozen_bn)  # finest-first

        flow = None
        out = []
        for lvl in sorted(self.levels, reverse=True):
            level = FlowLevel(
                self.feature_channels, lvl,
                tuple(self.disp_ranges[f"level-{lvl}"]), self.dap_init,
            )
            flow, flow_raw = level(
                img1, f1[lvl - finest], f2[lvl - finest], flow, raw=raw,
                dap=dap, ctx=ctx, scale=context_scale[f"level-{lvl}"],
                train=train, frozen_bn=frozen_bn,
            )
            out = [flow, flow_raw] + out

        if final_only:
            # an inference program reads ``Result.final()`` alone: the
            # finest refined flow, without the coarser levels' flows and
            # the ``raw`` copies as program outputs (the arithmetic that
            # leads to it is every level's, so nothing else is left out)
            return [flow]
        # finest first: [flow_f, flow_f_raw, ..., flow6, flow6_raw]
        return [f for f in out if f is not None]


@register_model
class Dicl(Model):
    """``dicl/baseline`` (reference dicl.py:300-375)."""

    type = "dicl/baseline"
    # 1: the ladder states its scopes (``warp``, ``matching``, ``context``)
    # and notes ``matching_volume_bytes`` and ``warp_calls``
    notes_revision = 1

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        param_cfg = cfg["parameters"]
        return cls(
            disp_ranges=param_cfg["displacement-range"],
            dap_init=param_cfg.get("dap-init", "identity"),
            feature_channels=param_cfg.get("feature-channels", 32),
            arguments=cfg.get("arguments", {}),
            on_epoch_args=cfg.get("on-epoch", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": False}),
        )

    def __init__(self, disp_ranges, dap_init="identity", feature_channels=32,
                 arguments={}, on_epoch_args={},
                 on_stage_args={"freeze_batchnorm": False}):
        self.disp_ranges = dict(disp_ranges)
        self.dap_init = dap_init
        self.feature_channels = feature_channels

        super().__init__(
            DiclModule(
                disp_ranges=dict(disp_ranges), dap_init=dap_init,
                feature_channels=feature_channels,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "raw": False,
            "dap": True,
            "context_scale": _DEFAULT_CONTEXT_SCALE,
        }
        return {
            "type": self.type,
            "parameters": {
                "feature-channels": self.feature_channels,
                "displacement-range": self.disp_ranges,
                "dap-init": self.dap_init,
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": False} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return DiclAdapter(self)


@register_model
class Dicl64to8(Model):
    """``dicl/64to8``: the DICL ladder stopped at 1/8 resolution, levels
    6..3 (reference dicl_64to8.py:154-202)."""

    type = "dicl/64to8"
    notes_revision = 1

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        param_cfg = cfg["parameters"]
        return cls(
            disp_ranges=param_cfg["displacement-range"],
            dap_init=param_cfg.get("dap-init", "identity"),
            feature_channels=param_cfg.get("feature-channels", 32),
            arguments=cfg.get("arguments", {}),
        )

    def __init__(self, disp_ranges, dap_init="identity", feature_channels=32,
                 arguments={}):
        self.disp_ranges = dict(disp_ranges)
        self.dap_init = dap_init
        self.feature_channels = feature_channels

        super().__init__(
            DiclModule(
                disp_ranges=dict(disp_ranges), dap_init=dap_init,
                feature_channels=feature_channels, levels=(6, 5, 4, 3),
            ),
            arguments=arguments,
        )

    def get_config(self):
        default_args = {
            "raw": False,
            "dap": True,
            "context_scale": {f"level-{lvl}": 1.0 for lvl in (6, 5, 4, 3)},
        }
        return {
            "type": self.type,
            "parameters": {
                "feature-channels": self.feature_channels,
                "displacement-range": self.disp_ranges,
                "dap-init": self.dap_init,
            },
            "arguments": default_args | self.arguments,
        }

    def get_adapter(self) -> ModelAdapter:
        return DiclAdapter(self)


class DiclAdapter(ModelAdapter):
    def wrap_result(self, result, original_shape) -> Result:
        return DiclResult(result, original_shape)


class DiclResult(Result):
    """List of per-level flows, finest (1/4 resolution) first
    (reference dicl.py:386-413)."""

    def __init__(self, output, target_shape):
        super().__init__()
        self.result = output
        self.shape = target_shape  # (H, W) of the input images

    def output(self, batch_index=None):
        if batch_index is None:
            return self.result
        return [x[batch_index : batch_index + 1] for x in self.result]

    def final(self):
        flow = jax.lax.stop_gradient(self.result[0])

        _, fh, fw, _ = flow.shape
        th, tw = self.shape

        # the model's last resize takes Up8's place in the phase table
        with jax.named_scope("up8"):
            flow = interpolate_bilinear(flow, (th, tw))
            return flow * jnp.asarray([tw / fw, th / fh], dtype=flow.dtype)

    def intermediate_flow(self):
        return self.result


@register_loss
class MultiscaleLoss(Loss):
    """``dicl/multiscale``: weighted per-level distances on upsampled flow
    (reference dicl.py:416-472)."""

    type = "dicl/multiscale"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("arguments", {}))

    def __init__(self, arguments={}):
        super().__init__(arguments)

    def get_config(self):
        default_args = {"ord": 2, "mode": "bilinear"}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, weights, ord=2,
                mode="bilinear", valid_range=None):
        if mode != "bilinear":
            raise ValueError(f"unsupported upsampling mode '{mode}'")

        th, tw = target.shape[1:3]
        valid_f = valid.astype(jnp.float32)

        loss = 0.0
        for i, flow in enumerate(result):
            _, fh, fw, _ = flow.shape
            flow = interpolate_bilinear(flow, (th, tw))
            flow = flow * jnp.asarray([tw / fw, th / fh], dtype=flow.dtype)

            mask = valid_f
            if valid_range is not None:
                mask = mask * (jnp.abs(target[..., 0]) < valid_range[i][0])
                mask = mask * (jnp.abs(target[..., 1]) < valid_range[i][1])

            if ord == "robust":
                # robust norm of the original DICL implementation
                dist = (jnp.abs(flow - target).sum(axis=-1) + 1e-8) ** 0.4
            else:
                dist = jnp.linalg.norm(flow - target, ord=float(ord), axis=-1)

            mean = jnp.sum(dist * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            loss = loss + weights[i] * mean

        return loss / len(result)
