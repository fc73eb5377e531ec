"""raft/cl: RAFT with hierarchical cost learning (kept-registered experiment).

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/outdated/raft_cl.py: a GA-Net hourglass produces raw
ladder features; the frame-2 head builds a 1/8..1/64 pyramid, the frame-1
head lifts every level to 1/8 through learned convex 2x upsampling chains;
a per-level MatchingNet+DAP correlation module feeds the RAFT GRU.

The auxiliary correlation losses (hinge / mse over self- and permuted
feature pairs) need the matching networks' parameters, which a pure loss
function cannot reach — so here the *model* computes those example costs
when asked (``corr_loss_examples=True``, drawing the permutation from the
'permute' rng stream) and the losses consume them from the result dict.
"""

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ....ops.upsample import interpolate_bilinear
from ...common.blocks.dicl import (
    ConvBlock,
    DisplacementAwareProjection,
    MatchingNet,
)
from ...common.corr.common import sample_window, stack_pair
from ...common.encoders.dicl import FeatureEncoderGa
from ...common.encoders.raft import FeatureEncoderS3
from ...common.grid import coordinate_grid
from ...config import register_loss, register_model
from ...model import Loss, Model, ModelAdapter, Result
from ..raft import BasicUpdateBlock, Up8Network

_LEVELS = 4  # 1/8 .. 1/64
_LADDER_CHANNELS = {3: 64, 4: 96, 5: 128, 6: 160}


class _FeatureNetDown(nn.Module):
    """Frame-2 head: per-level output convs (reference raft_cl.py:87-106)."""

    output_dim: int

    @nn.compact
    def __call__(self, ladder, train=False, frozen_bn=False):
        return tuple(
            ConvBlock(self.output_dim)(x, train, frozen_bn) for x in ladder
        )


class _FeatureNetUp(nn.Module):
    """Frame-1 head: per-level output convs + learned convex 2x upsampling
    chains lifting every level to 1/8 (reference raft_cl.py:108-175)."""

    output_dim: int

    @nn.compact
    def __call__(self, ladder, train=False, frozen_bn=False):
        x3, x4, x5, x6 = ladder  # finest first, raw ladder channels

        u = [ConvBlock(self.output_dim)(x, train, frozen_bn) for x in ladder]

        def genmask(x):
            c = x.shape[-1]
            m = nn.relu(nn.Conv(c, (3, 3))(x))
            m = nn.Conv(9, (1, 1))(m)
            return nn.softmax(m, axis=-1)  # (B, h, w, 9)

        def upsample(mask, v):
            # the reference's mask-weighted 2x block upsampling
            # (raft_cl.py:135-151): coarse pixels expand into the mask's 2x2
            # sub-blocks, weighted over 9 softmax channels that sum to one
            b, h, w, _ = mask.shape
            c = v.shape[-1]
            m = mask.reshape(b, h // 2, 2, w // 2, 2, 9)
            vv = v[:, :, None, :, None, None, :]  # (B, h/2, 1, w/2, 1, 1, C)
            out = (m[..., None] * vv).sum(axis=5)  # (B, h/2, 2, w/2, 2, C)
            return out.reshape(b, h, w, c)

        m5 = genmask(x5)
        m4 = genmask(x4)
        m3 = genmask(x3)

        u6 = upsample(m3, upsample(m4, upsample(m5, u[3])))
        u5 = upsample(m3, upsample(m4, u[2]))
        u4 = upsample(m3, u[1])

        return u[0], u4, u5, u6  # all at 1/8


class _ClCorrelationModule(nn.Module):
    """Per-level MatchingNet cost over displaced windows
    (reference raft_cl.py:180-246). ``setup``-style so the example-cost
    computation for the auxiliary correlation losses runs through the SAME
    matching networks as the lookup."""

    feature_dim: int
    radius: int
    dap_init: str = "identity"

    def setup(self):
        self.mnets = [MatchingNet() for _ in range(_LEVELS)]
        self.daps = [
            DisplacementAwareProjection((self.radius, self.radius),
                                        init=self.dap_init)
            for _ in range(_LEVELS)
        ]

    def __call__(self, fmap1, fmap2, coords, dap=True, train=False,
                 frozen_bn=False):
        b, h, w, _ = coords.shape
        k = 2 * self.radius + 1

        out = []
        for i, (f1, f2) in enumerate(zip(fmap1, fmap2)):
            window = sample_window(f2, coords / 2 ** i, self.radius)
            mvol = stack_pair(f1, window)

            cost = self.mnets[i](mvol, train, frozen_bn)
            if dap:
                cost = self.daps[i](cost)

            out.append(cost.reshape(b, h, w, k * k))

        return jnp.concatenate(out, axis=-1)

    def example_costs(self, level, mvol, train=False, frozen_bn=False):
        """Level ``level``'s matching net applied to a prepared volume."""
        return self.mnets[level](mvol, train, frozen_bn)


class RaftClModule(nn.Module):
    """raft/cl network (reference RaftModule, raft_cl.py:251-339)."""

    dap_init: str = "identity"
    corr_radius: int = 3
    feature_dim: int = 32

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 iterations=12, upnet=True, flow_init=None,
                 corr_loss_examples=False, final_only=False):
        hdim = cdim = 128

        fnet = FeatureEncoderGa(depth=6, out_levels=(2, 3, 4, 5), heads=False)
        fnet_u = _FeatureNetUp(self.feature_dim)
        fnet_d = _FeatureNetDown(self.feature_dim)

        l1, l2 = fnet((img1, img2), train, frozen_bn)
        fmap1 = fnet_u(l1, train, frozen_bn)
        fmap2 = fnet_d(l2, train, frozen_bn)

        cnet = FeatureEncoderS3(output_dim=hdim + cdim, norm_type="batch")
        ctx = cnet(img1, train, frozen_bn)
        h = jnp.tanh(ctx[..., :hdim])
        x = nn.relu(ctx[..., hdim:])

        b, hc, wc, _ = fmap1[0].shape
        coords0 = coordinate_grid(b, hc, wc)
        coords1 = coords0 + flow_init if flow_init is not None else coords0

        cvol = _ClCorrelationModule(self.feature_dim, self.corr_radius,
                                    self.dap_init)
        update = BasicUpdateBlock(hdim)
        upnet8 = Up8Network()

        out = []
        for _ in range(iterations):
            coords1 = jax.lax.stop_gradient(coords1)
            flow = coords1 - coords0

            corr = cvol(fmap1, fmap2, coords1, train=train, frozen_bn=frozen_bn)

            h, d = update(h, x, corr, flow)
            coords1 = coords1 + d
            flow = coords1 - coords0

            flow_up = upnet8(h, flow)
            if not upnet:
                flow_up = 8.0 * interpolate_bilinear(
                    flow, (img1.shape[1], img1.shape[2]))
            out.append(flow_up)

        result = {"flow": out, "f1": list(fmap1), "f2": list(fmap2)}

        if corr_loss_examples:
            # self-pair and permuted-pair matching costs for the auxiliary
            # correlation losses, through the cvol's own matching nets (the
            # reference computes these inside the loss with the live module,
            # raft_cl.py:474-503)
            pos, neg = [], []
            # permutation stream; falls back to a fixed key when the caller
            # provides no 'permute' rng (the negatives are then static)
            rng = (self.make_rng("permute") if self.has_rng("permute")
                   else jax.random.PRNGKey(0))
            for i, feats in enumerate(list(fmap1) + list(fmap2)):
                bb, hh, ww, cc = feats.shape
                level = i % _LEVELS

                pair = jnp.concatenate((feats, feats), axis=-1)
                pos.append(cvol.example_costs(
                    level, pair[:, None, None], train, frozen_bn))

                perm = jax.random.permutation(
                    jax.random.fold_in(rng, i), hh * ww)
                shuffled = feats.reshape(bb, hh * ww, cc)[:, perm]
                shuffled = shuffled.reshape(bb, hh, ww, cc)
                pair = jnp.concatenate((feats, shuffled), axis=-1)
                neg.append(cvol.example_costs(
                    level, pair[:, None, None], train, frozen_bn))

            result["corr_pos"] = pos
            result["corr_neg"] = neg

        return result


@register_model
class RaftCl(Model):
    """``raft/cl`` (reference raft_cl.py:341-378)."""

    type = "raft/cl"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            dap_init=p.get("dap-init", "identity"),
            corr_radius=p.get("corr-radius", 3),
            arguments=cfg.get("arguments", {}),
        )

    def __init__(self, dap_init="identity", corr_radius=3, arguments={}):
        self.dap_init = dap_init
        self.corr_radius = corr_radius

        super().__init__(
            RaftClModule(dap_init=dap_init, corr_radius=corr_radius),
            arguments=arguments,
        )

    def get_config(self):
        default_args = {"iterations": 12, "upnet": True}
        return {
            "type": self.type,
            "parameters": {
                "corr-radius": self.corr_radius,
                "dap-init": self.dap_init,
            },
            "arguments": default_args | self.arguments,
        }

    def get_adapter(self) -> ModelAdapter:
        return RaftClAdapter(self)


class RaftClAdapter(ModelAdapter):
    def wrap_result(self, result, original_shape) -> Result:
        return RaftClResult(result)


class RaftClResult(Result):
    """Dict result: 'flow' sequence + feature lists
    (reference raft_cl.py:389-406)."""

    def __init__(self, output):
        super().__init__()
        self.result = output

    def output(self, batch_index=None):
        if batch_index is None:
            return self.result
        return {
            k: [x[batch_index : batch_index + 1] for x in v]
            for k, v in self.result.items()
        }

    def final(self):
        return self.result["flow"][-1]

    def intermediate_flow(self):
        return self.result["flow"]


@register_loss
class ClSequenceLoss(Loss):
    """``raft/cl/sequence`` (reference raft_cl.py:408-448)."""

    type = "raft/cl/sequence"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("arguments", {}))

    def __init__(self, arguments={}):
        super().__init__(arguments)

    def get_config(self):
        default_args = {"ord": 1, "gamma": 0.8, "scale": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def _flow_loss(self, result, target, valid, ord, gamma):
        flows = result["flow"]
        n = len(flows)
        valid_f = valid.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(valid_f), 1.0)

        loss = 0.0
        for i, flow in enumerate(flows):
            weight = gamma ** (n - i - 1)
            dist = jnp.linalg.norm(flow - target, ord=float(ord), axis=-1)
            loss = loss + weight * jnp.sum(dist * valid_f) / denom
        return loss

    def compute(self, model, result, target, valid, ord=1, gamma=0.8,
                scale=1.0):
        return self._flow_loss(result, target, valid, ord, gamma) * scale


@register_loss
class ClSequenceCorrHingeLoss(ClSequenceLoss):
    """``raft/cl/sequence+corr_hinge`` (reference raft_cl.py:452-503);
    requires the model argument ``corr_loss_examples=True``."""

    type = "raft/cl/sequence+corr_hinge"

    def get_config(self):
        default_args = {"ord": 1, "gamma": 0.8, "alpha": 1.0, "margin": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, ord=1, gamma=0.8,
                alpha=1.0, margin=1.0):
        flow_loss = self._flow_loss(result, target, valid, ord, gamma)

        corr_loss = 0.0
        for pos in result["corr_pos"]:
            corr_loss += jnp.maximum(margin - pos, 0.0).mean()
        for neg in result["corr_neg"]:
            corr_loss += jnp.maximum(margin + neg, 0.0).mean()

        return flow_loss + alpha * corr_loss


@register_loss
class ClSequenceCorrMseLoss(ClSequenceLoss):
    """``raft/cl/sequence+corr_mse`` (reference raft_cl.py:506-554);
    requires the model argument ``corr_loss_examples=True``."""

    type = "raft/cl/sequence+corr_mse"

    def get_config(self):
        default_args = {"ord": 1, "gamma": 0.8, "alpha": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, ord=1, gamma=0.8,
                alpha=1.0):
        flow_loss = self._flow_loss(result, target, valid, ord, gamma)

        corr_loss = 0.0
        for pos in result["corr_pos"]:
            corr_loss += jnp.square(pos - 1.0).mean()
        for neg in result["corr_neg"]:
            corr_loss += jnp.square(neg).mean()

        return flow_loss + alpha * corr_loss
