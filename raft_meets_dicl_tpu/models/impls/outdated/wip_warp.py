"""wip/warp/1: coarse-to-fine warping with a recurrent level unit
(kept-registered experiment).

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/outdated/wip_warp.py: a GA-Net p26 feature pyramid, one
shared recurrent level unit (per-level cost volumes + DAP, a motion
encoder, SepConv GRU, and a soft-argmin flow head) applied coarse-to-fine
with backwards feature warping; the hidden state carries across levels
half-nearest / half-bilinear-doubled. The auxiliary multiscale corr losses
consume example costs computed by the model (``corr_loss_examples=True``),
like raft/cl.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ....ops.sample import sample_bilinear
from ....ops.upsample import interpolate_bilinear, upsample_flow_2x
from ...common import warp
from ...common.blocks.dicl import DisplacementAwareProjection, MatchingNet
from ...common.encoders.dicl import FeatureEncoderGa
from ...config import register_loss, register_model
from ...model import Loss, Model, ModelAdapter, Result
from ..dicl import displaced_pair_volume, soft_argmin_flow
from ..raft import SepConvGru

_LEVELS = 5  # 1/4 .. 1/64


def _nearest_resize(x, size):
    b, h, w, c = x.shape
    nh, nw = size
    iy = (jnp.arange(nh) * h // nh).astype(jnp.int32)
    ix = (jnp.arange(nw) * w // nw).astype(jnp.int32)
    return x[:, iy][:, :, ix]


class _MotionEncoder(nn.Module):
    """cost volume + context features + flow → motion features
    (reference wip_warp.py:160-181)."""

    output_channels: int

    @nn.compact
    def __call__(self, cvol, cmap, flow):
        b, h, w, du, dv = cvol.shape
        x = jnp.concatenate(
            (cvol.reshape(b, h, w, du * dv), cmap, flow), axis=-1)

        x = nn.leaky_relu(nn.Conv(128, (3, 3))(x))
        x = nn.leaky_relu(nn.Conv(128, (3, 3))(x))
        return nn.Conv(self.output_channels, (3, 3))(x)


class _ScoreFlowHead(nn.Module):
    """Hidden state → displacement scores → soft-argmin delta flow
    (reference wip_warp.py:184-226)."""

    disp_range: tuple = (5, 5)

    @nn.compact
    def __call__(self, x):
        b, h, w, _ = x.shape
        du, dv = 2 * self.disp_range[0] + 1, 2 * self.disp_range[1] + 1

        score = nn.leaky_relu(nn.Conv(256, (1, 1))(x))
        score = nn.leaky_relu(nn.Conv(du * dv, (1, 1))(score))
        return soft_argmin_flow(score.reshape(b, h, w, du, dv))


class _RecurrentLevelUnit(nn.Module):
    """Warp → per-level cost volume → motion encoder → GRU → flow head
    (reference wip_warp.py:249-288). setup-style so the matching nets are
    reachable for the example-cost computation."""

    disp_range: tuple
    feat_channels: int
    hidden_dim: int

    def setup(self):
        self.cvnets = [MatchingNet() for _ in range(_LEVELS)]
        self.daps = [DisplacementAwareProjection(self.disp_range)
                     for _ in range(_LEVELS)]
        self.menet = _MotionEncoder(96 - 2)
        self.gru = SepConvGru(self.hidden_dim)
        self.fhead = _ScoreFlowHead()

    def __call__(self, fmap1, fmap2, h, flow, i, train=False, frozen_bn=False):
        fmap2, _mask = warp.warp_backwards(fmap2, jax.lax.stop_gradient(flow))

        mvol = displaced_pair_volume(fmap1, fmap2, self.disp_range)
        cvol = self.cvnets[i](mvol, train, frozen_bn)  # (B, H, W, du, dv)
        cvol = self.daps[i](cvol)

        x = self.menet(cvol, fmap1, flow)
        x = jnp.concatenate((x, flow), axis=-1)

        h = self.gru(h, x)
        d = self.fhead(h)

        return h, flow + d

    def example_costs(self, level, mvol, train=False, frozen_bn=False):
        return self.cvnets[level](mvol, train, frozen_bn)


class WipWarpModule(nn.Module):
    """Coarse-to-fine warping network (reference WipModule,
    wip_warp.py:292-385)."""

    disp_range: tuple = (5, 5)
    feat_channels: int = 32
    hidden_dim: int = 96

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 corr_loss_examples=False, final_only=False):
        fnet = FeatureEncoderGa(output_dim=self.feat_channels, depth=6,
                                out_levels=(1, 2, 3, 4, 5))
        f1, f2 = fnet((img1, img2), train, frozen_bn)  # finest-first, 1/4..1/64

        rlu = _RecurrentLevelUnit(self.disp_range, self.feat_channels,
                                  self.hidden_dim)

        b = img1.shape[0]
        h6, w6 = f1[-1].shape[1:3]
        flow = jnp.zeros((b, h6, w6, 2), jnp.float32)
        h = jnp.zeros((b, h6, w6, self.hidden_dim), jnp.float32)

        out = []
        for li in range(_LEVELS - 1, -1, -1):  # coarse → fine
            if f1[li].shape[1:3] != flow.shape[1:3]:
                flow = upsample_flow_2x(flow)
                size = f1[li].shape[1:3]
                c = self.hidden_dim // 2
                h = jnp.concatenate((
                    _nearest_resize(h[..., :c], size),
                    interpolate_bilinear(h[..., c:], size) * 2.0,
                ), axis=-1)

            h, flow = rlu(f1[li], f2[li], h, flow, li, train, frozen_bn)
            out.append(flow)

        result = {
            "flow": list(reversed(out)),  # finest first
            "f1": list(f1),
            "f2": list(f2),
        }

        if corr_loss_examples:
            pos, neg = [], []
            rng = (self.make_rng("permute") if self.has_rng("permute")
                   else jax.random.PRNGKey(0))
            for i, feats in enumerate(list(f1) + list(f2)):
                bb, hh, ww, cc = feats.shape
                level = i % _LEVELS

                pair = jnp.concatenate((feats, feats), axis=-1)
                pos.append(rlu.example_costs(
                    level, pair[:, None, None], train, frozen_bn))

                perm = jax.random.permutation(
                    jax.random.fold_in(rng, i), hh * ww)
                shuffled = feats.reshape(bb, hh * ww, cc)[:, perm]
                shuffled = shuffled.reshape(bb, hh, ww, cc)
                pair = jnp.concatenate((feats, shuffled), axis=-1)
                neg.append(rlu.example_costs(
                    level, pair[:, None, None], train, frozen_bn))

            result["corr_pos"] = pos
            result["corr_neg"] = neg

        return result


@register_model
class WipWarp(Model):
    """``wip/warp/1`` (reference wip_warp.py:388-427)."""

    type = "wip/warp/1"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            disp_range=tuple(p.get("disp-range", (5, 5))),
            arguments=cfg.get("arguments", {}),
        )

    def __init__(self, disp_range=(5, 5), arguments={}):
        self.disp_range = tuple(disp_range)
        super().__init__(WipWarpModule(disp_range=self.disp_range),
                         arguments=arguments)

    def get_config(self):
        return {
            "type": self.type,
            "parameters": {"disp-range": list(self.disp_range)},
            "arguments": dict(self.arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return WipAdapter(self)


class WipAdapter(ModelAdapter):
    def wrap_result(self, result, original_shape) -> Result:
        return WipResult(result, original_shape)


class WipResult(Result):
    """Dict result with finest-first flow list; final() upsamples to the
    input resolution (reference wip_warp.py:430-463)."""

    def __init__(self, output, target_shape):
        super().__init__()
        self.result = output
        self.shape = target_shape

    def output(self, batch_index=None):
        if batch_index is None:
            return self.result
        return {
            k: [x[batch_index : batch_index + 1] for x in v]
            for k, v in self.result.items()
        }

    def final(self):
        flow = jax.lax.stop_gradient(self.result["flow"][0])

        _, fh, fw, _ = flow.shape
        th, tw = self.shape

        flow = interpolate_bilinear(flow, (th, tw))
        return flow * jnp.asarray([tw / fw, th / fh], dtype=flow.dtype)

    def intermediate_flow(self):
        return self.result["flow"]


@register_loss
class WipMultiscaleLoss(Loss):
    """``wip/warp/multiscale`` (reference wip_warp.py:465-522)."""

    type = "wip/warp/multiscale"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("arguments", {}))

    def __init__(self, arguments={}):
        super().__init__(arguments)

    def get_config(self):
        default_args = {"ord": 2, "mode": "bilinear", "alpha": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def _flow_loss(self, result, target, valid, weights, ord, mode,
                   valid_range):
        if mode != "bilinear":
            raise ValueError(f"unsupported upsampling mode '{mode}'")

        th, tw = target.shape[1:3]
        valid_f = valid.astype(jnp.float32)

        loss = 0.0
        flows = result["flow"]
        for i, flow in enumerate(flows):
            _, fh, fw, _ = flow.shape
            flow = interpolate_bilinear(flow, (th, tw))
            flow = flow * jnp.asarray([tw / fw, th / fh], dtype=flow.dtype)

            mask = valid_f
            if valid_range is not None:
                mask = mask * (jnp.abs(target[..., 0]) < valid_range[i][0])
                mask = mask * (jnp.abs(target[..., 1]) < valid_range[i][1])

            if ord == "robust":
                dist = (jnp.abs(flow - target).sum(axis=-1) + 1e-8) ** 0.4
            else:
                dist = jnp.linalg.norm(flow - target, ord=float(ord), axis=-1)

            mean = jnp.sum(dist * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            loss = loss + weights[i] * mean

        return loss / len(flows)

    def compute(self, model, result, target, valid, weights, ord=2,
                mode="bilinear", alpha=1.0, valid_range=None):
        # ``alpha`` is accepted (and ignored) for config round-tripping:
        # the reference's get_config advertises it on every multiscale
        # variant while only the corr-hinge/corr-mse subclasses consume
        # it (reference wip_warp.py:477,544,600) — a full config written
        # by gencfg must load back through this base class
        return self._flow_loss(result, target, valid, weights, ord, mode,
                               valid_range)


@register_loss
class WipMultiscaleCorrHingeLoss(WipMultiscaleLoss):
    """``wip/warp/multiscale+corr_hinge`` (reference wip_warp.py:525-578);
    requires the model argument ``corr_loss_examples=True``."""

    type = "wip/warp/multiscale+corr_hinge"

    def get_config(self):
        default_args = {"ord": 2, "mode": "bilinear", "margin": 1.0,
                        "alpha": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, weights, ord=2,
                mode="bilinear", margin=1.0, alpha=1.0, valid_range=None):
        flow_loss = self._flow_loss(result, target, valid, weights, ord,
                                    mode, valid_range)

        corr_loss = 0.0
        for pos in result["corr_pos"]:
            corr_loss += jnp.maximum(margin - pos, 0.0).mean()
        for neg in result["corr_neg"]:
            corr_loss += jnp.maximum(margin + neg, 0.0).mean()

        return flow_loss + alpha * corr_loss


@register_loss
class WipMultiscaleCorrMseLoss(WipMultiscaleLoss):
    """``wip/warp/multiscale+corr_mse`` (reference wip_warp.py:581-631);
    requires the model argument ``corr_loss_examples=True``."""

    type = "wip/warp/multiscale+corr_mse"

    def get_config(self):
        default_args = {"ord": 2, "mode": "bilinear", "alpha": 1.0}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, weights, ord=2,
                mode="bilinear", alpha=1.0, valid_range=None):
        flow_loss = self._flow_loss(result, target, valid, weights, ord,
                                    mode, valid_range)

        corr_loss = 0.0
        for pos in result["corr_pos"]:
            corr_loss += jnp.square(pos - 1.0).mean()
        for neg in result["corr_neg"]:
            corr_loss += jnp.square(neg).mean()

        return flow_loss + alpha * corr_loss
