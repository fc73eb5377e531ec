"""RAFT+DICL multi-level lookup hybrid.

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/raft_dicl_ml.py: asymmetric encoders — frame 1 as a
dilated feature *stack* at 1/8 resolution, frame 2 as a strided feature
*pyramid* (or a pooled variant for both) — and one fused correlation
module that samples every level around a single 1/8 flow estimate and
runs shared-or-per-level MatchingNets, with DAP applied per level
('separate') or across all levels at once ('full').
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ... import telemetry
from ...ops.pool import avg_pool2d, max_pool2d
from ..common.blocks.dicl import DisplacementAwareProjection, MatchingNet
from ..common.blocks.raft import ResidualBlock, kaiming_normal
from ..common.corr.common import (
    dicl_fast_enabled,
    record_matching_bytes,
    sample_window,
    sample_window_fast,
)
from ..common.encoders.raft import FeatureEncoderS3
from ..common.grid import coordinate_grid
from ..common.norm import Norm2d
from ..common.util import identity_1x1_init
from ..config import register_model
from ..model import Model, ModelAdapter
from .raft import (BasicUpdateBlock, RaftAdapter, make_flow_regression,
                   upsample_flows)


class _OutputNet(nn.Module):
    """Dilated 3x3 + 1x1 level head (reference raft_dicl_ml.py:18-32)."""

    output_dim: int
    dilation: int = 1
    norm_type: str = "batch"

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        x = nn.Conv(128, (3, 3), kernel_dilation=self.dilation,
                    kernel_init=kaiming_normal)(x)
        x = Norm2d(self.norm_type, 8)(x, train and not frozen_bn)
        x = nn.relu(x)
        return nn.Conv(self.output_dim, (1, 1), kernel_init=kaiming_normal)(x)


class StackEncoder(nn.Module):
    """Frame-1 stack: all levels at 1/8, increasing dilation
    (reference raft_dicl_ml.py:35-101)."""

    output_dim: int
    levels: int = 4
    norm_type: str = "batch"

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        if not 1 <= self.levels <= 4:
            raise ValueError("levels must be between 1 and 4 (inclusive)")

        outs = [_OutputNet(self.output_dim, 1, self.norm_type)(x, train, frozen_bn)]
        for lvl in range(1, self.levels):
            x = ResidualBlock(256, self.norm_type, stride=1)(x, train, frozen_bn)
            outs.append(_OutputNet(self.output_dim, 2 ** lvl, self.norm_type)(
                x, train, frozen_bn))

        return outs[0] if len(outs) == 1 else tuple(outs)


class PyramidEncoder(nn.Module):
    """Frame-2 pyramid: strided stages 384/576/864
    (reference raft_dicl_ml.py:104-170)."""

    output_dim: int
    levels: int = 4
    norm_type: str = "batch"

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        if not 1 <= self.levels <= 4:
            raise ValueError("levels must be between 1 and 4 (inclusive)")

        outs = [_OutputNet(self.output_dim, 1, self.norm_type)(x, train, frozen_bn)]
        for channels in (384, 576, 864)[: self.levels - 1]:
            x = ResidualBlock(channels, self.norm_type, stride=2)(x, train, frozen_bn)
            outs.append(_OutputNet(self.output_dim, 1, self.norm_type)(
                x, train, frozen_bn))

        return outs[0] if len(outs) == 1 else tuple(outs)


class MlCorrelationModule(nn.Module):
    """Fused multi-level DICL lookup around one 1/8 flow estimate
    (reference raft_dicl_ml.py:236-345).

    Matching runs through the shared fast path by default: the fused
    window sampler, the unstacked ``(f1, window)`` MatchingNet form (no
    stacked (B, du, dv, H, W, 2C) volume; the f1 half of the first layer
    joins the window's by a contraction, see ``ConvBlock``), matching in
    ``dtype`` when set, and ONE batched MatchingNet evaluation per GRU
    iteration instead of a python loop of ``levels`` hourglass calls —
    all levels share the 1/8 output resolution and channel count, so they
    concatenate along the batch when ``share=True`` and ride a
    stacked-params ``vmap`` when ``share=False``. Parameter paths and
    checkpoints are unchanged: the per-level modules below own the
    parameters in both paths; the vmap only *reads* their subtrees.

    The reference per-level loop remains the fallback (``fast=False``,
    the ``RMD_DICL_FAST=0`` escape hatch, initialization, live-BN
    training — whose sequential running-stat updates the batched call
    cannot reproduce — and, for ``share=False``, non-TPU backends by
    default, where CPU XLA's grouped-conv backward is pathological).
    """

    feature_dim: int
    levels: int
    radius: int
    dap_init: str = "identity"
    dap_type: str = "separate"
    norm_type: str = "batch"
    share: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, fmap1, fmap2, coords, dap=True, mask_costs=(),
                 train=False, frozen_bn=False, fast=None):
        if self.dap_type not in ("full", "separate"):
            raise ValueError(f"DAP type '{self.dap_type}' not supported")

        b, h, w, _ = coords.shape
        k = 2 * self.radius + 1

        if fast is None:
            # share=False batches via stacked-params vmap → grouped convs,
            # whose backward is pathological on CPU XLA (~6x the loop) but
            # MXU-native on TPU: off-TPU the default stays on the loop
            # (explicit fast=True still forces the batched path)
            fast = dicl_fast_enabled() and (
                self.share or jax.default_backend() == "tpu")
        # live batch norm computes per-level statistics sequentially (the
        # shared-params case updates running stats levels-times per call);
        # only the reference loop reproduces that
        live_bn = train and not frozen_bn and self.norm_type == "batch"
        fast = fast and not live_bn and not self.is_initializing()

        if self.share:
            shared_mnet = MatchingNet(norm_type=self.norm_type,
                                      dtype=self.dtype)
            mnets = [shared_mnet] * self.levels
            if self.dap_type == "separate":
                shared_dap = DisplacementAwareProjection(
                    (self.radius, self.radius), init=self.dap_init)
                daps = [shared_dap] * self.levels
        else:
            mnets = [MatchingNet(norm_type=self.norm_type, dtype=self.dtype)
                     for _ in range(self.levels)]
            if self.dap_type == "separate":
                daps = [DisplacementAwareProjection(
                            (self.radius, self.radius), init=self.dap_init)
                        for _ in range(self.levels)]

        # the levels are apart only here: one scope and one trace site a
        # sampler call, so that the device trace names the level and each
        # call's path (``sw_fused_calls``) is counted beside the others'
        # (``level{i}`` stays the call's innermost scope; ``sampler``,
        # ``mnet`` and ``dap`` round it and below name the owner for the
        # compiled text's readers, compile/owners.py)
        sample = sample_window_fast if fast else sample_window
        windows = []
        with jax.named_scope("sampler"):
            for i, f2 in enumerate(fmap2):
                with jax.named_scope(f"level{i}"), \
                        telemetry.trace_site(f"level{i}"):
                    windows.append(sample(f2, coords / 2 ** i, self.radius))
        fmap1 = list(fmap1)
        if self.dtype is not None:
            fmap1 = [f1.astype(self.dtype) for f1 in fmap1]
            windows = [win.astype(self.dtype) for win in windows]
        if not self.is_initializing():
            record_matching_bytes(*fmap1, *windows)
            # how many levels one MatchingNet evaluation covers: a
            # property of the program, not a count over its iterations
            telemetry.note_trace("matching_levels_batched",
                                 self.levels if fast else 1, scale=False)

        with jax.named_scope("mnet"):
            if fast:
                costs = self._batched_costs(mnets, fmap1, windows, train,
                                            frozen_bn)
            else:
                # reference per-level loop (also the init path: creates
                # the per-level parameters at their checkpoint paths)
                costs = [mnets[i]((f1, win), train, frozen_bn)
                         for i, (f1, win) in enumerate(zip(fmap1, windows))]

        with jax.named_scope("dap"):
            out = []
            for i, cost in enumerate(costs):       # cost: (B, H, W, du, dv)
                if i + 3 in mask_costs:
                    cost = jnp.zeros_like(cost)

                if dap and self.dap_type == "separate":
                    cost = daps[i](cost)

                out.append(cost.reshape(b, h, w, k * k))

            out = jnp.concatenate(out, axis=-1)

            if self.dap_type == "full":
                # always create the full-DAP params for config stability
                full = nn.Conv(
                    self.levels * k * k, (1, 1), use_bias=False,
                    kernel_init=(identity_1x1_init
                                 if self.dap_init == "identity"
                                 else nn.initializers.lecun_normal()),
                )
                projected = full(out)
                if dap:
                    out = projected

        return out

    def _batched_costs(self, mnets, fmap1, windows, train, frozen_bn):
        """One MatchingNet evaluation for all levels.

        ``share=True``: the levels concatenate along the batch axis into
        the single shared net — identical parameters, identical per-element
        math (norms are frozen/stat-free on this path).

        ``share=False``: the per-level parameter subtrees created by the
        reference loop are read from this module's scope, stacked along a
        level axis, and the net runs under ``jax.vmap`` — XLA sees one
        grouped convolution per layer instead of ``levels`` separate
        hourglasses, while the checkpoint keeps its per-level
        ``MatchingNet_i`` layout (the stacking is a trace-time view).
        """
        if self.share:
            f1a = jnp.concatenate(fmap1, axis=0)
            wina = jnp.concatenate(windows, axis=0)
            cost = mnets[0]((f1a, wina), train, frozen_bn)  # (L·B, H, W, k, k)
            return [cost[i * fmap1[0].shape[0]:(i + 1) * fmap1[0].shape[0]]
                    for i in range(self.levels)]

        variables = []
        for i in range(self.levels):
            vs = {"params": self.scope.get_variable(
                "params", f"MatchingNet_{i}")}
            if self.has_variable("batch_stats", f"MatchingNet_{i}"):
                vs["batch_stats"] = self.scope.get_variable(
                    "batch_stats", f"MatchingNet_{i}")
            variables.append(vs)
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *variables)

        template = MatchingNet(norm_type=self.norm_type, dtype=self.dtype,
                               parent=None)

        def one(vs, f1, win):
            return template.apply(vs, (f1, win), train, frozen_bn)

        costs = jax.vmap(one)(stacked, jnp.stack(fmap1), jnp.stack(windows))
        return [costs[i] for i in range(self.levels)]


class _MlStep(nn.Module):
    """One GRU iteration — the nn.scan body. Parameterized submodules are
    shared instances from the parent scope (see raft_dicl_ctf._CtfStep for
    why: identical parameter paths to the unrolled loop)."""

    cvol: nn.Module
    reg: nn.Module
    update: nn.Module
    dap: bool
    mask_costs: tuple
    corr_grad_stop: bool
    train: bool
    frozen_bn: bool

    @nn.compact
    def __call__(self, carry, _, fmap1, fmap2, x, coords0):
        from jax.ad_checkpoint import checkpoint_name

        # flow (not coords1) carry: program boundaries replay the same
        # ``coords0 + flow`` reconstruction, so ladder rungs chain
        # bit-exactly (see raft._RaftStep)
        h, flow = carry
        flow = jax.lax.stop_gradient(flow)
        coords1 = coords0 + flow

        with jax.named_scope("matching"):
            corr = self.cvol(fmap1, fmap2, coords1, dap=self.dap,
                             mask_costs=self.mask_costs, train=self.train,
                             frozen_bn=self.frozen_bn)
        with jax.named_scope("lookup"):
            corr = checkpoint_name(corr, "corr_features")

            corr_flows = tuple(flow + d for d in self.reg(corr))

            if self.corr_grad_stop:
                corr = jax.lax.stop_gradient(corr)

        with jax.named_scope("update"):
            h, d = self.update(h, x, corr, flow)
        coords1 = coords1 + d
        flow = coords1 - coords0

        return (h, flow), (flow, h, corr_flows)


class RaftPlusDiclMlModule(nn.Module):
    """RAFT+DICL multi-level network (reference raft_dicl_ml.py:350-470)."""

    dropout: float = 0.0
    mixed_precision: bool = False
    corr_levels: int = 4
    corr_radius: int = 4
    corr_channels: int = 32
    context_channels: int = 128
    recurrent_channels: int = 128
    dap_init: str = "identity"
    dap_type: str = "separate"
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    mnet_norm: str = "batch"
    encoder_type: str = "raft-cnn"
    share_dicl: bool = False
    corr_reg_type: str = "softargmax"
    corr_reg_args: dict = None
    remat: bool = True
    unroll: bool = False

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False, iterations=12,
                 dap=True, upnet=True, corr_flow=False, corr_grad_stop=False,
                 flow_init=None, hidden_init=None, mask_costs=(),
                 return_state=False, final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        dt = jnp.bfloat16 if self.mixed_precision else None

        # asymmetric encoders (reference :173-236)
        with jax.named_scope("encoders"):
            if self.encoder_type == "raft-cnn":
                base = FeatureEncoderS3(output_dim=256,
                                        norm_type=self.encoder_norm,
                                        dropout=0, dtype=dt)
                b1, b2 = base((img1, img2), train, frozen_bn)
                b1 = b1.astype(jnp.float32)
                b2 = b2.astype(jnp.float32)

                fmap1 = StackEncoder(self.corr_channels, self.corr_levels,
                                     self.encoder_norm)(b1, train, frozen_bn)
                fmap2 = PyramidEncoder(self.corr_channels, self.corr_levels,
                                       self.encoder_norm)(b2, train, frozen_bn)
                fmap1 = (fmap1,) if self.corr_levels == 1 else fmap1
                fmap2 = (fmap2,) if self.corr_levels == 1 else fmap2
            elif self.encoder_type in ("raft-avgpool", "raft-maxpool"):
                pool = (avg_pool2d if self.encoder_type.endswith("avgpool")
                        else max_pool2d)
                base = FeatureEncoderS3(output_dim=self.corr_channels,
                                        norm_type=self.encoder_norm, dropout=0,
                                        dtype=dt)
                f1, f2 = base((img1, img2), train, frozen_bn)
                f1 = f1.astype(jnp.float32)
                f2 = f2.astype(jnp.float32)

                fmap1 = tuple([f1] * self.corr_levels)
                pyramid = [f2]
                for _ in range(1, self.corr_levels):
                    pyramid.append(pool(pyramid[-1], 2))
                fmap2 = tuple(pyramid)
            else:
                raise ValueError(
                    f"unknown encoder type: '{self.encoder_type}'")

            cnet = FeatureEncoderS3(output_dim=hdim + cdim,
                                    norm_type=self.context_norm,
                                    dropout=self.dropout, dtype=dt)
            ctx = cnet(img1, train, frozen_bn)
            h = jnp.tanh(ctx[..., :hdim])
            x = nn.relu(ctx[..., hdim:])
            if hidden_init is not None:
                h = hidden_init.astype(h.dtype)

        b, hc, wc, _ = fmap1[0].shape
        coords0 = coordinate_grid(b, hc, wc)
        flow = (flow_init.astype(jnp.float32) if flow_init is not None
                else jnp.zeros((b, hc, wc, 2), jnp.float32))  # graftlint: disable=f32-literal -- flow fields are f32 by convention

        # the matching nets follow the model's mixed policy (the reference
        # autocast covers them too; cost volumes come back f32 regardless)
        cvol = MlCorrelationModule(
            feature_dim=self.corr_channels, levels=self.corr_levels,
            radius=self.corr_radius, dap_init=self.dap_init,
            dap_type=self.dap_type, norm_type=self.mnet_norm,
            share=self.share_dicl, dtype=dt,
        )
        reg = make_flow_regression(self.corr_reg_type, self.corr_levels,
                                   self.corr_radius,
                                   **(self.corr_reg_args or {}))
        update = BasicUpdateBlock(hdim, dtype=dt)

        # one (remat-wrapped) step body serves both realizations: the
        # lax.scan (default) or a python-unrolled loop (`unroll=True`,
        # kept as a debugging escape hatch)
        if self.remat:
            body = nn.remat(
                _MlStep, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "corr_features"),
            )
        else:
            body = _MlStep
        shared = dict(
            cvol=cvol, reg=reg, update=update, dap=dap,
            mask_costs=tuple(mask_costs), corr_grad_stop=corr_grad_stop,
            train=train, frozen_bn=frozen_bn,
        )

        # one trace site for the iterations: what the matching notes while
        # it traces (sampler path, matching bytes) stands for the
        # ``iterations`` trips of the body, once, however often the tracer
        # visits it (flax's lifted scan does so twice)
        with telemetry.trace_site("iteration", iterations):
            if self.unroll:
                step = body(**shared)
                carry = (h, flow)
                flows, hiddens, corr_flows = [], [], []
                for _ in range(iterations):
                    carry, (fl, hi, cf) = step(
                        carry, jnp.zeros((0,), dtype=jnp.bfloat16),
                        fmap1, fmap2, x, coords0)
                    flows.append(fl)
                    hiddens.append(hi)
                    corr_flows.append(cf)
                h, flow = carry

                flows = jnp.stack(flows)
                hiddens = jnp.stack(hiddens)
                corr_flows = tuple(
                    jnp.stack([cf[lvl] for cf in corr_flows])
                    for lvl in range(self.corr_levels)
                )
            else:
                # train-mode batch norm mutates running stats every
                # iteration; carrying the batch_stats collection through
                # the scan keeps the sequential-update semantics of the
                # unrolled loop while compiling ONE step body — the
                # 12x-unrolled train graph of this model (12 iterations x
                # 4 MatchingNets) is what crashed the TPU compiler service
                # at the reference Things config (b6/384x704; see PERF.md
                # round 5)
                live_bn = train and not frozen_bn
                step = nn.scan(
                    body,
                    variable_broadcast=(["params"] if live_bn
                                        else ["params", "batch_stats"]),
                    variable_carry=["batch_stats"] if live_bn else [],
                    split_rngs={"params": False, "dropout": True},
                    in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                             nn.broadcast),
                    out_axes=0,
                )(**shared)

                (h, flow), (flows, hiddens, corr_flows) = step(
                    (h, flow), jnp.zeros((iterations, 0), dtype=jnp.bfloat16),
                    fmap1, fmap2, x, coords0,
                )

        with jax.named_scope("up8"):
            out = upsample_flows(flows, hiddens, (h, flow),
                                 (img1.shape[1], img1.shape[2]), dtype=dt,
                                 upnet=upnet, final_only=final_only)

        if corr_flow:
            out_corr = [
                [corr_flows[lvl][i] for i in range(iterations)]
                for lvl in range(self.corr_levels)
            ]
            out = [*reversed(out_corr), out]  # coarse-to-fine, then final

        if return_state:
            final = flows[-1]
            if iterations >= 2:
                prev = flows[-2]
            elif flow_init is not None:
                prev = flow_init.astype(jnp.float32)
            else:
                prev = jnp.zeros_like(final)
            diff = (final - prev).astype(jnp.float32)
            delta = jnp.sqrt(jnp.mean(jnp.sum(diff * diff, axis=-1),
                                      axis=(1, 2)))
            return out, {"flow": final, "hidden": h, "delta": delta}

        return out


@register_model
class RaftPlusDiclMl(Model):
    """``raft+dicl/ml`` (reference raft_dicl_ml.py:448-582)."""

    type = "raft+dicl/ml"
    # 1: the iterations are one trace site and each level's sampler call
    # its own (``sw_fused_calls`` levels x iterations, the matching's
    # bytes times the iterations), ``matching_levels_batched`` is noted.
    # 2: the scopes ``sampler``, ``mnet`` and ``dap`` of PR 37
    # (``compile/owners.py``): an executable stored before them gives its
    # matching no owner below ``matching``
    notes_revision = 2

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            dropout=float(p.get("dropout", 0.0)),
            mixed_precision=bool(p.get("mixed-precision", False)),
            corr_levels=p.get("corr-levels", 4),
            corr_radius=p.get("corr-radius", 4),
            corr_channels=p.get("corr-channels", 32),
            context_channels=p.get("context-channels", 128),
            recurrent_channels=p.get("recurrent-channels", 128),
            dap_init=p.get("dap-init", "identity"),
            dap_type=p.get("dap-type", "separate"),
            encoder_norm=p.get("encoder-norm", "instance"),
            context_norm=p.get("context-norm", "batch"),
            mnet_norm=p.get("mnet-norm", "batch"),
            encoder_type=p.get("encoder-type", "raft-cnn"),
            share_dicl=p.get("share-dicl", False),
            corr_reg_type=p.get("corr-reg-type", "softargmax"),
            corr_reg_args=p.get("corr-reg-args", {}),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, dropout=0.0, mixed_precision=False, corr_levels=4,
                 corr_radius=4, corr_channels=32, context_channels=128,
                 recurrent_channels=128, dap_init="identity",
                 dap_type="separate", encoder_norm="instance",
                 context_norm="batch", mnet_norm="batch",
                 encoder_type="raft-cnn", share_dicl=False,
                 corr_reg_type="softargmax", corr_reg_args={}, arguments={},
                 on_epoch_args={}, on_stage_args={"freeze_batchnorm": True}):
        self.dropout = dropout
        self.mixed_precision = mixed_precision
        self.corr_levels = corr_levels
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.dap_init = dap_init
        self.dap_type = dap_type
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm
        self.mnet_norm = mnet_norm
        self.encoder_type = encoder_type
        self.share_dicl = share_dicl
        self.corr_reg_type = corr_reg_type
        self.corr_reg_args = dict(corr_reg_args)

        super().__init__(
            RaftPlusDiclMlModule(
                dropout=dropout, mixed_precision=mixed_precision,
                corr_levels=corr_levels, corr_radius=corr_radius,
                corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels, dap_init=dap_init,
                dap_type=dap_type, encoder_norm=encoder_norm,
                context_norm=context_norm, mnet_norm=mnet_norm,
                encoder_type=encoder_type, share_dicl=share_dicl,
                corr_reg_type=corr_reg_type,
                corr_reg_args=dict(corr_reg_args),
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "iterations": 12,
            "dap": True,
            "upnet": True,
            "corr_flow": False,
            "corr_grad_stop": False,
            "mask_costs": [],
        }
        return {
            "type": self.type,
            "parameters": {
                "dropout": self.dropout,
                "mixed-precision": self.mixed_precision,
                "corr-levels": self.corr_levels,
                "corr-radius": self.corr_radius,
                "corr-channels": self.corr_channels,
                "context-channels": self.context_channels,
                "recurrent-channels": self.recurrent_channels,
                "dap-init": self.dap_init,
                "dap-type": self.dap_type,
                "encoder-norm": self.encoder_norm,
                "context-norm": self.context_norm,
                "mnet-norm": self.mnet_norm,
                "encoder-type": self.encoder_type,
                "share-dicl": self.share_dicl,
                "corr-reg-type": self.corr_reg_type,
                "corr-reg-args": self.corr_reg_args,
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": True} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return RaftAdapter(self)
